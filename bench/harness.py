"""The benchmark's general part: finding a cell's files by name, the device
check, set-up, the measured window, the output check and the result line.

What belongs to one configuration, traffic mix or metric lives in files of
its own, found by the names in ``BENCHMARK.json``:

  bench/configs/<config>.json   the deployment: device geometry, DDR3
                                timing, the program family it runs
  bench/traffic/<traffic>.json  the traffic's parameters; may name its own
                                ``family``
  bench/families/<family>.py    ``Cell``: job inputs from the seed, the
                                timed path (``serve``: the client jobs
                                each call finishes), and the plain
                                reference
  bench/metrics/<metric>.py     ``value(run)``: one metric from a finished
                                window, or None where it finds nothing
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
import traceback
from pathlib import Path

import numpy as np

import refsim

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# Inputs are made ahead of the window for this many times the jobs that the
# warm job's time says the window holds.
AHEAD = 1.3
# Job indices of the two warm-up jobs: outside the window's 0, 1, 2, ...
WARM = (2**32, 2**32 + 1)
# The device state a check compares: rows, migration rows, DCC row.
STATE = ("bits", "mig_top", "mig_bot", "dcc")
# A traced run profiles the jobs that start in the window's first this many
# seconds, which keeps the trace small; the window goes on untraced.
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_module(kind: str, name: str, root: Path = ROOT):
    path = Path(root) / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Spec:
    """One cell as ``BENCHMARK.json`` and its files define it."""

    cell: dict
    config: dict
    traffic: dict
    family: str
    end_to_end: list
    per_layer: list
    root: Path = ROOT

    @classmethod
    def load(cls, name: str, root: Path = ROOT):
        """The cell ``name`` of ``<root>/BENCHMARK.json``, with the files
        its names point to under ``<root>/bench``."""
        root = Path(root)
        b = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in b["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in {root}/BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        cell = cells[name]
        conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
        config = json.loads((root / conf["file"]).read_text())
        traffic = json.loads((root / "bench" / "traffic"
                              / f"{cell['traffic']}.json").read_text())
        return cls(cell=cell, config=config, traffic=traffic,
                   family=traffic.get("family", config["family"]),
                   end_to_end=_for_cell(b["end_to_end"], name),
                   per_layer=_for_cell(b["per_layer"], name), root=root)

    def make_cell(self, pim, seed: int) -> "JobCell":
        fam = load_module("families", self.family, self.root)
        return fam.Cell(pim, self.config, self.traffic, seed)


@dataclasses.dataclass
class Done:
    """One client job that a call of ``JobCell.serve`` finished."""

    t_submit: float         # host clock: its inputs handed over
    t_done: float           # host clock: its reads and meters on the host
    commands: int           # simulated commands, from the reference's lists
    inp: object             # what the reference runs
    out: dict               # what the check compares


class JobCell:
    """Base of a family's ``Cell``. A job is one user submission: fresh
    inputs from the seed handed to the entry point, run until its reads and
    meters are on the host. Subclasses define ``inputs(i)``, the timed path
    and ``reference(inputs, dtype)``. A single-client family sets
    ``commands_per_job`` and defines ``run(inputs, span)``, one job a call;
    a family of several clients overrides ``serve``, ``warm_up`` and
    ``release`` and may add ``counters``."""

    commands_per_job = 0

    def __init__(self, pim, config, traffic, seed):
        import jax
        self.pim, self.jax = pim, jax
        self.config, self.traffic = config, traffic
        self.seed = seed % 2**64         # SeedSequence takes no negatives
        self.dev, self.timing = config["device"], config["timing"]
        self.cfg = pim.DeviceConfig(**self.dev,
                                    timing=pim.DDR3Timing(**self.timing))
        self.n_slots = self.cfg.n_slots
        self.device0 = None

    def make_device(self):
        """The device every job starts from: control rows set, meters 0."""
        self.device0 = self.pim.make_device(self.cfg)
        self.jax.block_until_ready(self.device0.banks)

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        self.device0 = None

    def serve(self, inp, span) -> list:
        """One call of the timed path: the client jobs it finished, as
        ``Done`` records. Here one job a call, timed from the hand-off of
        its inputs until its reads and meters are on the host."""
        t0 = time.perf_counter()
        out = self.run(inp, span)
        t1 = time.perf_counter()
        return [Done(t0, t1, self.commands_per_job, inp, out)]

    def warm_up(self, span) -> float:
        """Compile and warm what the window runs. Returns the host seconds
        of one warm call of ``serve``, which sizes the inputs made ahead.
        Here two jobs with inputs of their own: the first compiles; the
        second times a warm job."""
        warm = []
        for i in WARM:
            inp = self.inputs(i)
            t0 = time.perf_counter()
            done = self.serve(inp, span)
            warm.append(time.perf_counter() - t0)
            del done
        log(f"warm-up jobs {warm[0]:.3f} s (first, compiles) and "
            f"{warm[1]:.6f} s")
        return warm[1]

    def counters(self) -> dict:
        """The family's own cumulative counters; the window reports their
        growth beside the program's."""
        return {}

    @staticmethod
    def meter_dict(meter) -> dict:
        return {f.name: np.asarray(getattr(meter, f.name))
                for f in dataclasses.fields(meter)}

    def paper_line(self, out) -> str | None:
        return None

    def control_output(self, ref: dict) -> dict:
        """The reference's own results in the shape of a job's output: the
        control puts a lower-precision reference in the program's place."""
        dev = ref["dev"]
        return {"reads": ref["reads"], "meter": dev.meter(),
                "wall": ref["wall"], "energy": ref["energy"],
                "state_host": {k: getattr(dev, k) for k in STATE}}

    def compare(self, inp, out, ref) -> dict:
        """The numbers compared for one job, each against its limit."""
        got_r = [r for step in out["reads"] for slot in step for r in slot]
        want_r = [r for step in ref["reads"] for slot in step for r in slot]
        bad_reads = sum(refsim.words_differ(g, w)
                        for g, w in zip(got_r, want_r))
        bad_reads += sum(np.asarray(r).size
                         for r in (got_r[len(want_r):] + want_r[len(got_r):]))
        dev = ref["dev"]
        st = out["state_host"]
        bad_state = sum(refsim.words_differ(st[k], getattr(dev, k))
                        for k in STATE)
        want_m = dev.meter()
        bad_int = sum(refsim.words_differ(out["meter"][k], want_m[k])
                      for k in refsim.INT_FIELDS)
        rel = max([refsim.rel_err(out["meter"][k], want_m[k])
                   for k in refsim.FLOAT_FIELDS]
                  + [refsim.rel_err(out["wall"], ref["wall"]),
                     refsim.rel_err(out["energy"], ref["energy"])])
        return {"reads_bad_words": bad_reads, "state_bad_words": bad_state,
                "meter_int_bad": bad_int, "meter_rel_err": rel}


def fetch_state(out: dict) -> None:
    """Bring a kept job's final device state to the host."""
    s = out.pop("state")
    out["state_host"] = {k: np.asarray(getattr(s, k)) for k in STATE}


def first_jobs(cell: JobCell, n: int, span) -> list:
    """The first ``n`` jobs of a window, as ``(inputs, output)`` with their
    states on the host: the cell set up and warmed as a run does it, then
    its timed path."""
    cell.make_device()
    cell.warm_up(span)
    kept: list = []
    i = 0
    while len(kept) < n:
        kept.extend((d.inp, d.out) for d in cell.serve(cell.inputs(i), span))
        i += 1
    kept = kept[:n]
    for _, out in kept:
        fetch_state(out)
    return kept


def check(cell: JobCell, kept, limits: dict, control=None) -> dict:
    """Compare kept jobs with the plain reference. Counts add up over the
    jobs; an error ratio takes the worst job. With ``control`` (a dtype),
    the reference computed in that precision stands in for the program."""
    total: dict = {}
    for inp, out in kept:
        ref = cell.reference(inp, np.float64)
        if control is not None:
            out = cell.control_output(cell.reference(inp, control))
        nums = cell.compare(inp, out, ref)
        for k, v in nums.items():
            if k in total and k.endswith("_err"):
                total[k] = max(total[k], v)
            else:
                total[k] = total.get(k, 0) + v
    missing = set(limits) - set(total)
    if missing:
        raise KeyError(f"limits name numbers the check never made: "
                       f"{sorted(missing)}")
    return total


class Reservoir:
    """A uniform sample of ``m`` jobs of the window, drawn from the seed."""

    def __init__(self, m: int, seed: int):
        self.m = m
        self.rng = np.random.default_rng([seed, 3])
        self.kept: list = []

    def offer(self, i: int, item) -> None:
        if i < self.m:
            self.kept.append(item)
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.m:
            self.kept[j] = item


@dataclasses.dataclass
class Run:
    """What a finished window gives the metric readers."""

    seconds: float                  # window length, host clock
    jobs: list                      # (submit, done) of every client job
                                    # completed in the window
    commands: int                   # simulated commands of those jobs
    setup_s: float
    counters: dict                  # program and family counters' growth
                                    # in the window
    compiles: int                   # JAX backend compiles in the window
    trace: object = None            # tracered.Reduction of a traced window


class _Compiles:
    """Counts JAX's backend compiles (``n``) and jaxpr traces (``traces``;
    an eager op traces its primitive without compiling) while ``on``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        self.traces: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if not self.on:
            return
        if event == self.COMPILE:
            self.n += 1
        elif event == self.TRACE:
            name = str(kw.get("fun_name"))
            self.traces[name] = self.traces.get(name, 0) + 1


def counters(cell: JobCell) -> dict:
    """The program's own counters (scheduler dispatches and cache misses,
    runner traces) and the family's."""
    sched = importlib.import_module("repro.core.pim.schedule")
    runner = importlib.import_module("repro.core.pim.exec")
    c = {f"sched.{k}": v for k, v in sched.SCHED_STATS.items()}
    c.update({f"runner.{k}": v for k, v in runner.RUNNER_STATS.items()})
    c.update(cell.counters())
    return c


def measure(spec: Spec, pim, seed: int, seconds: float, trace: bool,
            t_start: float, trace_dir: str | None = None) -> dict:
    """Set up the cell, warm it, run the window (profiled into
    ``trace_dir`` where ``trace``), check the kept jobs. Returns the fields
    of the result line (without ``device``)."""
    import jax

    compiles = _Compiles()
    cell = spec.make_cell(pim, seed)
    cell.make_device()
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))

    warm_s = cell.warm_up(span)

    t0 = time.perf_counter()
    n_ahead = int(math.ceil(seconds / warm_s * AHEAD)) + 4
    ahead = [cell.inputs(i) for i in range(n_ahead)]
    gen_s = time.perf_counter() - t0
    per = (f" ({cell.commands_per_job} simulated commands a job)"
           if cell.commands_per_job else "")
    log(f"generator: {n_ahead} calls' inputs made ahead of the window in "
        f"{gen_s:.3f} s{per}")
    gc.collect()
    gc.freeze()         # the inputs made ahead stay out of every collection

    sampler = Reservoir(int(spec.traffic["check_jobs"]), seed)
    c0 = counters(cell)
    jobs, failed, error = [], 0, None
    commands = 0
    late = 0
    if trace:
        jax.profiler.start_trace(trace_dir)
    compiles.on = True
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    tracing = trace
    i = 0
    while True:
        if i < len(ahead):
            inp = ahead[i]
            ahead[i] = None
        else:
            late += 1
            inp = cell.inputs(i)
        try:
            done = cell.serve(inp, span)
        except Exception:            # noqa: BLE001 - reported, run fails
            failed += 1
            error = traceback.format_exc()
            break
        for d in done:
            sampler.offer(len(jobs), (d.inp, d.out))
            jobs.append((d.t_submit, d.t_done))
            commands += d.commands
        done = d = None     # outputs the sample did not keep go before the
                            # next call (a job's output holds a device state)
        i += 1
        t1 = time.perf_counter()
        if tracing and t1 - t_open >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing = False
        if t1 - t_open >= seconds:
            break
    t_close = time.perf_counter()
    compiles.on = False
    if tracing:
        jax.profiler.stop_trace()
    c1 = counters(cell)
    delta = {k: c1[k] - c0[k] for k in c1}
    attempted = len(jobs) + failed
    if late:
        log(f"generator fell behind: {late} calls' inputs were made inside "
            f"the window")
    log(f"window {t_close - t_open:.6f} s, {len(jobs)} jobs; in the "
        f"window: {compiles.n} backend compiles, jaxpr traces "
        f"{compiles.traces}, program counters {delta}")
    if error:
        print(error, file=sys.stderr, flush=True)

    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    for _, out in sampler.kept:
        fetch_state(out)
    cell.release()                      # the reference runs after the
    del ahead                           # program's state is freed
    gc.unfreeze()
    if sampler.kept:
        line = cell.paper_line(sampler.kept[0][1])
        if line:
            log(line)

    run = Run(seconds=t_close - t_open, jobs=jobs, commands=commands,
              setup_s=setup_s, counters=delta, compiles=compiles.n)
    t0 = time.perf_counter()
    limits = spec.traffic["limits"]
    numbers = check(cell, sampler.kept, limits) if sampler.kept else {}
    ref_s = time.perf_counter() - t0
    log(f"reference over {len(sampler.kept)} kept jobs: {ref_s:.3f} s")
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    checks["jobs_checked"] = {"value": len(sampler.kept), "limit": 1}
    correct = (failed == 0 and len(sampler.kept) >= 1
               and all(numbers.get(k, math.inf) <= lim
                       for k, lim in limits.items()))
    return {"run": run, "attempted": attempted, "failed": failed,
            "correct": correct, "checks": checks, "peak": peak,
            "cell": cell}


def metric_values(run: Run, metrics: list, root: Path = ROOT) -> dict:
    out = {}
    for m in metrics:
        v = load_module("metrics", m["name"], root).value(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def check_lines(checks: dict) -> list:
    lines = []
    for k, c in checks.items():
        op = ">=" if k == "jobs_checked" else "<="
        lines.append(f"check {k}: {c['value']!r} (limit {op} "
                     f"{c['limit']!r})")
    return lines

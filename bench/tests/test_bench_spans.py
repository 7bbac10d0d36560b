"""The breakdown by the program's spans and scopes (``spans.py``): on
hand-made nested events, on the benchmark's first CPU trace
(``data/cpu_trace.xplane.pb``, ``bench.*`` spans only) and on a CPU trace
that holds the program's spans nested in the benchmark's
(``data/cpu_spans_trace.xplane.pb`` with the compiled program's HLO text
``data/cpu_spans_trace.hlo.txt``, see ``data/record_cpu_spans_trace.py``);
and the per-layer metric that reads the scheduler's upload counter."""
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness
import spans
import tracered
from bench_cases import ROOT, tiny_spec

DATA = Path(__file__).with_name("data")
OLD = DATA / "cpu_trace.xplane.pb"
NEW = DATA / "cpu_spans_trace.xplane.pb"
HLO = DATA / "cpu_spans_trace.hlo.txt"
CHILDREN = ("pim.sched.lower", "pim.sched.plan", "pim.sched.payloads",
            "pim.sched.dispatch")


def _nested():
    # Window 0..120. Host: entry 0-100 holds pipeline 5-95, which holds
    # payloads 10-40 and dispatch 40-60; readback 100-120 holds reads
    # 102-110. Device: a loop 50-70 holds a kernel 55-60; a copy 112-115.
    return spans.Trace(
        devices={"/device:TPU:0": [
            (50, 70, "while.1", None),
            (55, 60, "rowops_shift_cols.1", "pim.runner.row_math"),
            (112, 115, "fusion.2", "pim.runner.meter_fold"),
            (130, 140, "late", None)]},
        threads={"/host:CPU/python": [
            (0, 100, "bench.entry"), (5, 95, "pim.sched.pipeline"),
            (10, 40, "pim.sched.payloads"), (40, 60, "pim.sched.dispatch"),
            (100, 120, "bench.readback"), (102, 110, "pim.sched.reads")]})


def test_innermost_cuts_nested_intervals():
    got = spans.innermost([(0, 10, "a"), (2, 5, "b"), (2, 4, "c"),
                           (8, 12, "d"), (20, 30, "e")])
    assert got == [(0, 2, "a"), (2, 4, "c"), (4, 5, "b"), (5, 8, "a"),
                   (8, 12, "d"), (20, 30, "e")]


def test_hand_made_nesting_self_times_and_gaps():
    b = spans.reduce(_nested())
    assert b.window_s == pytest.approx(120e-9)
    assert b.busy_s == pytest.approx(23e-9)
    assert b.jobs == 1
    self_ns = {n: round(s["self_s"] * 1e9) for n, s in b.spans.items()}
    assert self_ns == {"bench.entry": 10, "pim.sched.pipeline": 40,
                       "pim.sched.payloads": 30, "pim.sched.dispatch": 20,
                       "bench.readback": 12, "pim.sched.reads": 8}
    assert b.spans["pim.sched.pipeline"]["total_s"] == pytest.approx(90e-9)
    # every idle moment goes to the innermost span over it
    gaps_ns = {n: round(v * 1e9) for n, v in b.gaps.items()}
    assert gaps_ns == {"bench.entry": 10, "pim.sched.pipeline": 30,
                       "pim.sched.payloads": 30, "pim.sched.dispatch": 10,
                       "bench.readback": 9, "pim.sched.reads": 8,
                       tracered.OTHER: 0}
    assert sum(b.gaps.values()) == pytest.approx(b.idle_s, rel=1e-12)
    # busy time by the innermost operation's scope
    assert b.scopes == pytest.approx({spans.UNSCOPED: 15e-9,
                                      "pim.runner.row_math": 5e-9,
                                      "pim.runner.meter_fold": 3e-9})


def test_spans_of_another_thread_take_gaps_they_started_last():
    tr = _nested()
    tr.threads["/host:CPU/worker"] = [(20, 30, "pim.sched.reads")]
    b = spans.reduce(tr)
    assert b.gaps["pim.sched.payloads"] == pytest.approx(20e-9)
    assert b.gaps["pim.sched.reads"] == pytest.approx(18e-9)
    assert b.spans["pim.sched.payloads"]["self_s"] == pytest.approx(30e-9)
    assert sum(b.gaps.values()) == pytest.approx(b.idle_s, rel=1e-12)


def test_scope_of_reads_the_whole_path():
    deep = "/".join(["jit(pipe)", "while", "body"] * 30
                    + ["pim.runner.row_math", "jit(shift_cols)",
                       "rowops_shift_cols", "pallas_call"])
    assert len(deep) > 160
    assert spans.scope_of(deep) == "pim.runner.row_math"
    assert spans.scope_of("jit(f)/pim.step.bus_fold/pim.runner.x/add") == (
        "pim.runner.x")
    assert spans.scope_of("jit(f)/mul") is None


def test_hlo_scopes_joins_fusions_to_their_root():
    text = HLO.read_text()
    got = spans.hlo_scopes(text)
    scopes = {s for s in got.values() if s}
    assert {"pim.runner.row_math", "pim.runner.host_io",
            "pim.runner.meter_fold", "pim.step.bus_fold"} <= scopes
    assert all(m == "jit_pipe" for m, _ in got)


@pytest.mark.parametrize("path", [OLD, NEW], ids=["bench_only", "nested"])
def test_window_busy_jobs_as_tracered(path):
    """The window, busy time and jobs are tracered's, whatever program
    spans the trace holds; tracered still reads bench.* spans alone."""
    old = tracered.reduce(tracered.load(str(path), tracered.cpu_ops))
    b = spans.reduce(spans.load(str(path), tracered.cpu_ops))
    assert b.window_s == old.window_s
    assert b.busy_s == pytest.approx(old.busy_s, rel=1e-12)
    assert b.jobs == old.jobs == 3
    assert sum(b.gaps.values()) == pytest.approx(b.idle_s, rel=1e-9)
    assert sum(b.scopes.values()) == pytest.approx(b.busy_s, rel=1e-9)


def test_bench_only_trace_gaps_as_tracered():
    old = tracered.reduce(tracered.load(str(OLD), tracered.cpu_ops))
    b = spans.reduce(spans.load(str(OLD), tracered.cpu_ops))
    assert set(b.spans) == {"bench.entry", "bench.block", "bench.readback"}
    assert b.gaps == pytest.approx(old.gaps, rel=1e-9, abs=1e-15)


def test_nested_trace_gives_the_metrics_of_bench_spans_alone():
    """device_idle_pct, runner.device_ms_per_job and
    sched.dispatches_per_job read the same numbers from a trace that holds
    program spans as from the bench.* spans of it alone."""
    ev = tracered.load(str(NEW), tracered.cpu_ops)
    assert {n for _, _, n in ev.spans} == {"bench.entry", "bench.block",
                                           "bench.readback"}
    b = spans.reduce(spans.load(str(NEW), tracered.cpu_ops))
    alone = spans.Trace(devices=spans.load(str(NEW), tracered.cpu_ops)
                        .devices,
                        threads={"t": sorted(ev.spans)})
    a = spans.reduce(alone)
    assert (a.window_s, a.jobs) == (b.window_s, b.jobs)
    assert a.busy_s == b.busy_s
    run = harness.Run(seconds=1.0, jobs=[(0.0, 1.0)] * 3, commands=0,
                      setup_s=0.0, counters={"sched.dispatches": 3},
                      compiles=0, trace=tracered.reduce(ev))
    names = ["device_idle_pct", "runner.device_ms_per_job",
             "sched.dispatches_per_job"]
    vals = harness.metric_values(run, [{"name": n, "unit": "x"}
                                       for n in names])
    assert vals["device_idle_pct"]["value"] == pytest.approx(
        100 * (1 - a.busy_s / a.window_s), rel=1e-12)
    assert vals["runner.device_ms_per_job"]["value"] == pytest.approx(
        1e3 * a.busy_s / a.jobs, rel=1e-12)
    assert vals["sched.dispatches_per_job"]["value"] == 1.0


def test_nested_trace_breaks_down_entry_and_step():
    b = spans.reduce(spans.load(str(NEW), tracered.cpu_ops,
                                [HLO.read_text()]))
    for name in ("pim.sched.pipeline", "pim.sched.reads") + CHILDREN:
        assert b.spans[name]["n"] == 3, name
    entry = b.spans["bench.entry"]
    # the scheduler's spans cover the entry call: its own time is small
    assert entry["self_s"] < 0.1 * entry["total_s"]
    covered = sum(b.gaps.get(n, 0.0) for n in b.gaps
                  if n.startswith("pim.sched."))
    assert covered > 0.9 * (covered + b.gaps.get("bench.entry", 0.0))
    for scope in ("pim.runner.row_math", "pim.runner.host_io",
                  "pim.runner.meter_fold", "pim.step.bus_fold"):
        assert b.scopes[scope] > 0, scope
    assert sum(b.scopes.values()) == pytest.approx(b.busy_s, rel=1e-9)


def test_profile_a_tiny_cell(tmp_path):
    """``spans.profile`` end to end on the CPU: one traced window of the
    cell, its trace kept, the breakdown joined to the scheduler's own
    compiled program."""
    from repro.core import pim
    spec = tiny_spec("shift_n512")
    res, b = spans.profile(spec, pim, 2**31 + 5, 0.3, str(tmp_path),
                           tracered.cpu_ops)
    assert res["correct"], res["checks"]
    old = tracered.reduce(tracered.load(tracered.find_xplane(str(tmp_path)),
                                        tracered.cpu_ops))
    assert (b.window_s, b.jobs) == (old.window_s, old.jobs)
    assert b.spans["pim.sched.pipeline"]["n"] == b.jobs
    for name in CHILDREN:
        assert b.spans[name]["n"] == b.jobs, name
    assert b.scopes["pim.runner.row_math"] > 0
    assert b.scopes["pim.runner.meter_fold"] > 0
    assert sum(b.gaps.values()) == pytest.approx(b.idle_s, rel=1e-9)
    d = b.as_dict()
    assert set(d) >= {"window_s", "busy_s", "idle_s", "jobs", "spans",
                      "gaps", "scopes", "scope_ops"}


def test_spans_cli_refuses_a_cpu():
    """Like ``run.py``, the breakdown needs the chip: on the CPU it exits 2
    and prints nothing on standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/spans.py", "--workload",
                        "shift_n1", "--seed", "1", "--seconds", "1",
                        "--out", "unused"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "needs a tpu chip" in p.stderr


# -- the upload counter -------------------------------------------------------

def _metric(name):
    return harness.load_module("metrics", name, ROOT).value


def test_upload_bytes_metric_reads_traced_runs_only():
    value = _metric("sched.upload_bytes_per_job")
    run = harness.Run(seconds=1.0, jobs=[(0.0, 1.0)] * 4, commands=0,
                      setup_s=0.0, counters={"sched.upload_bytes": 4 * 512},
                      compiles=0, trace=object())
    assert value(run) == 512
    run.trace = None
    assert value(run) is None
    # a program without the counter: nothing to read, and no error
    assert value(harness.Run(1.0, [(0.0, 1.0)], 0, 0.0, {}, 0,
                             trace=object())) is None


@pytest.mark.parametrize("name", ["shift_n512", "shift_n1"])
def test_upload_bytes_exact_per_job(name):
    """Every job uploads its slots' fresh rows once: slots x 1 payload x
    words x 4 B."""
    from repro.core import pim
    spec = tiny_spec(name)
    res = harness.measure(spec, pim, 2**32 + 9, 0.1, False,
                          time.perf_counter())
    run = res["run"]
    dev = spec.config["device"]
    n_slots = (dev["channels"] * dev["ranks"] * dev["banks_per_rank"]
               * dev["subarrays"])
    assert run.counters["sched.upload_bytes"] == (
        len(run.jobs) * n_slots * dev["words"] * 4)

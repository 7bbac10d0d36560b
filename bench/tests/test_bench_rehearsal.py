"""CPU rehearsal of the benchmark at a tiny geometry: each cell's jobs
against the plain reference, the lower-precision control, the faults the
check must catch, fresh inputs per job, files found by name, and the
refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import ml_dtypes
import numpy as np
import pytest

import harness
from bench_cases import ROOT, tiny_spec

CELLS = ("shift_n512", "shift_n1")


@pytest.fixture(scope="module")
def pim():
    from repro.core import pim as pim_mod
    return pim_mod


def _span(name):
    import contextlib
    return contextlib.nullcontext()


def _jobs(spec, pim, seed, n):
    cell = spec.make_cell(pim, seed)
    return cell, harness.first_jobs(cell, n, _span)


@pytest.mark.parametrize("name", CELLS)
def test_jobs_match_reference_and_control_fails(name, pim):
    spec = tiny_spec(name)
    limits = spec.traffic["limits"]
    for seed in (3, 2**31 + 11, 2**33 + 1):
        cell, kept = _jobs(spec, pim, seed, 2)
        nums = harness.check(cell, kept, limits)
        assert all(nums[k] <= lim for k, lim in limits.items()), nums
        ctrl = harness.check(cell, [(inp, None) for inp, _ in kept], limits,
                             control=ml_dtypes.bfloat16)
        assert ctrl["meter_rel_err"] > limits["meter_rel_err"], ctrl
        assert ctrl["reads_bad_words"] == 0      # bits are exact either way


@pytest.mark.parametrize("name", CELLS)
def test_commands_per_job_counts_the_reference(name, pim):
    spec = tiny_spec(name)
    cell = spec.make_cell(pim, 1)
    # ISSUE, HOSTW, k SHIFTs, HOSTR in each of the 8 slots, one step a job
    k = spec.traffic["shift_k"]
    assert cell.commands_per_job == 8 * (3 + k)
    # each SHIFT is metered as one shift of 4 AAPs
    _, kept = _jobs(spec, pim, 1, 1)
    meter = kept[0][1]["meter"]
    assert meter["n_shift"].sum() == 8 * k
    assert meter["n_aap"].sum() == 8 * 4 * k


@pytest.mark.parametrize("name", CELLS)
def test_inputs_repeat_per_seed_and_index(name, pim):
    spec = tiny_spec(name)
    a = spec.make_cell(pim, 5)
    b = spec.make_cell(pim, 5)

    assert np.array_equal(a.inputs(4), b.inputs(4))
    assert not np.array_equal(a.inputs(4), a.inputs(5))


def _objects(steps):
    """Every object of a job's step layouts that a scheduler cache could
    key on by identity: payload arrays, programs, step lists."""
    out = [steps]
    for step in steps:
        out.append(step)
        for p in step:
            out.append(p)
            out.extend(p.payloads)
    return out


@pytest.mark.parametrize("name", CELLS)
def test_second_job_hits_no_identity_cache(name, pim, monkeypatch):
    from repro.core.pim import schedule as _  # noqa: F401
    sched = sys.modules["repro.core.pim.schedule"]
    spec = tiny_spec(name)
    cell = spec.make_cell(pim, 9)
    cell.make_device()
    passed = []
    real_entry, real_get = pim.schedule_pipeline, sched._payload_cache_get

    def entry(device, steps, *a, **kw):
        passed.append(_objects(steps))
        return real_entry(device, steps, *a, **kw)

    hits = []

    def spy(key):
        hit = real_get(key)
        if hit is not None and key[0] != "zeros":
            hits.append(key)
        return hit

    monkeypatch.setattr(pim, "schedule_pipeline", entry)
    cell.run(cell.inputs(0), _span)
    monkeypatch.setattr(sched, "_payload_cache_get", spy)
    cell.run(cell.inputs(1), _span)
    first, second = ({id(o) for o in objs} for objs in passed)
    assert not first & second
    assert hits == []


def _measure(spec, pim, seed=7, seconds=0.2):
    return harness.measure(spec, pim, seed, seconds, False,
                           time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_measure_end_to_end(name, pim):
    spec = tiny_spec(name)
    res = _measure(spec, pim)
    assert res["correct"], res["checks"]
    run = res["run"]
    assert res["attempted"] == len(run.jobs) >= 1 and res["failed"] == 0
    assert run.compiles == 0
    assert run.counters["sched.dispatches"] == len(run.jobs)
    assert run.counters["sched.plan_misses"] == 0
    assert run.counters["sched.payload_hits"] == 0
    assert run.counters["runner.traces"] == 0
    vals = harness.metric_values(run, spec.end_to_end)
    assert vals["sim_cmds_per_s"]["value"] > 0
    assert vals["setup_s"]["value"] > 0
    per = harness.metric_values(run, spec.per_layer)
    assert per == {"sched.dispatches_per_job": {"value": 1.0,
                                                "unit": "count"},
                   "sched.compiles_in_window": {"value": 0, "unit": "count"}}


# -- faults the check has to catch -----------------------------------------

def _state_unchanged(real):
    def f(device, *a, **kw):
        res = real(device, *a, **kw)
        res.state = device
        return res
    return f


def _half_batch(real):
    def f(device, steps, *a, **kw):
        steps = [list(s[:len(s) // 2]) + [None] * (len(s) - len(s) // 2)
                 for s in steps]
        return real(device, steps, *a, **kw)
    return f


def _altered_answer(real):
    """One bit of the first read flipped where the scheduler returns it."""
    def f(*a, **kw):
        res = real(*a, **kw)
        reads = res.reads
        step0 = reads[0]
        row = np.array(step0[0][0])
        row[0] ^= 1
        reads[0] = ((row,) + tuple(step0[0][1:]),) + tuple(step0[1:])
        return res
    return f


# The cells have no exchange between chips, so no fault leaves one out.
FAULTS = (_state_unchanged, _half_batch, _altered_answer)


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS for fault in FAULTS],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_run_incorrect(name, fault, pim, monkeypatch):
    entry = "schedule_pipeline"
    monkeypatch.setattr(pim, entry, fault(getattr(pim, entry)))
    res = _measure(tiny_spec(name), pim, seconds=0.1)
    assert not res["correct"], res["checks"]


# -- files found by name ------------------------------------------------------

def test_new_config_traffic_and_metric_found_by_name(tmp_path, pim):
    """A later cell, configuration and metric arrive as new files and
    entries only: nothing existing is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/shift_table23.json").read_text())
    conf["name"] = "shift_one_rank"
    conf["device"].update(channels=1, ranks=1, banks_per_rank=2,
                          subarrays=1, num_rows=16, words=8)
    (tmp_path / "bench/configs/shift_one_rank.json").write_text(
        json.dumps(conf))
    traffic = json.loads((ROOT / "bench/traffic/shift_n1.json")
                         .read_text())
    traffic.update(steps=3, shift_k=1, pool_rows=32)
    (tmp_path / "bench/traffic/shift_n1_tiny.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/jobs_per_s.py").write_text(
        "def value(run):\n    return len(run.jobs) / run.seconds\n")
    b["configs"].append({"name": "shift_one_rank", "source": "test",
                         "file": "bench/configs/shift_one_rank.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "shift_n1_tiny",
                           "config": "shift_one_rank",
                           "traffic": "shift_n1_tiny", "chips": 1,
                           "why": "test"})
    b["end_to_end"].append({"name": "jobs_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["shift_n1_tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    spec = harness.Spec.load("shift_n1_tiny", tmp_path)
    assert spec.family == "shift_pipeline"
    assert [m["name"] for m in spec.end_to_end] == [
        "sim_cmds_per_s", "job_p95_ms", "setup_s", "jobs_per_s"]
    res = _measure(spec, pim)
    assert res["correct"], res["checks"]
    vals = harness.metric_values(res["run"], spec.end_to_end, spec.root)
    assert vals["jobs_per_s"]["value"] > 0
    assert res["cell"].commands_per_job == 2 * 3 * 4


# -- the command line ---------------------------------------------------------

def _run_cli(cwd, code):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_tpu():
    p = _run_cli(ROOT, "import runpy, sys; sys.argv = ['bench/run.py', "
                       "'--workload', 'shift_n512', '--seed', '1', "
                       "'--seconds', '1', '--trace', '0']; "
                       "runpy.run_path('bench/run.py', run_name='__main__')")
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "needs 1 tpu chip" in p.stderr


def test_run_fails_with_benchmark_files_alone(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program to
    measure: the run fails and prints no result, even where it passes the
    look for a chip."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = _run_cli(tmp_path, "import sys; sys.path.insert(0, 'bench'); "
                           "import run; run.PLATFORM = 'cpu'; "
                           "sys.exit(run.main(['--workload', 'shift_n1', "
                           "'--seed', '1', '--seconds', '1']))")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr


def test_traced_run_reads_per_layer_metrics(tmp_path, pim):
    """A traced run on the CPU, where XLA's client threads stand in for the
    device: every per-layer metric of the cell reads a number."""
    import tracered
    spec = tiny_spec("shift_n512")
    res = harness.measure(spec, pim, 4, 0.3, True, time.perf_counter(),
                          trace_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    run = res["run"]
    run.trace = tracered.reduce(tracered.load(
        tracered.find_xplane(str(tmp_path)), tracered.cpu_ops))
    assert 0 < run.trace.jobs <= len(run.jobs)
    vals = harness.metric_values(run, spec.per_layer)
    assert set(vals) == {m["name"] for m in spec.per_layer}
    assert 0 <= vals["device_idle_pct"]["value"] < 100
    assert vals["runner.device_ms_per_job"]["value"] > 0


def test_run_prints_the_result_line(tmp_path):
    """``run.main`` end to end on the CPU, with the look for a chip pointed
    at the CPU and the cell cut to the test size: the
    last line of standard output is the result, the checks are the last
    lines of standard error."""
    from bench_cases import TINY_DEVICE, TINY_TRAFFIC
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    conf = tmp_path / "bench/configs/shift_table23.json"
    c = json.loads(conf.read_text())
    c["device"].update(TINY_DEVICE)
    conf.write_text(json.dumps(c))
    tr = tmp_path / "bench/traffic/shift_n512.json"
    tr.write_text(json.dumps(dict(json.loads(tr.read_text()),
                                  **TINY_TRAFFIC["shift_n512"])))
    p = _run_cli(tmp_path, "import sys; sys.path.insert(0, 'bench'); "
                           "import run; run.PLATFORM = 'cpu'; "
                           "sys.exit(run.main(['--workload', 'shift_n512',"
                           " '--seed', '3000000000', '--seconds', '1']))")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"sim_cmds_per_s", "job_p95_ms",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    err = p.stderr.strip().splitlines()
    assert [x.split(":")[0] for x in err[-len(line["checks"]):]] == [
        f"check {k}" for k in line["checks"]]

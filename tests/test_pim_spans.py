"""The scheduler's profiler spans and upload counter, and the named scopes
of the step and the runner.

Spans (``jax.profiler.TraceAnnotation``) go into the profiler's trace; the
tests record one on the CPU with the Python tracer off and read it back.
Scopes (``jax.named_scope``) only name operations: they show in the
compiled program's ``op_name`` metadata, and the differential tests hold
every simulated statistic to the eager path."""
import glob
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pim

# the package re-exports schedule() the function, shadowing the module
pim_schedule = importlib.import_module("repro.core.pim.schedule")

ROWS, WORDS = 32, 8
CHILDREN = ("pim.sched.lower", "pim.sched.plan", "pim.sched.payloads",
            "pim.sched.dispatch")


def _cfg():
    return pim.DeviceConfig(channels=1, ranks=1, banks_per_rank=2,
                            subarrays=2, num_rows=ROWS, words=WORDS)


def _prog(row, k=40, copy_to=None):
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.write_row(0, row)
    b.shift_k(0, 1, k)              # k >= 32: one fused shift run
    b.ambit_xor(0, 1, 2)
    b.read_row(2)
    if copy_to is not None:         # a cross-slot COPY, drained after
        b.copy_row(2, 5, dst_bank=copy_to[0], dst_sub=copy_to[1])
    return b.build()


def _layout(cfg, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [_prog(rng.integers(0, 2**32, WORDS, dtype=np.uint32), **kw)
            for _ in range(cfg.n_slots)]


def _upload(cfg, n_steps):
    return n_steps * cfg.n_slots * 1 * WORDS * 4


# -- the upload counter --------------------------------------------------------

def test_upload_bytes_exact_and_not_counted_on_a_cache_hit():
    cfg = _cfg()
    dev = pim.make_device(cfg)
    steps = [_layout(cfg, 1), _layout(cfg, 2)]
    pim.schedule_pipeline(dev, steps)
    assert pim_schedule.SCHED_STATS["upload_bytes"] == _upload(cfg, 2)
    pim.schedule_pipeline(dev, steps)          # the same payload arrays
    assert pim_schedule.SCHED_STATS["upload_bytes"] == _upload(cfg, 2)
    pim.schedule(dev, _layout(cfg, 3))         # fresh ones
    assert pim_schedule.SCHED_STATS["upload_bytes"] == _upload(cfg, 3)


def test_upload_bytes_zero_without_payloads():
    cfg = _cfg()
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.shift_k(0, 1, 3)
    pim.schedule(pim.make_device(cfg), [b.build()] * cfg.n_slots)
    assert pim_schedule.SCHED_STATS["upload_bytes"] == 0


def _two_groups(cfg, seed):
    """A fresh layout of two stream groups (shift by 40 and by 3)."""
    rng = np.random.default_rng(seed)
    return [_prog(rng.integers(0, 2**32, WORDS, dtype=np.uint32),
                  k=40 if s % 2 else 3) for s in range(cfg.n_slots)]


_CALLS = {
    # name: (call on layouts made by `new`, steps whose rows are uploaded)
    "schedule": (lambda dev, new: pim.schedule(dev, new()), 1),
    "pipeline_k1": (lambda dev, new: pim.schedule_pipeline(dev, [new()]), 1),
    "pipeline_replicated": (
        lambda dev, new: pim.schedule_pipeline(dev, new(), n_steps=3), 1),
    "pipeline_distinct": (
        lambda dev, new: pim.schedule_pipeline(dev, [new(), new(), new()]),
        3),
    "workload": (lambda dev, new: pim.schedule_workload(
        dev, [[new()], [new(), new()]]), 3),
}


@pytest.mark.parametrize("kind", sorted(_CALLS))
def test_payload_hits_misses_and_upload_bytes_exact(kind):
    """One payload-cache lookup per stream group (and phase): fresh rows
    miss and upload each distinct step's rows once; the same programs
    again hit and upload nothing."""
    cfg = _cfg()
    dev = pim.make_device(cfg)
    call, n_up = _CALLS[kind]
    lookups = 2 * (2 if kind == "workload" else 1)
    seeds = iter(range(100, 200))
    made = []

    def new():
        made.append(_two_groups(cfg, next(seeds)))
        return made[-1]

    def again():                        # the same objects, in order
        return made.pop(0)

    stats = pim_schedule.SCHED_STATS
    call(dev, new)
    assert (stats["payload_hits"], stats["payload_misses"]) == (0, lookups)
    assert stats["upload_bytes"] == _upload(cfg, n_up)
    call(dev, again)
    assert (stats["payload_hits"], stats["payload_misses"]) == (lookups,
                                                                lookups)
    assert stats["upload_bytes"] == _upload(cfg, n_up)
    call(dev, new)
    assert (stats["payload_hits"], stats["payload_misses"]) == (
        lookups, 2 * lookups)
    assert stats["upload_bytes"] == _upload(cfg, 2 * n_up)


def test_payload_lookups_without_payloads_upload_nothing():
    cfg = _cfg()
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.shift_k(0, 1, 3)
    dev = pim.make_device(cfg)
    pim.schedule_pipeline(dev, [b.build()] * cfg.n_slots, n_steps=2)
    pim.schedule_pipeline(dev, [b.build()] * cfg.n_slots, n_steps=2)
    stats = pim_schedule.SCHED_STATS
    assert (stats["payload_hits"], stats["payload_misses"]) == (1, 1)
    assert stats["upload_bytes"] == 0


# -- host spans ---------------------------------------------------------------

def _spans(tmp_path, fn):
    """``pim.*`` host spans that ``fn`` writes into a trace:
    ``(start_ns, end_ns, name, stats)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("pim.")]
    return sorted(out)


def _entry(kind, cfg, dev):
    """The entry point's call on fresh layouts (so no identity cache of
    the scheduler skips a layer)."""
    if kind == "schedule":
        return lambda: pim.schedule(dev, _layout(cfg, 7))
    if kind == "pipeline":
        return lambda: pim.schedule_pipeline(
            dev, [_layout(cfg, 7), _layout(cfg, 8)])
    order = (0, 1) if kind == "workload_order" else None
    return lambda: pim.schedule_workload(
        dev, [[_layout(cfg, 7)], [_layout(cfg, 9, k=3)]], order=order)


@pytest.mark.parametrize("kind", ["schedule", "pipeline", "workload",
                                  "workload_order"])
def test_entry_points_emit_the_same_children(kind, tmp_path):
    """Every entry point's span holds the same four children, tagged with
    the call's ordinal; no span is made per slot or per step."""
    cfg = _cfg()
    dev = pim.make_device(cfg)
    run = _entry(kind, cfg, dev)
    run()                                       # compiles, untraced
    pim.reset_stats()
    got = _spans(tmp_path, run)
    top = "pim.sched." + kind.split("_")[0]
    (t0, t1, _, stats), = [s for s in got if s[2] == top]
    assert stats["call"] == 0
    assert pim_schedule.SCHED_STATS["dispatches"] == 1
    names = [n for _, _, n, _ in got]
    assert set(names) == {top, *CHILDREN}
    for s, e, n, st in got:
        assert t0 <= s and e <= t1, n
        assert st["call"] == 0, n
    # at most one span of a layer per phase (two phases in a workload)
    for child in CHILDREN:
        assert names.count(child) <= 2, child
    assert cfg.n_slots > 2


def test_workload_identity_hit_only_dispatches(tmp_path):
    cfg = _cfg()
    dev = pim.make_device(cfg)
    phases = [pim_schedule.Phase(steps=(_layout(cfg, 7),)),
              pim_schedule.Phase(steps=(_layout(cfg, 9, k=3),))]
    pim.schedule_workload(dev, phases)
    got = _spans(tmp_path, lambda: pim.schedule_workload(dev, phases))
    assert [n for _, _, n, _ in got] == ["pim.sched.workload",
                                         "pim.sched.dispatch"]


def test_reads_span(tmp_path):
    cfg = _cfg()
    res = pim.schedule_pipeline(pim.make_device(cfg), [_layout(cfg)])
    got = _spans(tmp_path, lambda: res.reads)
    assert [n for _, _, n, _ in got] == ["pim.sched.reads"]
    got = _spans(tmp_path / "again", lambda: res.reads)   # memoized
    assert got == []


# -- device scopes ------------------------------------------------------------

def test_scopes_in_the_compiled_step():
    cfg = _cfg()
    dev = pim.make_device(cfg)
    layout = _layout(cfg, copy_to=(1, 0))
    pim.schedule(dev, layout)
    (plan,) = pim_schedule._plan_cache.values()
    payloads = tuple(pim_schedule._payload_stack([layout[k] for k in slots],
                                                 WORDS)
                     for slots in plan.group_slots)
    text = plan.fn.lower(dev.banks, jnp.float32(0), payloads).compile(
        ).as_text()
    for scope in ("pim.step.copy_drain", "pim.step.bus_fold",
                  "pim.runner.row_math", "pim.runner.residual_scan",
                  "pim.runner.host_io", "pim.runner.meter_fold"):
        assert f"/{scope}/" in text, scope


def test_scopes_in_the_lowered_runner():
    prog = _prog(np.arange(WORDS, dtype=np.uint32))
    runner = pim.make_runner(pim.compile_program(prog), refresh=True,
                             payload_arg=True)
    state = pim.make_subarray(ROWS, WORDS)
    text = runner.traced.lower(state, jnp.zeros((1, WORDS), jnp.uint32)
                               ).as_text(debug_info=True)
    for scope in ("pim.runner.row_math", "pim.runner.residual_scan",
                  "pim.runner.host_io", "pim.runner.meter_fold"):
        assert f"/{scope}/" in text, scope

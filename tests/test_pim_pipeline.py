"""Columnar IR, single-dispatch scheduler, and pipeline regression guards.

Locks down the host-side performance model (DESIGN.md §10): the cached
columnar encoding + O(1) stream digests (no re-hash on warm cache hits),
the vectorized cost-table gather (bit-exact vs the per-op reference), the
single-dispatch ``schedule()`` step (1 compile, then 0 — and exactly one
XLA dispatch per step), the payload-stack cache, and the ``lax.scan``
pipeline APIs (``schedule_pipeline`` / ``PimVM.run_pipeline``) being
bit-exact against the per-step path.
"""
import dataclasses
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pim
from repro.core.bitplane import PimVM
from repro.core.pim import compile as pim_compile
from repro.core.pim import exec as pim_exec
from repro.core.pim import ir

# the package re-exports schedule() the function, shadowing the module
pim_schedule = importlib.import_module("repro.core.pim.schedule")

WORDS = 8
ROWS = 32
T = pim.DEFAULT_TIMING


def _rand_row(rng, words=WORDS):
    return rng.integers(0, 2**32, (words,), dtype=np.uint32)


def _step_prog(data, k=4, rows=ROWS, words=WORDS):
    b = pim.ProgramBuilder(rows, words)
    b.issue()
    b.write_row(0, data)
    b.shift_k(0, 1, k)
    b.ambit_xor(0, 1, 2)
    b.read_row(2)
    return b.build()


def _cfg(channels=1, ranks=1, banks_per_rank=4):
    return pim.DeviceConfig(channels=channels, ranks=ranks,
                            banks_per_rank=banks_per_rank,
                            num_rows=ROWS, words=WORDS)


# Mid-test counter resets (post-warm) go through the shared helper; the
# autouse conftest fixture already zeroes everything per-test.
_reset_stats = pim.reset_stats


# ---------------------------------------------------------------------------
# Columnar encoding & digests
# ---------------------------------------------------------------------------

def test_columns_built_once_and_digest_cached():
    """build() warms the columnar encoding; stream_key/digest/cost passes
    never rebuild it (the no-re-hash-on-warm-hit regression)."""
    rng = np.random.default_rng(0)
    prog = _step_prog(_rand_row(rng))
    n0 = ir.COLUMN_STATS["builds"]
    for _ in range(5):
        pim.stream_key(prog)
        prog.digest
        prog.columns
    pim.cost_tables(prog)
    pim.cost_pass(prog)
    assert ir.COLUMN_STATS["builds"] == n0


def test_compiled_for_warm_hit_does_not_rehash():
    """_compiled_for on a warm cache entry is pure dict traffic: no new
    columnar builds, no compile misses."""
    rng = np.random.default_rng(1)
    prog = _step_prog(_rand_row(rng))
    first = pim_schedule._compiled_for(prog, T)
    _reset_stats()
    n0 = ir.COLUMN_STATS["builds"]
    for _ in range(10):
        assert pim_schedule._compiled_for(prog, T) is first
    assert ir.COLUMN_STATS["builds"] == n0
    assert pim_schedule.SCHED_STATS["compile_misses"] == 0


def test_with_payloads_shares_columns():
    rng = np.random.default_rng(2)
    prog = _step_prog(_rand_row(rng))
    n0 = ir.COLUMN_STATS["builds"]
    clone = prog.with_payloads([_rand_row(rng)])
    assert clone.columns is prog.columns
    assert clone.digest == prog.digest
    assert ir.COLUMN_STATS["builds"] == n0
    # payload DATA is excluded from the stream key (same count -> same key)
    assert pim.stream_key(clone) == pim.stream_key(prog)
    # ...but a different payload COUNT does change it
    extra = prog.with_payloads(list(prog.payloads) + [_rand_row(rng)])
    assert pim.stream_key(extra) != pim.stream_key(prog)


def test_digest_distinguishes_streams():
    b1 = pim.ProgramBuilder(ROWS, WORDS).rowclone(0, 1).build()
    b2 = pim.ProgramBuilder(ROWS, WORDS).rowclone(0, 2).build()
    b3 = pim.ProgramBuilder(ROWS, WORDS).rowclone(0, 1).build()
    assert b1.digest != b2.digest
    assert b1.digest == b3.digest           # content-addressed, not id


# ---------------------------------------------------------------------------
# Vectorized cost tables
# ---------------------------------------------------------------------------

def _mixed_program(rng, n_ops=24):
    user = ROWS - 8
    b = pim.ProgramBuilder(ROWS, WORDS)
    pick = lambda n: [int(r) for r in rng.choice(user, n, replace=False)]
    for kind in rng.choice(
            ["rowclone", "dra", "tra", "shift", "chain", "copy", "xor",
             "not", "maj", "write", "read", "fill", "issue"], n_ops):
        if kind == "rowclone":
            b.rowclone(*pick(2))
        elif kind == "dra":
            b.dra(*pick(2))
        elif kind == "tra":
            b.tra(*pick(3))
        elif kind == "shift":
            b.shift(*pick(2), int(rng.choice([-1, 1])))
        elif kind == "chain":
            src, dst = pick(2)
            b.shift_k(src, dst, int(rng.integers(2, 8)))
        elif kind == "copy":
            b.copy_row(*pick(2))
        elif kind == "xor":
            b.ambit_xor(*pick(3))
        elif kind == "not":
            b.ambit_not(*pick(2))
        elif kind == "maj":
            b.ambit_maj(*pick(4))
        elif kind == "write":
            b.write_row(pick(1)[0], _rand_row(rng))
        elif kind == "read":
            b.read_row(pick(1)[0])
        elif kind == "fill":
            b.fill(pick(1)[0], int(rng.integers(0, 2**32)))
        else:
            b.issue()
    return b.build()


@pytest.mark.parametrize("seed", range(10))
def test_cost_tables_bit_exact_vs_reference(seed):
    """The columnar template gather reproduces the per-op loop row-for-row:
    same rows, same order, same float32 bit patterns."""
    prog = _mixed_program(np.random.default_rng(seed))
    f_vec, i_vec = pim.cost_tables(prog)
    f_ref, i_ref = pim.cost_tables_reference(prog)
    assert f_vec.shape == f_ref.shape
    assert np.array_equal(f_vec.view(np.uint32), f_ref.view(np.uint32))
    assert np.array_equal(i_vec, i_ref)


def test_cost_tables_rejects_cross_slot_copy():
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.copy_row(0, 1, dst_bank=1, dst_sub=0)
    with pytest.raises(ValueError, match="cross-subarray COPY"):
        pim.cost_tables(b.build())


def test_fold_block_matches_row_at_a_time():
    """The block-unrolled in-jit fold equals a strictly-sequential numpy
    accumulate bit-for-bit, including the zero-row padding tail."""
    rng = np.random.default_rng(3)
    for n in (0, 1, 63, 64, 65, 163, 400):
        f_tab = rng.uniform(0, 100, (n, 6)).astype(np.float32)
        i_tab = rng.integers(0, 3, (n, 6), dtype=np.int32)
        f0 = rng.uniform(0, 10, 6).astype(np.float32)
        i0 = rng.integers(0, 5, 6, dtype=np.int32)
        ff, fi = pim_compile._fold_tables(
            jnp.asarray(f_tab), jnp.asarray(i_tab),
            jnp.asarray(f0), jnp.asarray(i0))
        ref_f = np.add.accumulate(
            np.concatenate([f0[None], f_tab]), axis=0,
            dtype=np.float32)[-1]
        ref_i = np.add.accumulate(
            np.concatenate([i0[None], i_tab]), axis=0, dtype=np.int32)[-1]
        assert np.array_equal(np.asarray(ff).view(np.uint32),
                              ref_f.view(np.uint32)), n
        assert np.array_equal(np.asarray(fi), ref_i), n


def test_fold_blocks_match_cost_tables_reference():
    """A real stream whose event count is no multiple of the 64-row fold
    block, folded in-jit under vmap from two nonzero meters, equals the
    strictly sequential fold of the per-op reference tables bit for bit —
    the blocked path and its zero padding both run."""
    rng = np.random.default_rng(31)
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.write_row(0, _rand_row(rng))
    b.shift_k(0, 1, 21)
    b.ambit_xor(0, 1, 2)
    b.read_row(2)
    f_ref, i_ref = pim.cost_tables_reference(b.build())
    assert len(f_ref) > pim_compile._FOLD_BLOCK
    assert len(f_ref) % pim_compile._FOLD_BLOCK
    f0 = rng.uniform(0, 1e4, (2, 6)).astype(np.float32)
    i0 = rng.integers(0, 100, (2, 6), dtype=np.int32)
    ff, fi = jax.vmap(pim_compile._fold_tables, in_axes=(None, None, 0, 0))(
        jnp.asarray(f_ref), jnp.asarray(i_ref), jnp.asarray(f0),
        jnp.asarray(i0))
    for k in range(2):
        want_f = np.add.accumulate(np.concatenate([f0[k][None], f_ref]),
                                   axis=0, dtype=np.float32)[-1]
        want_i = np.add.accumulate(np.concatenate([i0[k][None], i_ref]),
                                   axis=0, dtype=np.int32)[-1]
        assert np.array_equal(np.asarray(ff[k]).view(np.uint32),
                              want_f.view(np.uint32)), k
        assert np.array_equal(np.asarray(fi[k]), want_i), k


# ---------------------------------------------------------------------------
# Single-dispatch schedule: compile/dispatch count guards
# ---------------------------------------------------------------------------

def test_recurring_schedule_is_one_compile_then_zero():
    """3-step recurring pipeline via per-step schedule(): the first step
    pays 1 plan build / 1 compile / 1 runner trace; steps 2..3 pay ZERO of
    each and exactly one XLA dispatch per step."""
    rng = np.random.default_rng(4)
    base = _step_prog(_rand_row(rng), k=9)    # stream unique to this test:
    progs = [base] + [base.with_payloads([_rand_row(rng)])   # cold caches
                      for _ in range(3)]
    dev = pim.make_device(_cfg())
    _reset_stats()
    res = pim.schedule(dev, progs)
    assert pim_schedule.SCHED_STATS["plan_misses"] == 1
    assert pim_schedule.SCHED_STATS["compile_misses"] == 1
    assert pim_exec.RUNNER_STATS["traces"] == 1
    assert pim_schedule.SCHED_STATS["dispatches"] == 1
    for _ in range(2):
        res = pim.schedule(res.state, progs)
    assert pim_schedule.SCHED_STATS["plan_misses"] == 1
    assert pim_schedule.SCHED_STATS["compile_misses"] == 1
    assert pim_exec.RUNNER_STATS["traces"] == 1
    assert pim_schedule.SCHED_STATS["dispatches"] == 3


def test_schedule_pipeline_is_one_dispatch_for_k_steps():
    rng = np.random.default_rng(5)
    base = _step_prog(_rand_row(rng))
    progs = [base.with_payloads([_rand_row(rng)]) for _ in range(4)]
    dev = pim.make_device(_cfg())
    pr = pim.schedule_pipeline(dev, progs, n_steps=3)     # warm the compile
    _reset_stats()
    pr = pim.schedule_pipeline(pr.state, progs, n_steps=3)
    assert pim_schedule.SCHED_STATS["dispatches"] == 1
    assert pim_schedule.SCHED_STATS["plan_misses"] == 0
    assert pim_schedule.SCHED_STATS["compile_misses"] == 0
    assert pim_exec.RUNNER_STATS["traces"] == 0
    assert pr.n_steps == 3


def test_payload_stack_cached_for_recurring_programs():
    """Scheduling the SAME program objects twice must not re-stack (or
    re-upload) their HOSTW payload data."""
    rng = np.random.default_rng(6)
    progs = [_step_prog(_rand_row(rng)).with_payloads([_rand_row(rng)])
             for _ in range(2)]
    # same objects -> identical cached device batch
    s1 = pim_schedule._payload_stack(progs, WORDS)
    s2 = pim_schedule._payload_stack(progs, WORDS)
    assert s1 is s2
    # different payload arrays -> a different batch
    other = [p.with_payloads([_rand_row(rng)]) for p in progs]
    s3 = pim_schedule._payload_stack(other, WORDS)
    assert s3 is not s1


def test_payload_cache_byte_budget_evicts_pinned_arrays(monkeypatch):
    """Regression: the payload cache capped entry COUNT but not bytes — a
    serving loop churning payload batches pinned device memory without
    bound. Eviction by byte budget must actually drop the pinned stacked
    arrays (verified by weakref death), not just the dict entries."""
    import gc
    import weakref

    pim_schedule._payload_cache_clear()
    rng = np.random.default_rng(20)

    def batch():
        return [_step_prog(_rand_row(rng)).with_payloads([_rand_row(rng)])
                for _ in range(2)]

    probe = batch()
    per_entry = pim_schedule._entry_nbytes(
        (pim_schedule._payload_stack(probe, WORDS),
         tuple(a for p in probe for a in p.payloads)))
    pim_schedule._payload_cache_clear()
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX_BYTES",
                        3 * per_entry)

    first = batch()
    dead = weakref.ref(pim_schedule._payload_stack(first, WORDS))
    for _ in range(4):                  # 5 entries vs a 3-entry byte budget
        pim_schedule._payload_stack(batch(), WORDS)
    assert len(pim_schedule._payload_cache) <= 3
    assert pim_schedule._payload_cache_bytes <= 3 * per_entry
    gc.collect()
    assert dead() is None, "evicted entry still pins its device batch"
    # ... and the evicted programs now re-stack to a fresh batch
    fresh = pim_schedule._payload_stack(first, WORDS)
    np.testing.assert_array_equal(
        np.asarray(fresh[0, 0]), np.asarray(first[0].payloads[0]))


def test_payload_cache_keeps_one_oversized_entry(monkeypatch):
    """The newest entry is never evicted: one batch larger than the whole
    budget must still cache (recurring pipelines would otherwise re-upload
    it every call)."""
    pim_schedule._payload_cache_clear()
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX_BYTES", 1)
    rng = np.random.default_rng(21)
    progs = [_step_prog(_rand_row(rng)).with_payloads([_rand_row(rng)])
             for _ in range(2)]
    s1 = pim_schedule._payload_stack(progs, WORDS)
    assert pim_schedule._payload_stack(progs, WORDS) is s1
    assert len(pim_schedule._payload_cache) == 1


def test_payload_cache_id_recycling_never_aliases(monkeypatch):
    """The id()-keyed cache relies on entries pinning their key arrays.
    After byte-budget eviction releases the pins, a recycled id must MISS
    and restack — never serve the dead entry's data."""
    import gc

    pim_schedule._payload_cache_clear()
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX_BYTES", 1)
    rng = np.random.default_rng(22)
    stream = _step_prog(_rand_row(rng))

    old_prog = stream.with_payloads([_rand_row(rng)])
    evicted_id = id(old_prog.payloads[0])
    old_data = old_prog.payloads[0].copy()
    pim_schedule._payload_stack([old_prog], WORDS)
    # while cached the key array is pinned: its id cannot be recycled
    assert any(isinstance(k, tuple) and evicted_id in k
               for k in pim_schedule._payload_cache)
    # a second entry evicts the first (byte budget = 1), dropping the pin
    pim_schedule._payload_stack(
        [stream.with_payloads([_rand_row(rng)])], WORDS)
    assert not any(isinstance(k, tuple) and evicted_id in k
                   for k in pim_schedule._payload_cache)
    del old_prog
    gc.collect()
    # allocate until CPython hands back the evicted id (usually instant);
    # correctness must hold either way, the loop just makes the collision
    # scenario real rather than hypothetical
    recycled = None
    for _ in range(512):
        cand = stream.with_payloads([_rand_row(rng)])
        if id(cand.payloads[0]) == evicted_id:
            recycled = cand
            break
        del cand
    if recycled is None:
        pytest.skip("allocator never recycled the id")
    assert not np.array_equal(recycled.payloads[0], old_data)
    out = pim_schedule._payload_stack([recycled], WORDS)
    np.testing.assert_array_equal(np.asarray(out[0, 0]),
                                  recycled.payloads[0])


# -- the payload path: one host pass, one upload, stored byte counts --------

def _old_batch(progs, words=WORDS):
    """The payload batch as per-program ``np.stack`` calls built it."""
    if not progs[0].payloads:
        return jnp.zeros((len(progs), 0, words), jnp.uint32)
    return jnp.asarray(np.stack(
        [np.stack(p.payloads) for p in progs]).astype(np.uint32))


def _old_xs(batches, words=WORDS):
    """The scan's xs as a device ``jnp.stack`` of per-step batches built
    it."""
    return jnp.stack([_old_batch(b, words) for b in batches])


def _raw_prog(payloads):
    """A program whose payloads are kept as given (``with_payloads`` and
    the builder would convert them to uint32)."""
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    for i in range(len(payloads)):
        b.write_row(i, np.zeros(WORDS, np.uint32))
    b.read_row(0)
    return dataclasses.replace(b.build(), payloads=tuple(payloads))


def _payload_case(case, rng):
    """Per-step program lists of one stream group, for ``case``."""
    n = 3

    def fresh(n_pay=1, dtype=np.uint32):
        if dtype == np.uint32:
            return [_raw_prog([_rand_row(rng) for _ in range(n_pay)])
                    for _ in range(n)]
        info = np.iinfo(dtype)
        return [_raw_prog([rng.integers(info.min, info.max, WORDS,
                                        dtype=dtype, endpoint=True)
                           for _ in range(n_pay)]) for _ in range(n)]
    if case == "k1":
        return [fresh()]
    if case == "k3_same":
        progs = fresh()
        return [progs, progs, progs]
    if case == "k3_distinct":
        return [fresh() for _ in range(3)]
    if case == "k3_partly_repeated":
        a, b = fresh(), fresh()
        return [a, b, a]
    if case == "no_payloads":
        return [fresh(0), fresh(0)]
    if case == "two_payloads":
        return [fresh(2), fresh(2)]
    if case == "int64":
        return [fresh(1, np.int64), fresh(1, np.int64)]
    if case == "uint8_and_int64_mixed":
        a = fresh(2, np.uint8)
        return [[_raw_prog([a[0].payloads[0],
                            rng.integers(-2**40, 2**40, WORDS)])] + a[1:]]
    raise AssertionError(case)


PAYLOAD_CASES = ["k1", "k3_same", "k3_distinct", "k3_partly_repeated",
                 "no_payloads", "two_payloads", "int64",
                 "uint8_and_int64_mixed"]


@pytest.mark.parametrize("case", PAYLOAD_CASES)
def test_payload_xs_bit_equal_to_the_old_stacks(case):
    """The one-pass xs (and schedule()'s batch) equal, bit for bit and in
    dtype and shape, what the per-program np.stack / device jnp.stack path
    gave, on a miss and on the hit that follows."""
    batches = _payload_case(case, np.random.default_rng(30))
    want = np.asarray(_old_xs(batches))
    for _ in range(2):                          # the miss, then the hit
        got = pim_schedule._group_payloads(batches, WORDS, k_axis=True)
        assert got.dtype == jnp.uint32 and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), want)
        one = pim_schedule._payload_stack(batches[0], WORDS)
        assert one.dtype == jnp.uint32 and one.shape == want.shape[1:]
        np.testing.assert_array_equal(np.asarray(one), want[0])


def _assert_byte_books():
    """The running total is the sum of the stored counts, and each stored
    count is the bytes its entry pins."""
    entries = list(pim_schedule._payload_cache.values())
    assert pim_schedule._payload_cache_bytes == sum(e.nbytes
                                                    for e in entries)
    for e in entries:
        assert e.nbytes == e.array.nbytes + sum(a.nbytes for a in e.refs)
        assert e.nbytes == pim_schedule._entry_nbytes((e.array, e.refs))


def test_payload_cache_bytes_are_the_stored_counts(monkeypatch):
    """After any sequence of puts, hits and evictions (by count and by
    bytes, over every kind of entry), the byte total equals the sum of the
    stored per-entry counts, each the nbytes of what its entry pins, and
    every entry pins the arrays its key names."""
    rng = np.random.default_rng(31)
    per_batch = 2 * 3 * WORDS * 4          # device batch + its host rows
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX", 5)
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX_BYTES",
                        7 * per_batch)
    seen = []
    for i in range(60):
        if seen and rng.random() < 0.3:
            batches, k_axis = seen[int(rng.integers(len(seen)))]
        else:
            case = PAYLOAD_CASES[int(rng.integers(len(PAYLOAD_CASES)))]
            batches, k_axis = _payload_case(case, rng), bool(i % 2)
            seen.append((batches, k_axis))
        if k_axis:
            pim_schedule._group_payloads(batches, WORDS, k_axis=True)
        else:
            pim_schedule._payload_stack(batches[0], WORDS)
        _assert_byte_books()
        assert len(pim_schedule._payload_cache) <= 5
        assert (pim_schedule._payload_cache_bytes <= 7 * per_batch
                or len(pim_schedule._payload_cache) == 1)
        for key, e in pim_schedule._payload_cache.items():
            ref_ids = {id(a) for a in e.refs}
            assert all(k in ref_ids for k in key
                       if isinstance(k, int) and k > 1 << 16)
    assert pim_schedule.SCHED_STATS["payload_misses"] > 5  # evictions ran


def test_payload_cache_put_and_evict_never_touch_a_device_array(
        monkeypatch):
    """Putting or evicting an entry whose refs hold a device array reads
    attributes only: no device dispatch, no transfer, no jaxpr trace."""
    from jax._src import array as jax_array

    stacked = jax.device_put(np.ones((1, 4, 1, WORDS), np.uint32))
    batch = jax.device_put(np.ones((4, 1, WORDS), np.uint32))
    jax.block_until_ready((stacked, batch))
    jax.clear_caches()              # so any eager op would trace anew
    traces = []

    def hear(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traces.append(kw.get("fun_name"))

    def refuse(*a, **kw):
        raise AssertionError("touched a device array")

    for name in ("__iter__", "__getitem__", "__array__", "_unstack",
                 "_chunk_iter", "_value"):
        monkeypatch.setattr(jax_array.ArrayImpl, name, property(refuse)
                            if name == "_value" else refuse)
    monkeypatch.setattr(pim_schedule, "_PAYLOAD_CACHE_MAX", 1)
    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        with jax.transfer_guard("disallow"):
            assert (pim_schedule._entry_nbytes((stacked, (batch,)))
                    == stacked.nbytes + batch.nbytes)
            pim_schedule._payload_cache_put(("steps", 1), stacked, (batch,))
            pim_schedule._payload_cache_put(("steps", 2), stacked, (batch,))
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert list(pim_schedule._payload_cache) == [("steps", 2)]   # evicted
    _assert_byte_books()
    assert traces == []


def test_workload_fast_cache_pins_key_steps():
    """_workload_fast_cache keys on Phase.steps identity; the entry must
    pin the steps' programs while cached (no stale hit for a recycled id)
    and release them when evicted."""
    import gc
    import weakref

    rng = np.random.default_rng(23)
    cfg = _cfg(banks_per_rank=2)
    dev = pim.make_device(cfg)
    base = _step_prog(_rand_row(rng))
    # the compile cache keeps one representative program per stream: let
    # it be `base`, so only the layout caches can pin the layout below
    pim.compiled_for(base)
    layout = [base.with_payloads([_rand_row(rng)]) for _ in range(2)]
    phases = [pim_schedule.Phase.repeat(layout, 2)]
    pim.schedule_workload(dev, phases)
    ref = weakref.ref(layout[0])
    del layout, phases, base
    gc.collect()
    assert ref() is not None, "cached workload entry dropped its key pin"
    # both id-keyed layout caches pin the programs; once evicted from both,
    # nothing else holds them (the payload/compile caches key on payload
    # arrays and digests, not program objects)
    pim_schedule._workload_fast_cache.clear()
    pim_schedule._phase_lower_cache.clear()
    gc.collect()
    assert ref() is None, "programs leak after workload-cache eviction"


def test_schedule_result_metrics_are_plain_floats():
    """The lazily-converted metrics still read as plain host values."""
    rng = np.random.default_rng(7)
    dev = pim.make_device(_cfg(channels=2, banks_per_rank=2))
    progs = [_step_prog(_rand_row(rng)) for _ in range(4)]
    r0 = pim.schedule(dev, progs, async_host=True)
    r1 = pim.schedule(r0.state, progs, async_host=True)
    assert isinstance(r1.host_bus_ns, float)
    assert isinstance(r1.host_overlap_ns, float)
    assert isinstance(r1.channel_bus_ns, tuple)
    assert all(isinstance(x, float) for x in r1.channel_bus_ns)
    assert r1.host_overlap_ns > 0.0
    # the async credit chains lazily (a device value, not a blocking float)
    assert isinstance(r1.state.host_credit_ns, jax.Array)


# ---------------------------------------------------------------------------
# schedule_pipeline vs per-step path: bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_host", [False, True])
def test_pipeline_bit_exact_vs_per_step(async_host):
    rng = np.random.default_rng(8)
    cfg = _cfg(channels=2, banks_per_rank=2)
    steps = []
    base = _step_prog(_rand_row(rng))
    for _ in range(4):
        steps.append([base.with_payloads([_rand_row(rng)])
                      for _ in range(4)])

    dev = pim.make_device(cfg)
    walls, energies, reads = [], [], []
    for s in steps:
        r = pim.schedule(dev, s, async_host=async_host)
        dev = r.state
        walls.append(float(r.wall_ns))
        energies.append(float(r.energy_nj))
        reads.append(r.reads)

    pr = pim.schedule_pipeline(pim.make_device(cfg), steps,
                               async_host=async_host)
    assert np.array_equal(np.asarray(dev.banks.bits),
                          np.asarray(pr.state.banks.bits))
    for f in ("time_ns", "e_act", "e_pre", "e_burst", "e_background",
              "n_act", "n_pre", "n_aap", "n_shift", "n_tra"):
        assert np.array_equal(np.asarray(getattr(dev.banks.meter, f)),
                              np.asarray(getattr(pr.state.banks.meter, f))), f
    np.testing.assert_allclose(walls, np.asarray(pr.wall_ns), rtol=1e-6)
    np.testing.assert_allclose(energies, np.asarray(pr.energy_nj),
                               rtol=1e-6)
    preads = pr.reads
    for k in range(4):
        for slot in range(4):
            assert len(reads[k][slot]) == len(preads[k][slot])
            for x, y in zip(reads[k][slot], preads[k][slot]):
                assert np.array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_allclose(float(dev.host_credit_ns),
                               float(pr.state.host_credit_ns), rtol=1e-6)


def test_pipeline_with_copy_drain_matches_per_step():
    """A recurring gather step (cross-slot COPYs) drains identically under
    the scan."""
    rng = np.random.default_rng(9)
    cfg = _cfg(banks_per_rank=4)
    load = pim.ProgramBuilder(ROWS, WORDS)
    load.write_row(1, _rand_row(rng))
    moves = [((b, 0, 1), (0, 0, 2 + b)) for b in range(1, 4)]
    progs = pim.gather_rows(cfg, moves,
                            [load.build().with_payloads([_rand_row(rng)])
                             for _ in range(4)])
    dev = pim.make_device(cfg)
    r = pim.schedule(dev, progs)
    r = pim.schedule(r.state, progs)
    pr = pim.schedule_pipeline(pim.make_device(cfg), progs, n_steps=2)
    assert np.array_equal(np.asarray(r.state.banks.bits),
                          np.asarray(pr.state.banks.bits))
    assert pr.copy_ns == pytest.approx(r.copy_ns)
    assert pr.copy_queue_ns == pytest.approx(r.copy_queue_ns)
    np.testing.assert_allclose(float(r.wall_ns),
                               np.asarray(pr.wall_ns)[1], rtol=1e-6)


def test_pipeline_rejects_non_recurring_steps():
    rng = np.random.default_rng(10)
    s1 = [_step_prog(_rand_row(rng)) for _ in range(4)]
    s2 = [_step_prog(_rand_row(rng), k=7) for _ in range(4)]   # other chain
    with pytest.raises(ValueError, match="does not recur"):
        pim.schedule_pipeline(pim.make_device(_cfg()), [s1, s2])


# ---------------------------------------------------------------------------
# PimVM.run_pipeline
# ---------------------------------------------------------------------------

def _vm_step(vm, x):
    a = vm.load(x[0])
    b = vm.load(x[1])
    r = vm.xor(a, b)
    s = vm.shift_elem(r, 1)
    vm.free(a, b, r)
    return s


@pytest.mark.parametrize("n_banks", [1, 4])
def test_vm_run_pipeline_matches_reference(n_banks):
    rng = np.random.default_rng(11)
    vm = PimVM(width=8, num_rows=96, words=16, n_banks=n_banks,
               async_host=n_banks > 1)
    vm.mask(0xFE)                       # pre-create the shift mask
    xs = [(rng.integers(0, 256, vm.lanes), rng.integers(0, 256, vm.lanes))
          for _ in range(3)]
    got = vm.run_pipeline(_vm_step, xs)
    for k, (a, b) in enumerate(xs):
        assert np.array_equal(got[k], ((a ^ b) << 1) & 0xFF), k


def test_vm_run_pipeline_is_one_dispatch_when_sharded():
    rng = np.random.default_rng(12)
    vm = PimVM(width=8, num_rows=96, words=16, n_banks=2)
    vm.mask(0xFE)
    xs = [(rng.integers(0, 256, vm.lanes), rng.integers(0, 256, vm.lanes))
          for _ in range(3)]
    vm.run_pipeline(_vm_step, xs)       # warm compile
    _reset_stats()
    vm.run_pipeline(_vm_step, xs)
    assert pim_schedule.SCHED_STATS["dispatches"] == 1
    assert pim_exec.RUNNER_STATS["traces"] == 0


def test_vmapped_fold_ulp_exact_on_nonzero_meter():
    """Regression: the block-unrolled meter fold must replay eager's f32
    additions exactly even under vmap and with a NONZERO incoming meter —
    XLA CPU fast-math reassociation of the unrolled chain drifted e_act by
    an ulp before the fold's optimization barriers."""
    rng = np.random.default_rng(14)
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.write_row(0, _rand_row(rng))
    b.shift_k(0, 1, 3)
    prog = b.build()

    s = pim.reserve_control_rows(pim.make_subarray(ROWS, WORDS))
    s, _ = pim.run_program(s, prog)
    s, _ = pim.run_program(s, prog)      # eager: strict sequential adds

    dev = pim.make_device(pim.DeviceConfig(
        channels=1, ranks=1, banks_per_rank=2, num_rows=ROWS, words=WORDS))
    r = pim.schedule(dev, [prog, prog])          # vmapped, meter zero
    r = pim.schedule(r.state, [prog, prog])      # vmapped, meter NONZERO
    for f in ("time_ns", "e_act", "e_pre", "e_burst", "e_background"):
        want = np.asarray(getattr(s.meter, f))
        got = np.asarray(getattr(r.state.banks.meter, f))
        assert np.array_equal(np.broadcast_to(want, got.shape), got), f


def test_make_pipeline_runner_cached():
    rng = np.random.default_rng(13)
    prog = _step_prog(_rand_row(rng))
    compiled = pim_schedule._compiled_for(prog, T)
    p1 = pim.make_pipeline_runner(compiled, T)
    p2 = pim.make_pipeline_runner(compiled, T)
    assert p1 is p2

"""Host-side scheduler/orchestration benchmark (``scheduler_bench.json``).

Measures the control-plane costs the columnar-IR + single-dispatch
scheduler rework targets (ISSUE 5), starting the perf trajectory for the
host orchestration path:

  * ``cost_pass_first_us``   — first call of the vectorized columnar cost
    pass on the Table 2/3 N=1000 shift stream, vs the per-op Python loop +
    jitted-scan fold it replaced (``cost_pass_loop_first_us``).
  * ``steady_steps_per_s``   — steady-state throughput of a recurring
    32-bank schedule step, per-step Python loop vs ``schedule_pipeline``'s
    single ``lax.scan`` dispatch.
  * ``dispatches_per_step``  — XLA dispatches per steady-state step
    (acceptance bar: <= 1 for the per-step path, << 1 for the pipeline).
  * ``first_compile_ms``     — one-time cost of the first schedule call on
    a fresh layout (plan build + trace + XLA compile).

Numbers are host-orchestration wall time on whatever machine runs the
bench (CPU in CI) — the point is the *ratio* trajectory, not the absolute
microseconds.
"""
import importlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pim
from repro.core.pim import compile as pim_compile

pim_schedule = importlib.import_module("repro.core.pim.schedule")

TABLE23_SHIFTS = 1000
PIPELINE_STEPS = 100
BANKS = 32
ROWS, WORDS = 64, 64


def bench_cost_pass(report=print):
    """Columnar gather + numpy fold vs per-op loop + jitted scan fold."""
    prog = pim.shift_workload_program(TABLE23_SHIFTS, ROWS, WORDS)

    # Reference (pre-columnar) path FIRST, before anything warms the
    # _fold_tables jit cache: per-op Python table build + compiled fold.
    t0 = time.perf_counter()
    f_tab, i_tab = pim.cost_tables_reference(prog)
    f0 = jnp.zeros(6, jnp.float32)
    i0 = jnp.zeros(6, jnp.int32)
    ff, fi = pim_compile._fold_tables(jnp.asarray(f_tab), jnp.asarray(i_tab),
                                      f0, i0)
    jax.block_until_ready(ff)
    loop_first_us = (time.perf_counter() - t0) * 1e6

    t0 = time.perf_counter()
    meter = pim.cost_pass(prog)
    cost_first_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    meter = pim.cost_pass(prog)
    cost_warm_us = (time.perf_counter() - t0) * 1e6

    exact = float(meter.time_ns) == float(ff[0])
    report(f"cost pass (loop+scan, first) : {loop_first_us:12.1f} us")
    report(f"cost pass (columnar, first)  : {cost_first_us:12.1f} us  "
           f"({loop_first_us / cost_first_us:.1f}x, bit-exact={exact})")
    report(f"cost pass (columnar, warm)   : {cost_warm_us:12.1f} us")
    return {
        "cost_pass_loop_first_us": loop_first_us,
        "cost_pass_first_us": cost_first_us,
        "cost_pass_warm_us": cost_warm_us,
        "cost_pass_first_speedup": loop_first_us / cost_first_us,
        "cost_pass_bit_exact": exact,
    }


def _step_programs(rng):
    """One recurring 32-bank step — the paper's streaming shape: load a
    fresh row into each bank, run the 40-shift chain in-DRAM, read the
    result back. Same stream everywhere, per-bank payload data."""
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.write_row(0, rng.integers(0, 2 ** 32, (WORDS,), dtype=np.uint32))
    b.shift_k(0, 1, 40)
    b.read_row(1)
    base = b.build()
    return [base] + [
        base.with_payloads(
            [rng.integers(0, 2 ** 32, (WORDS,), dtype=np.uint32)])
        for _ in range(BANKS - 1)]


def bench_pipeline(report=print, reps=3):
    rng = np.random.default_rng(0)
    cfg = pim.paper_device(BANKS, num_rows=ROWS, words=WORDS)
    progs = _step_programs(rng)

    # First schedule call on a fresh layout: plan + trace + XLA compile.
    dev = pim.make_device(cfg)
    t0 = time.perf_counter()
    res = pim.schedule(dev, progs)
    jax.block_until_ready(res.state.banks.bits)
    first_compile_ms = (time.perf_counter() - t0) * 1e3

    # Steady state (best of `reps` — host timing is noisy in CI),
    # per-step Python loop vs one lax.scan dispatch.
    stats = pim_schedule.SCHED_STATS
    dev = res.state
    pr = pim.schedule_pipeline(dev, progs, n_steps=PIPELINE_STEPS)
    jax.block_until_ready(pr.state.banks.bits)
    loop_s, pipe_s = float("inf"), float("inf")
    for _ in range(reps):
        d0 = stats["dispatches"]
        t0 = time.perf_counter()
        for _ in range(PIPELINE_STEPS):
            res = pim.schedule(dev, progs)
            dev = res.state
        jax.block_until_ready(dev.banks.bits)
        loop_s = min(loop_s, time.perf_counter() - t0)
        loop_dispatch = (stats["dispatches"] - d0) / PIPELINE_STEPS

        d0 = stats["dispatches"]
        t0 = time.perf_counter()
        pr = pim.schedule_pipeline(pr.state, progs, n_steps=PIPELINE_STEPS)
        jax.block_until_ready(pr.state.banks.bits)
        pipe_s = min(pipe_s, time.perf_counter() - t0)
        pipe_dispatch = (stats["dispatches"] - d0) / PIPELINE_STEPS

    loop_sps = PIPELINE_STEPS / loop_s
    pipe_sps = PIPELINE_STEPS / pipe_s
    report(f"first schedule (fresh layout): {first_compile_ms:10.1f} ms")
    report(f"steady loop ({BANKS} banks)       : {loop_sps:10.1f} steps/s  "
           f"({loop_dispatch:.2f} dispatches/step)")
    report(f"steady pipeline (lax.scan)   : {pipe_sps:10.1f} steps/s  "
           f"({pipe_dispatch:.2f} dispatches/step, "
           f"{pipe_sps / loop_sps:.1f}x)")
    return {
        "workload": f"recurring_{BANKS}bank_step_x{PIPELINE_STEPS}",
        "first_compile_ms": first_compile_ms,
        "steady_loop_steps_per_s": loop_sps,
        "steady_pipeline_steps_per_s": pipe_sps,
        "pipeline_speedup": pipe_sps / loop_sps,
        "dispatches_per_step_loop": loop_dispatch,
        "dispatches_per_step_pipeline": pipe_dispatch,
    }


MP_BANKS = 8            # multi-phase RS workload geometry
MP_CW_PER_BANK = 8
MP_WORDS = 16
MP_ROWS = 64
MP_REPS = 5


def rs_workload(rng, *, banks: int = MP_BANKS,
                cw_per_bank: int = MP_CW_PER_BANK, rows: int = MP_ROWS,
                words: int = MP_WORDS, subarrays: int = 1):
    """The 3-phase RS(12,8) workload: encode (XOR-fold every codeword into
    per-bank accumulator rows — the fold of valid codewords is itself a
    valid codeword), reduce (log2(banks) gather+merge tree down to bank 0),
    readback. Expressed as one heterogeneous phase list for
    ``schedule_workload``; one codeword is corrupted so the folded
    syndromes are non-zero and detection is observable end-to-end.
    Programs run on subarray 0 of each bank of a
    ``paper_device(banks, subarrays=subarrays)`` of ``rows x words``."""
    from repro.core.bitplane import rs
    from repro.core.pim import isa
    n, npar = 12, 4
    lanes = words * 32 // 8
    acc, recv, stage = list(range(n)), list(range(n, 2 * n)), 2 * n
    msg = rng.integers(0, 256, size=(banks, cw_per_bank, 8, lanes))
    cw = np.zeros((banks, cw_per_bank, n, lanes), np.uint64)
    for b in range(banks):
        for k in range(cw_per_bank):
            par = rs.ref_rs_encode(msg[b, k], npar)
            cw[b, k] = np.concatenate(
                [msg[b, k].astype(np.uint64), par[::-1]], axis=0)
    cw[1, min(2, cw_per_bank - 1), 5, 3] ^= 0x5A    # one corrupted byte lane

    from repro.core.bitplane import layout as bl

    def pack(row):
        return bl.pack_elements(row, 8, words)

    cfg = pim.paper_device(banks, num_rows=rows, words=words,
                           subarrays=subarrays)
    bi = pim.ProgramBuilder(rows, words)
    for r in acc:
        bi.rowclone(isa.C0, r)
    phases = [pim.Phase.repeat([bi.build()] * banks, 1)]
    for j in range(n):                      # encode: fold codeword byte j
        b = pim.ProgramBuilder(rows, words)
        b.issue()
        b.write_row(stage, np.zeros(words, np.uint32))
        b.ambit_xor(acc[j], stage, acc[j])
        enc = b.build()
        phases.append(pim.Phase(steps=tuple(
            [enc.with_payloads([pack(cw[bk, k, j])])
             for bk in range(banks)]
            for k in range(cw_per_bank))))
    bm = pim.ProgramBuilder(rows, words)
    for j in range(n):
        bm.ambit_xor(acc[j], recv[j], acc[j])
    merge = bm.build()
    stride = 1
    while stride < banks:                   # reduce: gather+merge tree
        moves = [((b + stride, 0, acc[j]), (b, 0, recv[j]))
                 for b in range(0, banks, 2 * stride) for j in range(n)]
        phases.append(pim.Phase.repeat(pim.gather_rows(cfg, moves), 1))
        alive = set(range(0, banks, 2 * stride))
        phases.append(pim.Phase.repeat(
            [merge if b in alive else None for b in range(banks)], 1))
        stride *= 2
    br = pim.ProgramBuilder(rows, words)
    for j in range(n):
        br.read_row(acc[j])
    phases.append(pim.Phase.repeat(
        [br.build()] + [None] * (banks - 1), 1))
    return cfg, phases, cw, acc


def rs_check(state, cw, acc, words: int = MP_WORDS):
    """``(bit_exact, detected)`` for a finished :func:`rs_workload`: the
    folded codeword in bank 0 against the numpy XOR oracle, and whether
    its RS syndromes flag the injected corruption."""
    from repro.core.bitplane import layout as bl
    from repro.core.bitplane import rs
    lanes = words * 32 // 8
    bits = np.asarray(state.slot(0).bits)
    got = np.stack([bl.unpack_elements(bits[acc][j], 8, lanes)
                    for j in range(len(acc))])
    oracle = np.bitwise_xor.reduce(
        cw.reshape(-1, len(acc), lanes).astype(np.uint64), axis=0)
    return (bool(np.array_equal(got, oracle)),
            bool(np.any(rs.ref_rs_syndromes(got, 4))))


def bench_multi_phase(report=print):
    """The tentpole bar (ISSUE 6): the whole heterogeneous multi-phase
    workload as ONE dispatch vs the per-phase dispatch loop — one host
    dispatch per phase step, the O(phases x steps) baseline
    ``schedule_workload`` replaces. The ``schedule_pipeline``-per-phase
    loop (O(phases) dispatches) is reported as an extra datum."""
    rng = np.random.default_rng(0)
    cfg, phases, cw, acc = rs_workload(rng)
    n_steps = sum(len(p.steps) for p in phases)
    stats = pim_schedule.SCHED_STATS

    t0 = time.perf_counter()
    res = pim.schedule_workload(pim.make_device(cfg), phases)
    jax.block_until_ready(res.state.banks.bits)
    first_call_ms = (time.perf_counter() - t0) * 1e3

    # Correctness: the in-DRAM fold must equal the numpy XOR oracle, and
    # the folded syndromes must flag the injected corruption.
    bit_exact, detected = rs_check(res.state, cw, acc)

    # Per-phase dispatch loop reference (also warms every step layout).
    seq = [s for p in phases for s in p.steps]
    dev = pim.make_device(cfg)
    wall = energy = 0.0
    for s in seq:
        r = pim.schedule(dev, s)
        dev, wall, energy = r.state, wall + r.wall_ns, energy + r.energy_nj
    jax.block_until_ready(dev.banks.bits)
    meters_exact = (
        np.array_equal(np.asarray(dev.banks.bits),
                       np.asarray(res.state.banks.bits))
        and abs(wall - res.total_wall_ns) <= 1e-6 * wall
        and abs(energy - res.total_energy_nj) <= 1e-6 * energy)

    # Steady state: thread the device state through repeated submissions.
    wl = pim.make_device(cfg)
    wl = pim.schedule_workload(wl, phases).state
    jax.block_until_ready(wl.banks.bits)
    d0 = stats["dispatches"]
    t0 = time.perf_counter()
    for _ in range(MP_REPS):
        wl = pim.schedule_workload(wl, phases).state
    jax.block_until_ready(wl.banks.bits)
    wl_ms = (time.perf_counter() - t0) / MP_REPS * 1e3
    wl_disp = (stats["dispatches"] - d0) / MP_REPS / n_steps

    d0 = stats["dispatches"]
    t0 = time.perf_counter()
    for _ in range(MP_REPS):
        for s in seq:
            dev = pim.schedule(dev, s).state
    jax.block_until_ready(dev.banks.bits)
    loop_ms = (time.perf_counter() - t0) / MP_REPS * 1e3
    loop_disp = (stats["dispatches"] - d0) / MP_REPS / n_steps

    pp = pim.make_device(cfg)
    for p in phases:
        pp = pim.schedule_pipeline(pp, list(p.steps)).state
    jax.block_until_ready(pp.banks.bits)
    t0 = time.perf_counter()
    for _ in range(MP_REPS):
        for p in phases:
            pp = pim.schedule_pipeline(pp, list(p.steps)).state
    jax.block_until_ready(pp.banks.bits)
    pipe_ms = (time.perf_counter() - t0) / MP_REPS * 1e3

    report(f"multi-phase RS(12,8) ({len(phases)} phase segments, "
           f"{n_steps} steps): first call {first_call_ms:.0f} ms")
    report(f"  workload (1 dispatch)      : {wl_ms:8.2f} ms  "
           f"({wl_disp:.4f} dispatches/step)")
    report(f"  per-phase dispatch loop    : {loop_ms:8.2f} ms  "
           f"({loop_disp:.2f} dispatches/step, "
           f"{loop_ms / wl_ms:.1f}x slower)")
    report(f"  pipeline-per-phase loop    : {pipe_ms:8.2f} ms  "
           f"({pipe_ms / wl_ms:.1f}x slower)")
    report(f"  bit-exact={bit_exact} corruption-detected={detected} "
           f"meters-exact={meters_exact}")
    return {"multi_phase": {
        "workload": "rs_12_8_encode_reduce_readback",
        "banks": MP_BANKS, "words": MP_WORDS,
        "codewords_per_bank": MP_CW_PER_BANK,
        "phase_segments": len(phases), "steps": n_steps,
        "first_call_ms": first_call_ms,
        "steady_state_workload_ms": wl_ms,
        "steady_state_per_phase_loop_ms": loop_ms,
        "steady_state_pipeline_per_phase_ms": pipe_ms,
        "dispatches_per_step_workload": wl_disp,
        "dispatches_per_step_loop": loop_disp,
        "speedup_vs_per_phase_dispatch_loop": loop_ms / wl_ms,
        "speedup_vs_pipeline_per_phase": pipe_ms / wl_ms,
        "bit_exact_vs_oracle": bool(bit_exact),
        "meters_match_per_step_schedule": bool(meters_exact),
        "corruption_detected": detected,
    }}


def run(report=print, json_path=None):
    out = {"n_shifts": TABLE23_SHIFTS, "pipeline_steps": PIPELINE_STEPS}
    out.update(bench_cost_pass(report))
    out.update(bench_pipeline(report))
    out.update(bench_multi_phase(report))
    blob = json.dumps(out, indent=2, sort_keys=True)
    if json_path:
        with open(json_path, "w") as f:
            f.write(blob + "\n")
        report(f"wrote {json_path}")
    else:
        report(blob)
    return out


if __name__ == "__main__":
    import sys
    run(json_path=sys.argv[1] if len(sys.argv) > 1 else None)

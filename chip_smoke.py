"""Bring-up smoke run of the whole system on one TPU chip.

    python chip_smoke.py

One process drives the normal entry points once at full size and checks
every result against a plain reference:

  shift_stream  Table 2/3 ``shift_k(0, 1, 1000)`` on a fresh 8 KB row in
                every slot of ``paper_device(32, subarrays=2)`` (512 rows x
                2,048 words: 64 slots, 256 MiB of row state) as a K=8-step
                ``schedule_pipeline``; results against a numpy shift,
                meters against the host ``cost_pass``.
  rs_workload   RS(12,8) encode -> gather/merge -> readback through
                ``schedule_workload``; the folded codeword against the
                numpy XOR oracle, its syndromes must flag the corruption.
  tenants       ``PimServeFront`` with three tenants on 8/8/16 banks; each
                tenant's state and reads against its isolated run, and
                ``reconcile()``.
  kernels       the compiled text of the scheduler's cached step runners
                must call the rowops kernels (``tpu_custom_call``).
  lm_serve      Qwen3-4B at its published widths with random weights from
                a seed: ``init_params`` + ``greedy_generate`` (batch 4,
                prompt 128, 32 new tokens); the logits of the last decode
                step through the cache against a full-sequence prefill.

Times are of one bring-up run each (first call, compile included, then one
warm call), not benchmarks. Any failed check raises and exits non-zero.
The last line of stdout is the JSON device record. Without a TPU it exits
at once, naming the platform it found.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.scheduler_bench import rs_check, rs_workload  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import pim  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import init_params, prefill  # noqa: E402
from repro.serve.engine import DECODE_STATS, greedy_generate  # noqa: E402
from repro.serve.pim_front import PimServeFront  # noqa: E402

# The platform the run demands. Only the CPU rehearsal test changes it.
PLATFORM = "tpu"

SEED = 0

# Meter float fields: the differential harness's tolerance.
METER_RTOL = 1e-6

# Decode-through-cache vs full-sequence prefill, as max |diff| / max |logit|.
# Weights and activations are bf16 (8-bit mantissa: 2^-8 ~ 3.9e-3 relative
# rounding per op). The 1-token decode matmuls and cached attention round
# in another order than the 159-token prefill matmuls and flash attention,
# at each of 36 layers and again through the 31 cached decode steps; such
# independent roundings grow about as sqrt(36) * 3.9e-3 ~ 2.3e-2.
LOGIT_RTOL = 5e-2

PAPER_SHIFT_NS, PAPER_SHIFT_NJ = 208.7, 31.32     # paper Tables 2/3, N = 1


@dataclasses.dataclass(frozen=True)
class Sizes:
    banks: int
    subarrays: int
    rows: int
    words: int
    shift_steps: int
    shift_k: int
    rs_cw_per_bank: int
    tenant_banks: tuple
    tenant_steps: int
    lm_smoke: bool
    lm_batch: int
    lm_prompt: int
    lm_new: int


FULL = Sizes(banks=32, subarrays=2, rows=512, words=2048, shift_steps=8,
             shift_k=1000, rs_cw_per_bank=8, tenant_banks=(8, 8, 16),
             tenant_steps=4, lm_smoke=False, lm_batch=4, lm_prompt=128,
             lm_new=32)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _peak() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return "peak_bytes_in_use not reported"
    return f"peak_bytes_in_use {stats['peak_bytes_in_use']}"


def bring_up(phase: str, run):
    """Call ``run`` twice (cold: compile + run, then warm) and print the
    two times. ``run`` returns ``(result, arrays to block on)``."""
    t0 = time.perf_counter()
    _, arrays = run()
    jax.block_until_ready(arrays)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    result, arrays = run()
    jax.block_until_ready(arrays)
    warm = time.perf_counter() - t0
    say(phase, f"one bring-up run, not a benchmark: first call (compile + "
               f"run) {cold:.3f} s, warm call {warm:.3f} s; {_peak()}")
    return result


def paper_device(s: Sizes):
    return pim.paper_device(s.banks, num_rows=s.rows, words=s.words,
                            subarrays=s.subarrays)


def numpy_shift(rows: np.ndarray, k: int) -> np.ndarray:
    """Shift packed rows by ``k`` > 0 columns toward higher columns (column
    32*w + i is bit i of word w); columns shifted past the edge fall off
    and zeros enter at column 0 (the migration-cell boundary fill)."""
    bits = np.unpackbits(rows.astype("<u4").view(np.uint8), axis=-1,
                         bitorder="little")
    out = np.zeros_like(bits)
    out[..., k:] = bits[..., :-k]
    return np.packbits(out, axis=-1, bitorder="little").view("<u4")


def _meter_fields(meter):
    return {f.name: np.asarray(getattr(meter, f.name))
            for f in dataclasses.fields(meter)}


def shift_stream(s: Sizes, rng):
    cfg = paper_device(s)
    b = pim.ProgramBuilder(s.rows, s.words)
    b.issue()
    b.write_row(0, np.zeros(s.words, np.uint32))
    b.shift_k(0, 1, s.shift_k)
    b.read_row(1)
    base = b.build()
    data = rng.integers(0, 2**32, (s.shift_steps, cfg.n_slots, s.words),
                        dtype=np.uint32)
    steps = [[base.with_payloads([data[k, j]]) for j in range(cfg.n_slots)]
             for k in range(s.shift_steps)]

    def run():
        pr = pim.schedule_pipeline(pim.make_device(cfg), steps)
        return pr, pr.state.banks.bits

    pr = bring_up("shift_stream", run)
    want = numpy_shift(data, s.shift_k)
    reads = pr.reads
    for k in range(s.shift_steps):
        for j in range(cfg.n_slots):
            check(np.array_equal(np.asarray(reads[k][j][0]), want[k, j]),
                  f"shift_stream: step {k} slot {j} differs from the numpy "
                  f"shift by {s.shift_k}")

    ref = None
    for _ in range(s.shift_steps):
        ref = pim.cost_pass(base, cfg.timing, init=ref)
    ref = _meter_fields(ref)
    got = _meter_fields(pr.state.banks.meter)
    worst = 0.0
    for name, want_v in ref.items():
        got_v = got[name]
        if np.issubdtype(want_v.dtype, np.integer):
            check(np.all(got_v == want_v),
                  f"shift_stream: meter.{name} {got_v} != host {want_v}")
        else:
            rel = np.abs(got_v - want_v) / max(abs(float(want_v)), 1e-30)
            worst = max(worst, float(rel.max()))
            check(bool(np.all(rel <= METER_RTOL)),
                  f"shift_stream: meter.{name} rel err {rel.max()} > "
                  f"{METER_RTOL}")
    n_shift = s.shift_steps * s.shift_k
    say("shift_stream",
        f"PASS: {cfg.n_slots} slots x {s.shift_steps} steps bit-exact vs "
        f"numpy shift by {s.shift_k}; meter int fields equal host cost_pass, "
        f"float fields max rel err {worst:.3g} (rtol {METER_RTOL})")
    say("shift_stream",
        f"per shift (incl. one {s.words * 4} B host write + read per "
        f"{s.shift_k} shifts): {float(ref['time_ns']) / n_shift:.2f} ns, "
        f"{float(pr.state.banks.meter.total_energy_nj[0]) / n_shift:.3f} nJ"
        f" (paper {PAPER_SHIFT_NS} ns, {PAPER_SHIFT_NJ} nJ)")
    return base


def rs(s: Sizes, rng):
    cfg, phases, cw, acc = rs_workload(
        rng, banks=s.banks, cw_per_bank=s.rs_cw_per_bank, rows=s.rows,
        words=s.words, subarrays=s.subarrays)

    def run():
        res = pim.schedule_workload(pim.make_device(cfg), phases)
        return res, res.state.banks.bits

    res = bring_up("rs_workload", run)
    bit_exact, detected = rs_check(res.state, cw, acc, s.words)
    check(bit_exact, "rs_workload: folded codeword differs from the numpy "
                     "XOR oracle")
    check(detected, "rs_workload: syndromes missed the injected corruption")
    say("rs_workload",
        f"PASS: {len(phases)} phases, {res.n_steps} steps, "
        f"{s.banks * s.rs_cw_per_bank} codewords folded bit-exact vs numpy "
        f"XOR oracle; syndromes flag the corrupted codeword")
    return phases


def _xor_program(rows: int, words: int):
    b = pim.ProgramBuilder(rows, words)
    b.issue()
    b.write_row(24, np.zeros(words, np.uint32))
    b.ambit_xor(0, 24, 0)
    b.read_row(0)
    return b.build()


def tenants(s: Sizes, rng, shift_base):
    cfg = paper_device(s)
    xor = _xor_program(s.rows, s.words)
    bases = (shift_base, shift_base, xor)   # two coalesce, one stands apart
    workloads = {}
    for i, (nb, base) in enumerate(zip(s.tenant_banks, bases)):
        n_slots = nb * s.subarrays
        data = rng.integers(0, 2**32, (s.tenant_steps, n_slots, s.words),
                            dtype=np.uint32)
        workloads[f"t{i}"] = (nb, [[base.with_payloads([data[k, j]])
                                    for j in range(n_slots)]
                                   for k in range(s.tenant_steps)])

    def run():
        front = PimServeFront(cfg)
        for tid, (nb, steps) in workloads.items():
            front.submit(tid, steps, banks=nb)
        results = front.run()
        return (front, results), front.device.banks.bits

    front, results = bring_up("tenants", run)
    placements = {tid: front.report(tid).slots for tid in workloads}
    reads = {tid: [] for tid in workloads}
    for res in results:
        for tid in res.placements:
            got = res.tenant_reads(tid)
            reads[tid].extend(got if res.n_steps > 1 else [got])
    shared_bits = np.asarray(front.device.banks.bits)
    for tid, (nb, steps) in workloads.items():
        dev = pim.make_device(cfg.subdevice(nb))
        iso = []
        for step in steps:
            r = pim.schedule(dev, step)
            dev = r.state
            iso.append(r.reads)
        check(np.array_equal(shared_bits[list(placements[tid])],
                             np.asarray(dev.banks.bits)),
              f"tenants: {tid} state differs from its isolated run")
        check(len(reads[tid]) == len(steps), f"tenants: {tid} step count")
        for k in range(len(steps)):
            for j in range(nb * s.subarrays):
                for x, y in zip(reads[tid][k][j], iso[k][j], strict=True):
                    check(np.array_equal(np.asarray(x), np.asarray(y)),
                          f"tenants: {tid} step {k} slot {j} read differs "
                          f"from its isolated run")
    rec = front.reconcile()
    check(np.isclose(rec["tenant_energy_nj"], rec["device_energy_nj"],
                     rtol=1e-9)
          and np.isclose(rec["tenant_busy_ns"], rec["device_busy_ns"],
                         rtol=1e-9)
          and rec["tenant_host_bytes"] == rec["device_host_bytes"],
          f"tenants: reconcile() does not hold: {rec}")
    say("tenants",
        f"PASS: tenants on {'/'.join(map(str, s.tenant_banks))} banks: "
        f"states and reads equal each isolated run; reconcile() holds "
        f"({rec['device_steps']} device steps)")
    return xor


def kernels(s: Sizes, programs):
    """The scheduler caches one runner per stream and vmaps it over the
    slots of its group: compile the cached runners so, over every slot,
    and count the Pallas calls in their text."""
    cfg = paper_device(s)
    banks = jax.eval_shape(lambda: pim.make_device(cfg).banks)
    counts = {}
    for name, prog in programs.items():
        runner = pim.make_runner(pim.compiled_for(prog, cfg.timing),
                                 cfg.timing, payload_arg=True)
        payloads = jax.ShapeDtypeStruct(
            (cfg.n_slots, len(prog.payloads), s.words), jnp.uint32)
        text = jax.jit(jax.vmap(runner.traced)).lower(
            banks, payloads).compile().as_text()
        counts[name] = text.count('custom_call_target="tpu_custom_call"')
    if PLATFORM == "tpu":
        check(all(n > 0 for n in counts.values()),
              f"kernels: a scheduled runner calls no rowops kernel: "
              f"{counts}")
    say("kernels", f"{'PASS' if PLATFORM == 'tpu' else 'counted'}: "
                   f"tpu_custom_call per compiled step runner: {counts}")


def lm_serve(s: Sizes, rng):
    cfg = get_config("qwen3-4b", smoke=s.lm_smoke)
    say("lm_serve",
        f"Qwen3-4B{' (smoke)' if s.lm_smoke else ''}: d={cfg.d_model}, "
        f"{cfg.n_heads}H/{cfg.n_kv_heads}KV, head {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; depth {cfg.n_layers} of "
        f"{cfg.n_layers} layers (no cut); {cfg.dtype} weights")
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say("lm_serve", f"init_params: {n_params} parameters in "
                    f"{time.perf_counter() - t0:.3f} s; {_peak()}")
    prompts = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (s.lm_batch, s.lm_prompt)),
        jnp.int32)}

    traces = []

    def run():
        out = greedy_generate(cfg, params, prompts,
                              max_new_tokens=s.lm_new, return_logits=True)
        traces.append(DECODE_STATS["traces"])
        return out, out

    tokens, last = bring_up("lm_serve", run)
    check(traces[1] == traces[0], "lm_serve: the warm call re-traced decode")
    tokens = np.asarray(tokens)
    check(tokens.shape == (s.lm_batch, s.lm_new)
          and tokens.min() >= 0 and tokens.max() < cfg.vocab_size,
          f"lm_serve: tokens {tokens.shape} out of range")
    seq = jnp.concatenate([prompts["tokens"],
                           jnp.asarray(tokens[:, :-1], jnp.int32)], axis=1)
    ref, _ = jax.jit(prefill, static_argnames=("cfg", "max_cache_len"))(
        cfg, params, {"tokens": seq}, max_cache_len=seq.shape[1])
    ref = np.asarray(ref, np.float32).reshape(s.lm_batch, -1)
    got = np.asarray(last, np.float32).reshape(s.lm_batch, -1)
    check(got.shape == ref.shape and np.all(np.isfinite(got)),
          f"lm_serve: decode logits {got.shape} not finite or not shaped "
          f"like the reference {ref.shape}")
    rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    check(rel <= LOGIT_RTOL,
          f"lm_serve: decode vs prefill logits rel err {rel} > {LOGIT_RTOL}")
    say("lm_serve",
        f"PASS: {s.lm_batch} x {s.lm_new} tokens; last-step decode logits "
        f"vs full-sequence prefill of {seq.shape[1]} tokens: max|diff|/"
        f"max|logit| = {rel:.3g} (tolerance {LOGIT_RTOL}, bf16); argmax "
        f"agreement {agree:.2f}")


def run(sizes: Sizes) -> None:
    rng = np.random.default_rng(SEED)
    shift_base = shift_stream(sizes, rng)
    rs_phases = rs(sizes, rng)
    xor = tenants(sizes, rng, shift_base)
    encode = rs_phases[1].steps[0][0]
    kernels(sizes, {"shift_k": shift_base, "rs_encode": encode,
                    "tenant_xor": xor})
    pim.clear_caches()          # free the simulator's buffers for the LM
    lm_serve(sizes, rng)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        print(f"chip_smoke: needs a {PLATFORM} device, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"chip_smoke: compile cache {enable_compile_cache()}", flush=True)
    run(FULL)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

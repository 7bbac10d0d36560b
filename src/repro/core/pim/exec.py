"""Compiled executor for recorded PIM programs.

Lowers the fused segments of a :class:`~.compile.CompiledProgram` onto the
Pallas ``kernels/rowops`` kernels (``bitwise``, ``shift_cols``) — a k-long
chain of migration shifts becomes ONE k-column kernel shift, an Ambit MAJ
idiom becomes one bitwise kernel call — with a ``lax.scan`` interpreter for
residual primitives. The meter comes from the compile-time cost pass (one
fold over the increment tables, seeded with the incoming meter), so the
final ``SubarrayState`` is bit-exact against the eager ISA path: same bits,
same migration/DCC side state, same CostMeter to the last ulp.

``use_kernels`` defaults to kernel lowering only on real TPU backends: in
interpret mode (CPU hosts, like the rest of ``kernels/rowops``) the pure-jnp
row math produces identical uint32 results without the per-call interpreter
overhead. Force either path explicitly to compare.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import compile as pim_compile
from . import ir
from .compile import (CompiledProgram, SegHost, SegMaj, SegNot, SegScan,
                      SegShiftRun, compile_program)
from .isa import T0 as isa_T0, T1 as isa_T1, T2 as isa_T2
from .isa import maj3_words, shift_row_words
from .state import EVEN_MASK, ODD_MASK, SubarrayState, make_subarray
from .timing import DDR3Timing, DEFAULT_TIMING, apply_refresh


@dataclasses.dataclass
class ExecResult:
    """Final state plus host-read rows in ``read_row`` slot order."""

    state: SubarrayState
    reads: tuple


# How many times a runner body was (re)traced by jit. Steady-state pipelines
# must not grow this: regression tests assert "1 compile, then 0" across
# recurring schedule steps.
RUNNER_STATS = {"traces": 0}


def _as_compiled(program, cfg) -> CompiledProgram:
    if isinstance(program, CompiledProgram):
        return program
    return compile_program(program, cfg)


def _default_use_kernels() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Row math: kernel and jnp lowering produce identical uint32 results
# ---------------------------------------------------------------------------

def _shift_row(row, k: int, use_kernels: bool, interpret):
    if k == 0:
        return row
    if use_kernels:
        from ...kernels.rowops import ops as kops
        return kops.shift_cols(row[None, :], k, interpret=interpret)[0]
    return shift_row_words(row, k)


def _maj_rows(a, b, c, use_kernels: bool, interpret):
    if use_kernels:
        from ...kernels.rowops import ops as kops
        return kops.bitwise(a[None, :], b[None, :], c[None, :], op="maj",
                            interpret=interpret)[0]
    return maj3_words(a, b, c)


def _not_row(a, use_kernels: bool, interpret):
    if use_kernels:
        from ...kernels.rowops import ops as kops
        return kops.bitwise(a[None, :], op="not", interpret=interpret)[0]
    return ~a


def _shift1(row, delta: int):
    """One 1-bit shift, exactly mirroring ``shift_row_words(row, ±1)``."""
    if delta > 0:
        carry = jnp.concatenate(
            [jnp.zeros(row.shape[:-1] + (1,), jnp.uint32),
             row[..., :-1]], axis=-1) >> jnp.uint32(31)
        return (row << jnp.uint32(1)) | carry
    carry = jnp.concatenate(
        [row[..., 1:], jnp.zeros(row.shape[:-1] + (1,), jnp.uint32)],
        axis=-1) << jnp.uint32(31)
    return (row >> jnp.uint32(1)) | carry


# ---------------------------------------------------------------------------
# Residual-op lax.scan interpreter
# ---------------------------------------------------------------------------

_SCAN_COPY, _SCAN_TRA, _SCAN_NOT2DCC, _SCAN_DCC2 = 0, 1, 2, 3
_SCAN_SHIFT_R, _SCAN_SHIFT_L = 4, 5
_SCAN_MAJ, _SCAN_NOTPAIR = 6, 7          # fused macro rows (SegMaj / SegNot)

_SCAN_CODE = {ir.OP_ROWCLONE: _SCAN_COPY, ir.OP_DRA: _SCAN_COPY,
              ir.OP_COPY: _SCAN_COPY, ir.OP_TRA: _SCAN_TRA,
              ir.OP_NOT2DCC: _SCAN_NOT2DCC, ir.OP_DCC2: _SCAN_DCC2}


@dataclasses.dataclass(frozen=True)
class _SegTable:
    """Coalesced scan table: residual primitives plus fused MAJ/NOT macro
    rows, executed as ONE lax.scan loop (one trace, one XLA loop)."""

    rows: tuple  # of (code, a, b, c, d)


def _op_rows(op: ir.PimOp):
    if op.op == ir.OP_SHIFT:
        code = _SCAN_SHIFT_R if op.delta > 0 else _SCAN_SHIFT_L
    else:
        code = _SCAN_CODE[op.op]
    return (code, op.a, op.b, op.c, 0)


def _coalesce(segments, use_kernels):
    """With kernel lowering off, merge contiguous scan-able segments (incl.
    MAJ/NOT macros) into single _SegTable loops to keep traces tiny."""
    out, rows = [], []

    def flush():
        if rows:
            out.append(_SegTable(rows=tuple(rows)))
            rows.clear()

    for seg in segments:
        if isinstance(seg, SegScan):
            rows.extend(_op_rows(op) for op in seg.ops)
        elif not use_kernels and isinstance(seg, SegMaj):
            rows.append((_SCAN_MAJ, seg.a, seg.b, seg.c, seg.dst))
        elif not use_kernels and isinstance(seg, SegNot):
            rows.append((_SCAN_NOTPAIR, seg.src, seg.dst, 0, 0))
        else:
            flush()
            out.append(seg)
    flush()
    return tuple(out)


def _scan_segment(seg: _SegTable, carry, num_rows: int):
    import numpy as np
    tab = np.asarray(seg.rows, np.int32)
    code, opnd = jnp.asarray(tab[:, 0]), jnp.asarray(tab[:, 1:])
    t0, t1, t2 = (t % num_rows for t in (isa_T0, isa_T1, isa_T2))

    def do_copy(carry, a, b, c, d):
        bits, mt, mb, dcc = carry
        return bits.at[b].set(bits[a]), mt, mb, dcc

    def do_tra(carry, a, b, c, d):
        bits, mt, mb, dcc = carry
        m = maj3_words(bits[a], bits[b], bits[c])
        return bits.at[a].set(m).at[b].set(m).at[c].set(m), mt, mb, dcc

    def do_not2dcc(carry, a, b, c, d):
        bits, mt, mb, _ = carry
        return bits, mt, mb, ~bits[a]

    def do_dcc2(carry, a, b, c, d):
        bits, mt, mb, dcc = carry
        return bits.at[b].set(dcc), mt, mb, dcc

    def do_shift(delta):
        def f(carry, a, b, c, d):
            bits, _, _, dcc = carry
            row = bits[a]
            mt = row & (EVEN_MASK if delta > 0 else ODD_MASK)
            mb = row & (ODD_MASK if delta > 0 else EVEN_MASK)
            merged = _shift1(mt, delta) | _shift1(mb, delta)
            return bits.at[b].set(merged), mt, mb, dcc
        return f

    def do_maj(carry, a, b, c, d):
        bits, mt, mb, dcc = carry
        m = maj3_words(bits[a], bits[b], bits[c])
        bits = bits.at[t0].set(m).at[t1].set(m).at[t2].set(m)
        return bits.at[d].set(m), mt, mb, dcc

    def do_notpair(carry, a, b, c, d):
        bits, mt, mb, _ = carry
        dcc = ~bits[a]
        return bits.at[b].set(dcc), mt, mb, dcc

    branches = [do_copy, do_tra, do_not2dcc, do_dcc2,
                do_shift(+1), do_shift(-1), do_maj, do_notpair]

    def step(carry, x):
        c, o = x
        out = jax.lax.switch(c, branches, carry, o[0], o[1], o[2], o[3])
        return out, ()

    carry, _ = jax.lax.scan(step, carry, (code, opnd))
    return carry


# ---------------------------------------------------------------------------
# Segment walk
# ---------------------------------------------------------------------------

# The named scope of each segment kind: the part of the runner its
# operations belong to, in the compiled program and the device trace.
_SEG_SCOPE = {SegShiftRun: "pim.runner.row_math",
              SegMaj: "pim.runner.row_math",
              SegNot: "pim.runner.row_math",
              _SegTable: "pim.runner.residual_scan",
              SegHost: "pim.runner.host_io"}


def _run_segments(compiled: CompiledProgram, carry, use_kernels, interpret,
                  payloads=None):
    reads = []
    if payloads is None:
        payloads = [jnp.asarray(p) for p in compiled.program.payloads]
    for seg in _coalesce(compiled.segments, use_kernels):
        with jax.named_scope(_SEG_SCOPE.get(type(seg), "pim.runner")):
            carry = _run_segment(compiled, seg, carry, reads, use_kernels,
                                 interpret, payloads)
    return carry, tuple(reads)


def _run_segment(compiled: CompiledProgram, seg, carry, reads: list,
                 use_kernels, interpret, payloads):
    """One segment's operations on ``carry``; host reads append to
    ``reads``. Returns the new carry."""
    bits, mt, mb, dcc = carry
    if isinstance(seg, SegShiftRun):
        # k chained 1-bit shifts: shift (k-1) columns in one kernel call,
        # then replay the last hop so mig_top/mig_bot match eager exactly.
        y = _shift_row(bits[seg.src], seg.delta * (seg.k - 1),
                       use_kernels, interpret)
        mt = y & (EVEN_MASK if seg.delta > 0 else ODD_MASK)
        mb = y & (ODD_MASK if seg.delta > 0 else EVEN_MASK)
        merged = _shift1(mt, seg.delta) | _shift1(mb, seg.delta)
        return bits.at[seg.dst].set(merged), mt, mb, dcc
    if isinstance(seg, SegMaj):
        m = _maj_rows(bits[seg.a], bits[seg.b], bits[seg.c],
                      use_kernels, interpret)
        t0, t1, t2 = (t % compiled.num_rows
                      for t in (isa_T0, isa_T1, isa_T2))
        bits = bits.at[t0].set(m).at[t1].set(m).at[t2].set(m)
        return bits.at[seg.dst].set(m), mt, mb, dcc
    if isinstance(seg, SegNot):
        dcc = _not_row(bits[seg.src], use_kernels, interpret)
        return bits.at[seg.dst].set(dcc), mt, mb, dcc
    if isinstance(seg, _SegTable):
        return _scan_segment(seg, carry, compiled.num_rows)
    if isinstance(seg, SegHost):
        op = seg.op
        if op.op == ir.OP_READ:
            reads.append(bits[op.a])
        elif op.op == ir.OP_WRITE:
            return bits.at[op.b].set(payloads[op.payload]), mt, mb, dcc
        elif op.op == ir.OP_FILL:
            row = jnp.full((compiled.words,), jnp.uint32(op.payload))
            return bits.at[op.b].set(row), mt, mb, dcc
        return carry
    raise TypeError(seg)


def make_runner(program, cfg: DDR3Timing = DEFAULT_TIMING, *,
                use_kernels: bool | None = None,
                interpret: bool | None = None,
                refresh: bool = False,
                payload_arg: bool = False,
                verify: bool = False):
    """Build a jitted ``state -> ExecResult`` function for one program.

    The returned runner is cached per (program, flags, cfg-value) and is
    vmap-able, so ``bank_parallel`` maps ONE compiled program across banks
    instead of re-tracing the eager interpreter per bank. Its operations
    carry the named scopes ``pim.runner.row_math`` (fused shift runs,
    MAJ, NOT), ``pim.runner.residual_scan``, ``pim.runner.host_io`` and
    ``pim.runner.meter_fold`` in the compiled program and the device trace.

    With ``payload_arg=True`` the runner takes HOSTW payloads as a second
    argument — a ``(n_payloads, words)`` uint32 array — instead of baking
    ``program.payloads`` in as constants. This is how the device scheduler
    (``schedule.py``) runs banks whose command streams are identical but
    whose written data differs: one compiled runner, vmapped over
    ``(states, payload_stacks)``.

    ``verify=True`` statically lints the stream before building the
    runner and raises :class:`~.lint.LintError` on errors (a construction-
    time gate: cached runners are never rebuilt, so warm calls pay zero).
    """
    if verify:
        from . import lint      # lazy: lint is a pure-numpy leaf module
        src = program.program if hasattr(program, "program") else program
        report = lint.lint_program(src)
        if not report.ok:
            raise lint.LintError(report)
    compiled = _as_compiled(program, cfg)
    if use_kernels is None:
        use_kernels = _default_use_kernels()
    cache = getattr(compiled, "_runner_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(compiled, "_runner_cache", cache)
    # Key on the frozen cfg VALUE: id(cfg) could alias a dead cfg's reused id
    # (stale refresh constants) and always missed for equal-but-distinct cfgs.
    key = (use_kernels, interpret, refresh, payload_arg, cfg)
    if key in cache:
        return cache[key]

    f_tab = jnp.asarray(compiled.f_tab)
    i_tab = jnp.asarray(compiled.i_tab)

    @jax.jit
    def run(state: SubarrayState, payloads=None):
        RUNNER_STATS["traces"] += 1      # executes at trace time only
        carry = (state.bits, state.mig_top, state.mig_bot, state.dcc)
        (bits, mt, mb, dcc), reads = _run_segments(
            compiled, carry, use_kernels, interpret, payloads=payloads)
        with jax.named_scope("pim.runner.meter_fold"):
            f0 = jnp.stack([jnp.asarray(getattr(state.meter, k),
                                        jnp.float32)
                            for k in pim_compile._FLOAT_FIELDS])
            i0 = jnp.stack([jnp.asarray(getattr(state.meter, k), jnp.int32)
                            for k in pim_compile._INT_FIELDS])
            ff, fi = pim_compile._fold_tables(f_tab, i_tab, f0, i0)
            fields = {k: ff[j]
                      for j, k in enumerate(pim_compile._FLOAT_FIELDS)}
            fields.update({k: fi[j]
                           for j, k in enumerate(pim_compile._INT_FIELDS)})
            meter = type(state.meter)(**fields)
            if refresh:
                meter = apply_refresh(meter, cfg)
        return SubarrayState(bits=bits, mig_top=mt, mig_bot=mb, dcc=dcc,
                             meter=meter), reads

    if payload_arg:
        def runner(state: SubarrayState, payloads) -> ExecResult:
            out_state, reads = run(state, payloads)
            return ExecResult(state=out_state, reads=reads)
        runner.traced = run      # (state, payloads) -> (state, reads)
    else:
        def runner(state: SubarrayState) -> ExecResult:
            out_state, reads = run(state)
            return ExecResult(state=out_state, reads=reads)
        runner.traced = run      # raw (state) -> (state, reads), for vmap
    cache[key] = runner
    return runner


def make_pipeline_runner(program, cfg: DDR3Timing = DEFAULT_TIMING, *,
                         use_kernels: bool | None = None,
                         interpret: bool | None = None,
                         refresh: bool = False):
    """Build a jitted K-step pipeline ``(state, payload_steps) ->
    (state, reads_steps)`` for ONE recurring program.

    ``payload_steps`` is a ``(K, n_payloads, words)`` uint32 array — the
    HOSTW data of each step; the same command stream executes K times under
    one ``jax.lax.scan``, so a recurring single-subarray pipeline (e.g. a
    ``PimVM.run_pipeline`` on an unsharded VM) costs one XLA dispatch total
    instead of one per step. ``reads_steps`` leaves carry a leading step
    axis. Cached per (program, flags, cfg) like :func:`make_runner`."""
    compiled = _as_compiled(program, cfg)
    if use_kernels is None:
        use_kernels = _default_use_kernels()
    base = make_runner(compiled, cfg, use_kernels=use_kernels,
                       interpret=interpret, refresh=refresh,
                       payload_arg=True)
    cache = compiled._runner_cache      # make_runner just ensured it exists
    key = ("pipeline", use_kernels, interpret, refresh, cfg)
    if key in cache:
        return cache[key]

    @jax.jit
    def run_pipe(state: SubarrayState, payload_steps):
        def body(s, p):
            out, reads = base.traced(s, p)
            return out, reads

        return jax.lax.scan(body, state, payload_steps)

    cache[key] = run_pipe
    return run_pipe


def make_workload_runner(programs, cfg: DDR3Timing = DEFAULT_TIMING, *,
                         use_kernels: bool | None = None,
                         interpret: bool | None = None,
                         refresh: bool = False):
    """Build a jitted MULTI-PHASE pipeline ``(state, payload_phases) ->
    (state, reads_phases)`` for a sequence of recurring programs.

    ``programs`` is one recurring program per phase; ``payload_phases``
    is a matching tuple of ``(K_p, n_payloads_p, words)`` uint32 arrays —
    each phase's per-step HOSTW data. The phases run back-to-back as
    chained ``lax.scan``s (one per phase) inside ONE jit, so a whole
    heterogeneous single-subarray workload (e.g. ``PimVM.run_workload``
    on an unsharded VM) costs one XLA dispatch total. ``reads_phases``
    is a tuple of per-phase read pytrees, each with a leading step axis.
    Cached on the first program's compile artifact, keyed by the phase
    digest sequence."""
    compiled = [_as_compiled(p, cfg) for p in programs]
    if not compiled:
        raise ValueError("make_workload_runner needs at least one program")
    if use_kernels is None:
        use_kernels = _default_use_kernels()
    bases = tuple(
        make_runner(c, cfg, use_kernels=use_kernels, interpret=interpret,
                    refresh=refresh, payload_arg=True)
        for c in compiled)
    cache = compiled[0]._runner_cache   # make_runner just ensured it exists
    key = ("workload", tuple(c.program.digest for c in compiled),
           use_kernels, interpret, refresh, cfg)
    if key in cache:
        return cache[key]

    @jax.jit
    def run_workload(state: SubarrayState, payload_phases):
        reads_phases = []
        for base, payload_steps in zip(bases, payload_phases):
            def body(s, p, base=base):
                out, reads = base.traced(s, p)
                return out, reads

            state, reads = jax.lax.scan(body, state, payload_steps)
            reads_phases.append(reads)
        return state, tuple(reads_phases)

    cache[key] = run_workload
    return run_workload


def execute(program, state: SubarrayState | None = None,
            cfg: DDR3Timing = DEFAULT_TIMING, *,
            use_kernels: bool | None = None,
            interpret: bool | None = None, refresh: bool = False,
            verify: bool = False) -> ExecResult:
    """Compile (if needed) and run ``program`` against ``state`` (a fresh
    subarray by default). Meter increments accumulate on the incoming
    ``state.meter``. ``verify=True`` statically lints the stream first
    and raises :class:`~.lint.LintError` on errors."""
    if verify:
        from . import lint
        src = program.program if hasattr(program, "program") else program
        report = lint.lint_program(src)
        if not report.ok:
            raise lint.LintError(report)
    compiled = _as_compiled(program, cfg)
    if state is None:
        state = make_subarray(compiled.num_rows, compiled.words)
    runner = make_runner(compiled, cfg, use_kernels=use_kernels,
                         interpret=interpret, refresh=refresh)
    return runner(state)


def bank_parallel(program, cfg: DDR3Timing = DEFAULT_TIMING, *,
                  use_kernels: bool | None = None,
                  interpret: bool | None = None,
                  refresh: bool = False):
    """§5.1.4 on the compiled path: vmap ONE compiled program across a bank
    batch of states. Returns ``states_batched -> (states, wall_ns,
    energy_nj)`` — wall time is the max over banks, energy the sum."""
    runner = make_runner(program, cfg, use_kernels=use_kernels,
                         interpret=interpret, refresh=refresh)
    vrun = jax.vmap(runner.traced)

    def wrapped(states: SubarrayState):
        out, _ = vrun(states)
        wall_ns = jnp.max(out.meter.time_ns)
        energy_nj = jnp.sum(out.meter.total_energy_nj)
        return out, wall_ns, energy_nj

    return wrapped

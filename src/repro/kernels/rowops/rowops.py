"""Pallas TPU kernels for bulk PIM row operations.

The TPU-native re-tiling of the paper's subarray (DESIGN.md §2): a DRAM row's
65,536 bitlines become 2,048 packed uint32 lanes; the sense-amp-parallel
bitwise ops become VPU ops over (8, 128)-lane vregs; the migration-cell
staggered pairing becomes the inter-word carry network of ``shift_cols``.

Two execution styles:

  * per-op kernels (`bitwise`, `shift_cols`) — the paper-faithful
    command-by-command path: every ISA command round-trips rows HBM→VMEM→HBM,
    exactly like every AAP round-trips the row buffer.
  * the fused `ripple_add` kernel — the beyond-paper path: the whole w-round
    carry iteration runs on a VMEM-resident block, eliminating 3·(w-1)
    intermediate row round-trips (quantified in EXPERIMENTS.md §Perf).

Each ``pallas_call`` is named (``rowops_shift_cols``, ``rowops_bitwise_<op>``,
``rowops_ripple_add``), and the name is the kernel's in the compiled program
and the device trace.

Block shapes: rows are tiled (block_rows, W) — a full row of W words stays
contiguous in the block so the carry network never crosses a block boundary;
block_rows × W × 4 B must fit VMEM (default 8 × 2048 × 4 = 64 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 8


def _word_shift_up(x, n):
    """Shift whole words toward higher index along the minor axis, 0 fill."""
    if n == 0:
        return x
    if n >= x.shape[-1]:             # whole block shifted out (fused k≥32W)
        return jnp.zeros_like(x)
    pad = jnp.zeros(x.shape[:-1] + (n,), x.dtype)
    return jnp.concatenate([pad, x[..., :-n]], axis=-1)


def _word_shift_down(x, n):
    if n == 0:
        return x
    if n >= x.shape[-1]:
        return jnp.zeros_like(x)
    pad = jnp.zeros(x.shape[:-1] + (n,), x.dtype)
    return jnp.concatenate([x[..., n:], pad], axis=-1)


def _shift_cols_block(x, k: int):
    """Column shift with inter-word carry, entirely within the block."""
    kw, kb = divmod(abs(int(k)), 32)
    if k > 0:
        v = _word_shift_up(x, kw)
        if kb:
            v = (v << jnp.uint32(kb)) | (_word_shift_up(v, 1)
                                         >> jnp.uint32(32 - kb))
        return v
    if k < 0:
        v = _word_shift_down(x, kw)
        if kb:
            v = (v >> jnp.uint32(kb)) | (_word_shift_down(v, 1)
                                         << jnp.uint32(32 - kb))
        return v
    return x


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _bitwise_kernel(*refs, op: str):
    o_ref = refs[-1]
    a = refs[0][...]
    if op == "not":
        o_ref[...] = ~a
    elif op == "and":
        o_ref[...] = a & refs[1][...]
    elif op == "or":
        o_ref[...] = a | refs[1][...]
    elif op == "xor":
        o_ref[...] = a ^ refs[1][...]
    elif op == "maj":
        b, c = refs[1][...], refs[2][...]
        o_ref[...] = (a & b) | (b & c) | (a & c)
    else:
        raise ValueError(op)


def _shift_kernel(x_ref, o_ref, *, k: int):
    o_ref[...] = _shift_cols_block(x_ref[...], k)


def _ripple_add_kernel(a_ref, b_ref, o_ref, *, width: int, interior: int):
    """Fused w-round carry iteration — one HBM round-trip total."""
    a = a_ref[...]
    b = b_ref[...]
    interior_mask = jnp.uint32(interior)
    s = a ^ b
    c = a & b
    for _ in range(width - 1):
        cs = _shift_cols_block(c, +1) & interior_mask
        c = s & cs
        s = s ^ cs
    o_ref[...] = s


# ---------------------------------------------------------------------------
# pallas_call wrappers (grid/BlockSpec plumbing; jit wrappers live in ops.py)
# ---------------------------------------------------------------------------

def _row_grid(x, block_rows):
    n, w = x.shape
    br = min(block_rows, n)
    assert n % br == 0, f"rows {n} not divisible by block {br}"
    return (n // br,), br, w


def bitwise(a, b=None, c=None, *, op: str,
            block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False):
    grid, br, w = _row_grid(a, block_rows)
    spec = pl.BlockSpec((br, w), lambda i: (i, 0))
    nargs = {"not": 1, "and": 2, "or": 2, "xor": 2, "maj": 3}[op]
    args = [a, b, c][:nargs]
    assert all(x is not None for x in args), f"{op} needs {nargs} operands"
    return pl.pallas_call(
        functools.partial(_bitwise_kernel, op=op),
        grid=grid,
        in_specs=[spec] * nargs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.uint32),
        interpret=interpret,
        name=f"rowops_bitwise_{op}",
    )(*args)


def shift_cols(x, k: int, *, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False):
    grid, br, w = _row_grid(x, block_rows)
    spec = pl.BlockSpec((br, w), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_shift_kernel, k=k),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        interpret=interpret,
        name="rowops_shift_cols",
    )(x)


def ripple_add(a, b, *, width: int, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False):
    from .ref import _interior_mask  # single source of truth for the pattern
    grid, br, w = _row_grid(a, block_rows)
    spec = pl.BlockSpec((br, w), lambda i: (i, 0))
    interior = int(_interior_mask(width))
    return pl.pallas_call(
        functools.partial(_ripple_add_kernel, width=width, interior=interior),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.uint32),
        interpret=interpret,
        name="rowops_ripple_add",
    )(a, b)

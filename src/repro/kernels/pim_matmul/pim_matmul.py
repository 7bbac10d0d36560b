"""Bit-plane shift-and-add quantized matmul — the paper's algorithm on the MXU.

The paper motivates in-DRAM shifting with shift-and-add multiplication:
partial products are aligned by shifts and accumulated (§1). On TPU the
"shift" of a partial product by 2^b is a power-of-two scalar folded into the
MXU accumulation, and a "row" of the computation is a weight *bit plane*:

    Y = X @ W_int * scale = sum_b  c_b * (X @ plane_b) * scale,
    c = [1, 2, 4, ..., -(2^(bits-1))]   (two's complement planes)

Modes:
  * ``shift_add`` — paper-faithful: one MXU pass per bit plane (`bits` dots
    per block). This is the BASELINE recorded in EXPERIMENTS.md §Perf.
  * ``dequant``   — beyond-paper optimization: dequantize the int block in
    VMEM and run ONE MXU pass (bits× fewer MXU FLOPs, same result).

VMEM tiling (TPU v5e: 128-lane MXU, ~16 MiB VMEM):
  X block (bm, bk) bf16, W block (bk, bn) int8, acc (bm, bn) f32 in the
  output ref (revisited across the K grid axis). Defaults bm=bn=128 bk=512:
  128·512·2 + 512·128·1 + 128·128·4 ≈ 0.25 MiB per step — deep pipelining
  headroom. All dims MXU-aligned (multiples of 128... 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import plane_coeffs


def _matmul_kernel(x_ref, w_ref, o_ref, *, mode: str, bits: int, nk: int):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (arbitrary) axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = w_ref[...]

    if mode == "dequant":
        wf = w.astype(x.dtype)
        o_ref[...] += jnp.dot(x, wf, preferred_element_type=jnp.float32)
    elif mode == "shift_add":
        wu = w.astype(jnp.int32) & ((1 << bits) - 1)
        acc = jnp.zeros_like(o_ref)
        for i, coeff in enumerate(plane_coeffs(bits)):
            plane = ((wu >> i) & 1).astype(x.dtype)   # the bit plane
            acc += coeff * jnp.dot(x, plane,
                                   preferred_element_type=jnp.float32)
        o_ref[...] += acc
    else:
        raise ValueError(mode)


def pim_matmul_raw(x, w_int, *, mode: str, bits: int,
                   bm: int = 128, bn: int = 128, bk: int = 512,
                   interpret: bool = False):
    """Unscaled integer-plane matmul: returns f32 (M, N) = X @ W_int."""
    m, kdim = x.shape
    k2, n = w_int.shape
    assert kdim == k2, (x.shape, w_int.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (
        f"shape ({m},{kdim},{n}) not divisible by blocks ({bm},{bk},{bn})")
    grid = (m // bm, n // bn, kdim // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, mode=mode, bits=bits, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_int)

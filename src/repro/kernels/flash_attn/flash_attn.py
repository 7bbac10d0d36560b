"""Pallas TPU flash-attention forward kernel.

The §Perf analysis identified streamed f32 score tiles as the dominant HBM
term of every training/prefill cell — the scan-based flash implementation
(models/flash.py) writes each (qc × kc) tile's p-matrix to HBM between XLA
ops. This kernel keeps the whole online-softmax state (m, l, acc) in VMEM
scratch across the kv grid axis, so score tiles never leave the core:

  grid = (H, nq, nk), kv innermost ("arbitrary");
  q block (1, bq, dh) VMEM · k/v block (1, bk, dh) VMEM (kv head = h // G)
  scratch: m,l (bq,128-padded) f32 · acc (bq, dh) f32, persisted across nk;
  @pl.when(k == 0) init, @pl.when(k == nk − 1) finalize into the out block.

GQA mapping is done by the k/v BlockSpec index maps (no repeated k/v in
HBM). Causal/window/validity masking from position vectors, same semantics
as models/attention.chunked_attention. Forward only — the training backward
stays on the custom-VJP recompute path (models/flash.py); this kernel is
the serving/prefill fast path and the TPU target for the fwd.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 512


def _kernel(pq_ref, pk_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, window, nk: int):
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                    # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                    # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    pq = pq_ref[...].astype(jnp.float32)                # (bq,)
    pk = pk_ref[...].astype(jnp.float32)                # (bk,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = (pk[None, :] >= 0) & (pk[None, :] <= pq[:, None])
    if window is not None:
        ok &= (pq[:, None] - pk[None, :]) < float(window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1)
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(kidx == nk - 1)
    def _finalize():
        o_ref[0] = (acc_s[...]
                    / jnp.maximum(l_s[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, pos_q, pos_k, *, window=None,
                        scale: float | None = None, bq: int = DEFAULT_BQ,
                        bk: int = DEFAULT_BK, interpret: bool = False):
    """q: (H, Sq, dh); k/v: (KV, Sk, dh); pos_*: int32. → (H, Sq, dh)."""
    H, Sq, dh = q.shape
    KV, Sk, _ = k.shape
    assert H % KV == 0
    G = H // KV
    scale = (dh ** -0.5) if scale is None else scale
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    while Sq % bq:
        bq //= 2
    while Sk % bk:
        bk //= 2
    nq, nk = Sq // bq, Sk // bk
    grid = (H, nq, nk)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq,), lambda h, i, kc: (i,)),          # pos_q
            pl.BlockSpec((bk,), lambda h, i, kc: (kc,)),         # pos_k
            pl.BlockSpec((1, bq, dh), lambda h, i, kc: (h, i, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda h, i, kc, G=G: (h // G, kc, 0)),  # GQA map
            pl.BlockSpec((1, bk, dh),
                         lambda h, i, kc, G=G: (h // G, kc, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, kc: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denom
            pltpu.VMEM((bq, dh), jnp.float32),    # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_q, pos_k, q, k, v)

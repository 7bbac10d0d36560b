"""Batched serving engine: prefill + jitted single-token decode loop.

Greedy or temperature sampling over a batch of equal-length prompts (a
production engine adds continuous batching on top; the step function here is
exactly the unit the dry-run lowers as ``serve_step``).

Prefill and the whole decode loop — token sampling, key splitting, and the
per-token ``decode_step`` — are two module-level jitted programs that take
the weights and caches as arguments: generating N tokens costs one prefill
and one decode dispatch, and a second request with the same shapes reuses
both compiled programs. (A jit that closed over ``params`` would bake every
weight into the program as a constant and recompile per request.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import decode_step, prefill

# Host→device dispatches issued by the decode loop (excludes prefill): one
# jitted scan per generate call; and how many times that scan was traced
# (one per new shape or static setting). Reset-able by tests, which assert
# the loop stays a single dispatch and a repeated shape never re-traces.
DECODE_STATS = {"dispatches": 0, "traces": 0}

_prefill = jax.jit(prefill, static_argnames=("cfg", "max_cache_len"))


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "temperature"))
def _decode(cfg, params, lg0, caches, key, pos0, *, max_new_tokens: int,
            temperature: float):
    """Sample ``max_new_tokens`` tokens starting from the prefill logits
    ``lg0``. Returns ``(tokens (B, T), logits of the last sampled token)``.
    """
    DECODE_STATS["traces"] += 1         # executes at trace time only

    def sample(lg, k):
        lg = lg.reshape(lg.shape[0], -1)[:, :cfg.vocab_size]
        if temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(k, lg / temperature).astype(jnp.int32)

    lg0 = lg0.astype(jnp.float32)
    k0, key = jax.random.split(key)
    tok0 = sample(lg0, k0)[:, None]

    def body(carry, _):
        tok, pos, caches, key, _ = carry
        lg, caches = decode_step(cfg, params, {"tokens": tok}, pos, caches)
        lg = lg.astype(jnp.float32)
        k0, key = jax.random.split(key)
        nxt = sample(lg, k0)[:, None]
        return (nxt, pos + 1, caches, key, lg), nxt

    (_, _, _, _, last), rest = jax.lax.scan(
        body, (tok0, pos0, caches, key, lg0), None,
        length=max_new_tokens - 1)
    # tok0 (B, 1) + rest (T-1, B, 1) -> (B, T)
    return jnp.concatenate([tok0[None], rest], axis=0)[..., 0].T, last


def greedy_generate(cfg, params, batch, *, max_new_tokens: int,
                    max_cache_len: int | None = None, temperature: float = 0.0,
                    key=None, return_logits: bool = False):
    """batch: prompt inputs (see data.pipeline). Returns (B, max_new) tokens.

    ``return_logits=True`` returns ``(tokens, logits)`` instead, where
    ``logits`` are the float32 logits the last token was sampled from —
    after ``max_new_tokens - 1`` decode steps through the cache."""
    if max_new_tokens < 0:
        raise ValueError(
            f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        # the scan would get length=-1, which XLA rejects with an opaque
        # "invalid tensor dimension size" — zero tokens is just an empty
        # result, no prefill or decode needed
        if return_logits:
            raise ValueError("return_logits needs max_new_tokens >= 1")
        b = (batch["frame_embeds"] if cfg.frontend == "audio_frames"
             else batch["tokens"]).shape[0]
        return jnp.zeros((b, 0), jnp.int32)
    prompt_len = (batch["frame_embeds"].shape[1]
                  if cfg.frontend == "audio_frames"
                  else batch["tokens"].shape[1]
                  + (cfg.n_patches if cfg.frontend == "vision_patches" else 0))
    max_cache_len = max_cache_len or (prompt_len + max_new_tokens)

    logits, caches = _prefill(cfg, params, batch, max_cache_len=max_cache_len)
    key = key if key is not None else jax.random.PRNGKey(0)
    DECODE_STATS["dispatches"] += 1
    tokens, last = _decode(cfg, params, logits, caches, key,
                           jnp.int32(prompt_len),
                           max_new_tokens=max_new_tokens,
                           temperature=float(temperature))
    return (tokens, last) if return_logits else tokens

"""Serving engine behaviours beyond the system test."""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve import engine
from repro.serve.engine import DECODE_STATS, greedy_generate

from util import make_inputs


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-4b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_temperature_sampling_differs_but_valid(setup):
    cfg, params = setup
    prompts = make_inputs(cfg, 2, 16, labels=False)
    greedy = greedy_generate(cfg, params, prompts, max_new_tokens=12)
    hot = greedy_generate(cfg, params, prompts, max_new_tokens=12,
                          temperature=1.5, key=jax.random.PRNGKey(7))
    assert hot.shape == greedy.shape
    assert int(hot.max()) < cfg.vocab_size and int(hot.min()) >= 0
    assert not jnp.array_equal(greedy, hot)


def test_batch_requests_independent(setup):
    """Request i's output must not depend on what else is in the batch."""
    cfg, params = setup
    prompts = make_inputs(cfg, 3, 16, labels=False)
    full = greedy_generate(cfg, params, prompts, max_new_tokens=6)
    solo = greedy_generate(
        cfg, params, {"tokens": prompts["tokens"][1:2]}, max_new_tokens=6)
    assert jnp.array_equal(full[1:2], solo)


def test_generate_respects_cache_budget(setup):
    cfg, params = setup
    prompts = make_inputs(cfg, 1, 8, labels=False)
    out = greedy_generate(cfg, params, prompts, max_new_tokens=4,
                          max_cache_len=16)
    assert out.shape == (1, 4)


def test_decode_loop_is_single_dispatch(setup):
    """The whole decode loop (sampling + key splits + decode_step) runs as
    ONE jitted scan: generating N tokens costs one dispatch after prefill,
    not N host round-trips — and the fold into the scan is greedy-stable."""
    cfg, params = setup
    prompts = make_inputs(cfg, 2, 16, labels=False)
    out1 = greedy_generate(cfg, params, prompts, max_new_tokens=8)
    DECODE_STATS["dispatches"] = 0
    out2 = greedy_generate(cfg, params, prompts, max_new_tokens=8)
    assert DECODE_STATS["dispatches"] == 1
    assert out1.shape == (2, 8)
    assert jnp.array_equal(out1, out2)      # greedy decode is deterministic


def test_repeat_generate_traces_decode_once(setup):
    """The decode loop is one module-level jit taking the weights and caches
    as arguments: a second request with the same shapes reuses it."""
    cfg, params = setup
    prompts = make_inputs(cfg, 3, 13, labels=False)   # shapes no other test
    DECODE_STATS["traces"] = 0
    first = greedy_generate(cfg, params, prompts, max_new_tokens=5)
    second = greedy_generate(cfg, params, prompts, max_new_tokens=5)
    assert DECODE_STATS["traces"] == 1
    assert jnp.array_equal(first, second)


def _largest_constant(hlo_text: str) -> int:
    """Element count of the largest ``stablehlo.constant`` in the text."""
    sizes = [math.prod(int(d) for d in dims.split("x")[:-1])
             for dims in re.findall(
                 r"stablehlo\.constant dense[^\n]*?: tensor<([^>]*)>",
                 hlo_text)]
    return max(sizes, default=0)


def test_weights_reach_the_programs_as_arguments(setup):
    """Neither the prefill nor the decode program bakes a weight in as a
    constant (which also recompiled every request)."""
    cfg, params = setup
    prompts = make_inputs(cfg, 2, 8, labels=False)
    smallest = min(x.size for x in jax.tree_util.tree_leaves(params)
                   if x.ndim >= 2 and min(x.shape[-2:]) > 1)
    lowered_prefill = engine._prefill.lower(cfg, params, prompts,
                                            max_cache_len=12)
    logits, caches = engine._prefill(cfg, params, prompts, max_cache_len=12)
    lowered_decode = engine._decode.lower(
        cfg, params, logits, caches, jax.random.PRNGKey(0), jnp.int32(8),
        max_new_tokens=4, temperature=0.0)
    for lowered in (lowered_prefill, lowered_decode):
        assert _largest_constant(lowered.as_text()) < smallest


def test_return_logits_are_the_last_sampled_step(setup):
    """``return_logits`` hands back the logits the last token came from:
    their argmax is that token under greedy decoding."""
    cfg, params = setup
    prompts = make_inputs(cfg, 2, 8, labels=False)
    tokens, logits = greedy_generate(cfg, params, prompts, max_new_tokens=3,
                                     return_logits=True)
    assert logits.dtype == jnp.float32
    last = jnp.argmax(logits.reshape(2, -1)[:, :cfg.vocab_size], axis=-1)
    assert jnp.array_equal(last, tokens[:, -1])


def test_zero_new_tokens_returns_empty(setup):
    """Regression: max_new_tokens=0 used to reach lax.scan(length=-1) and
    die with an opaque MLIR "invalid tensor dimension size" — it must be
    an empty (B, 0) result, with no prefill or decode dispatched."""
    cfg, params = setup
    prompts = make_inputs(cfg, 3, 8, labels=False)
    DECODE_STATS["dispatches"] = 0
    out = greedy_generate(cfg, params, prompts, max_new_tokens=0)
    assert out.shape == (3, 0)
    assert out.dtype == jnp.int32
    assert DECODE_STATS["dispatches"] == 0


def test_one_new_token_edge(setup):
    """length=0 scan edge: a single token comes from prefill sampling
    alone and must match the first column of a longer generation."""
    cfg, params = setup
    prompts = make_inputs(cfg, 2, 8, labels=False)
    one = greedy_generate(cfg, params, prompts, max_new_tokens=1)
    assert one.shape == (2, 1)
    more = greedy_generate(cfg, params, prompts, max_new_tokens=4)
    assert jnp.array_equal(one, more[:, :1])


def test_negative_new_tokens_rejected(setup):
    cfg, params = setup
    prompts = make_inputs(cfg, 1, 8, labels=False)
    with pytest.raises(ValueError, match="max_new_tokens"):
        greedy_generate(cfg, params, prompts, max_new_tokens=-1)


def test_ssm_arch_generates():
    cfg = get_config("falcon-mamba-7b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompts = make_inputs(cfg, 2, 12, labels=False)
    out = greedy_generate(cfg, params, prompts, max_new_tokens=5)
    assert out.shape == (2, 5)
    assert int(out.max()) < cfg.vocab_size

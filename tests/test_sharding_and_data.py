"""Sharding rule engine, data pipeline, HLO analyzer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import Prefetcher, make_batch
from repro.data.synthetic import SyntheticTokens
from repro.launch import roofline, sharding
from repro.launch.hlo_analysis import HloModule, analyze, shape_bytes
from repro.launch.mesh import make_host_mesh
from repro.models import init_params


# --- sharding rules ---------------------------------------------------------

def test_param_rules_cover_all_archs():
    mesh = make_host_mesh()
    for arch in ("qwen3-moe-30b-a3b", "deepseek-v2-lite-16b",
                 "falcon-mamba-7b", "recurrentgemma-2b", "musicgen-medium"):
        cfg = get_config(arch, smoke=True)
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        _, report = sharding.param_shardings(cfg, mesh, params)
        assert not report.fallback_replicated, (arch,
                                                report.fallback_replicated)


def test_expected_specs():
    mesh = make_host_mesh()
    rep = sharding.ShardingReport()
    assert sharding.spec_for("stack/attn/wq", 3, mesh, rep) == \
        P(None, None, "model")
    assert sharding.spec_for("stack/attn/wo", 3, mesh, rep) == \
        P(None, "model", None)
    assert sharding.spec_for("stack/ffn/w1", 4, mesh, rep) == \
        P(None, "model", None, None)
    assert sharding.spec_for("embed", 2, mesh, rep) == P("model", None)
    assert sharding.spec_for("stack/ln1/w", 2, mesh, rep) == P(None, None)


def test_nondivisible_dims_degrade_to_replicated():
    mesh = make_host_mesh()          # model axis size = 1 → divisible always
    rep = sharding.ShardingReport()
    spec = sharding.spec_for("stack/attn/wq", 2, mesh, rep, shape=(7, 13))
    assert spec == P(None, None) or spec == P(None, "model")


def test_cache_shardings_pick_sequence_dim():
    mesh = make_host_mesh()
    tree = {"k": jax.ShapeDtypeStruct((4, 8, 64, 2, 16), jnp.bfloat16)}
    sh = sharding.cache_shardings(mesh, tree, batch=8)
    spec = sh["k"].spec
    assert spec[1] is not None or spec == P()        # batch dim → dp axes


# --- data pipeline -----------------------------------------------------------

def test_stream_deterministic_and_stateless():
    s = SyntheticTokens(1000, seed=3)
    a = s.block(1000, 128)
    b = np.concatenate([s.block(1000, 64), s.block(1064, 64)])
    assert np.array_equal(a, b)


def test_make_batch_resume_equivalence():
    cfg = get_config("qwen3-4b", smoke=True)
    b1 = make_batch(cfg, batch=4, seq=32, step=7)
    b2 = make_batch(cfg, batch=4, seq=32, step=7)
    for k in b1:
        assert np.array_equal(b1[k], b2[k])


def test_make_batch_shards_disjoint_and_consistent():
    cfg = get_config("qwen3-4b", smoke=True)
    full = make_batch(cfg, batch=8, seq=32, step=3)
    lo = make_batch(cfg, batch=8, seq=32, step=3, lo=0, hi=4)
    hi = make_batch(cfg, batch=8, seq=32, step=3, lo=4, hi=8)
    assert np.array_equal(full["tokens"],
                          np.concatenate([lo["tokens"], hi["tokens"]]))


def test_labels_are_shifted_inputs():
    cfg = get_config("qwen3-4b", smoke=True)
    b = make_batch(cfg, batch=2, seq=32, step=0)
    # label[t] is the next token of the underlying stream
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_separator_positions_masked():
    cfg = get_config("qwen3-4b", smoke=True)
    b = make_batch(cfg, batch=4, seq=600, step=0)
    assert (b["mask"] == (b["labels"] != 0)).all()
    assert (b["mask"] == 0).sum() > 0               # doc_len=512 < 600


def test_prefetcher_orders_steps():
    cfg = get_config("qwen3-4b", smoke=True)
    pf = Prefetcher(lambda s: make_batch(cfg, batch=2, seq=16, step=s),
                    start_step=5)
    try:
        for expect in (5, 6, 7):
            step, batch = pf.get()
            assert step == expect
    finally:
        pf.close()


# --- HLO analyzer -------------------------------------------------------------

def test_shape_bytes():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("(bf16[8]{0}, s32[2,2]{1,0})") == 16 + 16
    assert shape_bytes("pred[7]") == 7


def test_loop_scaling_exact_on_scanned_matmul():
    L, B, D = 6, 8, 64

    def fn(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        return jax.lax.scan(body, x, ws)[0]

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    compiled = jax.jit(fn).lower(xs, ws).compile()
    cost = analyze(compiled.as_text(), 1)
    assert cost.flops == 2 * L * B * D * D


def test_collective_ring_factors_synthetic():
    hlo = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(%p), replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %ag = f32[256]{0} all-gather(%ar), replica_groups=[2,4]<=[8], dimensions={0}
}
"""
    m = HloModule(hlo, 8)
    c = m.entry_cost()
    assert c.coll["all-reduce"] == 2 * 256 * 3 / 4
    assert c.coll["all-gather"] == 1024 * 3 / 4


# --- roofline peaks -----------------------------------------------------------

def test_peaks_are_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="cpu"):
        roofline.peaks("cpu")          # an unknown kind is never defaulted

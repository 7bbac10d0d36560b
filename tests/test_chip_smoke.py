"""``chip_smoke.py`` end to end at a tiny size on the CPU, its refusal to
run without a TPU, and the compile-cache placement it relies on."""
import importlib.util
import json
import os
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))       # chip_smoke imports `benchmarks`
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]
    sys.path.remove(str(ROOT))


def test_refuses_to_run_without_the_chip(smoke, capsys):
    assert smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == ""                        # no result line
    assert f"platform {jax.devices()[0].platform!r}" in out.err


def test_runs_every_phase_at_a_tiny_size(smoke, monkeypatch, capsys):
    # the test hook: demand the platform the tests run on, at a size the
    # CPU (Pallas in interpret mode) gets through in seconds
    monkeypatch.setattr(smoke, "PLATFORM", jax.devices()[0].platform)
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(smoke, "FULL", smoke.Sizes(
        banks=4, subarrays=2, rows=64, words=16, shift_steps=3, shift_k=40,
        rs_cw_per_bank=2, tenant_banks=(1, 1, 2), tenant_steps=2,
        lm_smoke=True, lm_batch=2, lm_prompt=8, lm_new=4))
    assert smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    for phase in ("shift_stream", "rs_workload", "tenants", "lm_serve"):
        assert any(line.startswith(f"[{phase}] PASS") for line in lines), \
            phase


def test_compile_cache_defers_to_the_environment(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

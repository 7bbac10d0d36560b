"""Compiles of the main path for a TPU v5e that is described, not attached.

Nothing runs here: each test lowers a kernel or a step runner for one chip
of a ``v5e:2x2`` topology and compiles it with the TPU compiler, which
refuses what the chip would refuse (unaligned blocks, too much fast
memory, programs that do not fit). This is the only test file that
describes the chip; the topology is described inside a fixture, never at
import, so every test worker collects the same tests.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import pim
from repro.kernels.rowops import ops as kops

ROWS, WORDS = 512, 2048          # the paper's subarray: 512 rows x 8 KB
HBM_BYTES = 16e9                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler log files
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001 - any describe failure
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep these out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _rows(n, sharding):
    return jax.ShapeDtypeStruct((n, WORDS), jnp.uint32, sharding=sharding)


def _assert_kernel(compiled):
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("k", [1, -1, 33, 1000])
def test_shift_cols_compiles(one_chip, k):
    _assert_kernel(kops.shift_cols.lower(
        _rows(1, one_chip), k, interpret=False).compile())


@pytest.mark.parametrize("op", ["maj", "not"])
def test_bitwise_compiles(one_chip, op):
    args = [_rows(1, one_chip)] * (3 if op == "maj" else 1)
    _assert_kernel(kops.bitwise.lower(*args, op=op,
                                      interpret=False).compile())


def test_ripple_add_compiles(one_chip):
    x = _rows(8, one_chip)
    _assert_kernel(kops.ripple_add.lower(x, x, width=8,
                                         interpret=False).compile())


def test_step_runner_compiles_for_64_slots(one_chip):
    """The scheduler's unit of work at paper geometry: one compiled runner
    (Table 2/3 shift stream plus an Ambit XOR, so shift, MAJ and NOT
    kernels) vmapped over the 64 slots of ``paper_device(32,
    subarrays=2)``, with the kernels lowered for the chip."""
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.write_row(0, np.zeros(WORDS, np.uint32))
    b.shift_k(0, 1, 1000)
    b.ambit_xor(0, 1, 2)
    b.read_row(2)
    prog = b.build()
    runner = pim.make_runner(pim.compile_program(prog), use_kernels=True,
                             interpret=False, payload_arg=True)
    cfg = pim.paper_device(32, subarrays=2)
    banks = _sds(jax.eval_shape(lambda: pim.make_device(cfg).banks),
                 one_chip)
    payloads = jax.ShapeDtypeStruct((cfg.n_slots, 1, WORDS), jnp.uint32,
                                    sharding=one_chip)
    compiled = jax.jit(jax.vmap(runner.traced)).lower(
        banks, payloads).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES


def test_compiled_runner_keeps_scopes_and_kernel_names(one_chip):
    """The names the device trace is read by survive the chip's compiler:
    the runner's named scopes as ``op_name`` metadata (the row math's
    around its kernel) and the ``rowops_*`` kernel names."""
    b = pim.ProgramBuilder(ROWS, WORDS)
    b.issue()
    b.write_row(0, np.zeros(WORDS, np.uint32))
    b.shift_k(0, 1, 512)
    b.ambit_xor(0, 1, 2)
    b.read_row(2)
    runner = pim.make_runner(pim.compile_program(b.build()),
                             use_kernels=True, interpret=False,
                             payload_arg=True)
    cfg = pim.paper_device(1, subarrays=2)
    banks = _sds(jax.eval_shape(lambda: pim.make_device(cfg).banks),
                 one_chip)
    payloads = jax.ShapeDtypeStruct((cfg.n_slots, 1, WORDS), jnp.uint32,
                                    sharding=one_chip)
    text = jax.jit(jax.vmap(runner.traced)).lower(
        banks, payloads).compile().as_text()
    for scope in ("pim.runner.row_math", "pim.runner.host_io",
                  "pim.runner.meter_fold"):
        assert f"/{scope}/" in text, scope
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("rowops_shift_cols", "rowops_bitwise_maj",
                 "rowops_bitwise_not"):
        assert any(f"%{name}" in k and "/pim.runner.row_math/" in k
                   for k in kernels), name
    x = _rows(8, one_chip)
    assert "%rowops_ripple_add" in kops.ripple_add.lower(
        x, x, width=8, interpret=False).compile().as_text()

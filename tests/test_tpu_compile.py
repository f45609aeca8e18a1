"""Compiles for a described TPU v5e chip, with no chip attached: the
Pallas kernels at xlstm-125m widths, and full-width xlstm-125m training
steps against one chip's HBM.  The compiler refuses here what the chip
would refuse (tiling, unsupported primitives, memory).

The topology is described only inside a fixture: a process that
describes it loads the TPU library and keeps it until it exits, so
describing it while modules are imported would give pytest workers
different sets of tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.library import ParallelismLibrary
from repro.core.profiler import HARDWARE, is_out_of_memory
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.slstm_step import slstm_step_scan
from repro.models.params import abstract_params
from repro.models.transformer import model_spec
from repro.optim.adamw import AdamWConfig
from repro.parallelism.build import BuiltJob

CFG = get_config("xlstm-125m")
B, S, H, D = 16, 1024, CFG.num_heads, CFG.head_dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — any failure: skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


KERNELS = {
    "flash_attention": (flash_attention,
                        [(B, S, H, D)] * 3),
    "mlstm_chunk": (mlstm_chunk, [(B, S, H, D)] * 3 + [(B, S, H)] * 2),
    "rglru_scan": (rglru_scan, [(B, S, CFG.d_model)] * 2),
    "slstm_step_scan": (slstm_step_scan,
                        [(B, S, H, D, 4)] + [(H, D, D)] * 4),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_step(topo, technique: str, n: int, batch: int):
    plan = ParallelismLibrary().get(technique).plan(CFG, n)
    built = BuiltJob(CFG, plan, AdamWConfig(lr=1e-3, warmup_steps=5,
                                            total_steps=40),
                     devices=topo.devices[:n])
    spec = model_spec(CFG)
    params = abstract_params(spec, jnp.float32)
    opt = {"mu": params, "nu": params,
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    tokens = {"tokens": jax.ShapeDtypeStruct((batch, S), jnp.int32)}
    return built.step.lower(params, opt, tokens).compile()


def test_xlstm_step_fits_one_v5e(topo, no_persistent_cache):
    """ddp x 1 at batch 4 x 1024, a plan the one-chip smoke sweep runs."""
    ma = _compile_step(topo, "ddp", 1, 4).memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used <= HARDWARE["v5e"].hbm_capacity


def test_oversized_xlstm_step_is_out_of_memory(topo, no_persistent_cache):
    """ddp x 1 at batch 16 x 1024 does not fit: the compiler's refusal is
    the error the Trial Runner records as an infeasible combo."""
    with pytest.raises(jax.errors.JaxRuntimeError) as info:
        _compile_step(topo, "ddp", 1, 16)
    assert is_out_of_memory(info.value)

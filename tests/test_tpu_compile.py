"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the Pallas kernels (with
``interpret=False``) and a full-width smollm-135m decode step compile here
for a v5e exactly as they would on the chip: a kernel that Mosaic refuses,
or a tile that overflows VMEM, fails these tests without any chip time.
Nothing runs; a compile that passes says nothing about results or speed.

The topology is described inside a module-scoped fixture and never at
import: only one process may load the TPU library at a time, and every
pytest worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import approx_matmul as K
from repro.models import transformer_lm as TLM
from repro.parallel.sharding import DEFAULT_RULES
from repro.quant.quantize import for_lm
from repro.serve import compiled_fns

# one decode and one prefill shape of smollm-135m's projections (M, K, N)
SHAPES = {"decode": (8, 576, 1536), "prefill": (256, 1536, 576)}

KERNELS = {
    "deficit": lambda x, w, s, b: K.approx_matmul_pallas(
        x, w, kernel="deficit", interpret=False),
    "deficit_fused": lambda x, w, s, b: K.fused_matmul_pallas(
        x, w, s, b, variant="deficit", interpret=False),
    "stage1": lambda x, w, s, b: K.approx_matmul_pallas(
        x, w, kernel="stage1", interpret=False),
    "stage1_fused": lambda x, w, s, b: K.fused_matmul_pallas(
        x, w, s, b, variant="stage1", interpret=False),
    "exact_fused": lambda x, w, s, b: K.fused_matmul_pallas(
        x, w, s, b, variant="exact", interpret=False),
    "rank1": lambda x, w, s, b: K.rank1_matmul_pallas(
        x, w, interpret=False),
    "rank1_fused": lambda x, w, s, b: K.rank1_fused_matmul_pallas(
        x, w, s, b, interpret=False),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("phase", sorted(SHAPES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, phase):
    m, k, n = SHAPES[phase]
    args = (jax.ShapeDtypeStruct((m, k), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip))
    compiled = jax.jit(KERNELS[kernel]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["approx_rank1_pallas",
                                     "approx_deficit_pallas"])
def test_smollm_decode_step_compiles_for_v5e(one_chip, backend,
                                             monkeypatch):
    # this process sees only the CPU, so the registry would pick the
    # Pallas interpreter; the chip never does
    monkeypatch.setattr("repro.kernels.ops._interpret_default",
                        lambda: False)
    cfg = registry.get("smollm-135m", quant=for_lm(backend))
    slots, max_len = 8, 512

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: TLM.init(cfg, jax.random.PRNGKey(0))))
    pool = on_chip(jax.eval_shape(
        lambda: TLM.init_cache(cfg, slots, max_len, cfg.param_dtype)))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    decode = compiled_fns(cfg, DEFAULT_RULES)[1]
    compiled = decode.lower(params, pool, tok, pos).compile()
    assert "tpu_custom_call" in compiled.as_text()

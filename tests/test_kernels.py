"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracle
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.approx_matmul import approx_matmul_pallas
from repro.kernels import ref as R

RNG = np.random.default_rng(7)


def _rand(m, k, n):
    x = RNG.integers(-127, 128, (m, k)).astype(np.int8)
    w = RNG.integers(-127, 128, (k, n)).astype(np.int8)
    return jnp.asarray(x), jnp.asarray(w)


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (16, 8, 24), (5, 7, 3),
                                   (32, 16, 8), (1, 1, 1), (9, 33, 17)])
def test_deficit_kernel_matches_oracle(m, k, n):
    x, w = _rand(m, k, n)
    got = approx_matmul_pallas(x, w, block=(8, 8, 8), interpret=True)
    want = R.approx_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", [(8, 8, 8), (16, 16, 8), (8, 16, 16)])
def test_deficit_kernel_block_sweep(block):
    x, w = _rand(24, 24, 24)
    got = approx_matmul_pallas(x, w, block=block, interpret=True)
    want = R.approx_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (16, 32, 8), (3, 5, 11)])
def test_stage1_kernel_matches_oracle(m, k, n):
    x, w = _rand(m, k, n)
    got = approx_matmul_pallas(x, w, block=(8, 8, 8), kernel="stage1",
                               interpret=True)
    want = R.stage1_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_zero_and_identity_operands():
    x = jnp.zeros((8, 8), jnp.int8)
    w = jnp.ones((8, 8), jnp.int8)
    out = approx_matmul_pallas(x, w, block=(8, 8, 8), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0)
    x = jnp.eye(8, dtype=jnp.int8) * 3
    out = approx_matmul_pallas(x, w, block=(8, 8, 8), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 3)  # 3*1 exact (tiny pp)


def test_approx_backend_is_differentiable_via_ste():
    """The approximate backends train through the straight-through
    estimator: gradients of the quantized matmul exist and are finite.
    (Compiles for the TPU itself are in test_tpu_compile.py.)"""
    from repro.quant.matmul import quantized_matmul
    from repro.quant.quantize import QuantConfig
    cfg = QuantConfig(backend="approx_lut")
    g = jax.grad(lambda a: quantized_matmul(
        a, jnp.ones((8, 4)) * 0.1, cfg).sum())(jnp.ones((2, 8)))
    assert np.all(np.isfinite(np.asarray(g)))


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 20), st.integers(1, 20), st.integers(1, 20),
       st.integers(0, 2 ** 31 - 1))
def test_property_kernel_matches_oracle(m, k, n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rng.integers(-127, 128, (k, n)).astype(np.int8))
    got = approx_matmul_pallas(x, w, block=(8, 8, 8), interpret=True)
    want = R.approx_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

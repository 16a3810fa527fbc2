"""Pipeline parallelism: pipelined stage execution == sequential reference.

Used to shell out to a subprocess for the 4-device 'stage' axis; the
repo-root conftest.py forces 8 host CPU devices, so the mesh is built
in-process from an explicit 4-device slice.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.parallel.pipeline import pipeline_apply


def test_pipeline_matches_sequential():
    if jax.device_count() < 4:
        pytest.skip("needs 4 forced host devices (see conftest.py)")
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("stage",))
    S, M, B, D = 4, 6, 2, 8
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, D, D)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    mb = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))

    ref = mb
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])

    with jax.set_mesh(mesh):
        out = pipeline_apply(stage_fn, mesh, ws, mb)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err

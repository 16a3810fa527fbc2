"""Distributed correctness: the sharded model must compute the same loss as
the single-device model.

Used to shell out to a subprocess to set the dry-run device count before
jax initialized; the repo-root conftest.py now forces 8 host CPU devices
into XLA_FLAGS for every test process, so this runs in-process like any
other test (and shares jit caches with the rest of the session).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.configs import registry
from repro.models import transformer_lm as TLM
from repro.nn import module as M
from repro.parallel.sharding import DEFAULT_RULES, prune_spec


def test_sharded_loss_matches_single_device():
    if jax.device_count() < 8:
        pytest.skip("needs 8 forced host devices (see conftest.py)")
    cfg = registry.reduced("smollm-135m", n_layers=2, d_model=64, d_ff=128,
                           vocab=64, vocab_pad=64, n_heads=4, n_kv_heads=2,
                           head_dim=16)
    key = jax.random.PRNGKey(0)
    params = TLM.init(cfg, key)
    b, s = 8, 16
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab),
             "labels": jax.random.randint(key, (b, s), 0, cfg.vocab)}

    # single device
    loss1 = float(TLM.forward_loss(params, batch, cfg, training=False))

    # sharded 4x2 mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    with jax.set_mesh(mesh):
        specs = M.param_shardings(TLM.descs(cfg), DEFAULT_RULES, mesh)
        p_sh = jax.tree.map(
            lambda x, sp: jax.device_put(
                x, NamedSharding(mesh, prune_spec(x.shape, sp.spec, mesh))),
            params, specs)
        b_sh = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, PS("data"))), batch)
        loss2 = float(jax.jit(
            lambda p, bt: TLM.forward_loss(p, bt, cfg, training=False))(
            p_sh, b_sh))
    assert abs(loss1 - loss2) < 2e-3, (loss1, loss2)

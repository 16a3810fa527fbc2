"""The plain reference: its multiplier and integer core."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference


def test_multiplier_table_matches_the_programs_gate_model():
    from repro.core.multiplier import exhaustive_products, proposed_multiplier
    ours = reference.approx_products()
    assert (ours == exhaustive_products(proposed_multiplier("proposed"))).all()
    a = np.arange(256)
    assert (ours <= a[:, None] * a[None, :]).all()
    assert (ours != a[:, None] * a[None, :]).any()


@pytest.mark.parametrize("core", ["exact", "approx:proposed"])
def test_integer_core_equals_table_lookup(core):
    rng = np.random.default_rng(0)
    x = rng.integers(-127, 128, (7, 40)).astype(np.int8)
    w = rng.integers(-127, 128, (40, 9)).astype(np.int8)
    x[0, :] = 127
    w[:, 0] = -127
    table = (reference.approx_products() if core != "exact"
             else np.arange(256)[:, None] * np.arange(256)[None, :])
    mag = table[np.abs(x.astype(np.int64))[:, :, None],
                np.abs(w.astype(np.int64))[None, :, :]]
    sign = np.sign(x.astype(np.int64))[:, :, None] * \
        np.sign(w.astype(np.int64))[None, :, :]
    want = (sign * mag).sum(1)
    got = reference._integer_core(jnp.asarray(x), jnp.asarray(w),
                                  reference.deficit_planes(core))
    assert (np.asarray(got) == want).all()


def test_qmm_int4_codes_are_coarser():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 8)) * 0.02, jnp.bfloat16)
    exact = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
    planes = reference.deficit_planes("exact")
    e8 = np.abs(np.asarray(reference.qmm(x, w, 127, planes), np.float32)
                - exact).mean()
    e4 = np.abs(np.asarray(reference.qmm(x, w, 7, planes), np.float32)
                - exact).mean()
    assert e4 > 5 * e8


def test_sequence_stats_targets_and_argmax():
    import harness
    import weights as W
    from conftest import BENCH, TINY
    conf = harness.load_json(BENCH / "configs" / "smollm-135m.int8.json")
    conf.update(TINY)
    w = W.make(conf, 3)
    toks = np.arange(1, 20, dtype=np.int32)
    best, at, arg = reference.sequence_stats(w, conf, toks)
    assert best.shape == at[:, 0].shape == arg.shape == (18,)
    assert (at[:, 0] <= best).all()
    b2, at2, _ = reference.sequence_stats(w, conf, toks, arg[:, None],
                                          vocab_chunk=100)
    np.testing.assert_array_equal(b2, best)
    np.testing.assert_array_equal(at2[:, 0], best)

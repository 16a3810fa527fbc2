"""Random weights of a configuration, made on the device from the seed.

One jitted call builds the whole tree in bf16, the dtype it is served in,
laid out as the program takes it: the leaf shapes are the configuration's
architecture module's (``bench/arch/<architectures[0]>.py``, ``shapes``),
one stacked group per entry of the program's block program. Every matrix
is drawn from N(0, initializer_range^2), the source's own initializer;
norm scales are ones. The program and the reference both get this tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import harness


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed, also one above 32 bits, as two uint32 words."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


@functools.partial(jax.jit,
                   static_argnames=("shapes", "ones", "std", "treedef"))
def _make(words, shapes, ones, std, treedef):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                words[0]), words[1])
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, shape, one in zip(keys, shapes, ones):
        if one:
            out.append(jnp.ones(shape, jnp.bfloat16))
        else:
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(jnp.bfloat16))
    return jax.tree.unflatten(treedef, out)


def make(conf: dict, seed: int):
    """The weight tree of configuration ``conf`` for ``seed``: one key of
    ``jax.random.split`` per leaf, in the flattened tree's order."""
    tree = harness.arch_module(conf).shapes(conf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    return _make(jnp.asarray(seed_words(seed)),
                 tuple(shape for _, shape in flat),
                 tuple("scale" in jax.tree_util.keystr(p) for p, _ in flat),
                 float(conf["initializer_range"]), treedef)

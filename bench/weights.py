"""Random weights of a configuration, made on the device from the seed.

One jitted call builds the whole tree in bf16, the dtype it is served in,
laid out as the program's decoder takes it (one scanned group of
``num_hidden_layers`` identical layers, tied embeddings). Every matrix is
drawn from N(0, initializer_range^2), the source's own initializer; norm
scales are ones. The program and the reference both get this tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed, also one above 32 bits, as two uint32 words."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def shapes(conf: dict) -> dict:
    """The tree's leaf shapes."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    hd = d // conf["num_attention_heads"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    layer = {"ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
             "attn": {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
                      "wo": (n, q, d)},
             "mlp": {"wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)}}
    return {"embed": {"table": (v, d)}, "final_ln": {"scale": (d,)},
            "blocks": [{"k0_self": layer}]}


@functools.partial(jax.jit, static_argnames=("frozen",))
def _make(words, frozen):
    conf = dict(frozen)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                words[0]), words[1])
    tree = shapes(conf)
    std = conf["initializer_range"]
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(
        x, tuple))
    keys = jax.random.split(key, len(leaves))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        if "scale" in path:
            out.append(jnp.ones(shape, jnp.bfloat16))
        else:
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(jnp.bfloat16))
    return jax.tree.unflatten(treedef, out)


def make(conf: dict, seed: int):
    """The weight tree of configuration ``conf`` for ``seed``."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "vocab_size", "num_attention_heads", "num_key_value_heads",
            "initializer_range")
    return _make(jnp.asarray(seed_words(seed)),
                 tuple((k, conf[k]) for k in keys))

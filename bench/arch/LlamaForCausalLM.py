"""``LlamaForCausalLM``: a decoder of identical pre-norm layers, each
grouped-query attention with rotary positions and a SwiGLU MLP, then a
final norm and a head that is the embedding table or, where the file says
``tie_word_embeddings: false``, a table of its own (``lm_head``).

The configuration file's keys are those of the source's ``config.json``.
The program keeps the layers as one scanned group (``blocks[0]
["k0_self"]``) and layer 0's cache as ``k`` and ``v``.

The reference part (``sequence_stats``, ``layer0_cache``) builds on
``bench/reference.py``'s numerics and imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import BF16, deficit_planes, qmm, rmsnorm, rope

# The file's size keys at the bench tests' tiny size (bench/tests).
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=1, vocab_size=512)

# File keys that ``reduced`` may cut, each with the program field it sets.
CUTS = {"num_hidden_layers": "n_layers"}


def fields(conf: dict) -> dict:
    """The program's ``ArchConfig`` fields that the file fixes."""
    heads = conf["num_attention_heads"]
    return {"d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
            "n_layers": conf["num_hidden_layers"], "n_heads": heads,
            "n_kv_heads": conf["num_key_value_heads"],
            "vocab": conf["vocab_size"], "dh": conf["hidden_size"] // heads,
            "rope_theta": conf["rope_theta"],
            "tied_embeddings": conf["tie_word_embeddings"],
            "param_dtype": jnp.dtype(conf["torch_dtype"])}


def shapes(conf: dict) -> dict:
    """The weight tree's leaf shapes, laid out as the program takes it."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    hd = d // conf["num_attention_heads"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    layer = {"ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
             "attn": {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
                      "wo": (n, q, d)},
             "mlp": {"wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)}}
    tree = {"embed": {"table": (v, d)}, "final_ln": {"scale": (d,)},
            "blocks": [{"k0_self": layer}]}
    if not conf["tie_word_embeddings"]:
        tree["lm_head"] = {"table": (v, d)}
    return tree


def cache0(conf: dict):
    """(path of layer 0's group in the program's pool, its leaves): each
    leaf is (layers, slots, positions, ...)."""
    return ("blocks", 0, "k0_self"), ("k", "v")


def projection_weights(conf: dict) -> int:
    """Weights that one token multiplies: q, k, v, o, gate, up and down of
    every layer, and the head (the embedding table or ``lm_head``)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hd = d // conf["num_attention_heads"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return conf["num_hidden_layers"] * layer + d * conf["vocab_size"]


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------

def _attention(q, k, v, n_valid):
    """Causal attention of one sequence: q (T, H, D), k/v (T, Hkv, D);
    rows at or past n_valid are padding."""
    t, h, d = q.shape
    g = h // k.shape[1]
    kk = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vv = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), kk) / math.sqrt(d)
    i = jnp.arange(t)
    mask = (i[None, :] <= i[:, None]) & (i[None, :] < n_valid)
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, vv).astype(BF16).reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=("dims", "qmax", "core"))
def _layer(x, lw, n_valid, pos, *, dims, qmax, core):
    heads, kv_heads, hd, eps, theta = dims
    planes = deficit_planes(core)
    t = x.shape[0]
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, lw["ln1"], eps)
        q = rope(qmm(h, lw["wq"], qmax, planes).reshape(t, heads, hd),
                 pos, theta)
        k = rope(qmm(h, lw["wk"], qmax, planes).reshape(t, kv_heads, hd),
                 pos, theta)
        v = qmm(h, lw["wv"], qmax, planes).reshape(t, kv_heads, hd)
        a = _attention(q, k, v, n_valid)
        x = (x + qmm(a, lw["wo"], qmax, planes)).astype(BF16)
        h2 = rmsnorm(x, lw["ln2"], eps)
        gate = qmm(h2, lw["wg"], qmax, planes).astype(jnp.float32)
        up = qmm(h2, lw["wu"], qmax, planes).astype(jnp.float32)
        act = (gate * jax.nn.sigmoid(gate)).astype(BF16).astype(jnp.float32)
        m = (act * up).astype(BF16)
        return (x + qmm(m, lw["wd"], qmax, planes)).astype(BF16)


@functools.partial(jax.jit, static_argnames=("eps", "qmax", "core"))
def _head(x, final_scale, table, targets, *, eps, qmax, core):
    """One vocabulary chunk's logit statistics per row: (best logit, its
    index within the chunk, logits of ``targets`` (rows, K), -inf where a
    target lies outside the chunk)."""
    planes = deficit_planes(core)
    h = rmsnorm(x, final_scale, eps)
    logits = qmm(h, table.T, qmax, planes).astype(jnp.float32)
    inside = (targets >= 0) & (targets < table.shape[0])
    at = jnp.take_along_axis(
        logits, jnp.clip(targets, 0, table.shape[0] - 1), -1)
    return (logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.where(inside, at, -jnp.inf))


@functools.partial(jax.jit, static_argnames=("dims", "qmax", "core"))
def _kv0(tokens, pos, table, lw, *, dims, qmax, core):
    """Layer 0's K (after rotary) and V of each token at its position: a
    function of the token and the position alone."""
    heads, kv_heads, hd, eps, theta = dims
    planes = deficit_planes(core)
    t = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(jnp.take(table, tokens, axis=0).astype(BF16), lw["ln1"],
                    eps)
        k = rope(qmm(h, lw["wk"], qmax, planes).reshape(t, kv_heads, hd),
                 pos, theta)
        v = qmm(h, lw["wv"], qmax, planes).reshape(t, kv_heads, hd)
    return k, v


def _dims(conf):
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["hidden_size"] // conf["num_attention_heads"],
            float(conf["rms_norm_eps"]), float(conf["rope_theta"]))


def layer_weights(w, i):
    """Layer ``i``'s weights as a flat dict, from the program-layout tree
    that ``bench/weights.py`` makes."""
    blk = w["blocks"][0]["k0_self"]
    return {"ln1": blk["ln1"]["scale"][i], "ln2": blk["ln2"]["scale"][i],
            **{n: blk["attn"][n][i] for n in ("wq", "wk", "wv", "wo")},
            **{n: blk["mlp"][n][i] for n in ("wg", "wu", "wd")}}


def layer0_cache(weights, conf, tokens, pos, *, qmax=127, core=None,
                 rows=1024):
    """Layer 0's cache leaves, as ``cache0`` names them, of ``tokens`` (n,)
    at positions ``pos`` (n,), in blocks of ``rows``: ``k`` (after rotary)
    and ``v``, numpy float32 (n, kv heads, head size) each. ``core``
    overrides the configuration's integer core."""
    core = core or conf["quant"]["core"]
    tokens = np.asarray(tokens, np.int32)
    pos = np.asarray(pos, np.int32)
    n = len(tokens)
    lw = layer_weights(weights, 0)
    ks, vs = [], []
    for r0 in range(0, n, rows):
        tb = np.zeros(rows, np.int32)
        pb = np.zeros(rows, np.int32)
        m = min(rows, n - r0)
        tb[:m], pb[:m] = tokens[r0:r0 + m], pos[r0:r0 + m]
        k, v = _kv0(jnp.asarray(tb), jnp.asarray(pb),
                    weights["embed"]["table"], lw, dims=_dims(conf),
                    qmax=qmax, core=core)
        ks.append(np.asarray(k, np.float32)[:m])
        vs.append(np.asarray(v, np.float32)[:m])
    return {"k": np.concatenate(ks), "v": np.concatenate(vs)}


def sequence_stats(weights, conf, tokens, targets=None, *, qmax=127,
                   core=None, vocab_chunk=8192):
    """Teacher-forced pass over one token sequence.

    tokens: (T,) int32; targets: (T-1, K) token ids to read at each
    position (default: the next token, K = 1). Returns numpy
    (best, at, argmax): for every position p < T-1, the reference's best
    logit, its logits for targets[p] (T-1, K), and the token it ranks
    first. ``core`` overrides the configuration's integer core.
    """
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if targets is None:
        targets = tokens[1:, None]
    targets = np.asarray(targets, np.int32).reshape(n - 1, -1)
    padded = 1 << max(3, (n - 1).bit_length())
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    dims = _dims(conf)
    core = core or conf["quant"]["core"]
    x = jnp.take(weights["embed"]["table"], jnp.asarray(toks),
                 axis=0).astype(BF16)
    pos = jnp.arange(padded, dtype=jnp.int32)
    for i in range(conf["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, i), jnp.int32(n), pos,
                   dims=dims, qmax=qmax, core=core)
    head = weights["embed" if conf["tie_word_embeddings"]
                   else "lm_head"]["table"]
    tg = np.zeros((padded, targets.shape[1]), np.int32)
    tg[:n - 1] = targets
    best = np.full(padded, -np.inf, np.float32)
    arg = np.zeros(padded, np.int32)
    at = np.full(tg.shape, -np.inf, np.float32)
    for v0 in range(0, head.shape[0], vocab_chunk):
        b, a, t = (np.asarray(o) for o in _head(
            x, weights["final_ln"]["scale"], head[v0:v0 + vocab_chunk],
            jnp.asarray(tg - v0), eps=float(conf["rms_norm_eps"]),
            qmax=qmax, core=core))
        better = b > best               # first maximum wins, as argmax
        arg = np.where(better, a + v0, arg)
        best = np.maximum(best, b)
        at = np.maximum(at, t)
    return best[:n - 1], at[:n - 1], arg[:n - 1]

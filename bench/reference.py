"""Plain reference of a served configuration, independent of the program.

A straightforward decoder forward pass in ``jax.numpy``, written from the
configuration file (``bench/configs/<config>.json``) alone: it imports
nothing of ``repro`` and takes nothing the program made. Weights come from
``bench/weights.py`` (the benchmark makes them from the seed and hands the
same tree to the program and to this reference).

Numerics follow what the configuration states:

* bf16 storage: every op reads bf16, computes in float32, and rounds its
  output to bf16 (embedding rows, norms, projections, rotary, attention
  output, residual adds, the SwiGLU product). Float32 matmuls run at
  ``highest`` precision.
* every projection, the tied LM head included, is W8A8: a per-output-
  channel weight scale and a per-token activation scale, ``amax / qmax``
  in the operand's dtype; codes ``clip(round(x / s), -qmax, qmax)``; an
  integer core ``sum_k P(x_q, w_q)``; dequant ``(acc * s_w) * s_x``.
* ``P`` is the exact product, or for ``core: approx:proposed`` the paper's
  8x8 unsigned approximate multiplier applied to magnitudes with the sign
  of the exact product. The multiplier is a gate-level model of the
  reduction tree below; its table is exact integer data, and the integer
  core computes ``x_q @ w_q - sum_k sign * D(|x_q|, |w_q|)`` with the
  deficit ``D = a*b - P`` as one-hot contractions over the few magnitudes
  whose row of ``D`` is not zero.

``qmax`` is 127 for the configuration as stated and 7 for the control
(int4 codes: the step below int8). ``layer0_kv`` gives the keys and values
that layer 0 writes to the cache, which depend on a token and its
position alone: the integer core's output after one normalisation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16

# ---------------------------------------------------------------------------
# The paper's multiplier (gate level)
# ---------------------------------------------------------------------------
#
# 8x8 unsigned partial-product array reduced in two stages of 4:2
# compressors (Dadda-style plan), then added exactly. Every 4:2 compressor
# is the proposed approximate one: no carry in or out, output
# 2*carry + sum = min(x1 + x2 + x3 + x4, 3). Stage 1 runs the ops listed per
# column on the column's bits in order (partial products by ascending
# multiplicand bit, then the carries that the previous column's ops made);
# what an op leaves over stays, followed by the op's sums. Stage 2 runs one
# compressor in each of columns 3..10 on the first four bits of that
# column. Half and full adders, and the final addition, are exact.

STAGE1_OPS = {4: ("ha",), 5: ("c",), 6: ("c", "ha"), 7: ("c", "c"),
              8: ("c", "fa"), 9: ("c", "ha"), 10: ("c",), 11: ("ha",)}
STAGE2_COLS = range(3, 11)
_WIDTH = {"c": 4, "fa": 3, "ha": 2}


def _op(kind, bits):
    total = sum(bits)
    if kind == "c":
        total = np.minimum(total, 3)
    return total & 1, total >> 1


def approx_products() -> np.ndarray:
    """(256, 256) int64: the approximate product of every pair of unsigned
    8-bit operands."""
    a = np.arange(256, dtype=np.int64)[:, None] * np.ones((1, 256), np.int64)
    b = a.T
    cols = [[] for _ in range(18)]
    for i in range(8):
        for j in range(8):
            cols[i + j].append(((a >> i) & 1) & ((b >> j) & 1))
    mid = [[] for _ in range(18)]
    for c in range(17):
        bits, sums = cols[c] + mid[c], []
        for kind in STAGE1_OPS.get(c, ()):
            w = _WIDTH[kind]
            if len(bits) < w:
                continue
            s, cy = _op(kind, bits[:w])
            bits = bits[w:]
            sums.append(s)
            mid[c + 1].append(cy)
        mid[c] = bits + sums
    total = np.zeros_like(a)
    for c in range(17):
        bits = mid[c]
        if c in STAGE2_COLS and len(bits) >= 4:
            s, cy = _op("c", bits[:4])
            bits = bits[4:] + [s]
            total = total + (cy << (c + 1))
        for bit in bits:
            total = total + (bit << c)
    return total


@functools.lru_cache(maxsize=4)
def deficit_planes(core: str):
    """(alphas, hi, lo) for the integer core ``core``: the magnitudes
    0..127 whose deficit row is not zero, and that row split into int8
    digits ``D = 128 * hi + lo``. Empty for the exact core."""
    if core == "exact":
        return (np.zeros(0, np.int32), np.zeros((0, 128), np.int8),
                np.zeros((0, 128), np.int8))
    if core != "approx:proposed":
        raise ValueError(f"unknown integer core {core!r}")
    m = np.arange(128, dtype=np.int64)
    d = m[:, None] * m[None, :] - approx_products()[:128, :128]
    if d.min() < 0 or d.max() >= 128 * 128:
        raise ValueError("deficit out of the two-digit int8 range")
    alphas = np.nonzero((d != 0).any(axis=1))[0].astype(np.int32)
    rows = d[alphas]
    return alphas, (rows // 128).astype(np.int8), (rows % 128).astype(np.int8)


# ---------------------------------------------------------------------------
# W8A8 projection
# ---------------------------------------------------------------------------

def _scale(x, axis, qmax):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    return jnp.maximum(amax, 1e-8) / qmax


def _codes(x, s, qmax):
    return jnp.clip(jnp.round(x / s), -qmax, qmax).astype(jnp.int8)


def _idot(a, b):
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _integer_core(xq, wq, planes):
    """sum_k P(xq[m, k], wq[k, n]) as int32."""
    acc = _idot(xq, wq)
    alphas, hi, lo = planes
    if len(alphas) == 0:
        return acc
    xm = jnp.abs(xq.astype(jnp.int32))
    xs = jnp.sign(xq.astype(jnp.int32))
    wm = jnp.abs(wq.astype(jnp.int32))
    ws = jnp.sign(wq.astype(jnp.int32)).astype(jnp.int8)
    # one-hot of the activation magnitude over the deficit rows, signed
    onehot = ((xm[..., None] == jnp.asarray(alphas)) * xs[..., None])
    onehot = onehot.astype(jnp.int8).reshape(xq.shape[0], -1)  # (m, k*A)
    for digit, weight in ((hi, 128), (lo, 1)):
        # (k, n, A): the deficit digit of every (alpha, |w|) pair, signed
        tab = jnp.asarray(digit.T)                              # (128, A)
        wd = jnp.take(tab, wm, axis=0) * ws[..., None]
        wd = jnp.transpose(wd, (0, 2, 1)).reshape(-1, wq.shape[1])
        acc = acc - weight * _idot(onehot, wd)
    return acc


def qmm(x, w, qmax, planes):
    """bf16 (m, k) @ bf16 (k, n) through the W8A8 (or W4A4) integer core;
    bf16 out."""
    sw = _scale(w, 0, qmax)                     # (1, n) in w's dtype
    sx = _scale(x, -1, qmax)                    # (m, 1) in x's dtype
    acc = _integer_core(_codes(x, sx, qmax), _codes(w, sw, qmax), planes)
    y = (acc.astype(jnp.float32) * sw.astype(jnp.float32)) \
        * sx.astype(jnp.float32)
    return y.astype(BF16)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(BF16)


def _rope(x, pos, theta):
    """x (T, H, D) bf16, rotate-half form; pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(BF16)


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
        h = _rmsnorm(x, lw["ln1"], eps)
        q = _rope(qmm(h, lw["wq"], qmax, planes).reshape(t, heads, hd),
                  pos, theta)
        k = _rope(qmm(h, lw["wk"], qmax, planes).reshape(t, kv_heads, hd),
                  pos, theta)
        v = qmm(h, lw["wv"], qmax, planes).reshape(t, kv_heads, hd)
        a = _attention(q, k, v, n_valid)
        x = (x + qmm(a, lw["wo"], qmax, planes)).astype(BF16)
        h2 = _rmsnorm(x, lw["ln2"], eps)
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
    h = _rmsnorm(x, final_scale, eps)
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
        h = _rmsnorm(jnp.take(table, tokens, axis=0).astype(BF16), lw["ln1"],
                     eps)
        k = _rope(qmm(h, lw["wk"], qmax, planes).reshape(t, kv_heads, hd),
                  pos, theta)
        v = qmm(h, lw["wv"], qmax, planes).reshape(t, kv_heads, hd)
    return k, v


def _dims(conf):
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["hidden_size"] // conf["num_attention_heads"],
            float(conf["rms_norm_eps"]), float(conf["rope_theta"]))


def layer0_kv(weights, conf, tokens, pos, *, qmax=127, core=None,
              rows=1024):
    """Layer 0's K and V (numpy float32, (n, kv heads, head size) each) of
    ``tokens`` (n,) at positions ``pos`` (n,), in blocks of ``rows``.
    ``core`` overrides the configuration's integer core."""
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
    return np.concatenate(ks), np.concatenate(vs)


def layer_weights(w, i):
    """Layer ``i``'s weights as a flat dict, from the program-layout tree
    that ``bench/weights.py`` makes."""
    blk = w["blocks"][0]["k0_self"]
    return {"ln1": blk["ln1"]["scale"][i], "ln2": blk["ln2"]["scale"][i],
            **{n: blk["attn"][n][i] for n in ("wq", "wk", "wv", "wo")},
            **{n: blk["mlp"][n][i] for n in ("wg", "wu", "wd")}}


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
    table = weights["embed"]["table"]
    x = jnp.take(table, jnp.asarray(toks), axis=0).astype(BF16)
    pos = jnp.arange(padded, dtype=jnp.int32)
    for i in range(conf["num_hidden_layers"]):
        x = _layer(x, layer_weights(weights, i), jnp.int32(n), pos,
                   dims=dims, qmax=qmax, core=core)
    tg = np.zeros((padded, targets.shape[1]), np.int32)
    tg[:n - 1] = targets
    best = np.full(padded, -np.inf, np.float32)
    arg = np.zeros(padded, np.int32)
    at = np.full(tg.shape, -np.inf, np.float32)
    for v0 in range(0, table.shape[0], vocab_chunk):
        b, a, t = (np.asarray(o) for o in _head(
            x, weights["final_ln"]["scale"], table[v0:v0 + vocab_chunk],
            jnp.asarray(tg - v0), eps=float(conf["rms_norm_eps"]),
            qmax=qmax, core=core))
        better = b > best               # first maximum wins, as argmax
        arg = np.where(better, a + v0, arg)
        best = np.maximum(best, b)
        at = np.maximum(at, t)
    return best[:n - 1], at[:n - 1], arg[:n - 1]

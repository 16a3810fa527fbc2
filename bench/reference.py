"""The numerics that every plain reference of the benchmark shares,
independent of the program.

Each architecture's reference (``bench/arch/<architectures[0]>.py``, its
``sequence_stats`` and ``layer0_cache``) is a straightforward forward pass
in ``jax.numpy`` written from the configuration file alone, built from the
pieces here: it imports nothing of ``repro`` and takes nothing the program
made. Weights come from ``bench/weights.py`` (the benchmark makes them from
the seed and hands the same tree to the program and to the reference).

Numerics follow what the configuration states:

* bf16 storage: every op reads bf16, computes in float32, and rounds its
  output to bf16 (embedding rows, norms, projections, rotary, attention
  output, residual adds, the gated MLP's product). Float32 matmuls run at
  ``highest`` precision.
* every projection, the LM head included, is W8A8 (``qmm``): a per-output-
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
* ``rmsnorm`` and rotate-half ``rope`` in the same bf16 rules.

``qmax`` is 127 for the configuration as stated and 7 for the control
(int4 codes: the step below int8).
"""
from __future__ import annotations

import functools

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
# Norm and rotary
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(BF16)


def rope(x, pos, theta):
    """x (T, H, D) bf16, rotate-half form; pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(BF16)

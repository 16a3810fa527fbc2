"""Pallas TPU kernels for approximate-multiplier matmuls.

Two tile bodies, shared by the int32 (pre-dequant) and fused-epilogue
kernels:

1. deficit — bit-exact emulation of the paper's multiplier. Per (bm, bn, bk)
   tile: the exact int8 dot runs on the MXU; the error term is accumulated
   by a fori_loop over k-chunks of width ``kv`` evaluating the *deficit
   planes* (core/deficit.py) on (kv, bm, bn) broadcasts — pure VPU bit-ops,
   no gathers, no 64K LUT in VMEM. This is the TPU-native port of the
   circuit: the same boolean sites, evaluated as vector ops. ``kv`` trades
   loop trips for intermediate size (kv * bm * bn i32 planes, capped at
   ``_PLANE_BYTES``); kv=1 reproduces the original one-column-at-a-time
   loop.

2. stage1 — the beyond-paper re-approximation: exact tile dot minus the 7
   rank-1 stage-1 site corrections, each itself a tile dot (all MXU work,
   ~8x an exact matmul, ~40x cheaper than full emulation and 3.5x more
   accurate than the paper's multiplier — see EXPERIMENTS.md).

3. rank1 — bit-exact emulation with NO element-wise deficit work: the
   error table is factored exactly as E = U @ V (core/factor.py), the
   sign-folded factor features are gathered outside the kernel (O(M*K + K*N)
   tiny-table gathers), and each (bm, bn, bk) tile issues the correction as
   int8 dot_generals on the accumulator tile — one per base-128 digit plane
   of V — alongside the exact int8 dot. Every op the kernel runs is an MXU
   matmul; correction contraction width is bk * R (R = per-design factor
   count, 49 for the proposed compressor on the int8 domain).

Entry points:

``approx_matmul_pallas``   (M, K) x (K, N) -> int32 (M, N); the raw
                           integer contract shared with the jnp backends.
``rank1_matmul_pallas``    same contract for the rank-factored kernel
                           (separate entry: it stages factor features and
                           carries extra operands).
``fused_matmul_pallas``    (B, M, K) or (M, K) int8 -> float32; the int32
                           accumulator lives in VMEM scratch and the
                           epilogue (dequant scale — per-tensor or
                           per-channel — optional bias, optional ReLU) runs
                           in-kernel on the final k-step. Leading batch dim
                           is a grid axis: (B, T, K) activations hit the
                           kernel without host-side reshape/copy.

Block sizes default to MXU-aligned (128, 128, 128). Every MXU dot takes
int8 operands with an int32 result (Mosaic refuses int32 x int32); int32
is for the VPU bit-plane work only. The deficit body's live planes are
what bounds VMEM: ``_PLANE_BYTES`` keeps them inside the compiler's
default 16 MiB scoped limit at every tile size
(tests/test_tpu_compile.py compiles each kernel for a v5e).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import deficit as D
from repro.core import factor as F
from repro.core.factor import STAGE1_SITES


def _exact_dot(x, w):
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# Shared tile bodies
# ---------------------------------------------------------------------------

# Bytes of one (kv, bm, bn) int32 deficit plane. The tile body keeps a few
# dozen such planes live, so this bounds the kernel's VMEM stack: at 512 KiB
# a (128, 128, 128) tile evaluates 8 k-columns per trip and fits the
# compiler's default 16 MiB scoped-VMEM limit.
_PLANE_BYTES = 512 * 1024


def _plane_kv(kv: int, bk: int, bm: int, bn: int) -> int:
    """Largest divisor of bk, not above kv, whose deficit planes fit
    ``_PLANE_BYTES``."""
    kv = max(1, min(kv, bk, _PLANE_BYTES // (4 * bm * bn)))
    while bk % kv:
        kv -= 1
    return kv


def _deficit_tile_err(xt_ref, w_ref, design: str, kv: int):
    """sum_k deficit(|x[m,k]|, |w[k,n]|) * sign for one (bm, bk, bn) tile.

    ``xt_ref`` holds the x tile transposed, (bk, bm), so that a k-chunk of
    either operand is a sublane slice of its ref (`pl.ds` reads; Mosaic has
    no dynamic lane slice). The deficit planes are evaluated on
    (kv, bm, bn) broadcasts, kv k-rows per loop trip, and summed over the
    leading axis. Integer-exact for any kv; padded k-rows contribute zero
    because their sign product is zero.
    """
    bk, bm = xt_ref.shape
    bn = w_ref.shape[1]
    kv = _plane_kv(kv, bk, bm, bn)

    def body(c, err):
        off = pl.multiple_of(c * kv, kv)
        a = xt_ref[pl.ds(off, kv), :].astype(jnp.int32)      # (kv, bm)
        b = w_ref[pl.ds(off, kv), :].astype(jnp.int32)       # (kv, bn)
        df = D.deficit_sum(jnp.abs(a)[:, :, None], jnp.abs(b)[:, None, :],
                           design)                           # (kv, bm, bn)
        sgn = jnp.sign(a)[:, :, None] * jnp.sign(b)[:, None, :]
        return err + (df * sgn).sum(axis=0)

    return jax.lax.fori_loop(0, bk // kv, body,
                             jnp.zeros((bm, bn), jnp.int32))


def _stage1_tile_corr(x, w):
    """sum of the 7 rank-1 stage-1 site corrections for one tile (each an
    MXU int8 dot over {-1,0,1} window features; the bit windows are VPU
    int32 work)."""
    xi, wi = x.astype(jnp.int32), w.astype(jnp.int32)
    xmag, wmag = jnp.abs(xi), jnp.abs(wi)
    xsgn, wsgn = jnp.sign(xi), jnp.sign(wi)

    def window(v, s):
        out = (v >> s) & 1
        for i in range(s + 1, s + 4):
            out = out & ((v >> i) & 1)
        return out

    corr = None
    for col, ra, rb in STAGE1_SITES:
        u = (window(xmag, ra) * xsgn).astype(jnp.int8)   # (bm, bk)
        v = (window(wmag, rb) * wsgn).astype(jnp.int8)   # (bk, bn)
        term = _exact_dot(u, v) << col
        corr = term if corr is None else corr + term
    return corr


# ---------------------------------------------------------------------------
# int32 kernels (pre-dequant contract, 2D)
# ---------------------------------------------------------------------------

def _approx_kernel(x_ref, xt_ref, w_ref, o_ref, *, design: str, kv: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += (_exact_dot(x_ref[...], w_ref[...])
                   - _deficit_tile_err(xt_ref, w_ref, design, kv))


def _stage1_kernel(x_ref, w_ref, o_ref):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x, w = x_ref[...], w_ref[...]
    o_ref[...] += _exact_dot(x, w) - _stage1_tile_corr(x, w)


def _rank1_tile_corr(xf, wf_digits):
    """Rank-factored correction for one tile: one int8 dot per digit plane
    of V, recomposed by base-128 shifts (exact in int32 modular arithmetic;
    the true value fits int32)."""
    corr = None
    for d, wf in enumerate(wf_digits):
        term = _exact_dot(xf, wf) << (7 * d)
        corr = term if corr is None else corr + term
    return corr


def _rank1_kernel(*refs, nd: int):
    x_ref, w_ref, xf_ref = refs[:3]
    wf_refs = refs[3:3 + nd]
    o_ref = refs[3 + nd]
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    w = w_ref[...]
    o_ref[...] += _exact_dot(x, w) - _rank1_tile_corr(
        xf_ref[...], [r[...] for r in wf_refs])


# ---------------------------------------------------------------------------
# fused-epilogue kernel (batched, float32 out)
# ---------------------------------------------------------------------------

def _fused_kernel(*refs, nk: int, design: str, variant: str, relu: bool,
                  kv: int):
    if variant == "deficit":   # only the deficit body reads the x^T tile
        x_ref, xt_ref, w_ref, s_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref = refs
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                             # (bm, bk) int8
    w = w_ref[...]                             # (bk, bn) int8
    acc = _exact_dot(x, w)
    if variant == "deficit":
        acc = acc - _deficit_tile_err(xt_ref, w_ref, design, kv)
    elif variant == "stage1":
        acc = acc - _stage1_tile_corr(x, w)
    # variant == "exact": plain int8 dot
    acc_ref[...] += acc

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]
        if relu:
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out


def _rank1_fused_kernel(*refs, nk: int, nd: int, relu: bool):
    x_ref, w_ref, xf_ref = refs[:3]
    wf_refs = refs[3:3 + nd]
    s_ref, b_ref, o_ref, acc_ref = refs[3 + nd:]
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _exact_dot(x_ref[0], w_ref[...]) - _rank1_tile_corr(
        xf_ref[0], [r[...] for r in wf_refs])

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]
        if relu:
            out = jnp.maximum(out, 0.0)
        o_ref[0] = out


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _pad_to(x, m, axes):
    pads = [(0, 0)] * x.ndim
    for ax, mult in zip(axes, m):
        pads[ax] = (0, (-x.shape[ax]) % mult)
    return jnp.pad(x, pads) if any(p != (0, 0) for p in pads) else x


def _compiler_params(interpret: bool, n_parallel: int):
    if interpret:  # interpreter ignores/rejects TPU compiler params
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",))}


@functools.partial(jax.jit, static_argnames=("block", "design", "interpret",
                                             "kernel", "kv"))
def approx_matmul_pallas(x_q: jax.Array, w_q: jax.Array,
                         block: Tuple[int, int, int] = (128, 128, 128),
                         design: str = "proposed",
                         kernel: str = "deficit",
                         interpret: bool = True,
                         kv: int = 32) -> jax.Array:
    """x_q (M,K) int8, w_q (K,N) int8 -> (M,N) int32 approximate matmul."""
    m, k = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x_q, (bm, bk), (0, 1))
    wp = _pad_to(w_q, (bk, bn), (0, 1))
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    if kernel == "deficit":
        body = functools.partial(_approx_kernel, design=design, kv=kv)
        in_specs = [x_spec, pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i)),
                    w_spec]
        operands = (xp, xp.T, wp)
    else:
        body, in_specs, operands = _stage1_kernel, [x_spec, w_spec], (xp, wp)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
        **_compiler_params(interpret, 2),
    )(*operands)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block", "design", "variant",
                                             "relu", "interpret", "kv"))
def fused_matmul_pallas(x_q: jax.Array, w_q: jax.Array,
                        scale: jax.Array, bias: jax.Array,
                        block: Tuple[int, int, int] = (128, 128, 128),
                        design: str = "proposed",
                        variant: str = "deficit",
                        relu: bool = False,
                        interpret: bool = True,
                        kv: int = 32) -> jax.Array:
    """Integer matmul with the dequant epilogue fused in-kernel.

    x_q:   (B, M, K) or (M, K) int8 — leading batch dim is a grid axis.
    w_q:   (K, N) int8.
    scale: (1, N) float32 combined dequant scale (sx * sw); per-tensor
           callers broadcast their scalar to (1, N).
    bias:  (1, N) float32 (pass zeros when absent).

    Returns float32 (B, M, N) / (M, N):
        out = relu?(acc_int32 * scale + bias)
    computed on the final k-step from the VMEM int32 accumulator — no
    separate dequant/bias/activation passes over HBM.
    """
    from jax.experimental.pallas import tpu as pltpu
    squeeze = x_q.ndim == 2
    if squeeze:
        x_q = x_q[None]
    batch, m, k = x_q.shape
    n = w_q.shape[1]
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x_q, (bm, bk), (1, 2))
    wp = _pad_to(w_q, (bk, bn), (0, 1))
    _, mp, kp = xp.shape
    np_ = wp.shape[1]
    sp = _pad_to(scale.astype(jnp.float32), (bn,), (1,))
    bp = _pad_to(bias.astype(jnp.float32), (bn,), (1,))
    grid = (batch, mp // bm, np_ // bn, kp // bk)

    x_specs = [pl.BlockSpec((None, bm, bk), lambda b, i, j, kk: (b, i, kk))]
    x_ops = [xp]
    if variant == "deficit":
        x_specs.append(pl.BlockSpec((None, bk, bm),
                                    lambda b, i, j, kk: (b, kk, i)))
        x_ops.append(jnp.swapaxes(xp, 1, 2))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, nk=kp // bk, design=design,
                          variant=variant, relu=relu, kv=kv),
        grid=grid,
        in_specs=x_specs
                 + [pl.BlockSpec((bk, bn), lambda b, i, j, kk: (kk, j)),
                    pl.BlockSpec((1, bn), lambda b, i, j, kk: (0, j)),
                    pl.BlockSpec((1, bn), lambda b, i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((None, bm, bn),
                               lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(*x_ops, wp, sp, bp)
    out = out[:, :m, :n]
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# rank-factored kernel (extra factor-feature operands)
# ---------------------------------------------------------------------------

def _rank1_features(xp: jax.Array, wp: jax.Array, design: str):
    """Sign-folded factor features for padded int8 operands.

    xf: (..., M, K*R) int8 in {-1, 0, 1} (k-major feature order);
    wfs: one (K*R, N) int8 tile per base-128 digit plane of V.
    Zero padding is safe: a zero operand gathers all-zero features.
    """
    fac = F.factorize(design)
    r = fac.R
    u_tbl = jnp.asarray(fac.u_signed)                       # (256, R) int8
    ix = xp.astype(jnp.uint8).astype(jnp.int32)
    iw = wp.astype(jnp.uint8).astype(jnp.int32)
    xf = jnp.take(u_tbl, ix, axis=0).reshape(*xp.shape[:-1],
                                             xp.shape[-1] * r)
    wfs = []
    for plane in F.v_digit_planes(fac):
        wf = jnp.take(jnp.asarray(plane), iw, axis=1)       # (R, K, N) int8
        wfs.append(wf.transpose(1, 0, 2).reshape(wp.shape[0] * r,
                                                 wp.shape[1]))
    return xf, wfs


@functools.partial(jax.jit, static_argnames=("block", "design", "interpret"))
def rank1_matmul_pallas(x_q: jax.Array, w_q: jax.Array,
                        block: Tuple[int, int, int] = (128, 128, 128),
                        design: str = "proposed",
                        interpret: bool = True) -> jax.Array:
    """x_q (M,K) int8, w_q (K,N) int8 -> (M,N) int32, bit-identical to the
    paper multiplier; every kernel op is a dot_general (no deficit planes).
    """
    fac = F.factorize(design)
    r, nd = fac.R, fac.n_digits
    m, k = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x_q, (bm, bk), (0, 1))
    wp = _pad_to(w_q, (bk, bn), (0, 1))
    mp, kp = xp.shape
    np_ = wp.shape[1]
    xf, wfs = _rank1_features(xp, wp, design)
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_rank1_kernel, nd=nd),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((bm, bk * r), lambda i, j, kk: (i, kk))]
                 + [pl.BlockSpec((bk * r, bn), lambda i, j, kk: (kk, j))
                    for _ in range(nd)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
        **_compiler_params(interpret, 2),
    )(xp, wp, xf, *wfs)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block", "design", "relu",
                                             "interpret"))
def rank1_fused_matmul_pallas(x_q: jax.Array, w_q: jax.Array,
                              scale: jax.Array, bias: jax.Array,
                              block: Tuple[int, int, int] = (128, 128, 128),
                              design: str = "proposed",
                              relu: bool = False,
                              interpret: bool = True) -> jax.Array:
    """Rank-factored kernel with the dequant(+bias)(+ReLU) epilogue fused
    in-kernel; same operand contract as `fused_matmul_pallas` (leading
    batch dim is a grid axis)."""
    from jax.experimental.pallas import tpu as pltpu
    fac = F.factorize(design)
    r, nd = fac.R, fac.n_digits
    squeeze = x_q.ndim == 2
    if squeeze:
        x_q = x_q[None]
    batch, m, k = x_q.shape
    n = w_q.shape[1]
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp = _pad_to(x_q, (bm, bk), (1, 2))
    wp = _pad_to(w_q, (bk, bn), (0, 1))
    _, mp, kp = xp.shape
    np_ = wp.shape[1]
    xf, wfs = _rank1_features(xp, wp, design)
    sp = _pad_to(scale.astype(jnp.float32), (bn,), (1,))
    bp = _pad_to(bias.astype(jnp.float32), (bn,), (1,))
    grid = (batch, mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_rank1_fused_kernel, nk=kp // bk, nd=nd,
                          relu=relu),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, bk), lambda b, i, j, kk: (b, i, kk)),
                  pl.BlockSpec((bk, bn), lambda b, i, j, kk: (kk, j)),
                  pl.BlockSpec((1, bm, bk * r),
                               lambda b, i, j, kk: (b, i, kk))]
                 + [pl.BlockSpec((bk * r, bn), lambda b, i, j, kk: (kk, j))
                    for _ in range(nd)]
                 + [pl.BlockSpec((1, bn), lambda b, i, j, kk: (0, j)),
                    pl.BlockSpec((1, bn), lambda b, i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(xp, wp, xf, *wfs, sp, bp)
    out = out[:, :m, :n]
    return out[0] if squeeze else out

"""Quantized matmul execution backends — a pluggable registry.

All integer backends share the contract:
    out_int32[m, n] = sum_k  P(x_q[m, k], w_q[k, n])
where P is the (possibly approximate) signed product of two int8 values in
[-127, 127]. Built-in entries (see `list_backends()`):

  int8_exact            P = a * b                        (MXU-native)
  approx_lut            P = sign * LUT_u8(|a|, |b|)      (paper-faithful, B1)
  approx_deficit        P = a*b - sign * deficit(|a|,|b|) (bit-identical to
                        LUT; gather-free, B2 — the Pallas kernel's math)
  approx_stage1         P = a*b - sign * stage1_err(|a|,|b|) (beyond-paper:
                        keeps only the rank-1-factorizable stage-1 compressor
                        errors -> 1 + ~6 extra MXU matmuls, see DESIGN.md §3)
  approx_stage1_fused   bit-identical to approx_stage1 in 4 matmuls
  approx_rank1          P identical to approx_lut, computed as exact int8
                        matmul minus R rank-factored correction GEMMs
                        (core/factor.py; MXU-shaped, no element-wise
                        deficit planes; float32 GEMMs with proven-exact
                        integer accumulation, K-chunked past k_exact_f32)
  approx_deficit_pallas the Pallas kernel (bit-identical to approx_lut);
                        supports the fused dequant/bias/ReLU epilogue and
                        leading-dim batching
  approx_stage1_pallas  Pallas stage-1 kernel (bit-identical to
                        approx_stage1); fused epilogue likewise
  approx_rank1_pallas   Pallas rank-factored kernel: exact tile dot plus
                        int8 digit-plane correction dots on the
                        accumulator tile (bit-identical to approx_lut);
                        fused epilogue likewise
  msr4_lut / msr4       MSR-4 weight compression (core/truncation.py):
                        weights decode to 5-bit mantissa << 2-bit shift,
                        activations stay exact. `_lut` is the gate-level
                        gather reference; `msr4` is decode + 1 int8 dot.
  drum6_lut / drum6     DRUM-style dynamic truncation to 6 significant
                        bits per operand with forced-one debias; core is
                        one dot over truncated operands.
  posneg_lut / posneg   Positive/Negative asymmetric floor truncation
                        (Spantidi et al.): k=4 for positive product
                        classes, k=6 for negative; core is 4 masked dots.

New backends are added with `register_backend(name, fn)` — per-layer
selection then works everywhere `QuantConfig.backend` is consumed (dense,
conv, benchmarks, parity tests) with no dispatch chains to edit.

Backward is always the straight-through estimator (exact float grads), which
is how the paper trains its Keras models (forward substitution only).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import factor as factorlib
from repro.core import luts
# Canonical site list lives with the factorization machinery; re-exported
# here because the stage-1 backends and Pallas kernels index it.
from repro.core.factor import STAGE1_SITES  # noqa: F401  (re-export)
from repro.core.multiplier import MultiplierConfig, proposed_multiplier
from repro.quant.quantize import QuantConfig, QMAX, abs_max_scale, quantize


def _err_lut_i16(mult_cfg: MultiplierConfig) -> np.ndarray:
    """(65536,) int16 signed-product error table indexed by
    (a & 0xFF) * 256 + (b & 0xFF) for signed int8 a, b."""
    return _err_lut_cached(mult_cfg.key, mult_cfg)


@lru_cache(maxsize=16)
def _err_lut_cached(key: str, mult_cfg: MultiplierConfig) -> np.ndarray:
    signed = luts.signed_product_lut(mult_cfg)       # (256,256) int32
    vals = np.arange(256)
    sval = np.where(vals < 128, vals, vals - 256)
    exact = sval[:, None] * sval[None, :]
    return (signed - exact).astype(np.int16).reshape(-1)


@lru_cache(maxsize=16)
def _err_lut_device(key: str, mult_cfg: MultiplierConfig) -> jax.Array:
    """Device-resident flattened error LUT, staged once per config (the
    numpy table was previously re-staged on every eager call).

    Staged eagerly even when first touched inside a jit trace — a traced
    value must never land in the cache."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(_err_lut_cached(key, mult_cfg))


def _mult_cfg(cfg: QuantConfig) -> MultiplierConfig:
    return MultiplierConfig(name=f"{cfg.structure}[{cfg.multiplier}]",
                            compressor=cfg.multiplier,
                            structure=cfg.structure)


# ---------------------------------------------------------------------------
# Integer matmul kernels (jnp reference implementations; the Pallas kernels
# in repro.kernels are registered as the *_pallas backends)
# ---------------------------------------------------------------------------

def int8_matmul(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _approx_error_lut(x_q, w_q, err_flat, chunk_elems=1 << 22):
    """sum_k E[x[m,k], w[k,n]] via gather (reference path).

    Problems at or below ``chunk_elems`` (M*K*N) run in one shot — no
    ``lax.map`` machinery for the small layer shapes the eval suites sweep;
    larger ones chunk over rows to keep the (m, k, n) intermediate
    cache-resident (measured on CPU: 4M-element chunks are ~4x faster at
    256^3 than one 16M-element shot — bigger is not better)."""
    m, k = x_q.shape
    n = w_q.shape[1]
    xi = x_q.astype(jnp.uint8).astype(jnp.int32)
    wi = w_q.astype(jnp.uint8).astype(jnp.int32)
    tbl = err_flat if isinstance(err_flat, jax.Array) else jnp.asarray(err_flat)

    def body(xc):
        idx = xc[:, :, None] * 256 + wi[None, :, :]
        return jnp.take(tbl, idx, axis=0).astype(jnp.int32).sum(axis=1)

    if m * k * n <= chunk_elems:
        return body(xi)
    chunk_m = max(1, min(m, chunk_elems // max(1, k * n)))
    pad = (-m) % chunk_m
    xi = jnp.pad(xi, ((0, pad), (0, 0)))
    out = jax.lax.map(body, xi.reshape(-1, chunk_m, k))
    return out.reshape(-1, n)[:m]


def approx_matmul_lut(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """Bit-exact approximate matmul via the signed error LUT."""
    mult_cfg = _mult_cfg(cfg)
    err = _err_lut_device(mult_cfg.key, mult_cfg)
    return int8_matmul(x_q, w_q) + _approx_error_lut(x_q, w_q, err)


def approx_matmul_deficit(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """Bit-exact approximate matmul via deficit planes (gather-free).

    Reference jnp implementation of the Pallas kernel's math; chunked over
    rows to bound the (m, k, n) intermediate.
    """
    from repro.core import deficit as D
    mult_cfg = _mult_cfg(cfg)
    m, k = x_q.shape
    n = w_q.shape[1]
    xs = x_q.astype(jnp.int32)
    ws = w_q.astype(jnp.int32)
    xmag = jnp.abs(xs)
    wmag = jnp.abs(ws)

    chunk_m = max(1, min(m, (1 << 20) // max(1, k * n)))
    pad = (-m) % chunk_m
    xmag_p = jnp.pad(xmag, ((0, pad), (0, 0)))
    xsgn_p = jnp.pad(jnp.sign(xs), ((0, pad), (0, 0)))

    wsgn = jnp.sign(ws)

    def body(args):
        xc, sc = args
        a = xc[:, :, None]           # (cm, k, 1)
        b = wmag.T[None, :, :].transpose(0, 2, 1)  # (1, k, n)
        prod = D.approx_product(a, jnp.broadcast_to(b, (xc.shape[0], k, n)),
                                mult_cfg)
        signed = prod * (sc[:, :, None] * wsgn[None, :, :])
        return signed.sum(axis=1).astype(jnp.int32)

    out = jax.lax.map(body, (xmag_p.reshape(-1, chunk_m, k),
                             xsgn_p.reshape(-1, chunk_m, k)))
    return out.reshape(-1, n)[:m]


def _window_and(mag: jax.Array, start: int) -> jax.Array:
    """AND of bits [start, start+4) of |v| as 0/1 int8."""
    m = mag.astype(jnp.int32)
    out = jnp.ones_like(m)
    for i in range(start, start + 4):
        out = out * ((m >> i) & 1)
    return out.astype(jnp.int8)


def approx_matmul_stage1(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """Beyond-paper re-approximation: exact matmul minus the rank-1
    stage-1 site corrections (each an extra int8 matmul on the MXU)."""
    out = int8_matmul(x_q, w_q)
    xs = x_q.astype(jnp.int32)
    ws = w_q.astype(jnp.int32)
    xsgn = jnp.sign(xs).astype(jnp.int8)
    wsgn = jnp.sign(ws).astype(jnp.int8)
    xmag = jnp.abs(xs)
    wmag = jnp.abs(ws)
    for col, ra, rb in STAGE1_SITES:
        u = _window_and(xmag, ra) * xsgn          # (m, k) in {-1,0,1}
        v = _window_and(wmag, rb) * wsgn          # (k, n)
        corr = int8_matmul(u, v)
        out = out - (corr << col)
    return out


def approx_matmul_stage1_fused(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """§Perf-fused stage-1 correction: sites sharing an operand window are
    merged by weighting the other side, collapsing 7 correction matmuls to
    3 (1 + 3 = 4 total vs 1 + 7 = 8). Bit-identical to approx_matmul_stage1:
      sites (5,0,2),(6,0,3),(7,0,4)  share the a-window rows 0-3
      sites (8,1,4),(9,2,4),(10,3,4) share the b-window rows 4-7
    Weighted features fit bf16 exactly (|value| <= 1792 < 2^11; fp32 accum).
    """
    out = int8_matmul(x_q, w_q)
    xs = x_q.astype(jnp.int32)
    ws = w_q.astype(jnp.int32)
    xsgn = jnp.sign(xs)
    wsgn = jnp.sign(ws)
    xmag = jnp.abs(xs)
    wmag = jnp.abs(ws)

    def f32mm(u, v):
        return jax.lax.dot_general(
            u.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)

    # group A: shared u = AND(a bits 0..3); v = sum_c 2^c * v_c
    uA = _window_and(xmag, 0).astype(jnp.int32) * xsgn
    vA = sum((_window_and(wmag, rb).astype(jnp.int32) << col)
             for col, ra, rb in STAGE1_SITES[:3]) * wsgn
    out = out - f32mm(uA, vA)
    # singleton site (7, 4, 0)
    col, ra, rb = STAGE1_SITES[3]
    out = out - (int8_matmul(_window_and(xmag, ra) * xsgn.astype(jnp.int8),
                             _window_and(wmag, rb) * wsgn.astype(jnp.int8))
                 << col)
    # group B: shared v = AND(b bits 4..7); u = sum_c 2^c * u_c
    uB = sum((_window_and(xmag, ra).astype(jnp.int32) << col)
             for col, ra, rb in STAGE1_SITES[4:]) * xsgn
    vB = _window_and(wmag, 4).astype(jnp.int32) * wsgn
    out = out - f32mm(uB, vB)
    return out


# ---------------------------------------------------------------------------
# Rank-factored correction backend (core/factor.py)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _rank1_tables_f32(design: str):
    """Sign-folded gather tables of the int8-domain factorization, staged
    on device once per design as float32 (u in {-1,0,1}, |v| small ints).
    Staged eagerly even under a jit trace (no tracers in the cache)."""
    fac = factorlib.factorize(design)
    with jax.ensure_compile_time_eval():
        return (jnp.asarray(fac.u_signed.astype(np.float32)),
                jnp.asarray(fac.v_signed.astype(np.float32)))


def k_chunk_plan(k: int, kc: int) -> Tuple[int, int]:
    """(n_chunks, pad) splitting a K-long contraction into chunks of at
    most ``kc`` terms: ``n_chunks * kc == k + pad``.

    This is the accumulation-order contract of the rank-factored
    correction: any f32 partial sum over <= kc terms is an exact integer
    below 2^24 (core/factor.py derives kc per design from the maximum
    column sum of |V|), so chunk results cast to int32 losslessly and the
    int32 chunk accumulation is exact in ANY order. A K-shard of the
    contraction is a prefix/suffix subset of the terms, so each shard's
    local chunks obey the same bound and the cross-shard int32 psum is
    bit-exact by construction (quant/sharded.py; docs/sharding.md).
    Padding appends zero terms, which contribute exactly 0.
    """
    if kc <= 0:
        raise ValueError(f"chunk size must be positive, got {kc}")
    chunks = max(1, -(-k // kc))
    return chunks, chunks * kc - k


def rank1_info(design: str) -> Dict:
    """Correction-complexity summary for one design (profiles/bench):
    R (factor count), exact rank, digit planes, f32-exact K bound."""
    fac = factorlib.factorize(design)
    return {"R": fac.R, "rank": fac.rank, "digits": fac.n_digits,
            "k_exact_f32": fac.k_exact_f32,
            "stage1_terms": len(fac.stage1)}


def approx_matmul_rank1(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """Bit-exact approximate matmul as exact int8 dot + rank-factored
    correction GEMMs — no O(M*K*N) element-wise deficit work.

    The error table factors exactly as E = U @ V (core/factor.py), so the
    correction is one dense contraction over (K, R):

        corr[m, n] = sum_{k, s} u[x[m,k], s] * v[s, w[k,n]]

    with operand signs folded into the uint8-indexed gather tables. The
    GEMM runs in float32 (the fast dense path) and is provably bit-exact:
    every partial sum is an integer below 2^24 as long as K <= k_exact_f32;
    longer contractions are split into K-chunks whose float32 results are
    exact integers, then accumulated in int32.
    """
    fac = factorlib.factorize(cfg.multiplier)
    u_tbl, v_tbl = _rank1_tables_f32(cfg.multiplier)
    m, k = x_q.shape
    n = w_q.shape[1]
    r = fac.R
    out = int8_matmul(x_q, w_q)
    ix = x_q.astype(jnp.uint8).astype(jnp.int32)
    iw = w_q.astype(jnp.uint8).astype(jnp.int32)
    xf = jnp.take(u_tbl, ix, axis=0)            # (m, k, R) f32
    wf = jnp.take(v_tbl, iw, axis=1)            # (R, k, n) f32
    kc = fac.k_exact_f32
    if k <= kc:
        corr = jax.lax.dot_general(
            xf, wf, (((1, 2), (1, 0)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
    else:
        chunks, pad = k_chunk_plan(k, kc)
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
        wf = jnp.pad(wf, ((0, 0), (0, pad), (0, 0)))
        xf = xf.reshape(m, chunks, kc, r)
        wf = wf.reshape(r, chunks, kc, n)
        per_chunk = jax.lax.dot_general(
            xf, wf, (((2, 3), (2, 0)), ((1,), (1,))),
            preferred_element_type=jnp.float32)      # (chunks, m, n)
        corr = per_chunk.astype(jnp.int32).sum(axis=0)
    return out - corr


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    """One integer-matmul execution path.

    fn:     (x_q (M,K) int8, w_q (K,N) int8, cfg) -> (M,N) int32 — the
            pre-dequant contract shared by every backend.
    grad:   backward rule; only 'ste' (straight-through, exact float grads)
            is defined today.
    fused:  optional (x_q (B,M,K)|(M,K), w_q, cfg, scale (1,N) f32,
            bias (1,N) f32, relu: bool) -> f32 — integer matmul with the
            dequant/bias/ReLU epilogue fused (Pallas entries). When set,
            `quantized_matmul` routes through it and batched leading dims
            hit the kernel directly.
    oracle: name of the registered backend this entry must bit-match
            pre-dequant (drives the parity suite in tests/test_backends.py).
    note:   one-line description for benchmarks/docs.
    """
    name: str
    fn: Callable[[jax.Array, jax.Array, QuantConfig], jax.Array]
    grad: str = "ste"
    fused: Optional[Callable] = None
    oracle: Optional[str] = None
    note: str = ""


_REGISTRY: Dict[str, Backend] = {}


def register_backend(name: str, fn: Callable, *, grad: str = "ste",
                     fused: Optional[Callable] = None,
                     oracle: Optional[str] = None, note: str = "",
                     overwrite: bool = False) -> Backend:
    """Register an integer-matmul backend under `name`.

    The entry becomes selectable per layer via `QuantConfig(backend=name)`
    and is enumerated by `list_backends()` (parity tests, benchmarks).

    `oracle` must name an already-registered backend: a dangling oracle
    reference would otherwise only surface deep inside a parity sweep or
    a profile-family walk, far from the registration that caused it."""
    if grad != "ste":
        raise ValueError(f"unknown grad rule {grad!r}; only 'ste' is defined")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         "(pass overwrite=True to replace)")
    if oracle is not None and oracle not in _REGISTRY:
        raise ValueError(f"backend {name!r} declares unknown oracle "
                         f"{oracle!r}; register the oracle first "
                         f"(registered: {list_backends()})")
    be = Backend(name=name, fn=fn, grad=grad, fused=fused, oracle=oracle,
                 note=note)
    _REGISTRY[name] = be
    return be


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown quant backend {name!r}; registered: "
                       f"{list_backends()}") from None


def list_backends() -> Tuple[str, ...]:
    """Registered backend names, in registration order."""
    return tuple(_REGISTRY)


def backend_notes() -> Dict[str, str]:
    """name -> one-line description, for reports and docs tables."""
    return {name: be.note for name, be in _REGISTRY.items()}


def stage1_exhaustive_products() -> np.ndarray:
    """(256, 256) int64 product table of the stage-1 re-approximation over
    the unsigned 8x8 domain: a*b minus every STAGE1_SITES correction whose
    4-bit operand windows are all ones. This is the multiplier the
    approx_stage1* backends emulate, in the same exhaustive-table form
    `core.multiplier.exhaustive_products` uses, so `core.metrics.evaluate`
    can score it against the paper designs."""
    a = np.arange(256, dtype=np.int64)
    out = a[:, None] * a[None, :]
    for col, ra, rb in STAGE1_SITES:
        ua = np.ones(256, np.int64)
        for i in range(ra, ra + 4):
            ua &= (a >> i) & 1
        ub = np.ones(256, np.int64)
        for i in range(rb, rb + 4):
            ub &= (a >> i) & 1
        out = out - ((ua[:, None] * ub[None, :]) << col)
    return out


def _deficit_pallas(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    from repro.kernels import ops as kops
    return kops.approx_matmul(x_q, w_q, cfg)


def _deficit_pallas_fused(x_q, w_q, cfg, scale, bias, relu):
    from repro.kernels import ops as kops
    return kops.approx_matmul_fused(x_q, w_q, cfg, scale, bias, relu)


def _stage1_pallas(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    from repro.kernels import ops as kops
    return kops.stage1_matmul(x_q, w_q)


def _stage1_pallas_fused(x_q, w_q, cfg, scale, bias, relu):
    from repro.kernels import ops as kops
    return kops.stage1_matmul_fused(x_q, w_q, cfg, scale, bias, relu)


def _rank1_pallas(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    from repro.kernels import ops as kops
    return kops.rank1_matmul(x_q, w_q, cfg)


def _rank1_pallas_fused(x_q, w_q, cfg, scale, bias, relu):
    from repro.kernels import ops as kops
    return kops.rank1_matmul_fused(x_q, w_q, cfg, scale, bias, relu)


register_backend("int8_exact", lambda x, w, cfg: int8_matmul(x, w),
                 note="W8A8 exact integer products (MXU-native)")
register_backend("approx_lut", approx_matmul_lut,
                 note="paper-faithful signed-LUT emulation (gather-bound)")
register_backend("approx_deficit", approx_matmul_deficit,
                 oracle="approx_lut",
                 note="deficit-plane emulation, gather-free jnp reference")
register_backend("approx_stage1", approx_matmul_stage1,
                 note="stage-1 rank-1 re-approximation (8 MXU matmuls)")
register_backend("approx_stage1_fused", approx_matmul_stage1_fused,
                 oracle="approx_stage1",
                 note="stage-1 re-approximation in 4 matmuls")
register_backend("approx_rank1", approx_matmul_rank1,
                 oracle="approx_lut",
                 note="exact int8 dot + rank-factored correction GEMM "
                      "(MXU-shaped, f32-exact, no deficit planes)")
register_backend("approx_deficit_pallas", _deficit_pallas,
                 fused=_deficit_pallas_fused, oracle="approx_lut",
                 note="Pallas deficit kernel + fused dequant/bias/ReLU "
                      "epilogue")
register_backend("approx_stage1_pallas", _stage1_pallas,
                 fused=_stage1_pallas_fused, oracle="approx_stage1",
                 note="Pallas stage-1 kernel + fused epilogue")
register_backend("approx_rank1_pallas", _rank1_pallas,
                 fused=_rank1_pallas_fused, oracle="approx_lut",
                 note="Pallas rank-factored kernel (int8 digit-plane "
                      "correction dots) + fused epilogue")


# ---------------------------------------------------------------------------
# MSR/truncation family (core/truncation.py gate references +
# quant/truncated.py vectorized cores)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=8)
def _trunc_err_device(kind: str) -> jax.Array:
    """Device-staged flattened signed error table for one truncation-family
    member (same gather layout as `_err_lut_device`)."""
    from repro.core import truncation
    with jax.ensure_compile_time_eval():
        return jnp.asarray(truncation.error_table(kind))


def _trunc_lut_matmul(kind: str):
    """Gate-level gather reference for a truncation-family member: exact
    int8 dot plus the exhaustive signed error table — the family's oracle,
    bit-identical to `core.truncation.product_table(kind)` by
    construction."""
    def fn(x_q, w_q, cfg: QuantConfig) -> jax.Array:
        return (int8_matmul(x_q, w_q)
                + _approx_error_lut(x_q, w_q, _trunc_err_device(kind)))
    fn.__name__ = f"{kind}_lut_matmul"
    return fn


from repro.quant import truncated as _truncated  # noqa: E402  (cores only;
# truncated.py does not import this module, so the import is acyclic)

register_backend("msr4_lut", _trunc_lut_matmul("msr4"),
                 note="MSR-4 weight-compression gate reference "
                      "(signed-LUT gather)")
register_backend("msr4", _truncated.msr4_matmul, oracle="msr4_lut",
                 note="MSR-4 5-bit mantissa+shift weight decode + one "
                      "exact int8 dot (weight-only approximation)")
register_backend("drum6_lut", _trunc_lut_matmul("drum6"),
                 note="DRUM-6 dynamic-truncation gate reference "
                      "(signed-LUT gather)")
register_backend("drum6", _truncated.drum6_matmul, oracle="drum6_lut",
                 note="DRUM-6: one dot over operands truncated to 6 "
                      "significant bits with forced-one debias")
register_backend("posneg_lut", _trunc_lut_matmul("posneg"),
                 note="Positive/Negative asymmetric-truncation gate "
                      "reference (signed-LUT gather)")
register_backend("posneg", _truncated.posneg_matmul, oracle="posneg_lut",
                 note="sign-classed floor truncation (k=4 positive / "
                      "k=6 negative product classes) as 4 masked dots")


def _resolve_backend(cfg: QuantConfig) -> Backend:
    """Registry lookup honoring the legacy enable_pallas() global remap."""
    name = cfg.backend
    if _use_pallas() and name in ("approx_lut", "approx_deficit"):
        name = "approx_deficit_pallas"
    return get_backend(name)


def integer_matmul(x_q, w_q, cfg: QuantConfig) -> jax.Array:
    """Pre-dequant int32 matmul via the backend selected by cfg.backend."""
    return _resolve_backend(cfg).fn(x_q, w_q, cfg)


_PALLAS = {"enabled": False}


def _use_pallas() -> bool:
    return _PALLAS["enabled"]


def enable_pallas(flag: bool = True):
    """Legacy switch: route approx_lut/approx_deficit through the Pallas
    kernel. Prefer selecting backend='approx_deficit_pallas' per layer; this
    global remains for benchmarks/scripts that toggle the whole model."""
    _PALLAS["enabled"] = flag


# ---------------------------------------------------------------------------
# Float-in/float-out quantized matmul with STE backward
# ---------------------------------------------------------------------------

def quantized_matmul(x: jax.Array, w: jax.Array, cfg: QuantConfig,
                     bias: Optional[jax.Array] = None,
                     activation: Optional[str] = None) -> jax.Array:
    """y = act(dequant(integer_matmul(q(x), q(w))) + bias).

    x: (..., k), w: (k, n), bias: (n,) or None, activation: None | 'relu'.
    Backends whose registry entry defines a fused epilogue run dequant,
    bias and activation in-kernel (batched over the leading dims); all
    others use the unfused composition. Backward is the straight-through
    estimator either way.
    """
    if activation not in (None, "relu"):
        raise ValueError(f"unsupported activation {activation!r}")
    if bias is None:
        return _qmm(x, w, cfg, activation)
    return _qmm_bias(x, w, bias, cfg, activation)


def _float_epilogue(y, bias, activation):
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    return y


def _pin(y):
    """Pin a float intermediate against XLA's algebraic simplifier.

    The per-token dequant is a broadcast multiply chain
    ``acc * sw * sx`` whose rounding depends on association order, and
    under jit XLA picks that order per *shape* — the same activation row
    can dequantize to different last-ulp floats in a (slots, 1) decode
    step vs a (slots, K) verify window. Integer accumulators, int8
    codes, and scales are bitwise shape-stable; only this epilogue was
    not. Barriers fix the order (weight scale, then row scale, then
    bias/activation) at every shape, which is what lets speculative
    verify windows be bitwise identical to sequential decode
    (serve/speculative.py, tests/test_speculative.py)."""
    return jax.lax.optimization_barrier(y)


def _qmm_forward(x, w, bias, cfg: QuantConfig, activation):
    """Shared quantize -> backend -> dequant/epilogue composition.

    act_scale='per_tensor': one dynamic scale for the whole activation;
    fused backends run dequant + bias + activation in-kernel.

    act_scale='per_token': each activation row m carries its own dynamic
    scale sx[m], so a token's int8 codes — and hence the backend's int32
    accumulators — are independent of which other tokens share the batch.
    This is what makes prefill and decode bit-identical pre-dequant (the
    LM parity contract, tests/test_lm_backends.py). Fused backends still
    run their kernel: it applies the per-channel weight dequant in its
    epilogue (scale = sw, zero bias) and the row scale / bias / activation
    are applied outside — the integer accumulators are identical to the
    unfused composition either way.
    """
    backend = _resolve_backend(cfg)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[1]
    per_token = cfg.act_scale == "per_token"
    if not per_token and cfg.act_scale != "per_tensor":
        raise ValueError(f"unknown act_scale {cfg.act_scale!r}; "
                         "choose 'per_tensor' or 'per_token'")
    # named scopes tag each phase in the compiled program's op metadata
    # (metadata only: the computation is unchanged), so a profile can
    # split a projection's device time into weight quantization,
    # activation quantization, the integer core and the dequant epilogue
    with jax.named_scope("qmm.wquant"):
        if cfg.per_channel:
            sw = abs_max_scale(w, axis=0, keepdims=True)   # (1, n)
        else:
            sw = abs_max_scale(w)
        w_q = quantize(w, sw)

    if backend.fused is not None and cfg.fuse_epilogue:
        # (B, T, K): leading dims become the kernel's batch grid axis
        if x.ndim <= 2:
            x3 = x.reshape(-1, k)
        else:
            x3 = x.reshape(-1, x.shape[-2], k)
        if per_token:
            with jax.named_scope("qmm.xquant"):
                sx = abs_max_scale(x3, axis=-1, keepdims=True)  # (..., M, 1)
                x_q = quantize(x3, sx)
            with jax.named_scope("qmm.dequant"):
                scale = jnp.broadcast_to(
                    jnp.asarray(sw, jnp.float32).reshape(1, -1), (1, n))
            with jax.named_scope("qmm.core"):
                y = backend.fused(x_q, w_q, cfg, scale,
                                  jnp.zeros((1, n), jnp.float32), False)
            with jax.named_scope("qmm.dequant"):
                y = _float_epilogue(_pin(_pin(y) * sx), bias, activation)
        else:
            with jax.named_scope("qmm.xquant"):
                sx = abs_max_scale(x3, axis=None, keepdims=False)
                x_q = quantize(x3, sx)
            with jax.named_scope("qmm.dequant"):
                scale = jnp.broadcast_to((sx * sw).reshape(1, -1), (1, n))
                b_arr = (jnp.zeros((1, n), jnp.float32) if bias is None
                         else bias.astype(jnp.float32).reshape(1, n))
            with jax.named_scope("qmm.core"):
                y = backend.fused(x_q, w_q, cfg, scale, b_arr,
                                  activation == "relu")
    else:
        with jax.named_scope("qmm.xquant"):
            x2 = x.reshape(-1, k)
            sx = abs_max_scale(x2, axis=-1 if per_token else None,
                               keepdims=per_token)   # (M, 1) | scalar
            x_q = quantize(x2, sx)
        with jax.named_scope("qmm.core"):
            acc = backend.fn(x_q, w_q, cfg)
        with jax.named_scope("qmm.dequant"):
            acc = acc.astype(jnp.float32)
            if per_token:
                # pinned order: weight scale, then row scale (see _pin)
                y = _pin(_pin(acc * sw) * sx)
            else:
                y = acc * (sx * sw)
            y = _float_epilogue(y, bias, activation)
    with jax.named_scope("qmm.dequant"):
        return y.reshape(*lead, n).astype(x.dtype)


def _qmm_grads(x, w, y, g, activation):
    # y is saved in the residuals only when the STE mask needs it
    if activation == "relu":
        g = g * (y > 0).astype(g.dtype)
    g2 = g.reshape(-1, w.shape[1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    dx = (g2 @ w.astype(jnp.float32).T).reshape(x.shape).astype(x.dtype)
    dw = (x2.T @ g2).astype(w.dtype)
    return dx, dw, g2.sum(axis=0)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qmm(x, w, cfg, activation):
    return _qmm_forward(x, w, None, cfg, activation)


def _qmm_fwd(x, w, cfg, activation):
    y = _qmm_forward(x, w, None, cfg, activation)
    return y, (x, w, y if activation == "relu" else None)


def _qmm_bwd(cfg, activation, res, g):
    x, w, y = res
    dx, dw, _ = _qmm_grads(x, w, y, g, activation)
    return dx, dw


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _qmm_bias(x, w, b, cfg, activation):
    return _qmm_forward(x, w, b, cfg, activation)


def _qmm_bias_fwd(x, w, b, cfg, activation):
    y = _qmm_forward(x, w, b, cfg, activation)
    return y, (x, w, b, y if activation == "relu" else None)


def _qmm_bias_bwd(cfg, activation, res, g):
    x, w, b, y = res
    dx, dw, db = _qmm_grads(x, w, y, g, activation)
    return dx, dw, db.reshape(b.shape).astype(b.dtype)


_qmm_bias.defvjp(_qmm_bias_fwd, _qmm_bias_bwd)

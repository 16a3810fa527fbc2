"""Attention variants: GQA (+RoPE, sliding window), cross-attention, MLA.

Cache conventions (per layer; stacked over layers by the model's scan):
  global attention : k/v (B, S_max, Hkv, Dh), written at absolute position.
  windowed         : ring buffer of W slots, slot = pos % W; absolute
                     positions are reconstructed for masking/RoPE.
  MLA              : compressed c_kv (B, S_max, kv_lora) + k_pe (B, S_max,
                     rope_dim) — the memory win of deepseek-v2.
Decode uses the absorbed MLA formulation (scores in the compressed space) so
no (B, S, H, Dh) expansion is ever materialized.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.module import ParamDesc
from repro.nn import layers as L
from repro.parallel.sharding import (ShardingRules, constrain,
                                     mesh_axis_size)
from repro.quant.quantize import QuantConfig

NEG = -2.0 ** 30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    window: int = 0                  # 0 = global causal
    cross: bool = False              # kv from encoder states
    p_bf16: bool = False             # bf16 softmax weights for the PV dot
    # MLA (all zero -> standard GQA)
    kv_lora: int = 0
    qk_nope: int = 0
    qk_rope: int = 0
    v_head_dim: int = 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora > 0


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def q_positions(pos: Optional[jax.Array], b: int, s: int) -> jax.Array:
    """Absolute positions of the current queries, one row per batch slot.

    pos None   -> prefill from 0 (every row 0..s-1)
    pos scalar -> uniform decode offset (the batch-synchronous case)
    pos (B,)   -> per-slot offsets (continuous batching: each slot of the
                  serving pool decodes at its own depth)
    Returns (B, s) int32.
    """
    base = jnp.arange(s, dtype=jnp.int32)[None, :]
    if pos is None:
        return jnp.broadcast_to(base, (b, s))
    pos = jnp.asarray(pos, jnp.int32)
    off = pos[None] if pos.ndim == 0 else pos
    return jnp.broadcast_to(off[:, None] + base, (b, s))


# ---------------------------------------------------------------------------
# Descriptors
# ---------------------------------------------------------------------------

def attn_desc(cfg: AttnConfig, dtype=jnp.float32):
    D = cfg.d_model
    if cfg.is_mla:
        qd = cfg.n_heads * (cfg.qk_nope + cfg.qk_rope)
        return {
            "wq": ParamDesc((D, qd), ("embed", "heads"), dtype=dtype),
            "wdkv": ParamDesc((D, cfg.kv_lora + cfg.qk_rope),
                              ("embed", "kv_lora"), dtype=dtype),
            "wuk": ParamDesc((cfg.kv_lora, cfg.n_heads, cfg.qk_nope),
                             ("kv_lora", "heads", None), dtype=dtype),
            "wuv": ParamDesc((cfg.kv_lora, cfg.n_heads, cfg.v_head_dim),
                             ("kv_lora", "heads", None), dtype=dtype),
            "wo": ParamDesc((cfg.n_heads * cfg.v_head_dim, D),
                            ("heads", "embed"), dtype=dtype),
        }
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    d = {
        "wq": ParamDesc((D, qd), ("embed", "heads"), dtype=dtype),
        "wk": ParamDesc((D, kvd), ("embed", "kv_heads"), dtype=dtype),
        "wv": ParamDesc((D, kvd), ("embed", "kv_heads"), dtype=dtype),
        "wo": ParamDesc((qd, D), ("heads", "embed"), dtype=dtype),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamDesc((qd,), ("heads",), "zeros", dtype=dtype)
        d["bk"] = ParamDesc((kvd,), ("kv_heads",), "zeros", dtype=dtype)
        d["bv"] = ParamDesc((kvd,), ("kv_heads",), "zeros", dtype=dtype)
    return d


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    if cfg.is_mla:
        return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
                "kpe": jnp.zeros((batch, max_len, cfg.qk_rope), dtype)}
    slots = min(cfg.window, max_len) if cfg.window else max_len
    return {"k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim),
                           dtype),
            "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim),
                           dtype)}


def cache_logical(cfg: AttnConfig):
    """Logical axis names per `init_cache` leaf (same tree structure,
    tuple-of-names leaves): batch rows over 'data', KV heads over 'model',
    positions replicated. `parallel.sharding.ShardingRules` maps these to
    mesh axes; docs/sharding.md has the full table."""
    if cfg.is_mla:
        return {"ckv": ("batch", None, "kv_lora"),
                "kpe": ("batch", None, None)}
    return {"k": ("batch", None, "kv_heads", None),
            "v": ("batch", None, "kv_heads", None)}


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

KV_CHUNK = 1024


def _sdpa(q, k, v, q_pos, k_pos, window, rules: ShardingRules,
          causal: bool = True, kv_chunk: int = KV_CHUNK,
          p_bf16: bool = False):
    """Blockwise (flash-style) attention: online softmax over KV chunks so
    neither an (Sq, Sk) score tensor nor an (Sq, Sk) mask is materialized —
    chunk masks are rebuilt from absolute positions inside the scan body.

    q: (B,Sq,H,D) k/v: (B,Sk,Hkv,D[v]); q_pos (Sq,)/(B?,Sq) and k_pos
    (Sk,)/(B?,Sk) with -1 marking invalid slots — a full (B, S) position
    matrix means every batch row masks against its own absolute positions
    (per-slot continuous batching). Exact up to fp associativity; fp32
    accum.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    dv = v.shape[-1]
    sk = k.shape[1]
    c = min(kv_chunk, sk)
    pad = (-sk) % c
    k_pos = jnp.broadcast_to(jnp.atleast_2d(k_pos), (b, sk))
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (sk + pad) // c

    qh = q.reshape(b, sq, hkv, g, d).astype(jnp.float32) * (d ** -0.5)
    kc = k.reshape(b, n_chunks, c, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, c, hkv, dv).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(b, n_chunks, c).transpose(1, 0, 2)   # (n, B, c)
    qp = jnp.broadcast_to(jnp.atleast_2d(q_pos), (b, sq))    # (B, Sq)

    msz = mesh_axis_size("model")

    def _c3(t):   # (B, Hkv, G, Sq[, D]) carries
        # constrain over MERGED heads (hkv*g) when that divides the model
        # axis — covers kimi (8 kv x 8 groups on 16) without padding; fall
        # back to kv_heads sharding otherwise (smollm: 3 kv heads)
        if (hkv * g) % msz == 0:
            shp = t.shape
            t = t.reshape(shp[0], hkv * g, *shp[3:])
            t = constrain(t, rules, "batch", "heads",
                          *([None] * (t.ndim - 2)))
            return t.reshape(shp)
        return constrain(t, rules, "batch", "kv_heads",
                         *([None] * (t.ndim - 2)))

    m0 = _c3(jnp.full((b, hkv, g, sq), NEG, jnp.float32))
    l0 = _c3(jnp.zeros((b, hkv, g, sq), jnp.float32))
    a0 = _c3(jnp.zeros((b, hkv, g, sq, dv), jnp.float32))

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, kpj = xs                                # (B,c,Hkv,D), (B,c)
        dist = qp[:, :, None] - kpj[:, None, :]         # (B, Sq, c)
        mj = kpj[:, None, :] >= 0
        if causal:
            mj = mj & (dist >= 0)
            if window:
                mj = mj & (dist < window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, kj.astype(jnp.float32))
        s = jnp.where(mj[:, None, None], s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = _c3(l * corr + p.sum(axis=-1))
        pv = p.astype(jnp.bfloat16) if p_bf16 else p
        acc = _c3(acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhv->bhgqv", pv, vj,
            preferred_element_type=jnp.float32))
        return (_c3(m_new), l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kpc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h * dv).astype(v.dtype)
    return constrain(out, rules, "batch", "seq", "heads")


def apply(params, x, cfg: AttnConfig, rules: ShardingRules,
          quant: QuantConfig, *, cache=None, pos=None, enc=None,
          qat: bool = False):
    """Returns (out, new_cache).

    Modes:
      train/prefill : x (B,S,D), pos None -> positions 0..S-1; cache written
                      if provided.
      decode        : x (B,1,D) with integer `pos` — a scalar for uniform
                      batch-synchronous decode, or a (B,) vector for
                      per-slot positions (continuous batching: each row of
                      the cache pool is at its own depth; writes and masks
                      are computed per row).
      cross         : enc (B,Se,De) provides K/V; no cache, no causal mask.
    """
    if cfg.is_mla:
        return _apply_mla(params, x, cfg, rules, quant, cache=cache, pos=pos,
                          qat=qat)
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = L.dense({"w": params["wq"], **_b(params, "bq")}, x, quant, qat)
    q = q.reshape(b, s, cfg.n_heads, dh)
    kv_src = enc if cfg.cross else x
    k = L.dense({"w": params["wk"], **_b(params, "bk")}, kv_src, quant, qat)
    v = L.dense({"w": params["wv"], **_b(params, "bv")}, kv_src, quant, qat)
    k = k.reshape(b, kv_src.shape[1], cfg.n_kv_heads, dh)
    v = v.reshape(b, kv_src.shape[1], cfg.n_kv_heads, dh)

    # "attn" scopes rotary, the cache write, scores, softmax and the value
    # product in the compiled program's op metadata; the projections
    # around it carry their own "qmm.*" scopes (quant/matmul.py)
    with jax.named_scope("attn"):
        out, new_cache = _attend(q, k, v, cfg, rules, cache, pos)
    return L.dense({"w": params["wo"]}, out, quant, qat), new_cache


def _attend(q, k, v, cfg: AttnConfig, rules, cache, pos):
    """Attention proper, between the q/k/v and output projections:
    (context (B, s, H*Dv), new cache or the cache passed in)."""
    b, s = q.shape[:2]
    if cfg.cross:
        enc_pos = jnp.arange(k.shape[1])[None, :]
        out = _sdpa(q, k, v, jnp.zeros((b, s), jnp.int32), enc_pos, 0, rules,
                    causal=False, p_bf16=cfg.p_bf16)
        return out, cache

    q_pos = q_positions(pos, b, s)                   # (B, s) absolute
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)

    if cache is None:
        out = _sdpa(q, k, v, q_pos, q_pos, cfg.window, rules,
                    p_bf16=cfg.p_bf16)
        return out, None

    slots = cache["k"].shape[1]
    bidx = jnp.arange(b)[:, None]
    slot_ids = jnp.arange(slots)[None, :]
    if cfg.window and slots == cfg.window:
        # ring buffer: slot = absolute position mod W, per batch row
        write_idx = q_pos % slots                    # (B, s)
        ck = cache["k"].at[bidx, write_idx].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, write_idx].set(v.astype(cache["v"].dtype))
        last = q_pos[:, -1:]                         # (B, 1)
        k_abs = last - ((last - slot_ids) % slots)   # abs pos held per slot
        k_pos = jnp.where(k_abs >= 0, k_abs, -1)     # (B, slots)
    else:
        ck = cache["k"].at[bidx, q_pos].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, q_pos].set(v.astype(cache["v"].dtype))
        written = q_pos[:, -1:] + 1                  # (B, 1)
        k_pos = jnp.where(slot_ids < written, slot_ids, -1)
    out = _sdpa(q, ck, cv, q_pos, k_pos, cfg.window, rules,
                p_bf16=cfg.p_bf16)
    return out, {"k": ck, "v": cv}


def _b(params, name):
    return {"b": params[name]} if name in params else {}


# ---------------------------------------------------------------------------
# MLA (deepseek-v2) — absorbed formulation
# ---------------------------------------------------------------------------

def _apply_mla(params, x, cfg: AttnConfig, rules, quant, *, cache, pos, qat):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope, cfg.qk_rope
    q = L.dense({"w": params["wq"]}, x, quant, qat).reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    dkv = L.dense({"w": params["wdkv"]}, x, quant, qat)
    ckv_new, kpe_new = dkv[..., :cfg.kv_lora], dkv[..., cfg.kv_lora:]

    with jax.named_scope("attn"):     # as in apply()
        q_pos = q_positions(pos, b, s)                   # (B, s) absolute
        q_pe = rope(q_pe, q_pos, cfg.rope_theta)
        kpe_new = rope(kpe_new[:, :, None, :], q_pos,
                       cfg.rope_theta)[:, :, 0, :]

        if cache is not None:
            bidx = jnp.arange(b)[:, None]
            ckv = cache["ckv"].at[bidx, q_pos].set(
                ckv_new.astype(cache["ckv"].dtype))
            kpe = cache["kpe"].at[bidx, q_pos].set(
                kpe_new.astype(cache["kpe"].dtype))
            written = q_pos[:, -1:] + 1                  # (B, 1)
            slots = ckv.shape[1]
            slot_ids = jnp.arange(slots)[None, :]
            k_pos = jnp.where(slot_ids < written, slot_ids, -1)  # (B, slots)
            new_cache = {"ckv": ckv, "kpe": kpe}
        else:
            ckv, kpe = ckv_new, kpe_new
            k_pos = q_pos
            new_cache = None

        # absorbed scores: q_nope^T (Wuk^T ckv)  ->  (q_nope Wuk) . ckv
        # evaluated blockwise over KV chunks (online softmax; no (Sq,Sk)
        # tensor)
        q_abs = jnp.einsum("bshn,lhn->bshl", q_nope, params["wuk"],
                           preferred_element_type=jnp.float32)
        q_abs = q_abs * ((dn + dr) ** -0.5)
        q_pe32 = q_pe.astype(jnp.float32) * ((dn + dr) ** -0.5)
        sk = ckv.shape[1]
        c = min(KV_CHUNK, sk)
        pad = (-sk) % c
        ckv_p = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))) if pad else ckv
        kpe_p = jnp.pad(kpe, ((0, 0), (0, pad), (0, 0))) if pad else kpe
        kpos1 = jnp.broadcast_to(jnp.atleast_2d(k_pos), (b, sk))
        kpos1 = (jnp.pad(kpos1, ((0, 0), (0, pad)), constant_values=-1)
                 if pad else kpos1)
        n_chunks = (sk + pad) // c
        lora = ckv.shape[-1]
        ckv_c = ckv_p.reshape(b, n_chunks, c, lora).transpose(1, 0, 2, 3)
        kpe_c = kpe_p.reshape(b, n_chunks, c, dr).transpose(1, 0, 2, 3)
        kpos_c = kpos1.reshape(b, n_chunks, c).transpose(1, 0, 2)   # (n, B, c)
        qp1 = jnp.broadcast_to(jnp.atleast_2d(q_pos), (b, s))       # (B, s)

        def _c3(t):   # (B, H, Sq[, lora]) carries
            return constrain(t, rules, "batch", "heads",
                             *([None] * (t.ndim - 2)))

        m0 = _c3(jnp.full((b, h, s), NEG, jnp.float32))
        l0 = _c3(jnp.zeros((b, h, s), jnp.float32))
        a0 = _c3(jnp.zeros((b, h, s, lora), jnp.float32))

        def body(carry, xs):
            m, l, acc = carry
            ckv_j, kpe_j, kpj = xs                          # kpj (B, c)
            dist = qp1[:, :, None] - kpj[:, None, :]        # (B, Sq, c)
            mj = (kpj[:, None, :] >= 0) & (dist >= 0)
            sc = (jnp.einsum("bshl,bkl->bhsk", q_abs,
                             ckv_j.astype(jnp.float32))
                  + jnp.einsum("bshr,bkr->bhsk", q_pe32,
                               kpe_j.astype(jnp.float32)))
            sc = jnp.where(mj[:, None], sc, NEG)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = _c3(l * corr + p.sum(axis=-1))
            pv = p.astype(jnp.bfloat16) if cfg.p_bf16 else p
            acc = _c3(acc * corr[..., None] + jnp.einsum(
                "bhsk,bkl->bhsl", pv, ckv_j,
                preferred_element_type=jnp.float32))
            return (_c3(m_new), l, acc), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (ckv_c, kpe_c, kpos_c))
        ctx = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(0, 2, 1, 3)
        out = jnp.einsum("bshl,lhv->bshv", ctx.astype(x.dtype), params["wuv"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
        out = out.reshape(b, s, h * cfg.v_head_dim)
        out = constrain(out, rules, "batch", "seq", "heads")
    return L.dense({"w": params["wo"]}, out, quant, qat), new_cache

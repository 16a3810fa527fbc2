"""One scan-over-layers decoder LM covering all assigned families.

A config compiles to a "block program": a list of (repeat, [layer kinds])
groups. Each group's params are stacked on a leading `repeat` axis and run
under jax.lax.scan (small HLO even for 62-layer models); the inner kind list
is unrolled inside the scan body. This expresses heterogeneous stacks:

  dense / moe / audio :  [(L, ('self',))]
  gemma3 5:1          :  [(L//6, ('local',)*5 + ('global',)), ...]
  llama-3.2-vision    :  [(L//5, ('self',)*4 + ('cross',))]
  rwkv6               :  [(L, ('rwkv',))]
  hymba               :  [(L, ('hymba',))]

Caches/states mirror the block program and are scanned alongside params, so
prefill/decode flow through the same code path as training.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.nn import attention as A
from repro.nn import layers as L
from repro.nn import moe as MOE
from repro.nn import ssm as SSM
from repro.nn.module import ParamDesc, stack, init_params as _init
from repro.parallel.sharding import (ShardingRules, DEFAULT_RULES, constrain,
                                     prune_spec)
from repro.quant.quantize import QuantConfig, BF16


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    mlp_act: str = "swiglu"          # swiglu|geglu|gelu
    # layer pattern
    local_window: int = 0
    local_ratio: int = 0             # N local layers per 1 global (gemma3: 5)
    cross_every: int = 0             # 1 cross-attn layer per N (llama-vision)
    enc_dim: int = 0
    enc_len: int = 0
    # moe
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    moe_d_ff: int = 0
    moe_int8_gather: bool = False    # quantized expert all-gather (§Perf)
    moe_capacity: float = 1.25       # MoE capacity factor (§Perf)
    attn_p_bf16: bool = False        # bf16 softmax weights in flash (§Perf)
    # mla
    kv_lora: int = 0
    qk_nope: int = 128
    qk_rope: int = 64
    v_head_dim: int = 128
    # ssm
    ssm: str = ""                    # ''|rwkv6|hymba
    ssm_state: int = 16
    rwkv_chunked: bool = False       # chunk-parallel WKV (see §Perf)
    # io
    embed_stub: bool = False
    n_codebooks: int = 1
    tied_embeddings: bool = True
    # numerics
    param_dtype: Any = jnp.float32
    quant: QuantConfig = BF16
    vocab_pad: int = 0               # padded vocab (0 -> no padding)
    remat: bool = True
    sub_quadratic: bool = False      # eligible for long_500k

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return self.vocab_pad or self.vocab

    def attn_cfg(self, kind: str) -> A.AttnConfig:
        window = self.local_window if kind == "local" else 0
        if kind == "hymba_attn":
            window = self.local_window
        return A.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.dh,
            rope_theta=self.rope_theta, qkv_bias=self.qkv_bias,
            window=window, cross=(kind == "cross"),
            p_bf16=self.attn_p_bf16,
            kv_lora=self.kv_lora, qk_nope=self.qk_nope if self.kv_lora else 0,
            qk_rope=self.qk_rope if self.kv_lora else 0,
            v_head_dim=self.v_head_dim if self.kv_lora else 0)

    def moe_cfg(self) -> MOE.MoEConfig:
        return MOE.MoEConfig(d_model=self.d_model, n_experts=self.n_experts,
                             top_k=self.top_k, d_ff=self.moe_d_ff or self.d_ff,
                             n_shared=self.n_shared,
                             int8_gather=self.moe_int8_gather,
                             capacity_factor=self.moe_capacity)

    def rwkv_cfg(self) -> SSM.RWKVConfig:
        return SSM.RWKVConfig(d_model=self.d_model, n_heads=self.n_heads)

    def mamba_cfg(self) -> SSM.MambaConfig:
        return SSM.MambaConfig(d_model=self.d_model, d_inner=self.d_model,
                               n_state=self.ssm_state)

    # ---- block program ----
    def blocks(self) -> List[Tuple[int, Tuple[str, ...]]]:
        Lc = self.n_layers
        if self.ssm == "rwkv6":
            return [(Lc, ("rwkv",))]
        if self.ssm == "hymba":
            return [(Lc, ("hymba",))]
        if self.local_ratio:
            per = self.local_ratio + 1
            n_groups, rem = divmod(Lc, per)
            prog = [(n_groups, ("local",) * self.local_ratio + ("global",))]
            if rem:
                prog.append((1, ("global",) * rem))
            return prog
        if self.cross_every:
            per = self.cross_every
            n_groups, rem = divmod(Lc, per)
            prog = [(n_groups, ("self",) * (per - 1) + ("cross",))]
            if rem:
                prog.append((1, ("self",) * rem))
            return prog
        return [(Lc, ("self",))]


# ---------------------------------------------------------------------------
# Descriptors
# ---------------------------------------------------------------------------

def _mlp_desc(cfg: ArchConfig, dtype):
    D, F = cfg.d_model, cfg.d_ff
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {"wg": ParamDesc((D, F), ("fsdp", "mlp"), dtype=dtype),
                "wu": ParamDesc((D, F), ("fsdp", "mlp"), dtype=dtype),
                "wd": ParamDesc((F, D), ("mlp", "fsdp"), dtype=dtype)}
    return {"wu": ParamDesc((D, F), ("fsdp", "mlp"), dtype=dtype),
            "wd": ParamDesc((F, D), ("mlp", "fsdp"), dtype=dtype)}


def _layer_desc(cfg: ArchConfig, kind: str, dtype):
    d: Dict[str, Any] = {"ln1": L.rmsnorm_desc(cfg.d_model, dtype),
                         "ln2": L.rmsnorm_desc(cfg.d_model, dtype)}
    if kind == "rwkv":
        d["tmix"] = SSM.rwkv_tmix_desc(cfg.rwkv_cfg(), dtype)
        d["cmix"] = SSM.rwkv_cmix_desc(cfg.d_model, cfg.d_ff, dtype)
        return d
    if kind == "hymba":
        d["attn"] = A.attn_desc(cfg.attn_cfg("hymba_attn"), dtype)
        d["mamba"] = SSM.mamba_desc(cfg.mamba_cfg(), dtype)
        d["mlp"] = _mlp_desc(cfg, dtype)
        return d
    d["attn"] = A.attn_desc(cfg.attn_cfg(kind), dtype)
    if cfg.n_experts and kind in ("self", "local", "global"):
        d["moe"] = MOE.moe_desc(cfg.moe_cfg(), dtype)
    else:
        d["mlp"] = _mlp_desc(cfg, dtype)
    return d


def descs(cfg: ArchConfig):
    dtype = cfg.param_dtype
    tree: Dict[str, Any] = {}
    if not cfg.embed_stub:
        tree["embed"] = L.embed_desc(cfg.padded_vocab, cfg.d_model, dtype)
    if cfg.embed_stub or not cfg.tied_embeddings:
        v = cfg.padded_vocab
        if cfg.n_codebooks > 1:
            tree["lm_head"] = {"table": ParamDesc(
                (cfg.n_codebooks, v, cfg.d_model), (None, "vocab", "embed"),
                "embed", 0.02, dtype)}
        else:
            tree["lm_head"] = L.embed_desc(v, cfg.d_model, dtype)
    if cfg.cross_every:
        tree["enc_proj"] = {"w": ParamDesc((cfg.enc_dim, cfg.d_model),
                                           ("embed", "fsdp"), dtype=dtype)}
    tree["final_ln"] = L.rmsnorm_desc(cfg.d_model, dtype)
    tree["blocks"] = []
    for rep, kinds in cfg.blocks():
        group = {f"k{i}_{kind}": _layer_desc(cfg, kind, dtype)
                 for i, kind in enumerate(kinds)}
        tree["blocks"].append(stack(group, rep))
    return tree


def init(cfg: ArchConfig, key: jax.Array):
    return _init(descs(cfg), key)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Cache pytree mirroring the block program (stacked per group)."""
    def kind_cache(kind):
        if kind == "rwkv":
            H, N = cfg.n_heads, cfg.d_model // cfg.n_heads
            return {"S": jnp.zeros((batch, H, N, N), jnp.float32),
                    "xprev": jnp.zeros((batch, cfg.d_model), jnp.float32),
                    "cm_xprev": jnp.zeros((batch, cfg.d_model), jnp.float32)}
        if kind == "hymba":
            mc = cfg.mamba_cfg()
            return {"attn": A.init_cache(cfg.attn_cfg("hymba_attn"), batch,
                                         max_len, dtype),
                    "h": jnp.zeros((batch, mc.d_inner, mc.n_state),
                                   jnp.float32),
                    "conv": jnp.zeros((batch, mc.conv_k - 1, mc.d_inner),
                                      jnp.float32)}
        if kind == "cross":
            return {}  # encoder K/V recomputed from enc states
        return A.init_cache(cfg.attn_cfg(kind), batch, max_len, dtype)

    blocks = []
    for rep, kinds in cfg.blocks():
        group = {f"k{i}_{kind}": kind_cache(kind)
                 for i, kind in enumerate(kinds)}
        blocks.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (rep,) + x.shape).copy(), group))
    return {"blocks": blocks}


def cache_logical(cfg: ArchConfig):
    """Logical axis names per `init_cache` leaf — the same tree structure
    with tuple-of-names leaves (tuples marked as leaves via is_leaf when
    traversing). Batch rows map to 'data', (KV) heads to 'model', positions
    and state feature dims stay replicated; the stacked group dim is
    'layers'. Consumed by :func:`cache_specs` for the sharded serving
    engine (docs/sharding.md)."""
    def kind_axes(kind):
        if kind == "rwkv":
            return {"S": ("batch", "heads", None, None),
                    "xprev": ("batch", None),
                    "cm_xprev": ("batch", None)}
        if kind == "hymba":
            return {"attn": A.cache_logical(cfg.attn_cfg("hymba_attn")),
                    "h": ("batch", None, None),
                    "conv": ("batch", None, None)}
        if kind == "cross":
            return {}
        return A.cache_logical(cfg.attn_cfg(kind))

    is_ax = lambda x: isinstance(x, tuple)  # noqa: E731
    blocks = []
    for rep, kinds in cfg.blocks():
        group = {f"k{i}_{kind}": kind_axes(kind)
                 for i, kind in enumerate(kinds)}
        blocks.append(jax.tree.map(lambda ax: ("layers",) + ax, group,
                                   is_leaf=is_ax))
    return {"blocks": blocks}


def cache_specs(cfg: ArchConfig, cache, rules: ShardingRules, mesh):
    """PartitionSpec tree (same treedef as `cache`) for any `init_cache` /
    `init_page_store` pytree, with non-dividing mesh axes pruned — the
    batch dim of a page store is its page dim, so the same rules shard a
    serving pool over slots and a page store over pages. Leaves may be
    arrays or ShapeDtypeStructs (anything with .shape)."""
    logical = cache_logical(cfg)
    flat, treedef = jax.tree.flatten(cache)
    lflat = jax.tree.flatten(
        logical, is_leaf=lambda x: isinstance(x, tuple))[0]
    assert len(flat) == len(lflat), "cache_logical drifted from init_cache"
    specs = [prune_spec(x.shape, rules.spec(ax, mesh), mesh)
             for x, ax in zip(flat, lflat)]
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _mlp(params, x, cfg: ArchConfig, qat: bool):
    q = cfg.quant
    if cfg.mlp_act in ("swiglu", "geglu"):
        g = L.dense({"w": params["wg"]}, x, q, qat)
        u = L.dense({"w": params["wu"]}, x, q, qat)
        act = jax.nn.silu(g) if cfg.mlp_act == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(L.dense({"w": params["wu"]}, x, q, qat))
    return L.dense({"w": params["wd"]}, h, q, qat)


def _layer(params, x, kind: str, cfg: ArchConfig, rules, *, cache, pos, enc,
           qat):
    q = cfg.quant
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(params["ln1"], x)
    if kind == "rwkv":
        st = None if cache is None else {"S": cache["S"],
                                         "xprev": cache["xprev"]}
        mix, new_st = SSM.rwkv_tmix(params["tmix"], h, cfg.rwkv_cfg(), rules,
                                    q, state=st, qat=qat,
                                    chunked=cfg.rwkv_chunked)
        x = x + mix
        h2 = L.rmsnorm(params["ln2"], x)
        cm_prev = None if cache is None else cache["cm_xprev"]
        ff, cm_x = SSM.rwkv_cmix(params["cmix"], h2, rules, q, xprev=cm_prev,
                                 qat=qat)
        x = x + ff
        new_cache = (None if cache is None else
                     {"S": new_st["S"], "xprev": new_st["xprev"],
                      "cm_xprev": cm_x})
        return x, new_cache, aux
    if kind == "hymba":
        attn_cache = None if cache is None else cache["attn"]
        ao, new_attn = A.apply(params["attn"], h, cfg.attn_cfg("hymba_attn"),
                               rules, q, cache=attn_cache, pos=pos, qat=qat)
        st = None if cache is None else {"h": cache["h"],
                                         "conv": cache["conv"]}
        so, new_st = SSM.mamba(params["mamba"], h, cfg.mamba_cfg(), rules, q,
                               state=st, qat=qat)
        x = x + 0.5 * (ao + so)                  # parallel heads fusion
        h2 = L.rmsnorm(params["ln2"], x)
        x = x + _mlp(params["mlp"], h2, cfg, qat)
        new_cache = (None if cache is None else
                     {"attn": new_attn, "h": new_st["h"],
                      "conv": new_st["conv"]})
        return x, new_cache, aux
    # attention kinds: self/local/global/cross
    ao, new_cache = A.apply(params["attn"], h, cfg.attn_cfg(kind), rules, q,
                            cache=cache if cache else None, pos=pos,
                            enc=enc if kind == "cross" else None, qat=qat)
    x = x + ao
    h2 = L.rmsnorm(params["ln2"], x)
    if "moe" in params:
        mo, aux = MOE.apply(params["moe"], h2, cfg.moe_cfg(), rules, q,
                            qat=qat)
        x = x + mo
    else:
        x = x + _mlp(params["mlp"], h2, cfg, qat)
    if kind == "cross":
        new_cache = {} if cache is not None else None
    return x, new_cache, aux


def backbone(params, x, cfg: ArchConfig, rules: ShardingRules, *,
             caches=None, pos=None, enc=None, qat=False, training=False):
    """x: (B,S,D) embeddings -> (hidden, new_caches, aux)."""
    if cfg.cross_every and enc is not None:
        enc = jnp.einsum("bsd,dk->bsk", enc, params["enc_proj"]["w"])
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for bi, (rep, kinds) in enumerate(cfg.blocks()):
        bparams = params["blocks"][bi]
        bcache = None if caches is None else caches["blocks"][bi]

        def body(carry, xs):
            h, aux = carry
            lp, lc = xs
            with jax.named_scope("layer"):
                for i, kind in enumerate(kinds):
                    key = f"k{i}_{kind}"
                    c = None if lc is None else lc[key]
                    h, nc, a = _layer(lp[key], h, kind, cfg, rules,
                                      cache=c, pos=pos, enc=enc, qat=qat)
                    if lc is not None:
                        lc = dict(lc)
                        lc[key] = nc if nc is not None else lc[key]
                    aux = aux + a
                    h = constrain(h, rules, "batch", "seq", "embed")
            return (h, aux), lc

        if cfg.remat and training:
            body = jax.checkpoint(body)
        # op metadata only: "layer_scan" tags the loop's own slicing of
        # each layer's weights and cache out of the stacked arrays and its
        # write-back of the cache; "layer" the norms, residuals and
        # activations that no projection ("qmm.*") or attention scope holds
        with jax.named_scope("layer_scan"):
            (x, aux_total), nbc = jax.lax.scan(
                body, (x, aux_total), (bparams, bcache))
        new_caches.append(nbc)
    x = L.rmsnorm(params["final_ln"], x)
    return x, ({"blocks": new_caches} if caches is not None else None), \
        aux_total


def embed_tokens(params, tokens, cfg: ArchConfig):
    if cfg.embed_stub:
        return tokens  # already (B, S, D) frontend embeddings
    return L.embed(params["embed"], tokens).astype(jnp.bfloat16) \
        if cfg.param_dtype == jnp.bfloat16 else L.embed(params["embed"],
                                                        tokens)


def lm_logits(params, hidden, cfg: ArchConfig,
              rules: ShardingRules = DEFAULT_RULES, *, qat: bool = False):
    """Final projection to vocab. Quantized configs dispatch through the
    backend registry like every other projection (the LM head is the widest
    matmul in the stack); multi-codebook heads stay float — the per-codebook
    einsum has no (k, n) registry lowering yet (documented in
    docs/quantization.md)."""
    with jax.named_scope("lm_head"):     # op metadata only, as qmm.*
        if cfg.n_codebooks > 1:
            out = jnp.einsum("bsd,cvd->bscv", hidden,
                             params["lm_head"]["table"],
                             preferred_element_type=jnp.float32)
            return constrain(out, rules, "batch", "seq", None, "vocab")
        table = (params["lm_head"]["table"] if "lm_head" in params
                 else params["embed"]["table"])
        out = L.logits({"table": table}, hidden, true_vocab=cfg.vocab,
                       quant=cfg.quant, qat=qat)
        return constrain(out, rules, "batch", "seq", "vocab")


def forward_loss(params, batch, cfg: ArchConfig,
                 rules: ShardingRules = DEFAULT_RULES, *, qat=False,
                 training=True):
    """batch: {tokens|embeds, labels} -> scalar loss."""
    x = embed_tokens(params, batch.get("tokens", batch.get("embeds")), cfg)
    x = constrain(x, rules, "batch", "seq", "embed")
    enc = batch.get("enc")
    h, _, aux = backbone(params, x, cfg, rules, enc=enc, qat=qat,
                         training=training)
    lg = lm_logits(params, h, cfg, qat=qat)
    labels = batch["labels"]
    if cfg.n_codebooks > 1:
        loss = L.softmax_cross_entropy(
            lg.reshape(-1, lg.shape[-1]), labels.reshape(-1), cfg.vocab)
    else:
        loss = L.softmax_cross_entropy(lg, labels, cfg.vocab)
    return loss + aux


def prefill(params, tokens, cfg: ArchConfig, caches,
            rules: ShardingRules = DEFAULT_RULES, enc=None, lengths=None,
            pos_offset=None):
    """Batched prefill -> (next-token logits (B, 1, V), caches).

    lengths: optional (B,) int32 true prompt lengths for a right-padded
    batch — logits are gathered at each row's last *real* token instead of
    the shared last column (mixed-length serving; the padded tail's KV is
    masked out of later decode steps by absolute position). Without
    `lengths` the batch is assumed unpadded.

    pos_offset: optional int32 scalar (or (B,) vector) absolute position of
    ``tokens[:, 0]`` — a *suffix* prefill over a cache already holding KV
    for positions ``[0, pos_offset)``. Queries attend causally to the
    cached prefix plus the in-flight suffix, exactly as a full prefill
    would at the same absolute positions; this is what lets the serving
    engine skip recomputing a prefix-cache hit (docs/serving.md). None (or
    0) is a cold prefill from position 0.
    """
    x = embed_tokens(params, tokens, cfg)
    pos = None if pos_offset is None else jnp.asarray(pos_offset, jnp.int32)
    h, caches, _ = backbone(params, x, cfg, rules, caches=caches, pos=pos,
                            enc=enc)
    if lengths is not None:
        idx = jnp.asarray(lengths, jnp.int32) - 1
        h = h[jnp.arange(h.shape[0]), idx][:, None]      # (B, 1, D)
    else:
        h = h[:, -1:]
    return lm_logits(params, h, cfg), caches


def decode_step(params, token, pos, cfg: ArchConfig, caches,
                rules: ShardingRules = DEFAULT_RULES, enc=None):
    """token: (B,1) ids or (B,1,D) stub embeds; pos: int32 scalar array for
    uniform batch-synchronous decode, or a (B,) vector giving each cache
    row its own absolute position (per-slot continuous batching —
    repro.serve drives this with the slot pool's position vector)."""
    x = embed_tokens(params, token, cfg)
    h, caches, _ = backbone(params, x, cfg, rules, caches=caches, pos=pos,
                            enc=enc)
    return lm_logits(params, h, cfg), caches


def verify_step(params, window, pos, cfg: ArchConfig, caches,
                rules: ShardingRules = DEFAULT_RULES, enc=None):
    """One speculative verify pass: a (B, K) token window per cache row.

    ``window[b]`` holds the row's committed next-input token followed by
    K-1 draft proposals; ``pos`` is the (B,) position of ``window[:, 0]``,
    so row b's tokens sit at absolute positions ``pos[b] + [0, K)``
    (nn/attention builds exactly that query-position grid and masks
    causally by absolute distance). Logits row j is the model's next-token
    distribution after consuming ``window[:, :j+1]`` — bitwise identical
    to the j-th sequential :func:`decode_step` over the same tokens, for
    every registered backend (the per-token dequant order is pinned
    shape-stable in quant/matmul; tests/test_speculative.py proves the
    composition). KV for all K window positions is written to the cache;
    the caller must erase positions past the accepted frontier with
    :func:`rollback_positions` before the next step.

    This is :func:`decode_step` at width K — one function, one compiled
    body per width, no drift between the verify and decode paths.
    """
    return decode_step(params, window, pos, cfg, caches, rules, enc)


def rollback_positions(caches, start, stop):
    """Zero cache positions ``[start[b], stop[b])`` of every row b.

    The speculative un-commit: a verify pass writes KV for the whole
    (B, K) window, and rejected suffix positions must be erased so the
    pool row is bitwise identical to the sequential-decode row (freshly
    initialized caches are zero, so "erased" and "never written" are the
    same state — the invariant tests/test_speculative.py checks leaf by
    leaf). Only position-indexed cache layouts are rollback-able (every
    leaf is (rep, batch, max_len, ...) — the same
    ``serve.padded_prefill_ok`` predicate that gates paging gates
    speculation); SSM states fold tokens in irreversibly.

    start/stop: (B,) int32 position bounds per row (start >= stop is a
    no-op for that row). Pure masking — no float arithmetic, so it is
    exact under any backend, jit, or shard_map.
    """
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)

    def leaf(x):
        p = jnp.arange(x.shape[2], dtype=jnp.int32)
        drop = (p[None, :] >= start[:, None]) & (p[None, :] < stop[:, None])
        shape = (1, x.shape[1], x.shape[2]) + (1,) * (x.ndim - 3)
        return jnp.where(drop.reshape(shape), jnp.zeros((), x.dtype), x)

    return jax.tree.map(leaf, caches)


# ---------------------------------------------------------------------------
# Paged cache indirection (repro.serve page pool — see docs/serving.md)
# ---------------------------------------------------------------------------
#
# A page store is an init_cache pytree with (batch -> n_pages,
# max_len -> page_size): every positional leaf becomes (rep, n_pages,
# page_size, ...). Gather/scatter move whole pages between the store and a
# cache row by page index — static shapes per chain length, so both lower
# to one take/one scatter per leaf (TPU/Pallas friendly). Only archs whose
# caches are purely position-indexed are pageable: recurrent SSM states
# and windowed ring buffers have no per-position storage to page
# (serve.padded_prefill_ok is the same predicate).

def init_page_store(cfg: ArchConfig, n_pages: int, page_size: int,
                    dtype=jnp.bfloat16):
    """KV page store: ``n_pages`` pages of ``page_size`` positions each."""
    return init_cache(cfg, n_pages, page_size, dtype)


def gather_pages(cache, pages, page_ids):
    """Copy a page chain into positions ``[0, n*page_size)`` of a batch=1
    cache (the copy-on-write copy: shared pages are read, never written).

    cache: init_cache(cfg, 1, max_len) pytree; pages: init_page_store
    pytree; page_ids: (n,) int page indices, in position order.
    """
    ids = jnp.asarray(page_ids, jnp.int32)

    def leaf(row, pg):
        sel = jnp.take(pg, ids, axis=1)               # (rep, n, ps, ...)
        sel = sel.reshape(sel.shape[0], 1,
                          sel.shape[1] * sel.shape[2], *sel.shape[3:])
        return jax.lax.dynamic_update_slice_in_dim(
            row, sel.astype(row.dtype), 0, axis=2)

    return jax.tree.map(leaf, cache, pages)


def store_pages(pages, pool, slot: int, page_ids, page_indices):
    """Freeze pages out of one slot row of a serving pool.

    For each (page_ids[i], page_indices[i]) pair, positions
    ``[page_indices[i]*ps, (page_indices[i]+1)*ps)`` of ``pool[:, slot]``
    are copied into page ``page_ids[i]`` of the store. Returns the updated
    store.
    """
    ids = jnp.asarray(page_ids, jnp.int32)
    idxs = jnp.asarray(page_indices, jnp.int32)

    def leaf(pg, pl):
        ps = pg.shape[2]
        row = pl[:, slot]                             # (rep, max_len, ...)
        n_pos = row.shape[1] // ps
        segs = row[:, :n_pos * ps].reshape(
            row.shape[0], n_pos, ps, *row.shape[2:])
        sel = jnp.take(segs, idxs, axis=1)            # (rep, n, ps, ...)
        return pg.at[:, ids].set(sel.astype(pg.dtype))

    return jax.tree.map(leaf, pages, pool)

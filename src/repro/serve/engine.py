"""Continuous-batching inference engine over the quantized backend registry.

Fixed-slot decode over a block-paged persistent KV store (static shapes —
TPU/Pallas friendly):

  * one decode workspace, allocated once: every cache leaf has a `slots`
    batch axis and `max_len` positions; a request owns exactly one slot
    row from admission to finish and all its decode writes land there
  * decode advances ALL slots each step with a per-slot position vector
    (`models/transformer_lm.decode_step` with `pos: (slots,)`); parked
    (free) slots run token 0 at position 0 and their writes are overwritten
    at the next admission
  * admission (scheduler.SlotScheduler) happens between decode steps: a
    freed slot is refilled immediately under the 'continuous' policy
    instead of waiting for the wave to drain
  * **prefix cache** (serve/paging.py): finished sequences are frozen into
    refcounted pages of a shared page store, indexed by a radix tree over
    token ids. Admission matches the new prompt against the tree; cached
    full pages are gathered into the fresh cache row (the copy-on-write
    copy — shared pages are immutable) and only the *suffix* is prefilled,
    at its true absolute offset (`prefill(..., pos_offset=)`). A cache-hit
    decode is bitwise-identical to the cold-miss decode, per backend
    (tests/test_serve.py; the invariance argument is in docs/serving.md).
    Paging is gated to position-indexed cache layouts — the same
    `padded_prefill_ok` predicate; SSM/windowed archs serve unpaged.
  * finish reasons are always explicit: 'eos' | 'max_new' | 'max_len'
    (a request that hits the cache ceiling reports it — nothing is
    silently truncated)

The model executes through the quant backend registry via
``quantize.for_lm``: per-token activation scales make every int8 code (and
so every approximate-multiplier accumulator) a function of its own row
only. Combined with position-masked attention over the fixed-size pool,
that yields the engine's bitwise batching-invariance contract — a
request's greedy tokens are identical served alone, in a full batch,
admitted mid-decode into a reused slot, or admitted onto a prefix-cache
hit, for every registered backend (tests/test_serve.py; docs/serving.md).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.models import transformer_lm as TLM
from repro.models.transformer_lm import ArchConfig
from repro.nn.module import ParamDesc
from repro.parallel.sharding import (ShardingRules, DEFAULT_RULES,
                                     prune_spec)
from repro.serve.metrics import RequestTiming, summarize
from repro.serve.paging import PrefixCache
from repro.serve.sampling import GREEDY, SamplingConfig, sample_token
from repro.serve.scheduler import SlotScheduler

FINISH_REASONS = ("eos", "max_new", "max_len")


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray                  # (len,) int32, len >= 1
    max_new: int = 16
    sampling: SamplingConfig = GREEDY
    # per-request speculation cap: None -> the engine's SpecConfig window,
    # 0 -> sequential decode for this request, n -> accept at most n
    # drafts per verify pass (clamped to the engine window). The emitted
    # tokens are identical either way — spec_k only changes how many
    # arrive per pass (serve/speculative.py).
    spec_k: Optional[int] = None
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    timing: RequestTiming = dataclasses.field(default_factory=RequestTiming)


@functools.lru_cache(maxsize=8)
def compiled_fns(cfg: ArchConfig, rules: ShardingRules):
    """Jitted prefill/decode shared across Engine instances (both frozen
    dataclasses hash) — the drain baseline and the continuous engine in
    benchmarks/serve_perf.py reuse one compilation, so the tok/s gap they
    report is scheduling, not compile luck.

    Bounded (maxsize=8): an eval sweep over every backend x variant would
    otherwise pin every compiled prefill/decode executable for the process
    lifetime. Engines keep their own references, so eviction never breaks
    a live engine — it only allows dead executables to be collected. Eval
    runners call :func:`clear_compiled_fns` between suites.
    """
    prefill = jax.jit(lambda p, t, c, l, off: TLM.prefill(
        p, t, cfg, c, rules, lengths=l, pos_offset=off))
    decode = jax.jit(lambda p, c, t, pos: TLM.decode_step(p, t, pos, cfg, c,
                                                          rules))
    return prefill, decode


def clear_compiled_fns() -> None:
    """Drop all cached compiled prefill/decode executables (eval runners
    call this between suites so back-to-back backend sweeps don't
    accumulate live executables). Covers every executable cache the
    serving stack owns: the single-device pairs, the mesh-wrapped
    shard_map pairs, and — because a Speculator obtains its draft pair
    through these same caches — the speculative compiled fns
    (tests/test_serve.py pins this as a regression)."""
    compiled_fns.cache_clear()
    mesh_compiled_fns.cache_clear()


# ---------------------------------------------------------------------------
# Engine-over-mesh: sharded storage, bit-exact compute (docs/sharding.md)
# ---------------------------------------------------------------------------
#
# The sharded engine keeps params FSDP/TP-sharded and the KV pool sharded
# (slot rows over 'data', KV heads over 'model') but computes each step
# through the UNCHANGED single-device model inside one shard_map:
#
#   gather   params are all-gathered in full; cache leaves are gathered
#            over their 'model' (head) axes only, keeping the slot dim
#            local. all_gather moves bytes — no arithmetic, so the
#            reconstructed operands are the single-device values bit for
#            bit.
#   compute  each device runs TLM.prefill/decode_step on its local slot
#            rows. The CACHE evolution is bitwise identical to the solo
#            decode of those rows (integer matmul cores + element-wise
#            writes); float LOGITS are only ulp-close — XLA fuses the
#            float attention/softmax epilogue differently inside the
#            shard_map program, reassociating last-ulp rounding — and
#            argmax-identical (asserted in
#            test_sharded_compiled_fns_parity). The guarantee the served
#            engine carries is therefore the token-level
#            batching-invariance contract from PR 4: a request's greedy
#            tokens are identical no matter which other slots share the
#            pool, mesh or no mesh — proven per backend in
#            tests/test_serve.py.
#   scatter  model-sharded output dims are sliced back to the local shard
#            by mesh position (a pure slice), so storage stays sharded
#            between steps.
#
# GSPMD auto-partitioning of the full LM is deliberately NOT used here: it
# reassociates float contractions across shards (K-dim FSDP sums, fused
# gemm tiling), which breaks bitwise parity. This formulation keeps every
# float op local and unchanged; the only cross-device ops are exact byte
# movement. check_vma=False because Pallas backends define no varying-axes
# rule.


def _flat_specs(spec_tree):
    """Flatten a PartitionSpec tree (PS is a tuple subclass, so plain
    flatten would explode each spec into its entries)."""
    return jax.tree.flatten(spec_tree,
                            is_leaf=lambda x: isinstance(x, PS))[0]


def _gather_leaf(x, spec, skip_dim=None):
    """all_gather a shard_map-local shard back to the full array along
    every sharded dim of `spec`, minor mesh axis first within a dim so
    blocks land in their original order. Pure byte movement."""
    for d, entry in enumerate(spec):
        if entry is None or d == skip_dim:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        for ax in reversed(axes):
            x = jax.lax.all_gather(x, ax, axis=d, tiled=True)
    return x


def _slice_leaf(x, spec, sizes, skip_dim=None):
    """Inverse of `_gather_leaf`: slice this device's shard back out of a
    full array (major mesh axis first within a dim)."""
    for d, entry in enumerate(spec):
        if entry is None or d == skip_dim:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        n = 1
        idx = jnp.int32(0)
        for ax in axes:
            n *= sizes[ax]
            idx = idx * sizes[ax] + jax.lax.axis_index(ax)
        loc = x.shape[d] // n
        x = jax.lax.dynamic_slice_in_dim(x, idx * loc, loc, axis=d)
    return x


def _param_plan(cfg: ArchConfig, rules: ShardingRules, mesh: Mesh):
    """(treedef, [pruned PartitionSpec]) over the cfg's param tree."""
    descs = TLM.descs(cfg)
    is_desc = lambda t: isinstance(t, ParamDesc)  # noqa: E731
    leaves, treedef = jax.tree.flatten(descs, is_leaf=is_desc)
    specs = [prune_spec(d.shape, rules.spec(d.logical, mesh), mesh)
             for d in leaves]
    return treedef, specs


def _tree_shardings(mesh: Mesh, treedef, specs):
    return jax.tree.unflatten(
        treedef, [NamedSharding(mesh, s) for s in specs])


def _write_slot(pool, one, slot):
    """Full-row copy of a freshly prefilled batch=1 cache into slot row
    `slot` of the pool (same update as the single-device admission path;
    traced `slot` so the jitted mesh version compiles once)."""
    return jax.tree.map(lambda p, o: p.at[:, slot].set(o[:, 0]), pool, one)


@functools.lru_cache(maxsize=8)
def mesh_compiled_fns(cfg: ArchConfig, rules: ShardingRules, mesh: Mesh,
                      slots: int, max_len: int, cache_dtype):
    """Sharded counterpart of :func:`compiled_fns`.

    Returns (prefill, decode, shardings): jitted prefill/decode with the
    same signatures as the single-device pair, plus the NamedSharding
    trees ({'params', 'pool'}) the Engine pins its storage to. Cached per
    (cfg, rules, mesh, slots, max_len, cache_dtype) — Mesh and the frozen
    dataclasses all hash."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ptd, pspecs = _param_plan(cfg, rules, mesh)
    pool = jax.eval_shape(
        lambda: TLM.init_cache(cfg, slots, max_len, cache_dtype))
    one = jax.eval_shape(
        lambda: TLM.init_cache(cfg, 1, max_len, cache_dtype))
    ctd = jax.tree.structure(pool)
    pool_specs = _flat_specs(TLM.cache_specs(cfg, pool, rules, mesh))
    one_specs = _flat_specs(TLM.cache_specs(cfg, one, rules, mesh))
    # how the pool's slot dim is sharded (None when slots don't divide)
    bspec = prune_spec((slots,), rules.spec(("batch",), mesh), mesh)
    slot_ax = bspec[0] if len(bspec) else None

    def gather_params(pflat):
        return jax.tree.unflatten(
            ptd, [_gather_leaf(x, s) for x, s in zip(pflat, pspecs)])

    def gather_cache(cflat, specs):
        # model (head) axes gathered in full; slot dim (1) stays local
        return jax.tree.unflatten(ctd, [
            _gather_leaf(x, s, skip_dim=1) for x, s in zip(cflat, specs)])

    def prefill_body(pflat, cflat, toks, lengths, off):
        logits, new = TLM.prefill(
            gather_params(pflat), toks, cfg, gather_cache(cflat, one_specs),
            rules, lengths=lengths, pos_offset=off)
        return logits, [_slice_leaf(x, s, sizes, skip_dim=1)
                        for x, s in zip(jax.tree.leaves(new), one_specs)]

    def decode_body(pflat, cflat, tok, pos):
        logits, new = TLM.decode_step(
            gather_params(pflat), tok, pos, cfg,
            gather_cache(cflat, pool_specs), rules)
        return logits, [_slice_leaf(x, s, sizes, skip_dim=1)
                        for x, s in zip(jax.tree.leaves(new), pool_specs)]

    sm_prefill = jax.shard_map(
        prefill_body, mesh=mesh,
        in_specs=(pspecs, one_specs, PS(None, None), PS(None), PS()),
        out_specs=(PS(None, None, None), one_specs), check_vma=False)
    sm_decode = jax.shard_map(
        decode_body, mesh=mesh,
        in_specs=(pspecs, pool_specs, PS(slot_ax, None), PS(slot_ax)),
        out_specs=(PS(slot_ax, None, None), pool_specs), check_vma=False)

    def prefill(p, toks, cache, lengths, off):
        logits, nf = sm_prefill(jax.tree.leaves(p), jax.tree.leaves(cache),
                                toks, lengths, off)
        return logits, jax.tree.unflatten(ctd, nf)

    def decode(p, cache, tok, pos):
        logits, nf = sm_decode(jax.tree.leaves(p), jax.tree.leaves(cache),
                               tok, pos)
        return logits, jax.tree.unflatten(ctd, nf)

    shardings = {"params": _tree_shardings(mesh, ptd, pspecs),
                 "pool": _tree_shardings(mesh, ctd, pool_specs)}
    return jax.jit(prefill), jax.jit(decode), shardings


def padded_prefill_ok(cfg: ArchConfig) -> bool:
    """Whether prompts may be padded to a length bucket at prefill.

    Padding writes junk KV beyond the true length; that is safe only where
    decode masks it out by absolute position and overwrites it in place —
    i.e. position-indexed caches (global GQA, MLA). Recurrent SSM states
    fold junk tokens in irreversibly, and windowed ring buffers alias junk
    slots onto real positions, so those archs prefill at the exact prompt
    length (one compile per distinct length — documented in
    docs/serving.md). The prefix cache is gated on the same predicate: only
    position-indexed caches have per-position KV to page."""
    return cfg.ssm == "" and cfg.local_ratio == 0 and cfg.local_window == 0


class Engine:
    """Single-host continuous-batching server for token LMs."""

    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 rules: ShardingRules = DEFAULT_RULES,
                 admission: str = "continuous",
                 stream: Optional[Callable[[int, int], None]] = None,
                 cache_dtype=None,
                 prefix_caching: bool = True, page_size: int = 8,
                 cache_pages: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 spec=None, draft_params=None):
        assert not cfg.embed_stub, "serving drives token models"
        # the KV cache holds what the layers compute, in the params' dtype
        # unless asked otherwise: a bf16 model's scan carry is bf16, and
        # attention returns the cache dtype
        if cache_dtype is None:
            cache_dtype = cfg.param_dtype
        self.cfg, self.params, self.rules = cfg, params, rules
        self.slots, self.max_len, self.eos_id = slots, max_len, eos_id
        self.stream = stream
        self.sched = SlotScheduler(slots, admission)
        self.pool = TLM.init_cache(cfg, slots, max_len, cache_dtype)
        self._cache_dtype = cache_dtype
        self._slot_req: List[Optional[ServeRequest]] = [None] * slots
        self._tok = np.zeros(slots, np.int32)     # next input token per slot
        self._pos = np.zeros(slots, np.int32)     # its absolute position
        # a 1-device mesh adds nothing but compile variance — run plain
        self.mesh = (mesh if mesh is not None and mesh.devices.size > 1
                     else None)
        if self.mesh is not None:
            self._prefill, self._decode, shardings = mesh_compiled_fns(
                cfg, rules, self.mesh, slots, max_len, cache_dtype)
            self.params = jax.device_put(self.params, shardings["params"])
            self.pool = jax.device_put(self.pool, shardings["pool"])
            # pinned out_shardings: slot writes must not drift the pool's
            # storage layout between steps
            self._pool_write = jax.jit(_write_slot,
                                       out_shardings=shardings["pool"])
        else:
            self._prefill, self._decode = compiled_fns(cfg, rules)
            self._pool_write = None
        self.completed: List[ServeRequest] = []
        self.decode_steps = 0
        self.busy_slot_steps = 0
        self.prefills = 0
        self.prefill_tokens = 0       # real (unpadded) tokens prefilled
        self.prefix_hit_tokens = 0    # prompt tokens served from the cache
        # ---- paged prefix cache (gated to position-indexed cache layouts)
        self.page_size = page_size
        self.prefix: Optional[PrefixCache] = None
        if prefix_caching and padded_prefill_ok(cfg) \
                and 0 < page_size <= max_len:
            n_pages = cache_pages or 2 * slots * (max_len // page_size)
            self.prefix = PrefixCache(page_size, n_pages)
            self.pages = TLM.init_page_store(cfg, n_pages, page_size,
                                             cache_dtype)
            if self.mesh is not None:
                self._pages_shardings = _tree_shardings(
                    self.mesh, jax.tree.structure(self.pages),
                    _flat_specs(TLM.cache_specs(
                        cfg, self.pages, rules, self.mesh)))
                self.pages = jax.device_put(self.pages,
                                            self._pages_shardings)
        self._slot_chain: List[Tuple[int, ...]] = [()] * slots
        # ---- draft-model speculation (serve/speculative.py) -------------
        self.speculator = None
        if spec is not None:
            from repro.serve.speculative import Speculator
            self.speculator = Speculator(
                spec, cfg, self.params, draft_params, slots=slots,
                max_len=max_len, rules=rules, cache_dtype=cache_dtype,
                mesh=self.mesh)
            # verify reuses self._decode at width spec.k (jit and the
            # shard_map bodies re-specialize per token-window width) and
            # un-commits through the same rollback the draft pool uses,
            # pinned to the pool's sharding on a mesh
            if self.mesh is not None:
                self._rollback = jax.jit(
                    TLM.rollback_positions,
                    out_shardings=mesh_compiled_fns(
                        cfg, rules, self.mesh, slots, max_len,
                        cache_dtype)[2]["pool"])
            else:
                self._rollback = jax.jit(TLM.rollback_positions)

    # ---- request intake --------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        req.prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        # reset engine-owned state so a caller may resubmit the same
        # request object to another run (the historical Server allowed it)
        req.output = []
        req.finish_reason = None
        req.timing = RequestTiming(submit_t=time.time())
        self.sched.submit(req)

    # ---- admission -------------------------------------------------------
    def _bucket(self, plen: int, offset: int = 0) -> int:
        """Compile-friendly prefill length: next power of two >= plen
        (capped so offset + bucket stays inside the cache), or the exact
        length where padding is unsafe."""
        if not padded_prefill_ok(self.cfg):
            return plen
        bucket = 8
        while bucket < plen:
            bucket *= 2
        return min(bucket, self.max_len - offset)

    def _admit(self) -> None:
        with jax.profiler.TraceAnnotation("serve.admit"):
            for slot, req in self.sched.admit():
                self._admit_one(slot, req)

    def _admit_one(self, slot: int, req: ServeRequest) -> None:
        plen = len(req.prompt)
        if plen > self.max_len:
            # rejected before prefill: no room for even the prompt
            req.finish_reason = "max_len"
            self._retire(slot, store=False)
            return
        # longest cached full-page prefix, capped at plen-1 so at least
        # one suffix token remains to produce the first logits
        chain: Tuple[int, ...] = ()
        hit = 0
        with jax.profiler.TraceAnnotation("serve.match"):
            if self.prefix is not None:
                chain = tuple(self.prefix.match(req.prompt[:plen - 1]))
                hit = len(chain) * self.page_size
                if chain:
                    self.prefix.acquire(chain)   # pinned until retirement
                    self.prefix_hit_tokens += hit
        self._slot_chain[slot] = chain
        suffix = req.prompt[hit:]
        bucket = self._bucket(len(suffix), offset=hit)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(suffix)] = suffix
        with jax.profiler.TraceAnnotation("serve.gather"):
            fresh = TLM.init_cache(self.cfg, 1, self.max_len,
                                   self._cache_dtype)
            if chain:
                # the COW copy: shared pages -> this request's private row
                fresh = TLM.gather_pages(fresh, self.pages, chain)
        with jax.profiler.TraceAnnotation("serve.prefill"):
            logits, fresh = self._prefill(
                self.params, jnp.asarray(toks), fresh,
                jnp.asarray([len(suffix)], jnp.int32), jnp.int32(hit))
        self.prefills += 1
        self.prefill_tokens += len(suffix)
        # full-row copy: the freed slot inherits nothing from its previous
        # occupant (zero KV-cache leakage on reuse)
        with jax.profiler.TraceAnnotation("serve.write_slot"):
            if self._pool_write is not None:
                self.pool = self._pool_write(self.pool, fresh,
                                             jnp.int32(slot))
            else:
                self.pool = _write_slot(self.pool, fresh, slot)
        self._slot_req[slot] = req
        self._pos[slot] = plen
        if req.max_new <= 0:
            req.finish_reason = "max_new"
        else:
            first = sample_token(logits[0, 0], req.sampling, req.rid, 0)
            self._emit(req, first)
        if req.finish_reason:
            self._retire(slot)
        else:
            self._tok[slot] = req.output[-1]
            if self.speculator is not None:
                # draft-side cold prefill of the full prompt (the draft
                # never reads the paged prefix store)
                self.speculator.admit(slot, req.prompt, self._bucket)

    # ---- token emission / finish ----------------------------------------
    def _emit(self, req: ServeRequest, tok: int) -> None:
        req.output.append(tok)
        if req.timing.first_token_t is None:
            req.timing.first_token_t = time.time()
        if self.stream is not None:
            self.stream(req.rid, tok)
        if self.eos_id is not None and tok == self.eos_id:
            req.finish_reason = "eos"
        elif len(req.output) >= req.max_new:
            req.finish_reason = "max_new"
        elif len(req.prompt) + len(req.output) - 1 >= self.max_len:
            # the next decode would write KV past the cache ceiling —
            # report it instead of silently truncating
            req.finish_reason = "max_len"

    def _retire(self, slot: int, store: bool = True) -> None:
        with jax.profiler.TraceAnnotation("serve.retire"):
            req = self.sched.release(slot)
            req.timing.done_t = time.time()
            if self.prefix is not None:
                if store:
                    self._store_pages(slot, req)
                if self._slot_chain[slot]:
                    self.prefix.release(self._slot_chain[slot])
                self._slot_chain[slot] = ()
            self._slot_req[slot] = None
            self._tok[slot] = 0
            self._pos[slot] = 0     # park: writes land at pos 0 of a dead
            #                         row and are overwritten by the next
            #                         admission
            self.completed.append(req)

    def _store_pages(self, slot: int, req: ServeRequest) -> None:
        """Publish this request's KV to the prefix cache. KV exists for
        positions [0, plen + m - 1): the prompt plus every generated token
        that was fed back (the last sampled token never was), so the
        cacheable key is prompt ++ output[:-1]."""
        seq = req.prompt if not req.output else np.concatenate(
            [req.prompt, np.asarray(req.output[:-1], np.int32)])
        with jax.profiler.TraceAnnotation("serve.publish"):
            new = self.prefix.insert(seq)   # radix insert and evictions
        if new:
            with jax.profiler.TraceAnnotation("serve.store_pages"):
                self.pages = TLM.store_pages(
                    self.pages, self.pool, slot,
                    [p for p, _ in new], [i for _, i in new])
                if self.mesh is not None:
                    # keep the store's head/page sharding pinned (the eager
                    # scatter above follows GSPMD propagation, not our
                    # layout)
                    self.pages = jax.device_put(self.pages,
                                                self._pages_shardings)

    # ---- the serving loop ------------------------------------------------
    def _spec_eligible(self, active: List[int]) -> bool:
        """A spec pass needs every active slot's K window positions in
        bounds (position writes are structural — a row cannot opt out of
        the batched verify), and at least one request that wants drafts.
        Near the cache ceiling the engine falls back to plain steps; the
        acceptance contract is interleaving-independent, so mixing pass
        kinds never changes the served tokens."""
        if self.speculator is None:
            return False
        k = self.speculator.spec.k
        if any(self._pos[s] + k > self.max_len for s in active):
            return False
        return any((self._slot_req[s].spec_k is None
                    or self._slot_req[s].spec_k > 0) for s in active)

    def step(self) -> bool:
        """Admit into free slots, then one decode step over the whole pool
        — a (slots, K) speculative verify pass when configured and in
        bounds, a (slots, 1) sequential step otherwise. Returns False once
        queue and pool are both empty.

        Each phase runs inside a ``jax.profiler.TraceAnnotation`` named
        ``serve.*`` (one per step, admission or retirement), so a profiler
        trace shows where the host time of a step went; with no profiler
        running each costs about a microsecond."""
        with jax.profiler.TraceAnnotation("serve.step"):
            self._admit()
            active = [s for s in range(self.slots) if self._slot_req[s]]
            if not active:
                return not self.sched.idle
            if self._spec_eligible(active):
                self._spec_step(active)
                return True
            if self.speculator is not None:
                # keep the draft pool on the true stream through the
                # fallback
                self.speculator.advance(self._tok, self._pos)
            with jax.profiler.TraceAnnotation("serve.decode"):
                logits, self.pool = self._decode(
                    self.params, self.pool, jnp.asarray(self._tok[:, None]),
                    jnp.asarray(self._pos))
            self.decode_steps += 1
            self.busy_slot_steps += len(active)
            with jax.profiler.TraceAnnotation("serve.logits"):
                rows = np.asarray(logits[:, 0])     # one host transfer
            with jax.profiler.TraceAnnotation("serve.sample"):
                for s in active:
                    req = self._slot_req[s]
                    self._pos[s] += 1
                    tok = sample_token(rows[s], req.sampling, req.rid,
                                       len(req.output))
                    self._emit(req, tok)
                    if req.finish_reason:
                        self._retire(s)
                    else:
                        self._tok[s] = tok
            return True

    def _spec_step(self, active: List[int]) -> None:
        """One draft-propose / target-verify / commit / rollback pass.

        Commits n in [1, K] tokens per active slot: emission j samples
        verify logits row j (bitwise equal to the j-th sequential
        decode's row) keyed by the committed-token counter, and continues
        while the emitted token equals the draft the next row was
        verified against. Rejected window positions are erased from both
        pools so every row ends bitwise identical to its
        sequential-decode state (docs/serving.md)."""
        spec = self.speculator
        k = spec.spec.k
        p0 = self._pos.copy()
        window = spec.propose(self._tok, self._pos)
        with jax.profiler.TraceAnnotation("serve.decode"):
            logits, self.pool = self._decode(
                self.params, self.pool, jnp.asarray(window),
                jnp.asarray(self._pos))
        self.decode_steps += 1
        self.busy_slot_steps += len(active)
        spec.metrics.passes += 1
        with jax.profiler.TraceAnnotation("serve.logits"):
            rows = np.asarray(logits)               # (slots, K, V)
        frontier = p0.copy()                        # rollback start/slot
        retired: List[int] = []
        with jax.profiler.TraceAnnotation("serve.sample"):
            for s in active:
                req = self._slot_req[s]
                cap = (k if req.spec_k is None
                       else 1 + min(max(req.spec_k, 0), k - 1))
                emitted = 0
                for j in range(cap):
                    tok = sample_token(rows[s, j], req.sampling, req.rid,
                                       len(req.output))
                    self._emit(req, tok)
                    emitted += 1
                    if req.finish_reason:
                        break
                    # continue only while the next verified row consumed
                    # this exact token (the draft proposal at window j+1)
                    if j + 1 >= cap or tok != window[s, j + 1]:
                        break
                spec.metrics.record(drafted=cap - 1, committed=emitted)
                frontier[s] = p0[s] + emitted
                if req.finish_reason:
                    retired.append(s)
                else:
                    self._tok[s] = req.output[-1]
                    self._pos[s] = p0[s] + emitted
        # un-commit rejected positions [frontier, p0 + K) in both pools.
        # Parked rows (frontier == p0 == 0 stays) collected junk at
        # [0, K) during the pass — erased the same way.
        stop = p0 + k
        self.pool = self._rollback(self.pool, jnp.asarray(frontier),
                                   jnp.asarray(stop))
        spec.rollback(frontier, stop)
        for s in retired:
            self._retire(s)

    def run(self) -> Dict:
        """Serve until the queue drains; returns the stats summary."""
        t0 = time.time()
        while self.step():
            pass
        return summarize(self.completed, time.time() - t0,
                         n_slots=self.slots, decode_steps=self.decode_steps,
                         busy_slot_steps=self.busy_slot_steps,
                         prefills=self.prefills, waves=self.sched.waves,
                         prefill_tokens=self.prefill_tokens,
                         prefix_hit_tokens=self.prefix_hit_tokens,
                         prefix_stats=(self.prefix.stats()
                                       if self.prefix else None),
                         spec=(self.speculator.metrics.summary()
                               if self.speculator else None))

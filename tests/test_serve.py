"""Continuous-batching engine: batching invariance, finish reasons,
scheduler properties, and the serve_loop right-padding regression.

The engine contract (docs/serving.md): a request's decoded tokens are
bitwise-identical whether it is served alone, in a full batch, or admitted
mid-decode into a reused slot — for every registered backend. The pieces
that make it true are each pinned here:

  * length-aware prefill (logits gathered at each row's true last token —
    the old serve_loop read the padded last column: the regression test's
    single-request oracles catch exactly that)
  * per-slot position vectors through nn/attention (global GQA, windowed
    ring buffers, and MLA caches all write+mask per row)
  * full-row cache copy at admission (zero KV leakage on slot reuse)
  * explicit finish reasons (eos | max_new | max_len — no silent
    truncation)
  * FIFO slot scheduler (property-tested: conservation, capacity, no
    starvation under random arrival orders)
  * paged prefix cache (property-tested bookkeeping: refcount
    conservation, no page aliasing, pinned chains never evicted, the
    candidate heap evicting exactly a whole-tree walk's victims in O(1)
    pops per page — and the engine-level contract: a cache-hit decode is
    bitwise equal to the cold-miss decode, per backend)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import registry
from repro.models import transformer_lm as TLM
from repro.quant import matmul as QM
from repro.quant.quantize import for_lm
from repro.serve import (Engine, FINISH_REASONS, PagePool, PrefixCache,
                         SamplingConfig, ServeRequest, SlotScheduler,
                         clear_compiled_fns, compiled_fns,
                         padded_prefill_ok, sample_token)
from repro.train.serve_loop import Request, Server

BACKENDS = list(QM.list_backends())
MAX_LEN = 32


@pytest.fixture(autouse=True)
def _release_executables():
    """Drop the compiled programs once the process nears its mapping limit.
    Each XLA:CPU executable holds its own memory mappings, a process may
    hold vm.max_map_count (65530 by default) of them, and this file's
    tests, run in one process, compile enough prefill/decode programs to
    reach that: XLA then crashes the process mid-compile."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:        # no procfs: nothing to watch
        return
    if n_maps > 40000:
        clear_compiled_fns()
        jax.clear_caches()


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = registry.reduced("smollm-135m", n_layers=2, d_model=64, n_heads=4,
                           n_kv_heads=2, d_ff=128, vocab=64, vocab_pad=64,
                           head_dim=16)
    params = TLM.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _oracle(cfg, params, prompt, max_new, max_len=MAX_LEN):
    """Hand-rolled single-request greedy decode: exact-length prefill,
    scalar positions — the reference the serving paths must reproduce."""
    caches = TLM.init_cache(cfg, 1, max_len, jnp.float32)
    logits, caches = TLM.prefill(params, jnp.asarray(prompt[None, :]), cfg,
                                 caches)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(out) < max_new and pos < max_len:
        logits, caches = TLM.decode_step(
            params, jnp.asarray([[out[-1]]], np.int32), jnp.int32(pos),
            cfg, caches)
        out.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return out


def _serve(cfg, params, reqs, *, slots=4, policy="continuous",
           max_len=MAX_LEN, eos_id=None):
    eng = Engine(cfg, params, slots=slots, max_len=max_len,
                 admission=policy, eos_id=eos_id)
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    return {r.rid: r for r in eng.completed}, stats


# ---------------------------------------------------------------------------
# Parity-matrix coverage: a registered backend must never ship unswept
# ---------------------------------------------------------------------------

def test_parity_matrix_covers_registry():
    """The token-parity sweeps below parametrize over BACKENDS, captured
    from `list_backends()` at import. Fails if the sweep list is ever
    frozen to a literal or a backend registers after collection — the
    regression that would let a backend skip batching-invariance and
    sharded-engine parity."""
    assert BACKENDS == list(QM.list_backends())
    for member in ("msr4", "drum6", "posneg"):   # the truncation family
        assert member in BACKENDS


def test_committed_serve_artifact_covers_registry():
    """experiments/eval/serve.json must carry a row for every registered
    backend (plus bf16): registering a backend without regenerating the
    serve artifact would silently drop it from the published parity
    table."""
    import json
    from pathlib import Path
    art = Path(__file__).resolve().parents[1] / "experiments/eval/serve.json"
    rows = json.loads(art.read_text())["tables"]["serve"]
    labels = {r["backend"] for r in rows}
    missing = ({"bf16", *QM.list_backends()}) - labels
    assert not missing, (f"serve artifact missing backends {sorted(missing)}"
                         " — regenerate with `python -m repro.eval run "
                         "--suite serve --smoke`")


# ---------------------------------------------------------------------------
# serve_loop regression: right-padding bug + finish reasons
# ---------------------------------------------------------------------------

def test_server_mixed_lengths_match_single_request_oracle(tiny_lm):
    # THE regression: the old Server right-padded prompts but read the
    # first decoded token from the last column, so every shorter prompt in
    # a mixed batch decoded from padding. Each request's single-request
    # oracle is the ground truth.
    cfg, params = tiny_lm
    lens = [3, 8, 5, 2]
    prompts = _prompts(cfg.vocab, lens, seed=1)
    srv = Server(cfg, params, batch_slots=4, max_len=MAX_LEN)
    for rid, p in enumerate(prompts):
        srv.submit(Request(rid=rid, prompt=p, max_new=6))
    stats = srv.run()
    assert stats["requests"] == 4 and stats["batches"] == 1
    for r in srv.completed:
        assert r.output == _oracle(cfg, params, prompts[r.rid], 6), \
            f"rid {r.rid} (plen {lens[r.rid]}) diverged from its oracle"
        assert r.finish_reason == "max_new"


def test_finish_reason_max_new(tiny_lm):
    cfg, params = tiny_lm
    done, _ = _serve(cfg, params,
                     [ServeRequest(rid=0, prompt=_prompts(cfg.vocab, [4])[0],
                                   max_new=3)])
    assert len(done[0].output) == 3
    assert done[0].finish_reason == "max_new"


def test_finish_reason_max_len_reports_truncation(tiny_lm):
    # old serve_loop: steps = min(max_new, max_len - plen - 1) silently
    # dropped tokens. Now the cap is explicit: a prompt of plen can emit at
    # most max_len - plen + 1 tokens and the request says why it stopped.
    cfg, params = tiny_lm
    plen, max_len = 10, 12
    done, _ = _serve(cfg, params,
                     [ServeRequest(rid=0,
                                   prompt=_prompts(cfg.vocab, [plen])[0],
                                   max_new=10)],
                     max_len=max_len)
    assert len(done[0].output) == max_len - plen + 1
    assert done[0].finish_reason == "max_len"
    # a prompt that cannot even prefill is rejected with the same reason
    done, _ = _serve(cfg, params,
                     [ServeRequest(rid=1,
                                   prompt=_prompts(cfg.vocab,
                                                   [max_len + 1])[0],
                                   max_new=4)],
                     max_len=max_len)
    assert done[1].output == [] and done[1].finish_reason == "max_len"


def test_finish_reason_eos_truncates_at_first_hit(tiny_lm):
    cfg, params = tiny_lm
    prompt = _prompts(cfg.vocab, [5], seed=3)[0]
    base, _ = _serve(cfg, params,
                     [ServeRequest(rid=0, prompt=prompt, max_new=8)])
    toks = base[0].output
    eos = toks[1] if len(toks) > 1 else toks[0]
    done, _ = _serve(cfg, params,
                     [ServeRequest(rid=0, prompt=prompt, max_new=8)],
                     eos_id=eos)
    assert done[0].finish_reason == "eos"
    assert done[0].output == toks[:toks.index(eos) + 1]


def test_every_completed_request_has_a_reason(tiny_lm):
    cfg, params = tiny_lm
    reqs = [ServeRequest(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(_prompts(cfg.vocab, [3, 6, 2], seed=4))]
    done, _ = _serve(cfg, params, reqs, slots=2)
    for r in done.values():
        assert r.finish_reason in FINISH_REASONS


# ---------------------------------------------------------------------------
# batching invariance: alone == full batch == admitted mid-decode, per backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["bf16"] + BACKENDS)
def test_batching_invariance_per_backend(tiny_lm, backend):
    cfg0, params = tiny_lm
    cfg = dataclasses.replace(cfg0, quant=for_lm(backend))
    prompts = _prompts(cfg.vocab, [3, 6, 4], seed=5)
    probe = ServeRequest(rid=9, prompt=prompts[2], max_new=4)

    def fresh(rid, i, max_new):
        return ServeRequest(rid=rid, prompt=prompts[i], max_new=max_new)

    # (a) alone on the same pool shape
    alone, _ = _serve(cfg, params, [fresh(9, 2, 4)], slots=2)
    # (b) in a full batch from step zero
    full, _ = _serve(cfg, params, [fresh(0, 0, 3), fresh(9, 2, 4)], slots=2)
    # (c) admitted mid-decode into a reused slot: two running requests,
    #     probe queued; it enters the slot freed by the shorter one
    mid, stats = _serve(cfg, params,
                        [fresh(0, 0, 2), fresh(1, 1, 5), fresh(9, 2, 4)],
                        slots=2)
    assert stats["waves"] >= 2, "probe was not admitted mid-decode"
    a, b, c = alone[9].output, full[9].output, mid[9].output
    assert a == b == c, (
        f"{backend}: alone={a} full={b} mid-decode={c} — continuous "
        f"batching changed this request's tokens")
    # oracle anchor (greedy reference decode, exact-length prefill)
    assert a == _oracle(cfg, params, prompts[2], 4), \
        f"{backend}: engine diverged from the reference decode"


def test_slot_reuse_has_no_kv_leakage(tiny_lm):
    # slots=1 forces the second request into the exact cache row the first
    # just used; equality with its solo serve proves the full-row copy
    # wiped the previous occupant
    cfg, params = tiny_lm
    p1, p2 = _prompts(cfg.vocab, [7, 4], seed=6)
    both, _ = _serve(cfg, params,
                     [ServeRequest(rid=0, prompt=p1, max_new=3),
                      ServeRequest(rid=1, prompt=p2, max_new=5)], slots=1)
    solo, _ = _serve(cfg, params,
                     [ServeRequest(rid=1, prompt=p2, max_new=5)], slots=1)
    assert both[1].output == solo[1].output


def test_sampled_requests_are_batching_invariant(tiny_lm):
    # sampling draws are keyed by (seed, rid, step), never by slot/batch
    cfg, params = tiny_lm
    scfg = SamplingConfig(kind="top_k", temperature=0.9, top_k=8, seed=7)
    prompts = _prompts(cfg.vocab, [3, 5], seed=7)
    alone, _ = _serve(cfg, params,
                      [ServeRequest(rid=1, prompt=prompts[1], max_new=6,
                                    sampling=scfg)], slots=2)
    both, _ = _serve(cfg, params,
                     [ServeRequest(rid=0, prompt=prompts[0], max_new=4,
                                   sampling=scfg),
                      ServeRequest(rid=1, prompt=prompts[1], max_new=6,
                                   sampling=scfg)], slots=2)
    assert alone[1].output == both[1].output


# ---------------------------------------------------------------------------
# per-slot position vectors at the model level (all cache layouts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "gemma3-27b",
                                  "deepseek-v2-236b"])
def test_vector_pos_decode_matches_scalar(arch):
    # the tentpole's model change: decode_step with a (B,) position vector
    # must equal per-row scalar decodes — bitwise for the global-GQA and
    # windowed ring-buffer cache layouts. MLA is exact-math-equal but not
    # bitwise across batch sizes: XLA reassociates the absorbed-space
    # einsum reductions differently at batch 1 vs 2 (observed ~2.5e-7),
    # independent of the position plumbing under test here.
    cfg = registry.reduced(arch, d_model=64, n_heads=4, d_ff=128, vocab=64,
                           vocab_pad=64, head_dim=16)
    params = TLM.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    plens = (3, 5)
    caches, toks = [], []
    for plen in plens:
        c = TLM.init_cache(cfg, 1, 16, jnp.float32)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, plen)),
                             jnp.int32)
        logits, c = TLM.prefill(params, prompt, cfg, c)
        caches.append(c)
        toks.append(int(jnp.argmax(logits[0, -1])))
    pool = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1),
                        caches[0], caches[1])
    lv, _ = TLM.decode_step(params, jnp.asarray([[toks[0]], [toks[1]]],
                                                jnp.int32),
                            jnp.asarray(plens, jnp.int32), cfg, pool)
    for i, plen in enumerate(plens):
        ls, _ = TLM.decode_step(params, jnp.asarray([[toks[i]]], jnp.int32),
                                jnp.int32(plen), cfg, caches[i])
        msg = (f"{arch}: row {i} (pos {plen}) diverged under "
               f"vector-pos decode")
        if arch == "deepseek-v2-236b":
            np.testing.assert_allclose(np.asarray(lv[i]), np.asarray(ls[0]),
                                       rtol=1e-4, atol=1e-5, err_msg=msg)
        else:
            np.testing.assert_array_equal(np.asarray(lv[i]),
                                          np.asarray(ls[0]), err_msg=msg)


def test_prefill_lengths_gathers_true_last_token(tiny_lm):
    cfg, params = tiny_lm
    prompts = _prompts(cfg.vocab, [3, 6], seed=8)
    padded = np.zeros((2, 6), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    caches = TLM.init_cache(cfg, 2, 16, jnp.float32)
    lg, _ = TLM.prefill(params, jnp.asarray(padded), cfg, caches,
                        lengths=jnp.asarray([3, 6], jnp.int32))
    for i, p in enumerate(prompts):
        c1 = TLM.init_cache(cfg, 1, 16, jnp.float32)
        ref, _ = TLM.prefill(params, jnp.asarray(p[None, :]), cfg, c1)
        np.testing.assert_array_equal(np.asarray(lg[i]), np.asarray(ref[0]))


def test_padded_prefill_gate():
    # recurrent states / ring buffers cannot absorb padded junk; the gate
    # routes those archs to exact-length prefill
    assert padded_prefill_ok(registry.reduced("smollm-135m"))
    assert padded_prefill_ok(registry.reduced("deepseek-v2-236b"))
    assert not padded_prefill_ok(registry.reduced("gemma3-27b"))
    assert not padded_prefill_ok(registry.reduced("rwkv6-3b"))
    assert not padded_prefill_ok(registry.reduced("hymba-1.5b"))


# ---------------------------------------------------------------------------
# scheduler properties (pure Python — no jax in the loop)
# ---------------------------------------------------------------------------

def _simulate(steps_list, n_slots, policy="continuous", late_split=0):
    """Drive the scheduler with a fake decode loop: each item needs
    `steps` decode steps. Returns (admit_order, done_order, max_running,
    drain_violations)."""
    sched = SlotScheduler(n_slots, policy)
    items = [{"rid": i, "left": s} for i, s in enumerate(steps_list)]
    early, late = items[:len(items) - late_split], \
        items[len(items) - late_split:]
    for it in early:
        sched.submit(it)
    admit_order, done = [], []
    max_running = 0
    drain_violations = 0
    guard = 0
    while not sched.idle or late:
        guard += 1
        assert guard < 10_000, "scheduler livelocked"
        if guard == 3 and late:          # mid-run arrivals
            for it in late:
                sched.submit(it)
            late = []
        before = sched.running
        batch = sched.admit()
        if batch and policy == "drain" and before > 0:
            drain_violations += 1
        admit_order.extend(it["rid"] for _, it in batch)
        max_running = max(max_running, sched.running)
        for slot in sorted(list(sched.occupied())):
            it = sched.item(slot)
            it["left"] -= 1
            if it["left"] <= 0:
                done.append(sched.release(slot)["rid"])
    return admit_order, done, max_running, drain_violations, sched


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 9), min_size=1, max_size=24),
       st.integers(1, 5))
def test_scheduler_conserves_and_never_exceeds_capacity(steps, n_slots):
    admit_order, done, max_running, _, sched = _simulate(steps, n_slots)
    # conservation: every submitted rid completes exactly once
    assert sorted(done) == list(range(len(steps)))
    assert sched.submitted == sched.completed == len(steps)
    # capacity: the pool never overflows
    assert max_running <= n_slots
    # no starvation: FIFO admission — arrival order is admission order
    assert admit_order == list(range(len(steps)))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(1, 9), min_size=2, max_size=16),
       st.integers(1, 4), st.integers(0, 5))
def test_scheduler_handles_mid_run_arrivals(steps, n_slots, late):
    late = min(late, len(steps) - 1)
    admit_order, done, max_running, _, sched = _simulate(
        steps, n_slots, late_split=late)
    assert sorted(done) == list(range(len(steps)))
    assert max_running <= n_slots
    assert admit_order == list(range(len(steps)))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(1, 6), min_size=1, max_size=16),
       st.integers(1, 4))
def test_drain_policy_only_admits_into_an_empty_pool(steps, n_slots):
    _, done, _, violations, _ = _simulate(steps, n_slots, policy="drain")
    assert violations == 0
    assert sorted(done) == list(range(len(steps)))


def test_scheduler_rejects_bad_args():
    with pytest.raises(ValueError, match="policy"):
        SlotScheduler(2, "round_robin")
    with pytest.raises(ValueError, match="n_slots"):
        SlotScheduler(0)


# ---------------------------------------------------------------------------
# engine metrics
# ---------------------------------------------------------------------------

def test_engine_stats_are_sane(tiny_lm):
    cfg, params = tiny_lm
    reqs = [ServeRequest(rid=i, prompt=p, max_new=4)
            for i, p in enumerate(_prompts(cfg.vocab, [3, 5, 4, 6, 2],
                                           seed=9))]
    done, stats = _serve(cfg, params, reqs, slots=2)
    assert stats["requests"] == 5 and stats["prefills"] == 5
    assert stats["new_tokens"] == sum(len(r.output) for r in done.values())
    assert 0.0 < stats["occupancy"] <= 1.0
    assert stats["tok_per_s"] > 0
    assert stats["waves"] >= 2          # mid-decode admissions happened
    for r in done.values():
        assert r.timing.ttft_s is not None and r.timing.ttft_s >= 0
        assert r.timing.total_s >= r.timing.ttft_s


def test_resubmitting_a_request_object_starts_fresh(tiny_lm):
    # submit() resets engine-owned state (output/finish_reason/timing), so
    # reusing one request object across runs — which the historical Server
    # supported — cannot accumulate stale tokens
    cfg, params = tiny_lm
    req = ServeRequest(rid=0, prompt=_prompts(cfg.vocab, [4], seed=10)[0],
                       max_new=3)
    first, _ = _serve(cfg, params, [req], slots=1)
    toks = list(first[0].output)
    second, _ = _serve(cfg, params, [req], slots=1)
    assert second[0].output == toks
    assert second[0].finish_reason == "max_new"


def test_engine_rejects_empty_prompt(tiny_lm):
    cfg, params = tiny_lm
    eng = Engine(cfg, params, slots=1, max_len=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(ServeRequest(rid=0, prompt=np.zeros(0, np.int32)))


# ---------------------------------------------------------------------------
# paged KV pool bookkeeping (pure Python — no jax in the loop)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2), min_size=1, max_size=60),
       st.integers(1, 8))
def test_page_pool_conserves_pages(ops, n_pages):
    # random alloc/incref/decref walk; at every step the ledger balances
    pool = PagePool(n_pages)
    held = []                     # one entry per reference we hold
    for op in ops:
        if op == 0:
            p = pool.alloc()
            if p is not None:
                assert p not in held, "alloc handed out a live page"
                held.append(p)
        elif op == 1 and held:
            pool.incref(held[0])
            held.append(held[0])
        elif op == 2 and held:
            pool.decref(held.pop())
        live = pool.live
        # conservation: every page is either free or live, never both/lost
        assert pool.n_free + len(live) == n_pages
        assert sorted(set(held)) == live
        for p in set(held):
            assert pool.refcount(p) == held.count(p)


def test_page_pool_rejects_use_of_free_pages():
    pool = PagePool(2)
    p = pool.alloc()
    pool.decref(p)
    with pytest.raises(RuntimeError, match="decref on free"):
        pool.decref(p)
    with pytest.raises(RuntimeError, match="incref on free"):
        pool.incref(p)
    with pytest.raises(ValueError, match="n_pages"):
        PagePool(0)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.lists(st.integers(0, 3), min_size=1, max_size=12),
                min_size=1, max_size=8),
       st.integers(1, 3))
def test_prefix_cache_no_aliasing_and_conservation(seqs, page_size):
    # drive the admission lifecycle (match -> acquire -> insert -> release)
    # over random token streams from a tiny alphabet (maximal prefix
    # overlap); the radix tree must never alias a page between two nodes
    # nor leak one
    cache = PrefixCache(page_size, n_pages=16)
    for seq in seqs:
        chain = cache.match(seq)
        assert len(chain) * page_size <= len(seq)
        cache.acquire(chain)
        cache.insert(seq)
        cache.release(chain)
        pages = cache.pages()
        assert len(pages) == len(set(pages)), "page aliased between nodes"
        assert len(pages) + cache.pool.n_free == 16, "page leaked"
        # with no request in flight the tree holds exactly one ref per page
        assert all(cache.pool.refcount(p) == 1 for p in pages)


def test_prefix_cache_longest_match_is_full_pages_only():
    cache = PrefixCache(2, 8)
    cache.insert([1, 2, 3, 4, 5, 6])
    assert len(cache.match([1, 2, 3, 4, 9, 9])) == 2   # diverges at page 3
    assert len(cache.match([1, 2])) == 1
    assert cache.match([9, 9]) == []
    assert len(cache.match([1, 2, 3])) == 1            # partial page: no match
    # matching twice returns the same chain (stable page ids)
    assert cache.match([1, 2, 3, 4]) == cache.match([1, 2, 3, 4])


def test_prefix_cache_eviction_spares_pinned_chains():
    cache = PrefixCache(1, 4)
    cache.insert([1, 2])
    chain = cache.match([1, 2])
    cache.acquire(chain)              # a live request pins the chain
    new = cache.insert([7, 8, 9])     # wants 3 pages; only 2 free
    assert len(new) == 2, "insert must stop early when nothing is evictable"
    assert cache.match([1, 2]) == chain, "pinned chain was evicted"
    assert [cache.pool.refcount(p) for p in chain] == [2, 2]
    cache.release(chain)
    # unpinned leaves are now fair game: LRU eviction frees room
    assert len(cache.insert([5, 5, 5])) == 3
    assert cache.evictions >= 3
    # the ledger still balances after evictions
    assert len(cache.pages()) + cache.pool.n_free == 4


class _WalkLRUCache:
    """The prefix cache's contract written the plain way: a radix tree of
    dict nodes whose eviction walks every node for the least-recently-used
    leaf held by the tree alone. PrefixCache must pick the same victims."""

    def __init__(self, page_size, n_pages):
        self.page_size = page_size
        self.pool = PagePool(n_pages)
        self.root = {}              # chunk -> [page, last_used, children]
        self.clock = 0
        self.evictions = 0

    def _chunks(self, tokens):
        ps = self.page_size
        return [tuple(tokens[i:i + ps])
                for i in range(0, len(tokens) - len(tokens) % ps, ps)]

    def _tick(self):
        self.clock += 1
        return self.clock

    def _walk(self):
        stack = [(self.root, c, n) for c, n in self.root.items()]
        while stack:
            parent, chunk, node = stack.pop()
            yield parent, chunk, node
            stack.extend((node[2], c, n) for c, n in node[2].items())

    def pages(self):
        return sorted(node[0] for _, _, node in self._walk())

    def match(self, tokens):
        chain, level = [], self.root
        for chunk in self._chunks(tokens):
            node = level.get(chunk)
            if node is None:
                break
            node[1] = self._tick()
            chain.append(node[0])
            level = node[2]
        return chain

    def insert(self, tokens):
        new, pinned, level = [], [], self.root
        for idx, chunk in enumerate(self._chunks(tokens)):
            node = level.get(chunk)
            if node is None:
                page = self.pool.alloc()
                while page is None and self._evict_one():
                    page = self.pool.alloc()
                if page is None:
                    break
                node = level[chunk] = [page, self._tick(), {}]
                new.append((page, idx))
            else:
                node[1] = self._tick()
            self.pool.incref(node[0])
            pinned.append(node[0])
            level = node[2]
        for page in pinned:
            self.pool.decref(page)
        return new

    def _evict_one(self):
        victim = None
        for parent, chunk, node in self._walk():
            if node[2] or self.pool.refcount(node[0]) != 1:
                continue
            if victim is None or node[1] < victim[2][1]:
                victim = (parent, chunk, node)
        if victim is None:
            return False
        parent, chunk, node = victim
        del parent[chunk]
        self.pool.decref(node[0])
        self.evictions += 1
        return True


@settings(max_examples=200, deadline=None)
@given(st.lists(st.lists(st.integers(0, 3), min_size=1, max_size=10),
                min_size=20, max_size=60),
       st.integers(1, 3), st.integers(1, 6), st.integers(0, 1))
def test_prefix_cache_evicts_the_walks_victims(steps, page_size, n_pages,
                                               rebuild_often):
    # random match / acquire / insert / release orders over a tiny alphabet
    # (deep shared prefixes), with pins held across inserts; a step's first
    # draw picks the operation, the rest are its tokens. With no slack the
    # candidate heap is rebuilt at nearly every push, so rebuilds are
    # checked too; with the default slack stale entries pile up.
    cache = PrefixCache(page_size, n_pages)
    if rebuild_often:
        cache._HEAP_SLACK = 0
    oracle = _WalkLRUCache(page_size, n_pages)
    pins = []
    for op, *toks in steps:
        if op in (0, 3):                 # match, and pin unless op 3
            chain = cache.match(toks)
            assert chain == oracle.match(toks)
            if chain and op == 0:
                cache.acquire(chain)
                for page in chain:
                    oracle.pool.incref(page)
                pins.append(chain)
        elif op == 1 and pins:           # release one of the pins
            chain = pins.pop(len(toks) % len(pins))
            cache.release(chain)
            for page in chain:
                oracle.pool.decref(page)
        else:
            assert cache.insert(toks) == oracle.insert(toks)
        assert cache.pages() == oracle.pages()
        assert cache.n_nodes == len(oracle.pages())
        assert cache.evictions == oracle.evictions
        assert [cache.pool.refcount(p) for p in range(n_pages)] == \
            [oracle.pool.refcount(p) for p in range(n_pages)]
        assert len(cache._heap) <= 2 * cache.n_nodes + cache._HEAP_SLACK


def test_prefix_cache_eviction_pops_a_constant_per_page():
    # the benchmark's store: 16384 pages of 8 tokens filled with 32-page
    # chains, so that each fresh 256-token sequence evicts 32 pages; the
    # candidate heap must find each victim in O(1) pops, not a tree walk
    rng = np.random.default_rng(0)
    cache = PrefixCache(8, 16384)
    while cache.pool.n_free:
        cache.insert(rng.integers(1, 49152, 32 * 8))
    assert cache.evictions == 0 and cache.n_nodes == 16384
    bound = 2 * cache.n_nodes + cache._HEAP_SLACK
    for _ in range(20):
        seq = rng.integers(1, 49152, 256)
        assert len(cache.insert(seq)) == 32
        assert len(cache.match(seq)) == 32      # a hit re-keys the leaf
        assert len(cache._heap) <= bound
    assert cache.evictions == 20 * 32
    assert cache.stale_pops <= cache.evictions
    assert cache.stats()["stale_pops"] == cache.stale_pops
    assert len(cache.pages()) + cache.pool.n_free == 16384


# ---------------------------------------------------------------------------
# prefix cache at the engine level: hit == cold miss, bitwise, per backend
# ---------------------------------------------------------------------------

def _shared_prompts(vocab, seed, suffixes=(4, 3, 5)):
    """Prompts sharing an 8-token prefix (2 pages at page_size=4)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, 8).astype(np.int32)
    return [np.concatenate([shared,
                            rng.integers(0, vocab, n).astype(np.int32)])
            for n in suffixes]


@pytest.mark.parametrize("backend", ["bf16"] + BACKENDS)
def test_prefix_hit_equals_cold_miss_per_backend(tiny_lm, backend):
    # THE paging contract: after request A retires and publishes the shared
    # prefix, request B's admission gathers those pages instead of
    # prefilling them — and decodes the exact same tokens as a cold engine
    # that prefills everything. KV at position i is a pure function of
    # tokens 0..i (per-token act scales, position-masked attention), so the
    # gathered pages are bitwise what the cold prefill would have written.
    cfg0, params = tiny_lm
    cfg = dataclasses.replace(cfg0, quant=for_lm(backend))
    pa, pb, _ = _shared_prompts(cfg.vocab, seed=21)

    warm = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4)
    warm.submit(ServeRequest(rid=0, prompt=pa, max_new=4))
    warm.run()                        # retires A, publishes its pages
    warm.submit(ServeRequest(rid=1, prompt=pb, max_new=5))
    warm.run()
    assert warm.prefix_hit_tokens >= 8, "request B missed the shared prefix"
    hit = next(r for r in warm.completed if r.rid == 1).output

    cold = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4)
    cold.submit(ServeRequest(rid=1, prompt=pb, max_new=5))
    cold.run()
    assert cold.prefix_hit_tokens == 0
    miss = cold.completed[0].output

    off = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                 prefix_caching=False)
    off.submit(ServeRequest(rid=1, prompt=pb, max_new=5))
    off.run()
    assert hit == miss == off.completed[0].output, (
        f"{backend}: hit={hit} miss={miss} unpaged={off.completed[0].output}"
        " — the prefix cache changed this request's tokens")
    assert hit == _oracle(cfg, params, pb, 5), \
        f"{backend}: paged engine diverged from the reference decode"


def test_mid_decode_admission_on_cache_hit_matches_solo(tiny_lm):
    # the probe queues behind a full pool, is admitted mid-decode into a
    # reused slot AND lands on a prefix-cache hit (the first retiree
    # published the shared pages) — still bitwise equal to its solo serve
    cfg, params = tiny_lm
    p0, p1, probe = _shared_prompts(cfg.vocab, seed=22)

    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4)
    for rid, (p, m) in enumerate([(p0, 2), (p1, 6), (probe, 4)]):
        eng.submit(ServeRequest(rid=rid, prompt=p, max_new=m))
    stats = eng.run()
    assert stats["waves"] >= 2, "probe was not admitted mid-decode"
    assert eng.prefix_hit_tokens >= 8, "probe admission was not a cache hit"
    mid = next(r for r in eng.completed if r.rid == 2).output

    solo = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4)
    solo.submit(ServeRequest(rid=2, prompt=probe, max_new=4))
    solo.run()
    assert mid == solo.completed[0].output


def test_prefix_cache_survives_slot_reuse_without_leakage(tiny_lm):
    # slots=1: every request reuses the same slot row; published pages must
    # come from each request's own KV, not the previous occupant's
    cfg, params = tiny_lm
    pa, pb, pc = _shared_prompts(cfg.vocab, seed=23)
    eng = Engine(cfg, params, slots=1, max_len=MAX_LEN, page_size=4)
    for rid, p in enumerate([pa, pb, pc]):
        eng.submit(ServeRequest(rid=rid, prompt=p, max_new=3))
    eng.run()
    for rid, p in [(1, pb), (2, pc)]:
        solo = Engine(cfg, params, slots=1, max_len=MAX_LEN,
                      prefix_caching=False)
        solo.submit(ServeRequest(rid=rid, prompt=p, max_new=3))
        solo.run()
        assert next(r for r in eng.completed if r.rid == rid).output \
            == solo.completed[0].output


def test_prefix_cache_gating(tiny_lm):
    cfg, params = tiny_lm
    assert Engine(cfg, params, slots=1, max_len=16).prefix is not None
    assert Engine(cfg, params, slots=1, max_len=16,
                  prefix_caching=False).prefix is None
    # a page never fits: paging disables itself instead of crashing
    assert Engine(cfg, params, slots=1, max_len=4,
                  page_size=8).prefix is None
    # windowed/SSM cache layouts have no per-position KV to page (same
    # predicate as padded prefill; rwkv/hymba covered by
    # test_padded_prefill_gate)
    gcfg = registry.reduced("gemma3-27b", d_model=64, n_heads=4, d_ff=128,
                            vocab=64, vocab_pad=64, head_dim=16)
    gparams = TLM.init(gcfg, jax.random.PRNGKey(0))
    assert Engine(gcfg, gparams, slots=1, max_len=16).prefix is None


@pytest.mark.parametrize("spec_k", [0, 3])
def test_bf16_params_serve_with_default_cache_dtype(spec_k):
    # regression: the cache dtype used to default to float32 whatever the
    # params were, and a bf16-param model then failed to trace (the layer
    # scan's bf16 carry came back float32 from attention over an f32
    # cache). The default now follows the params. spec_k=3 adds the
    # speculative verify + rollback_positions path over the bf16 pool.
    from repro.serve import SpecConfig
    cfg = registry.reduced("smollm-135m", n_layers=2, d_model=64, n_heads=4,
                           n_kv_heads=2, d_ff=128, vocab=64, vocab_pad=64,
                           head_dim=16, param_dtype=jnp.bfloat16)
    params = TLM.init(cfg, jax.random.PRNGKey(0))
    pa, pb, pc = _shared_prompts(cfg.vocab, seed=24)
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4,
                 spec=SpecConfig(k=spec_k) if spec_k else None)
    assert {x.dtype for x in jax.tree.leaves(eng.pool)} == {jnp.dtype(jnp.bfloat16)}
    eng.submit(ServeRequest(rid=0, prompt=pa, max_new=4))
    eng.run()                         # retires A, publishes its pages
    eng.submit(ServeRequest(rid=1, prompt=pb, max_new=5))
    eng.submit(ServeRequest(rid=2, prompt=pc, max_new=3))
    eng.run()
    assert eng.prefix_hit_tokens >= 16, "B and C missed the shared prefix"
    done = {r.rid: r for r in eng.completed}
    assert sorted(done) == [0, 1, 2]
    for rid, max_new in [(0, 4), (1, 5), (2, 3)]:
        assert done[rid].finish_reason == "max_new"
        assert len(done[rid].output) == max_new


# ---------------------------------------------------------------------------
# serving-path regressions: eval sweep, sampling, compiled-fn cache
# ---------------------------------------------------------------------------

def test_parity_handles_empty_outputs():
    # regression: an engine run that produced no tokens used to divide by
    # zero in the serve suite's parity metric
    from repro.eval.serve import _parity
    assert _parity({}, {}) == (0.0, 0.0)
    assert _parity({0: []}, {0: []}) == (0.0, 0.0)
    assert _parity({0: [1, 2]}, {})[0] == 0.0
    assert _parity({0: [1, 2, 9]}, {0: [1, 2, 3]}) == (pytest.approx(200 / 3),
                                                       2.0)


def test_serve_suite_survives_non_bf16_first_sweep(monkeypatch):
    # regression: the suite runner assumed sweep_points yields bf16 first
    # and crashed in _parity(outs, None) otherwise; the bf16 reference is
    # now computed explicitly before the loop
    import repro.eval.runners as runners
    from repro.eval import serve as SERVE
    monkeypatch.setattr(
        runners, "sweep_points",
        lambda variants=True: [("int8_exact", "int8_exact", "proposed")])
    art = SERVE.run(smoke=True, seed=0)
    rows = art["tables"]["serve"]
    assert [r["backend"] for r in rows] == ["int8_exact"]
    assert rows[0]["solo_match"] is True
    assert 0.0 <= rows[0]["hit_rate"] <= 1.0
    assert 0.0 <= rows[0]["match_bf16"] <= 100.0


def test_top_k_samples_at_most_k_candidates():
    # regression: the old threshold keep (scaled >= kth value) admitted
    # every logit tied at the k-th place; lax.top_k keeps exactly k,
    # breaking ties by index
    logits = jnp.asarray([5.0, 5.0, 5.0, 0.0])
    scfg = SamplingConfig(kind="top_k", temperature=1.0, top_k=2, seed=0)
    draws = {sample_token(logits, scfg, rid=0, step=s) for s in range(40)}
    assert draws <= {0, 1}, f"drew outside the top-2 set: {draws}"
    assert draws == {0, 1}, "a kept candidate became unreachable"


def test_sampling_rejects_nonpositive_temperature():
    # regression: temperature <= 0 used to clamp to 1e-6 and silently
    # become near-argmax sampling
    for kind in ("temperature", "top_k"):
        for temp in (0.0, -1.0):
            with pytest.raises(ValueError, match="temperature"):
                SamplingConfig(kind=kind, temperature=temp, top_k=4)
    SamplingConfig(kind="greedy", temperature=0.0)   # greedy ignores it


def test_compiled_fns_cache_is_bounded_and_clearable(tiny_lm):
    # regression: the jit cache was an unbounded lru_cache — an eval sweep
    # over every backend x variant pinned every executable for the process
    # lifetime with no way to drop them
    cfg, params = tiny_lm
    assert compiled_fns.cache_info().maxsize is not None
    Engine(cfg, params, slots=1, max_len=8)
    assert compiled_fns.cache_info().currsize >= 1
    clear_compiled_fns()
    assert compiled_fns.cache_info().currsize == 0


# ---------------------------------------------------------------------------
# Engine over a mesh: sharded serving is bitwise single-device serving
# ---------------------------------------------------------------------------
#
# The Engine(mesh=...) contract (docs/sharding.md): params FSDP/TP-sharded,
# KV pool + page store sharded (slots over 'data', KV heads over 'model'),
# every decoded token bitwise identical to the single-device engine — per
# backend, through prefill, decode, mid-decode admission into a reused
# slot, and prefix-cache hits. One scenario exercises all four at once.

from jax.sharding import PartitionSpec  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.parallel.sharding import DEFAULT_RULES  # noqa: E402
from repro.serve import mesh_compiled_fns  # noqa: E402


@pytest.fixture(scope="module")
def serve_mesh():
    m = make_serving_mesh()
    if m.devices.size < 2:
        pytest.skip("sharded serving parity needs >1 device "
                    "(conftest forces 8 host devices)")
    return m


def _run_scenario(cfg, params, prompts, mesh):
    """slots=2, three prompts sharing an 8-token prefix: request 2 queues
    behind a full pool, is admitted mid-decode into the slot freed by
    request 0, and lands on the prefix pages request 0 published."""
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN, page_size=4,
                 mesh=mesh)
    for rid, (p, m) in enumerate(zip(prompts, (2, 6, 4))):
        eng.submit(ServeRequest(rid=rid, prompt=p, max_new=m))
    stats = eng.run()
    assert stats["waves"] >= 2, "probe was not admitted mid-decode"
    assert eng.prefix_hit_tokens >= 8, "probe admission missed the prefix"
    return {r.rid: r.output for r in eng.completed}, eng


@pytest.mark.parametrize("backend", ["bf16"] + BACKENDS)
def test_sharded_engine_matches_single_device(tiny_lm, serve_mesh, backend):
    cfg0, params = tiny_lm
    cfg = dataclasses.replace(cfg0, quant=for_lm(backend))
    prompts = _shared_prompts(cfg.vocab, seed=31)
    ref, _ = _run_scenario(cfg, params, prompts, None)
    out, eng = _run_scenario(cfg, params, prompts, serve_mesh)
    assert out == ref, (
        f"{backend}: sharded={out} single-device={ref} — the mesh changed "
        "decoded tokens (prefill/decode/mid-admission/cache-hit scenario)")
    # anchor the whole chain to the hand-rolled reference decode
    assert out[1] == _oracle(cfg, params, prompts[1], 6), \
        f"{backend}: sharded engine diverged from the reference decode"


def test_sharded_engine_storage_is_sharded(tiny_lm, serve_mesh):
    cfg, params = tiny_lm
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN, mesh=serve_mesh)
    # params: at least the MLP/attention projections are model-sharded and
    # the stacked layer dim keeps FSDP on 'data' where it divides
    specs = {s.spec for s in jax.tree.leaves(
        jax.tree.map(lambda x: x.sharding, eng.params))}
    assert any("model" in str(s) for s in specs), specs
    # pool: slot rows over 'data' (slots=2 divides the data axis)
    kv = eng.pool["blocks"][0]["k0_self"]["k"]
    assert kv.sharding.spec[1] == "data", kv.sharding.spec
    # page store exists and is pinned to its own sharding tree
    assert eng.pages is not None and eng._pages_shardings is not None


def test_sharded_compiled_fns_parity(tiny_lm, serve_mesh):
    # below the Engine: the mesh prefill/decode pair reproduces the
    # single-device compiled pair — cache trees bitwise, logits ulp-close
    # and token-identical (see inline notes)
    cfg0, params = tiny_lm
    for backend in ("int8_exact", "approx_deficit_pallas", "approx_rank1"):
        cfg = dataclasses.replace(cfg0, quant=for_lm(backend))
        pre_m, dec_m, sh = mesh_compiled_fns(cfg, DEFAULT_RULES, serve_mesh,
                                             2, MAX_LEN, jnp.float32)
        pre_s, dec_s = compiled_fns(cfg, DEFAULT_RULES)
        toks = jnp.asarray(_prompts(cfg.vocab, [8], seed=33)[0][None, :])
        lens = jnp.asarray([8], jnp.int32)
        one = TLM.init_cache(cfg, 1, MAX_LEN, jnp.float32)
        lg_s, c_s = pre_s(params, toks, one, lens, jnp.int32(0))
        lg_m, c_m = pre_m(jax.device_put(params, sh["params"]), toks, one,
                          lens, jnp.int32(0))
        # cache bitwise; logits ulp-close + argmax-identical (XLA fuses
        # the float epilogue differently inside the shard_map program —
        # see the decode note below)
        np.testing.assert_allclose(np.asarray(lg_m), np.asarray(lg_s),
                                   atol=1e-6, rtol=0, err_msg=backend)
        assert (np.argmax(np.asarray(lg_m), -1)
                == np.argmax(np.asarray(lg_s), -1)).all(), backend
        for a, b in zip(jax.tree.leaves(c_m), jax.tree.leaves(c_s)):
            assert (np.asarray(a) == np.asarray(b)).all(), backend
        # decode: the mesh shards slots over 'data' (1 row per device
        # group here), so the reference is the solo B=1 decode of each
        # slot row. The CACHE evolution is bitwise — every write goes
        # through the quantized matmul layer (bitwise by construction,
        # test_sharded_backends) and per-slot position indexing. Float
        # LOGITS are only ulp-close: XLA fuses the decode differently
        # inside the shard_map program (the surrounding all-gathers change
        # fusion decisions), reassociating the final float reductions.
        # The contract the Engine serves on is token-level (argmax), the
        # PR 4 batching-invariance contract, asserted exactly.
        pool_s = jax.tree.map(
            lambda one_leaf: jnp.concatenate([one_leaf, one_leaf], axis=1),
            c_s)
        tok = jnp.asarray([[3], [5]], jnp.int32)
        pos = jnp.asarray([8, 8], jnp.int32)
        dlg_m, dc_m = dec_m(jax.device_put(params, sh["params"]),
                            jax.device_put(pool_s, sh["pool"]), tok, pos)
        for s in range(2):
            row = jax.tree.map(lambda leaf: leaf[:, s:s + 1], pool_s)
            rlg, rc = dec_s(params, row, tok[s:s + 1], pos[s:s + 1])
            np.testing.assert_allclose(np.asarray(dlg_m[s]),
                                       np.asarray(rlg[0]), atol=1e-6,
                                       rtol=0, err_msg=f"{backend} {s}")
            assert (np.argmax(np.asarray(dlg_m[s]), -1)
                    == np.argmax(np.asarray(rlg[0]), -1)).all(), (backend, s)
            for a, b in zip(jax.tree.leaves(dc_m), jax.tree.leaves(rc)):
                assert (np.asarray(a[:, s]) == np.asarray(b[:, 0])).all(), \
                    (backend, s)
    clear_compiled_fns()


def test_one_device_mesh_serves_unsharded(tiny_lm):
    # a degenerate mesh adds nothing: the engine silently runs the plain
    # single-device path (and still decodes the same tokens)
    cfg, params = tiny_lm
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                 mesh=make_serving_mesh(shape=(1, 1)))
    assert eng.mesh is None and eng._pool_write is None


def test_sharded_engine_odd_slots_replicate(tiny_lm, serve_mesh):
    # slots=3 does not divide the data axis: the pool replicates over
    # 'data' instead of sharding — decode still matches bitwise
    cfg0, params = tiny_lm
    cfg = dataclasses.replace(cfg0, quant=for_lm("approx_deficit"))
    prompts = _prompts(cfg.vocab, [3, 6, 4, 5], seed=35)
    reqs = lambda: [ServeRequest(rid=i, prompt=p, max_new=3)  # noqa: E731
                    for i, p in enumerate(prompts)]
    ref = Engine(cfg, params, slots=3, max_len=MAX_LEN)
    out = Engine(cfg, params, slots=3, max_len=MAX_LEN, mesh=serve_mesh)
    for eng in (ref, out):
        for r in reqs():
            eng.submit(r)
        eng.run()
    assert {r.rid: r.output for r in out.completed} \
        == {r.rid: r.output for r in ref.completed}


def test_clear_compiled_fns_drops_every_executable_cache(tiny_lm,
                                                         serve_mesh):
    # regression: clear_compiled_fns() must empty BOTH lru caches in one
    # hook — the single-device pairs, the mesh-wrapped shard_map pairs,
    # and (because a Speculator obtains its draft pair through the same
    # caches) the speculative executables. An earlier sketch cleared only
    # compiled_fns, leaving mesh executables pinned across eval sweeps.
    from repro.serve import SpecConfig, clear_compiled_fns, compiled_fns

    cfg, params = tiny_lm
    clear_compiled_fns()
    assert compiled_fns.cache_info().currsize == 0
    assert mesh_compiled_fns.cache_info().currsize == 0

    # populate all three users: plain engine, mesh engine, speculative
    # engine whose draft backend differs from the target
    Engine(cfg, params, slots=2, max_len=MAX_LEN)
    Engine(cfg, params, slots=2, max_len=MAX_LEN, mesh=serve_mesh)
    Engine(cfg, params, slots=2, max_len=MAX_LEN, mesh=serve_mesh,
           spec=SpecConfig(k=2, draft_backend="approx_stage1"))
    assert compiled_fns.cache_info().currsize >= 1
    # target + draft cfgs each hold a mesh entry
    assert mesh_compiled_fns.cache_info().currsize >= 2

    clear_compiled_fns()
    assert compiled_fns.cache_info().currsize == 0, \
        "single-device executables survived clear_compiled_fns()"
    assert mesh_compiled_fns.cache_info().currsize == 0, \
        "mesh/speculative executables survived clear_compiled_fns()"

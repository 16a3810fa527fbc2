"""One benchmark cell: build, warm, bring to steady state, measure, check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives it: ``configs/<config>.json``, ``traffic/<mix>.json`` (whose
``kind`` names ``gen/<kind>.py``) and ``metrics/<metric>.py``.

The window drives ``repro.serve.Engine`` through ``submit`` and ``step``
only; the program gets the generated prompts and the benchmark-made
weights, nothing else.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import inspect
import json
import math
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_S = 10.0          # longest traced stretch of a --trace 1 window
INDEX_CHAIN_PAGES = 32  # pages per synthetic chain that fills the page index
WARM_THREADS = 8        # threads that build the small eager programs
STORE_THREADS = 2       # ... of them running store_pages (a store copy each)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (generators and readers)."""
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # configs/<config>.json
    mix: dict           # traffic/<mix>.json
    end_to_end: list    # BENCHMARK.json entries reported with --trace 0
    per_layer: list     # ... with --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {list(cells)}")
    w = cells[name]
    return Cell(name=name, chips=w["chips"],
                conf=load_json(BENCH / "configs" / f"{w['config']}.json"),
                mix=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file, checked against
    the file's sizes."""
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.nn import layers as L
    from repro.quant.quantize import for_lm
    q = conf["quant"]
    cfg = dataclasses.replace(
        registry.get(conf["registry"]),
        quant=for_lm(q["backend"], q.get("multiplier", "proposed")))
    want = {"d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
            "n_layers": conf["num_hidden_layers"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "vocab": conf["vocab_size"],
            "dh": conf["hidden_size"] // conf["num_attention_heads"],
            "rope_theta": conf["rope_theta"],
            "tied_embeddings": conf["tie_word_embeddings"],
            "param_dtype": jnp.dtype(conf["torch_dtype"])}
    got = {k: getattr(cfg, k) for k in want}
    # the program's RMSNorm takes its epsilon from its default argument
    want["rms_norm_eps"] = conf["rms_norm_eps"]
    got["rms_norm_eps"] = inspect.signature(
        L.rmsnorm).parameters["eps"].default
    if got != want:
        raise ValueError(f"registry {conf['registry']!r} differs from the "
                         f"configuration file: {got} != {want}")
    return cfg


def pow2_at_least(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


class CompileCounter:
    """Counts executables built (compiled, or loaded from the persistent
    cache) in this process. JAX keeps its listeners for the process's
    life, so there is one counter per process: ``CompileCounter.get()``."""
    _one = None

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def warm(engine, cfg, traffic, log):
    """Build every program the cell's traffic can reach, before the window:
    the prefill buckets (compiled ahead, not run), the decode step, and the
    eager admission and retirement ops at every page count the mix's
    requests use (``traffic.page_counts``), which is the same for every
    seed."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer_lm as TLM
    from repro.serve.engine import _write_slot
    max_len, ps = engine.max_len, engine.page_size
    fresh = TLM.init_cache(cfg, 1, max_len, engine._cache_dtype)
    lo = 8 if traffic.shares_prefix else pow2_at_least(traffic.min_prompt)
    buckets = [b for b in (8 << i for i in range(12))
               if lo <= b <= min(max_len, pow2_at_least(traffic.max_prompt))]
    for b in buckets:
        engine._prefill.lower(engine.params, jnp.zeros((1, b), jnp.int32),
                              fresh, jnp.asarray([1], jnp.int32),
                              jnp.int32(0)).compile()
    engine._decode.lower(engine.params, engine.pool,
                         jnp.zeros((engine.slots, 1), jnp.int32),
                         jnp.zeros((engine.slots,), jnp.int32)).compile()
    engine.pool = _write_slot(engine.pool, fresh, 0)
    if engine.prefix is not None:
        store, gather = traffic.page_counts(ps)
        # many small programs: build them from several threads at once;
        # each store_pages call returns a new page store (3 GB at the
        # cells' size), so few of those run together
        _run_all([functools.partial(TLM.store_pages, engine.pages,
                                    engine.pool, 0, list(range(n)),
                                    list(range(n)))
                  for n in sorted(store)], STORE_THREADS)
        _run_all([functools.partial(TLM.gather_pages, fresh, engine.pages,
                                    list(range(n)))
                  for n in sorted(gather)], WARM_THREADS)
    log(f"warm: prefill buckets {buckets}, decode ({engine.slots}, 1), "
        f"page counts {len(store)} stored, {len(gather)} gathered"
        if engine.prefix is not None else f"warm: prefill buckets {buckets}")


def _run_all(jobs, threads):
    """Run ``jobs`` on ``threads`` threads, keeping no result."""
    import jax

    def one(job):
        jax.block_until_ready(job())

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, jobs))


def fill_page_index(engine, rng):
    """Publish synthetic token chains until every page of the prefix store
    is taken, as in a server that has run for a while: from then on every
    retirement evicts, in set-up and window alike. The chains' tokens are
    drawn apart from the traffic's and never match a prompt; their pages'
    KV is never read."""
    prefix = engine.prefix
    if prefix is None:
        return 0
    n = 0
    while prefix.pool.n_free > 0:
        prefix.insert(rng.integers(1, engine.cfg.vocab,
                                   INDEX_CHAIN_PAGES * engine.page_size))
        n += 1
    return n


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of a traced run: the benchmark's own, around the calls
    it makes into the program, written into the profiler's trace too."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.steps = []          # (start, end) of Engine.step()
        self.calls = []          # (kind, start, end, padded tokens)

    def wrap(self, engine):
        import jax
        pre, dec = engine._prefill, engine._decode

        def prefill(params, toks, cache, lengths, off):
            with jax.profiler.TraceAnnotation("bench.prefill"):
                t = time.perf_counter()
                out = jax.block_until_ready(
                    pre(params, toks, cache, lengths, off))
                self.calls.append(("prefill", t, time.perf_counter(),
                                   toks.shape[1]))
            return out

        def decode(params, pool, tok, pos):
            with jax.profiler.TraceAnnotation("bench.decode"):
                t = time.perf_counter()
                out = jax.block_until_ready(dec(params, pool, tok, pos))
                self.calls.append(("decode", t, time.perf_counter(),
                                   tok.shape[0]))
            return out

        engine._prefill, engine._decode = prefill, decode


@dataclasses.dataclass
class Window:
    t0: float                # traffic start (perf_counter)
    w0: float = math.nan     # window open
    w1: float = math.nan     # window close
    builds: int = 0          # executables built inside the window
    counters0: dict = None
    counters1: dict = None
    trace_dir: str = None
    trace_s: float = 0.0
    lag_max_s: float = 0.0   # how late the generator submitted a request


COUNTERS = ("decode_steps", "busy_slot_steps", "prefills", "prefill_tokens",
            "prefix_hit_tokens")


def _counters(engine):
    return {k: getattr(engine, k) for k in COUNTERS}


def drive(engine, traffic, seconds, *, recs, counter, spans, trace_dir,
          log):
    """Run the traffic until the window (``seconds`` long, opened once the
    mix is in steady state) has closed. Returns the Window."""
    import jax
    from repro.serve.engine import ServeRequest
    win = Window(t0=time.perf_counter())
    done_seen = len(engine.completed)
    outstanding = 0
    tracing = False
    builds0 = 0
    while True:
        now = time.perf_counter()
        for r in traffic.poll(now - win.t0, outstanding):
            rid = len(recs)
            r.update(rid=rid, times=[], output=None, hit=None, done=None,
                     submit=now)
            recs.append(r)
            win.lag_max_s = max(win.lag_max_s, now - win.t0 - r["due"])
            engine.submit(ServeRequest(rid=rid, prompt=r["prompt"],
                                       max_new=r["max_new"]))
            outstanding += 1
        if math.isnan(win.w0) and (
                (traffic.open_loop and now - win.t0 >= traffic.warm_s)
                or (not traffic.open_loop and engine.decode_steps > 0)):
            jax.block_until_ready(engine.pool)
            win.w0 = time.perf_counter()
            win.w1 = win.w0 + seconds
            win.counters0 = _counters(engine)
            builds0 = counter.n
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                tracing = True
            log(f"window opens {win.w0 - win.t0:.3f} s into the traffic")
        if not math.isnan(win.w0):
            if tracing and now >= min(win.w1, win.w0 + TRACE_S):
                jax.block_until_ready(engine.pool)
                win.trace_s = time.perf_counter() - win.w0
                jax.profiler.stop_trace()
                tracing = False
            if now >= win.w1:
                break
        if engine.sched.idle:
            nxt = traffic.next_due()
            wait = (0.001 if nxt is None
                    else max(0.0, win.t0 + nxt - time.perf_counter()))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(min(wait, 0.05))
            continue
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        if spans.enabled:
            spans.steps.append((t, time.perf_counter()))
        for req in engine.completed[done_seen:]:
            r = recs[req.rid]
            r["output"] = list(req.output)
            r["finish"] = req.finish_reason
            r["done"] = time.perf_counter()
            outstanding -= 1
            traffic.on_finish(r, r["done"] - win.t0)
        done_seen = len(engine.completed)
    if tracing:
        jax.block_until_ready(engine.pool)
        win.trace_s = time.perf_counter() - win.w0
        jax.profiler.stop_trace()
    win.builds = counter.n - builds0
    win.counters1 = _counters(engine)
    win.trace_dir = trace_dir
    return win


def live_rows(engine):
    """Layer 0's cache as the timed path left it, for every request in a
    slot: its tokens fed so far (the prompt and every output token but the
    last), their positions, and the keys and values at them (numpy
    float32, (n, kv heads, head size))."""
    kv = engine.pool["blocks"][0]["k0_self"]
    k0, v0 = np.asarray(kv["k"][0]), np.asarray(kv["v"][0])
    toks, pos, ks, vs = [], [], [], []
    for slot, req in enumerate(engine._slot_req):
        if req is None:
            continue
        seq = np.concatenate([req.prompt,
                              np.asarray(req.output[:-1], np.int32)])
        toks.append(seq)
        pos.append(np.arange(len(seq)))
        ks.append(k0[slot, :len(seq)])
        vs.append(v0[slot, :len(seq)])
    return {"tokens": np.concatenate(toks).astype(np.int32),
            "pos": np.concatenate(pos).astype(np.int32),
            "k": np.concatenate(ks).astype(np.float32),
            "v": np.concatenate(vs).astype(np.float32)}


def serve_setup_requests(engine, traffic):
    """Serve what the mix needs in place before its traffic starts (its
    requests take negative ids, which the window's records leave out)."""
    from repro.serve.engine import ServeRequest
    reqs = traffic.setup_requests()
    for i, r in enumerate(reqs):
        engine.submit(ServeRequest(rid=-1 - i, prompt=r["prompt"],
                                   max_new=r["max_new"]))
    while reqs and engine.step():
        pass


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(recs, win, traffic, setup_s):
    w0, w1 = win.w0, win.w1
    emitted = sum(1 for r in recs for t in r["times"] if w0 <= t <= w1)
    gaps = [b - a for r in recs for a, b in zip(r["times"], r["times"][1:])
            if w0 <= b <= w1]
    out = {"out_tok_s": emitted / (w1 - w0),
           "itl_p95_ms": 1e3 * p95(gaps) if gaps else None,
           "setup_s": setup_s}
    if traffic.open_loop:
        ttft = []
        for r in recs:
            due = win.t0 + r["due"]
            if not w0 <= due <= w1:
                continue
            first = r["times"][0] if r["times"] else math.inf
            ttft.append((min(first, w1) - due))
        out["ttft_p95_ms"] = 1e3 * p95(ttft) if ttft else None
    return out, emitted


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the per-layer readers see."""
    win: Window
    spans: Spans
    trace: dict
    delta: dict
    emitted: int
    ops_per_token: int
    peak: dict


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs}; this benchmark runs "
                         "on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, log, require_tpu: bool = True,
             arch=None, trace_dir: str = None, fault=None,
             control: bool = False) -> dict:
    """Set up, measure and check one run; returns the result line.

    ``arch`` replaces the configuration's ArchConfig (the tests' reduced
    rehearsal); ``fault`` is called with the engine before the window to
    break the timed path (the tests of the check); ``control`` also reads
    the control on the same sample (``calibrate.py``, the tests)."""
    import jax
    device = device_info(cell.chips, require_tpu)
    counter = CompileCounter.get()
    builds_before = counter.n
    from repro.serve.engine import Engine
    import ops
    import weights as W
    import check

    conf, mix = cell.conf, cell.mix
    cfg = arch if arch is not None else arch_config(conf)
    recs = []
    last_hit = [0]

    def stream(rid, tok):
        if rid < 0:
            return
        r = recs[rid]
        r["times"].append(time.perf_counter())
        if r["hit"] is None:
            r["hit"] = engine.prefix_hit_tokens - last_hit[0]
            last_hit[0] = engine.prefix_hit_tokens

    params = W.make(conf, seed)
    engine = Engine(cfg, params, slots=conf["slots"], max_len=conf["max_len"],
                    page_size=conf["page_size"],
                    cache_pages=conf["cache_pages"], stream=stream)
    gen = load_module(BENCH / "gen" / f"{mix['kind']}.py")
    traffic = gen.Traffic(mix, seed, cfg.vocab, conf["slots"], seconds)
    warm(engine, cfg, traffic, log)
    n_chains = fill_page_index(engine, np.random.default_rng([seed, 2]))
    serve_setup_requests(engine, traffic)
    last_hit[0] = engine.prefix_hit_tokens
    log(f"set-up: {counter.n - builds_before} executables built, page index "
        f"filled with {n_chains} chains")
    spans = Spans(trace)
    if trace:
        spans.wrap(engine)
    if fault is not None:
        fault(engine)
    win = drive(engine, traffic, seconds, recs=recs, counter=counter,
                spans=spans, trace_dir=trace_dir if trace else None, log=log)
    setup_s = win.w0 - t_process
    log(f"executables built inside the window: {win.builds}")
    log(f"generator lag, most: {1e3 * win.lag_max_s:.3f} ms")
    e2e, emitted = end_to_end(recs, win, traffic, setup_s)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    result = {"correct": None, "attempted": 0, "failed": 0, "metrics": {},
              "device": device}
    due = [r for r in recs if win.w0 <= win.t0 + r["due"] <= win.w1]
    result["attempted"] = len(due)
    unstarted = sum(1 for r in due if not r["times"] or r["times"][0] > win.w1)
    log(f"requests due in the window: {len(due)}, not started by its close:"
        f" {unstarted}")
    result["load"] = {"due": len(due), "unstarted_at_close": unstarted,
                      "lag_max_s": win.lag_max_s,
                      "builds_in_window": win.builds}
    result["failed"] = sum(1 for r in recs if r.get("finish") not in
                           (None, "max_new"))
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        import trace as T
        red = T.reduce(T.load(T.find(win.trace_dir))) if win.trace_dir \
            else {}
        delta = {k: win.counters1[k] - win.counters0[k] for k in COUNTERS}
        run = Run(win=win, spans=spans, trace=red, delta=delta,
                  emitted=emitted, ops_per_token=ops.int8_ops_per_token(conf),
                  peak=load_json(BENCH / "peaks.json")[device["kind"]]
                  if device["platform"] == "tpu" else {"int8_ops": math.nan})
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in red["top_ops"]],
                "idle_gaps": [[n, s] for n, s in red["idle_by_span"]]}
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}

    # ---- correctness, after the window, with the program's state freed
    finished = [r for r in recs if r["done"] is not None
                and win.w0 <= r["done"] <= win.w1] or \
        [r for r in recs if r["done"] is not None]
    pick = check.choose(finished, seed, conf["check"]["served_tokens"],
                        conf["check"]["token_budget"])
    sample = [finished[i] for i in pick]
    live = live_rows(engine)
    del engine, params, traffic
    gc.collect()
    t = time.perf_counter()
    ref_weights = W.make(conf, seed)
    numbers, served, split = check.verify(ref_weights, conf, sample, live)
    log(f"check: {len(sample)} requests ({sum(1 for r in sample if r['hit'])}"
        f" on a prefix hit), {served} served tokens; layer 0's cache of "
        f"{len(live['tokens'])} tokens, keys {split['k']} and values "
        f"{split['v']} differing; {time.perf_counter() - t:.1f} s")
    result["correct"] = bool(sample) and all(
        v <= lim for _, v, lim in numbers)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in numbers}
    if control:
        result["control"] = check.control_numbers(ref_weights, conf,
                                                  sample, live)
        log(f"controls {result['control']}")
    result["checks"] = result.pop("checks")
    for n, v, lim in numbers:
        log(f"{n} {v} limit {lim}")
    return result

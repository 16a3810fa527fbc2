"""Smoke test of the serving path on a TPU.

Serves smollm-135m at its published widths (30 layers, d_model 576, 9 query
and 3 KV heads, d_ff 1536, vocab 49152, bf16 params initialised from
``--seed``) through ``repro.serve.Engine``, after checking every Pallas
kernel against its oracle on the chip. Everything runs in this one process,
which holds the chip; it starts no other.

    python chip_smoke.py              # one chip: device, kernels, serve
    python chip_smoke.py --chips 4    # four chips: the mesh engine against
                                      # a single-device engine, nothing else

Phases (one chip):
  device   JAX must report a TPU; a CPU fallback fails here.
  kernels  each Pallas kernel (deficit, stage1, rank1; int32 and fused
           forms; compiled with interpret=False, and its compiled text must
           hold a ``tpu_custom_call``) gives int32 accumulators bitwise
           equal to its registry oracle on the chip, at the smollm-135m
           projection shapes for M = 8 (decode) and M = 256 (prefill); so
           does approx_deficit, the gather-free jnp form of approx_lut. A
           sample of rows also matches the gate-level product table on the
           host.
  serve    8 requests (prompts of 32-256 tokens, half sharing a 64-token
           prefix, 16 greedy new tokens each) through an 8-slot engine with
           continuous admission and the prefix cache, under bf16,
           int8_exact, approx_deficit and the two Pallas backends. Every
           request must finish, the logits must be finite and agree with
           the served tokens, the prefix cache must hit, and each Pallas
           backend must serve the same tokens as approx_deficit.

approx_lut, the registry oracle of all three, is not served: its 64K-entry
gather ran at about 1e8 multiplies per second on a v5e, and the 8 requests
at full width take some 2.6e11 (prompts padded to their prefill buckets,
every slot in every decode step), some 40 minutes. The kernel phase holds
approx_deficit and both Pallas kernels bitwise to approx_lut on the chip at
every projection shape the serve phase multiplies.

Any failed check exits non-zero. The last line of standard output is a JSON
object naming the device. The timings printed before it are smoke timings,
compilation included, and not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
SLOTS, MAX_LEN, MAX_NEW = 8, 512, 16
SHARED_PREFIX = 64
# request ids per submission wave: request 0 publishes the shared prefix's
# pages when it retires, so the second wave's shared-prefix requests hit
WAVES = ((0, 1, 3, 5), (2, 4, 6, 7))
SERVE_BACKENDS = ("bf16", "int8_exact", "approx_deficit",
                  "approx_rank1_pallas", "approx_deficit_pallas")
# what the Pallas backends' served tokens are held to: bitwise equal to
# approx_lut at every projection shape (kernel phase), and gather-free
SERVE_ORACLE = "approx_deficit"
MESH_BACKENDS = ("int8_exact", "approx_rank1_pallas")
# (K, N) of smollm-135m's projections: q/o, k/v, gate/up, down, LM head
PROJECTIONS = ((576, 576), (576, 192), (576, 1536), (1536, 576),
               (576, 49152))
M_DECODE, M_PREFILL = 8, 256


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


class CompileClock:
    """Seconds JAX spent in XLA compilation (persistent-cache reads
    included), from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (devices: {devs}); this smoke runs on the chip "
          "only")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _host_tables():
    """Signed product tables over the int8 domain, indexed by the operands'
    uint8 bit patterns: the paper multiplier (gate-level LUT) and the
    stage-1 re-approximation."""
    from repro.core import luts
    from repro.core.multiplier import proposed_multiplier
    from repro.quant.matmul import stage1_exhaustive_products
    paper = luts.signed_product_lut(proposed_multiplier("proposed"))
    v = np.arange(256)
    s = np.where(v < 128, v, v - 256)
    mag = stage1_exhaustive_products()[np.abs(s)][:, np.abs(s)]
    stage1 = np.sign(s)[:, None] * np.sign(s)[None, :] * mag
    return {"approx_lut": paper.astype(np.int64),
            "approx_stage1": stage1.astype(np.int64)}


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import approx_matmul as K
    from repro.quant import matmul as QM
    from repro.quant.quantize import QuantConfig

    def oracle(name):
        fn, cfg = QM.get_backend(name).fn, QuantConfig(backend=name)
        return jax.jit(lambda x, w: fn(x, w, cfg))

    oracles = {n: oracle(n) for n in ("approx_lut", "approx_stage1",
                                      "int8_exact", SERVE_ORACLE)}
    # name -> (kernel(x, w, scale, bias), oracle); fused forms run with
    # scale 1 and bias 0, so their f32 output is the accumulator as f32
    kernels = {
        SERVE_ORACLE: (oracles.pop(SERVE_ORACLE), "approx_lut"),
        "deficit": (lambda x, w, s, b: K.approx_matmul_pallas(
            x, w, kernel="deficit", interpret=False), "approx_lut"),
        "deficit_fused": (lambda x, w, s, b: K.fused_matmul_pallas(
            x, w, s, b, variant="deficit", interpret=False), "approx_lut"),
        "stage1": (lambda x, w, s, b: K.approx_matmul_pallas(
            x, w, kernel="stage1", interpret=False), "approx_stage1"),
        "stage1_fused": (lambda x, w, s, b: K.fused_matmul_pallas(
            x, w, s, b, variant="stage1", interpret=False), "approx_stage1"),
        "exact_fused": (lambda x, w, s, b: K.fused_matmul_pallas(
            x, w, s, b, variant="exact", interpret=False), "int8_exact"),
        "rank1": (lambda x, w, s, b: K.rank1_matmul_pallas(
            x, w, interpret=False), "approx_lut"),
        "rank1_fused": (lambda x, w, s, b: K.rank1_fused_matmul_pallas(
            x, w, s, b, interpret=False), "approx_lut"),
    }
    tables = _host_tables()
    rng = np.random.default_rng(seed)
    for m in (M_DECODE, M_PREFILL):
        for k, n in PROJECTIONS:
            t0 = time.perf_counter()
            xh = rng.integers(-127, 128, (m, k)).astype(np.int8)
            wh = rng.integers(-127, 128, (k, n)).astype(np.int8)
            x, w = jnp.asarray(xh), jnp.asarray(wh)
            one = jnp.ones((1, n), jnp.float32)
            zero = jnp.zeros((1, n), jnp.float32)
            want = {name: np.asarray(fn(x, w)) for name, fn in oracles.items()}
            rows = rng.choice(m, size=2, replace=False)
            for name, table in tables.items():
                host = table[xh[rows].view(np.uint8)[:, :, None],
                             wh.view(np.uint8)[None]].sum(axis=1)
                check(np.array_equal(want[name][rows], host),
                      f"{name} on the chip != host product table at "
                      f"{m}x{k}x{n}")
            for name, (fn, ref) in kernels.items():
                if name == SERVE_ORACLE:
                    got = np.asarray(fn(x, w))
                else:
                    compiled = jax.jit(fn).lower(x, w, one, zero).compile()
                    check("tpu_custom_call" in compiled.as_text(),
                          f"{name} at {m}x{k}x{n} compiled without a "
                          "Pallas TPU kernel")
                    got = np.asarray(compiled(x, w, one, zero))
                exp = want[ref]
                if got.dtype == np.float32:
                    exp = exp.astype(np.float32)
                check(np.array_equal(got, exp),
                      f"{name} != {ref} at {m}x{k}x{n} "
                      f"({int((got != exp).sum())} entries differ)")
            log(f"kernels {m}x{k}x{n}: {len(kernels) - 1} Pallas kernels "
                f"and {SERVE_ORACLE} bitwise equal to their oracles "
                f"({time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_prompts(seed: int, vocab: int):
    """8 prompts of 32-256 tokens; the even-numbered ones open with one
    shared 64-token prefix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX)
    prompts = []
    for rid in range(SLOTS):
        if rid % 2 == 0:
            n = int(rng.integers(SHARED_PREFIX + 8, 257))
            p = np.concatenate([shared,
                                rng.integers(0, vocab, n - SHARED_PREFIX)])
        else:
            p = rng.integers(0, vocab, int(rng.integers(32, 257)))
        prompts.append(p.astype(np.int32))
    return prompts


def serve(cfg, params, prompts, mesh=None):
    """Serve the prompts in WAVES; returns (engine, {rid: request})."""
    from repro.serve import Engine, ServeRequest
    eng = Engine(cfg, params, slots=SLOTS, max_len=MAX_LEN, mesh=mesh)
    for wave in WAVES:
        for rid in wave:
            eng.submit(ServeRequest(rid=rid, prompt=prompts[rid],
                                    max_new=MAX_NEW))
        eng.run()
    return eng, {r.rid: r for r in eng.completed}


def _bucket(n: int) -> int:
    """The engine's cold-prefill length bucket for an n-token prompt."""
    b = 8
    while b < n:
        b *= 2
    return min(b, MAX_LEN)


def probe_logits(cfg, params, prompt, served):
    """Re-run the cold prefill of `prompt` and one decode step through the
    engine's own compiled functions (same shapes, so no new compile):
    logits must be finite, and their argmax must be the tokens served."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer_lm as TLM
    from repro.parallel.sharding import DEFAULT_RULES
    from repro.serve import compiled_fns
    prefill, decode = compiled_fns(cfg, DEFAULT_RULES)
    toks = np.zeros((1, _bucket(len(prompt))), np.int32)
    toks[0, :len(prompt)] = prompt
    cache = TLM.init_cache(cfg, 1, MAX_LEN, cfg.param_dtype)
    logits, cache = prefill(params, jnp.asarray(toks), cache,
                            jnp.asarray([len(prompt)], jnp.int32),
                            jnp.int32(0))
    first = np.asarray(logits[0, 0], np.float32)
    check(np.isfinite(first).all(), "prefill logits are not finite")
    check(int(first.argmax()) == served[0],
          "prefill argmax != first served token")
    pool = jax.tree.map(lambda c: jnp.repeat(c, SLOTS, axis=1), cache)
    logits, _ = decode(params, pool,
                       jnp.full((SLOTS, 1), served[0], jnp.int32),
                       jnp.full((SLOTS,), len(prompt), jnp.int32))
    step = np.asarray(logits[:, 0], np.float32)
    check(np.isfinite(step).all(), "decode logits are not finite")
    check(int(step[0].argmax()) == served[1],
          "decode argmax != second served token")


def serve_phase(seed: int, clock: CompileClock) -> None:
    import dataclasses
    import jax
    from repro.configs import registry
    from repro.models import transformer_lm as TLM
    from repro.quant.quantize import for_lm
    from repro.serve import FINISH_REASONS

    base = registry.get(ARCH)
    log(f"serve: {ARCH} layers={base.n_layers} d_model={base.d_model} "
        f"heads={base.n_heads}/{base.n_kv_heads} d_ff={base.d_ff} "
        f"vocab={base.vocab} params={np.dtype(base.param_dtype).name}, "
        f"slots={SLOTS} max_len={MAX_LEN}")
    params = TLM.init(base, jax.random.PRNGKey(seed))
    prompts = make_prompts(seed, base.vocab)
    log(f"serve: prompt lengths {[len(p) for p in prompts]}, "
        f"{MAX_NEW} new tokens each")
    tokens = {}
    for backend in SERVE_BACKENDS:
        cfg = dataclasses.replace(base, quant=for_lm(backend))
        c0, t0 = clock.seconds, time.perf_counter()
        eng, done = serve(cfg, params, prompts)
        wall = time.perf_counter() - t0
        check(sorted(done) == list(range(SLOTS)),
              f"{backend}: served {sorted(done)}")
        for rid, r in done.items():
            check(r.finish_reason in FINISH_REASONS
                  and len(r.output) == MAX_NEW,
                  f"{backend}: request {rid} ended {r.finish_reason!r} "
                  f"after {len(r.output)} tokens")
        check(eng.prefix_hit_tokens >= 3 * SHARED_PREFIX,
              f"{backend}: prefix cache hit {eng.prefix_hit_tokens} tokens")
        probe_logits(cfg, params, prompts[0], done[0].output)
        if backend.endswith("_pallas"):
            from repro.serve import compiled_fns
            from repro.parallel.sharding import DEFAULT_RULES
            text = compiled_fns(cfg, DEFAULT_RULES)[1].lower(
                params, eng.pool, np.zeros((SLOTS, 1), np.int32),
                np.zeros(SLOTS, np.int32)).as_text()
            check("tpu_custom_call" in text,
                  f"{backend}: decode step lowered without a Pallas kernel")
        tokens[backend] = {rid: r.output for rid, r in done.items()}
        ttft = [r.timing.ttft_s * 1e3 for r in done.values()]
        log(f"serve {backend}: 8/8 finished, prefix hits "
            f"{eng.prefix_hit_tokens} tokens | smoke timings (compile "
            f"included, not a benchmark): wall {wall:.1f} s, compile "
            f"{clock.seconds - c0:.1f} s, TTFT mean {np.mean(ttft):.0f} ms "
            f"max {np.max(ttft):.0f} ms, "
            f"{SLOTS * MAX_NEW / wall:.1f} tok/s")
    for backend in SERVE_BACKENDS:
        if backend.endswith("_pallas"):
            check(tokens[backend] == tokens[SERVE_ORACLE],
                  f"{backend} served different tokens than {SERVE_ORACLE}")
    log(f"serve: Pallas backends served the same tokens as {SERVE_ORACLE}")


# ---------------------------------------------------------------------------
# four chips: the mesh engine
# ---------------------------------------------------------------------------

def mesh_phase(seed: int) -> None:
    import dataclasses
    import jax
    from repro.configs import registry
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer_lm as TLM
    from repro.quant.quantize import for_lm

    mesh = make_serving_mesh(devices=jax.devices()[:4])
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    log(f"mesh: {shape}")
    check(mesh.devices.shape == (2, 2), f"mesh shape {shape}")
    base = registry.get(ARCH)
    params = TLM.init(base, jax.random.PRNGKey(seed))
    prompts = make_prompts(seed, base.vocab)
    for backend in MESH_BACKENDS:
        cfg = dataclasses.replace(base, quant=for_lm(backend))
        out = {}
        for label, mesh_arg in (("single", None), ("mesh", mesh)):
            t0 = time.perf_counter()
            _, done = serve(cfg, params, prompts, mesh=mesh_arg)
            check(sorted(done) == list(range(SLOTS)),
                  f"{backend} {label}: served {sorted(done)}")
            out[label] = {rid: r.output for rid, r in done.items()}
            log(f"mesh {backend} {label}: 8/8 finished "
                f"({time.perf_counter() - t0:.1f} s, smoke timing)")
        differ = [rid for rid in range(SLOTS)
                  if out["single"][rid] != out["mesh"][rid]]
        log(f"mesh {backend}: requests whose tokens differ between the "
            f"(2, 2) mesh engine and one device: {differ}")
        check(not differ, f"{backend}: mesh engine tokens != single device "
                          f"for requests {differ}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-engine phase on four chips")
    args = ap.parse_args(argv)

    device = device_phase(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        kernel_phase(args.seed)
        serve_phase(args.seed, clock)
    log(f"total: {time.perf_counter() - t0:.1f} s, compile "
        f"{clock.seconds:.1f} s, persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

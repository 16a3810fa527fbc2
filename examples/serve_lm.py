"""Serve a small LM with continuous batching — the end-to-end driver.

Thin wrapper over `repro.serve.Engine`: a mixed-length request queue is
served through the fixed-slot KV pool, with the paper's technique plugged
in as the quant backend of every projection (QKV, attention output, MLP,
LM head) via per-token activation scales (docs/quantization.md). Freed
slots are refilled mid-decode; `--policy drain` switches to the
batch-synchronous baseline for comparison (docs/serving.md).

Run:  PYTHONPATH=src python examples/serve_lm.py [--backend approx_lut]
      PYTHONPATH=src python examples/serve_lm.py --sampling top_k --top-k 8
      PYTHONPATH=src python examples/serve_lm.py --spec-k 4 \
        --draft-backend approx_stage1       # speculative, tokens unchanged
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_lm.py --mesh data,model
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer_lm as TLM
from repro.quant.matmul import list_backends
from repro.quant.quantize import for_lm
from repro.serve import Engine, SamplingConfig, ServeRequest

ap = argparse.ArgumentParser()
ap.add_argument("--backend", default="bf16",
                choices=["bf16", *list_backends()])
ap.add_argument("--requests", type=int, default=8)
ap.add_argument("--max-new", type=int, default=12)
ap.add_argument("--slots", type=int, default=4)
ap.add_argument("--policy", default="continuous",
                choices=["continuous", "drain"])
ap.add_argument("--sampling", default="greedy",
                choices=["greedy", "temperature", "top_k"])
ap.add_argument("--temperature", type=float, default=0.8)
ap.add_argument("--top-k", type=int, default=8)
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--shared-prefix", type=int, default=0,
                help="prepend a common prefix of this many tokens to every "
                     "prompt (shared-system-prompt traffic: requests after "
                     "the first retirement hit the paged prefix cache)")
ap.add_argument("--no-prefix-cache", action="store_true",
                help="disable the paged KV prefix cache")
ap.add_argument("--stream", action="store_true",
                help="print tokens as they are emitted")
ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                help="speculative decoding with a K-wide verify window "
                     "(serve/speculative.py) — served tokens are bitwise "
                     "identical to sequential decode, only the number of "
                     "passes changes; 0 disables")
ap.add_argument("--draft-backend", default="bf16",
                choices=["bf16", *list_backends()],
                help="backend the draft model proposes on (same params)")
ap.add_argument("--mesh", default=None, metavar="AXES",
                help="run the engine over a device mesh (docs/sharding.md): "
                     "comma-separated axis names, e.g. 'data,model' splits "
                     "the visible devices over those axes "
                     "(launch/mesh.py picks the factorization); served "
                     "tokens are identical to the single-device engine")
args = ap.parse_args()
enable_compile_cache()

cfg = registry.reduced("smollm-135m", n_layers=4, d_model=128, d_ff=256)
cfg = dataclasses.replace(cfg, quant=for_lm(args.backend))
params = TLM.init(cfg, jax.random.PRNGKey(0))
scfg = SamplingConfig(kind=args.sampling, temperature=args.temperature,
                      top_k=args.top_k, seed=args.seed)
stream = ((lambda rid, tok: print(f"  rid {rid} -> {tok}"))
          if args.stream else None)
mesh = None
if args.mesh:
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(
        axis_names=tuple(a.strip() for a in args.mesh.split(",")))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
          f"{mesh.devices.size} device(s)")
spec = None
if args.spec_k > 0:
    from repro.serve import SpecConfig
    spec = SpecConfig(k=args.spec_k, draft_backend=args.draft_backend)
eng = Engine(cfg, params, slots=args.slots, max_len=64,
             admission=args.policy, stream=stream,
             prefix_caching=not args.no_prefix_cache, mesh=mesh, spec=spec)
rng = np.random.default_rng(args.seed)
shared = rng.integers(0, cfg.vocab, args.shared_prefix).astype(np.int32)
for rid in range(args.requests):
    plen = int(rng.integers(4, 17))          # mixed-length workload
    prompt = np.concatenate(
        [shared, rng.integers(0, cfg.vocab, plen).astype(np.int32)])
    eng.submit(ServeRequest(
        rid=rid, prompt=prompt,
        max_new=int(rng.integers(min(4, args.max_new), args.max_new + 1)),
        sampling=scfg))
stats = eng.run()
for r in sorted(eng.completed, key=lambda r: r.rid):
    ttft = (f"{r.timing.ttft_s * 1e3:7.1f} ms"
            if r.timing.ttft_s is not None else "      —")
    print(f"rid {r.rid}: {len(r.output):2d} tokens ({r.finish_reason}), "
          f"ttft {ttft}")
print(f"backend={args.backend} policy={args.policy}: "
      f"{stats['requests']} requests in {stats['decode_steps']} decode "
      f"steps / {stats['waves']} admission waves, {stats['new_tokens']} "
      f"tokens, {stats['tok_per_s']:.1f} tok/s, "
      f"occupancy {stats['occupancy']:.2f}, "
      f"prefix hit rate {stats['prefix_hit_rate']:.2f} "
      f"({stats['prefix_hit_tokens']} of "
      f"{stats['prefix_hit_tokens'] + stats['prefill_tokens']} prompt "
      f"tokens from cache)")
if spec is not None:
    print(f"speculative K={args.spec_k} draft={args.draft_backend}: "
          f"{stats['spec_passes']} verify passes, "
          f"{stats['spec_committed']} committed "
          f"({stats['spec_accept_mean']:.2f} drafts accepted/pass, "
          f"hist {stats['spec_accept_hist']}) — tokens bitwise identical "
          f"to --spec-k 0")

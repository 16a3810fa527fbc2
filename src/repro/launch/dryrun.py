import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. assembles fully-sharded ABSTRACT params/optimizer/caches/inputs
     (ShapeDtypeStruct — no allocation; kimi-k2's 1T params stay abstract),
  3. jits train_step (train_4k) or serve_step (prefill/decode cells) with
     explicit in/out shardings, calls .lower().compile(),
  4. records memory_analysis / cost_analysis / per-collective bytes parsed
     from the post-SPMD HLO into experiments/dryrun/*.json
     (consumed by benchmarks/roofline.py and EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--quant approx_lut]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SP
from repro.models import transformer_lm as TLM
from repro.optim import adamw
from repro.parallel.sharding import DEFAULT_RULES
from repro.train import steps as ST

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

def run_cell(arch: str, shape: str, multi_pod: bool, quant: str = "bf16",
             microbatches: int = 1, overrides=None, tag_suffix: str = ""):
    cfg = registry.get(arch)
    if overrides:
        cfg_over = {k: v for k, v in overrides.items()
                    if not k.startswith("_")}
        if cfg_over:
            cfg = dataclasses.replace(cfg, **cfg_over)
    if quant != "bf16":
        from repro.quant.quantize import QuantConfig
        cfg = dataclasses.replace(cfg, quant=QuantConfig(backend=quant))
    seq, batch, kind = registry.SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = DEFAULT_RULES
    t0 = time.time()

    if kind == "train" and microbatches == 1 and cfg.d_model >= 4096:
        # big-model default: bound remat-residual memory (DESIGN.md §5)
        microbatches = 8

    with jax.set_mesh(mesh):
        inputs = SP.input_specs(cfg, shape, mesh, rules)
        if kind == "train":
            opt_cfg = adamw.AdamWConfig(quantized_state=True)
            params, opt = SP.model_state_specs(cfg, mesh, rules, opt_cfg)
            import jax.numpy as _jnp
            accum = (_jnp.bfloat16 if (overrides or {}).get(
                "_accum_bf16") else _jnp.float32)
            step = ST.make_train_step(cfg, opt_cfg, rules,
                                      num_microbatches=microbatches,
                                      accum_dtype=accum)
            jitted = jax.jit(step, donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, inputs)
        else:
            params = SP.model_state_specs(cfg, mesh, rules)
            caches = SP.cache_specs(cfg, shape, mesh, rules)
            if kind == "prefill":
                def prefill_step(params, caches, batch):
                    enc = batch.get("enc")
                    return TLM.prefill(params, batch["tokens"], cfg, caches,
                                       rules, enc=enc)
                jitted = jax.jit(prefill_step, donate_argnums=(1,))
                lowered = jitted.lower(params, caches, inputs)
            else:
                serve = ST.make_serve_step(cfg, rules)
                if cfg.cross_every:
                    def step(params, caches, token, pos, enc):
                        return serve(params, caches, token, pos, enc=enc)
                    jitted = jax.jit(step, donate_argnums=(1,))
                    lowered = jitted.lower(params, caches, inputs["tokens"],
                                           inputs["pos"], inputs["enc"])
                else:
                    jitted = jax.jit(serve, donate_argnums=(1,))
                    lowered = jitted.lower(params, caches, inputs["tokens"],
                                           inputs["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    from repro.launch.hlo_costs import HloCost, builtin_cost_analysis
    cost = builtin_cost_analysis(compiled)
    hc = HloCost(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "quant": quant, "kind": kind,
        "seq": seq, "batch": batch,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # trip-count-corrected (repro.launch.hlo_costs); XLA's builtin
        # cost_analysis counts while bodies once and is kept for reference
        "flops_per_device": hc.flops + hc.flops_int8,
        "flops_int8_per_device": hc.flops_int8,
        "bytes_per_device": hc.hbm_bytes,
        "bytes_dots_per_device": hc.hbm_bytes_dots,
        "collective_bytes_per_device": dict(hc.collectives),
        "xla_flops_uncorrected": cost.get("flops", -1.0),
        "xla_bytes_uncorrected": cost.get("bytes accessed", -1.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
    }
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}_{shape}_{rec['mesh']}" + \
        (f"_{quant}" if quant != "bf16" else "") + tag_suffix
    rec["tag"] = tag
    (OUT_DIR / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    print(f"[OK] {arch:24s} {shape:12s} {rec['mesh']:8s} "
          f"flops/dev={rec['flops_per_device']:.3e} "
          f"peak={rec['memory']['peak_bytes']/2**30 if rec['memory']['peak_bytes'] else -1:.2f}GiB "
          f"lower={t_lower:.0f}s compile={t_compile:.0f}s")
    print("  memory_analysis:", mem)
    print("  collectives:", {k: f"{v/2**20:.1f}MiB"
                             for k, v in hc.collectives.items()})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", default="bf16")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    help="ArchConfig overrides key=value (perf experiments)")
    ap.add_argument("--tag", default="", help="suffix for the output json")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            val = v.lower() == "true"
        else:
            try:
                val = int(v)
            except ValueError:
                try:
                    val = float(v)
                except ValueError:
                    val = v
        overrides[k] = val

    cells = []
    archs = registry.ARCH_NAMES if (args.all or not args.arch) \
        else [args.arch]
    for a in archs:
        shapes = registry.applicable_shapes(a) if (args.all or not args.shape)\
            else [args.shape]
        for s in shapes:
            cells.append((a, s))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for a, s in cells:
        for mp in meshes:
            try:
                run_cell(a, s, mp, args.quant, args.microbatches,
                         overrides, args.tag)
            except Exception as e:  # noqa
                failures.append((a, s, mp, repr(e)))
                print(f"[FAIL] {a} {s} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"\nAll {len(cells) * len(meshes)} cells compiled.")


if __name__ == "__main__":
    main()

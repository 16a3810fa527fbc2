"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV at the end, and a human report
during the run. ``--quick`` (default) keeps CPU wall-time modest; ``--full``
uses the paper-scale training budgets.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

OUT = Path(__file__).resolve().parent.parent / "experiments"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table2,table3,table4,table5,"
                         "fig7,kernels,lm,serve")
    ap.add_argument("--out", type=Path, default=OUT,
                    help="output directory for result artifacts (default: "
                         "experiments/; scripts/bench_gate.py redirects "
                         "this to a scratch dir)")
    args = ap.parse_args(sys.argv[1:])
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    out_dir: Path = args.out

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import tables as T
    from benchmarks import kernel_perf as K
    from benchmarks import lm_perf as LMP
    from benchmarks import serve_perf as SP

    results = {}
    csv = []

    def bench(name, fn):
        if only and name not in only:
            return
        t0 = time.time()
        rows = fn()
        dt = (time.time() - t0) * 1e6
        results[name] = rows
        derived = ""
        if name == "table2":
            derived = f"mred_match={rows[-1]['mred']==rows[-1]['mred_paper']}"
        elif name == "table5":
            accs = {r["design"]: r["acc"] for r in rows
                    if r["model"] == "lenet5"}
            if "approx_lut" in accs and "bf16" in accs:
                derived = (f"lenet_approx_minus_exact="
                           f"{accs['approx_lut'] - accs['bf16']:.2f}pp")
        elif name == "fig7":
            derived = f"rows={len(rows)}"
        elif name == "lm":
            dec = {r["backend"]: r["decode_tok_per_s"] for r in rows}
            if "bf16" in dec and "approx_stage1_fused" in dec:
                derived = (f"stage1_fused_decode_vs_bf16="
                           f"{dec['approx_stage1_fused'] / dec['bf16']:.2f}x")
        elif name == "serve":
            loaded = SP.loaded_points(rows)
            if loaded:
                worst = min(r["speedup_vs_drain"] for r in loaded)
                derived = f"continuous_vs_drain_worst={worst:.2f}x"
        csv.append(f"{name},{dt:.0f},{derived}")

    bench("table1", T.table1_compressor)
    bench("table2", T.table2_error_metrics)
    bench("table3", T.table3_compressor_hw)
    bench("table4", T.table4_multiplier_hw)
    bench("table5", lambda: T.table5_mnist(quick=quick))
    bench("fig7", lambda: T.fig7_denoising(quick=quick))
    bench("kernels", lambda: K.run(quick=quick))
    bench("lm", lambda: LMP.run(quick=quick))
    bench("serve", lambda: SP.run(quick=quick))

    out_dir.mkdir(parents=True, exist_ok=True)
    # versioned standalone artifacts: the kernel/serving perf trajectories
    # are diffed across PRs like the eval tables (schema v1)
    if "kernels" in results:
        from repro.eval import artifacts
        artifacts.save(out_dir / "bench_kernels.json",
                       K.artifact(results["kernels"], quick))
    if "lm" in results:
        from repro.eval import artifacts
        artifacts.save(out_dir / "bench_lm.json",
                       LMP.artifact(results["lm"], quick))
    if "serve" in results:
        from repro.eval import artifacts
        artifacts.save(out_dir / "bench_serve.json",
                       SP.artifact(results["serve"], quick))
    # a partial run (--only) must not drop the other suites' committed
    # baselines: merge over the existing file
    merged_path = out_dir / "bench_results.json"
    if only and merged_path.exists():
        results = {**json.loads(merged_path.read_text()), **results}
    merged_path.write_text(json.dumps(results, indent=1, default=float))
    print("\nname,us_per_call,derived")
    for line in csv:
        print(line)


if __name__ == "__main__":
    main()

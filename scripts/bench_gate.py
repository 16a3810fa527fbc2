"""Bench regression gate: fail CI when a kernel/bench wall-time regresses.

Runs ``benchmarks/run.py`` into a scratch directory and compares every
``us_per_call`` row against the committed baselines in
``experiments/bench_results.json``:

    PYTHONPATH=src python scripts/bench_gate.py --only kernels
    PYTHONPATH=src python scripts/bench_gate.py --only kernels --update

A row regresses when ``new > threshold * baseline`` (default 1.5x),
where both sides are **normalized by the same run's int8_exact time at
the same shape** whenever that base row exists — so the comparison is a
machine-speed-independent slowdown ratio and a CI runner that is
uniformly slower (or faster) than the machine that produced the baseline
neither trips nor masks the gate. Rows without a same-shape exact base
(epilogue/staging rows) compare raw wall-times; ``--absolute`` forces
raw comparison everywhere.

Rows faster than the floor (default 500 us) are reported but never fail
the gate — sub-millisecond CPU timings are too noisy to block a merge
on. Rows present only in the fresh run (new backends/shapes) are
informational. Rows present only in the baseline fail — silently
dropping a benchmark is itself a gated regression — unless the fresh run
swept no rows at all at that (suite, shape), which marks a deliberate
sweep-level difference (e.g. a --full baseline's 2048 rows vs a quick CI
run) and is reported informationally. ``--update`` re-baselines: it
copies the fresh results over the committed files (bench_results.json
plus any versioned artifacts the run produced).

This script runs the benchmark as a child process and never imports JAX
itself: on a machine with an accelerator the chip belongs to one process,
and a parent that had touched JAX would hold it while the child waited.
Keep it that way.
"""
from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BASELINE = ROOT / "experiments" / "bench_results.json"
ARTIFACTS = ("bench_kernels.json", "bench_lm.json", "bench_serve.json")


# per-suite base backend for normalization: serve rows have no int8_exact
# point in the quick sweep, but every (policy, offered, share) cell has a
# bf16 row
BASE_BACKEND = {"serve": "bf16"}
DEFAULT_BASE = "int8_exact"


def _rows(results: dict, only: set | None):
    """(suite, backend, m, k, n, policy, offered, share, spec_k) ->
    us_per_call for every timed row. Kernel rows carry shape in (m, k, n);
    serve rows carry their sweep point in (policy, offered, share) plus
    the speculative window spec_k (0 on non-speculative rows) — unused
    components sit at their defaults so kernel keys are unchanged."""
    out = {}
    for suite, rows in results.items():
        if only and suite not in only:
            continue
        if not isinstance(rows, list):
            continue
        for row in rows:
            us = row.get("us_per_call")
            if not isinstance(us, (int, float)) or us <= 0:
                continue
            key = (suite, row.get("backend", row.get("name", "?")),
                   row.get("m", 0), row.get("k", 0), row.get("n", 0),
                   row.get("policy", ""), row.get("offered", 0),
                   row.get("share", -1), row.get("spec_k", 0))
            out[key] = float(us)
    return out


def _normalized(rows: dict, absolute: bool):
    """(values, gated_keys): us_per_call scaled by the same run's base
    backend (int8_exact for kernels, bf16 for serve) at the same
    shape/sweep point (a machine-independent slowdown).

    Rows at shapes with no base row (e.g. the eager-staging
    illustration rows) keep raw wall-times and are excluded from
    `gated_keys` — raw cross-machine comparisons would make CI flaky —
    unless `absolute`, which gates everything raw. The trade-off of
    normalized mode: a regression in the base backend itself (ratio
    always 1.0) or one exactly proportional to it is invisible; run with
    --absolute on stable hardware to audit that blind spot.
    """
    if absolute:
        return dict(rows), set(rows)
    base = {(key[0],) + key[2:]: us for key, us in rows.items()
            if key[1] == BASE_BACKEND.get(key[0], DEFAULT_BASE)}
    values = {key: us / base.get((key[0],) + key[2:], 1.0)
              for key, us in rows.items()}
    gated = {key for key in rows if (key[0],) + key[2:] in base}
    return values, gated


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="kernels",
                    help="comma list forwarded to benchmarks/run.py "
                         "(default: kernels)")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="fail when new > threshold * baseline")
    ap.add_argument("--floor-us", type=float, default=500.0,
                    help="rows faster than this never fail (timing noise)")
    ap.add_argument("--update", action="store_true",
                    help="re-baseline: commit the fresh results")
    ap.add_argument("--absolute", action="store_true",
                    help="compare raw wall-times instead of "
                         "exact-normalized slowdown ratios")
    ap.add_argument("--full", action="store_true",
                    help="forward --full to benchmarks/run.py")
    ap.add_argument("--use", type=Path, default=None,
                    help="compare an existing bench output directory "
                         "(from `run.py --out DIR`) instead of re-running")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    if not BASELINE.exists() and not args.update:
        print(f"[bench_gate] no baseline at {BASELINE}; run with --update "
              "to create one", file=sys.stderr)
        return 2

    with tempfile.TemporaryDirectory(prefix="bench_gate_") as tmp:
        if args.use is not None:
            tmp = str(args.use)
        else:
            cmd = [sys.executable, str(ROOT / "benchmarks" / "run.py"),
                   "--out", tmp]
            if args.only:
                cmd += ["--only", args.only]
            if args.full:
                cmd.append("--full")
            proc = subprocess.run(cmd, cwd=ROOT)
            if proc.returncode != 0:
                print(f"[bench_gate] bench run failed ({proc.returncode})",
                      file=sys.stderr)
                return proc.returncode
        fresh_path = Path(tmp) / "bench_results.json"
        fresh = json.loads(fresh_path.read_text())

        if args.update:
            base = (json.loads(BASELINE.read_text())
                    if BASELINE.exists() else {})
            base.update(fresh)      # suites not re-run keep old baselines
            BASELINE.write_text(json.dumps(base, indent=1, default=float))
            for name in ARTIFACTS:
                src = Path(tmp) / name
                if src.exists():
                    shutil.copy(src, ROOT / "experiments" / name)
            print(f"[bench_gate] re-baselined suites "
                  f"{sorted(fresh)} in {BASELINE}")
            return 0

        base = _rows(json.loads(BASELINE.read_text()), only)
        new = _rows(fresh, only)

    base_norm, base_gated = _normalized(base, args.absolute)
    new_norm, new_gated = _normalized(new, args.absolute)
    fresh_shapes = {(key[0],) + key[2:] for key in new}

    regressions, missing, unswept, noise = [], [], [], []
    for key, old_val in sorted(base_norm.items()):
        if key not in new_norm:
            # a shape the fresh run swept at all? then a dropped row is a
            # real regression; otherwise it's a sweep-level difference
            # (e.g. --full baseline vs quick CI run)
            (missing if (key[0],) + key[2:] in fresh_shapes
             else unswept).append(key)
            continue
        ratio = new_norm[key] / old_val
        if ratio > args.threshold:
            line = (f"{'/'.join(map(str, key))}: {base[key]:.0f} -> "
                    f"{new[key]:.0f} us ({ratio:.2f}x normalized)")
            if key not in base_gated or key not in new_gated:
                noise.append(line + " [no exact base: raw, not gated]")
            elif max(new[key], base[key]) < args.floor_us:
                noise.append(line)
            else:
                regressions.append(line)
    added = sorted(set(new) - set(base))

    for line in noise:
        print(f"[bench_gate] below-floor drift (ignored): {line}")
    for key in added:
        print(f"[bench_gate] new row (no baseline): "
              f"{'/'.join(map(str, key))}")
    for key in unswept:
        print(f"[bench_gate] baseline row at a shape this run did not "
              f"sweep (ignored): {'/'.join(map(str, key))}")
    if missing:
        for key in missing:
            print(f"[bench_gate] MISSING row (was in baseline): "
                  f"{'/'.join(map(str, key))}", file=sys.stderr)
    if regressions:
        print(f"[bench_gate] {len(regressions)} regression(s) over "
              f"{args.threshold:.2f}x:", file=sys.stderr)
        for line in regressions:
            print(f"[bench_gate]   {line}", file=sys.stderr)
    if regressions or missing:
        print("[bench_gate] FAIL (re-baseline intentional changes with "
              "--update)", file=sys.stderr)
        return 1
    print(f"[bench_gate] OK: {len(base)} baselined rows within "
          f"{args.threshold:.2f}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())

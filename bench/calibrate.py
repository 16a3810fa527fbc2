"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --controls <k> --seeds <n> [<n> ...] [--backend <name>]

Runs the cell once per seed in one process (set-up compiles once), each
run as ``run.py`` makes it, and prints the program's compared numbers;
on the first ``k`` seeds it also reads, on the same sample, the controls
of ``check.controls``: the reference at int4, and for the paper's
multiplier the reference with the exact product, in the program's place.
``--backend`` serves the cell with another backend of the program (the
program's own exact path as a control) and compares it with the
configuration's reference as usual. One JSON line per seed. The
benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--backend", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
    import harness
    import run
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    arch = None
    if args.backend:
        import dataclasses
        from repro.quant.quantize import for_lm
        arch = dataclasses.replace(harness.arch_config(cell.conf),
                                   quant=for_lm(args.backend))
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_process=t, log=run.log, arch=arch,
                               control=i < args.controls)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control": res.get("control"),
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one chip per cell (it starts no child). ``<cell>`` is a name
from ``BENCHMARK.json``'s ``workloads``. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared for ``correct`` with its limit,
which the last lines of standard error repeat. Exits non-zero, printing
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

T_PROCESS = time.time()
T_PERF = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PERF:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
    import harness
    cell = harness.load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    trace_dir = str(ROOT / ".bench_out" / "trace" / args.workload)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        t_process=T_PERF - (time.time() - T_PROCESS), log=log,
        trace_dir=trace_dir)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

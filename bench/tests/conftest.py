"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are outside the repository's tier-1 ``testpaths``. ``tiny_cell``
builds a cell at a reduced size (two layers, d_model 128, vocab 512) with
traffic scaled to match, so that a whole run, check included, fits in a
test.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=1, vocab_size=512,
            slots=4, max_len=256, cache_pages=256, initializer_range=0.2)

MIXES = {
    "batch": {"kind": "closed",
              "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 48},
              "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
              "pool": 64, "depth": 6},
}

# The widest logit gap allowed at the tiny size (initializer_range 0.2,
# so that context moves the logits enough for a broken cache to show).
# Set from readings on the CPU over six seeds (11, 2**31 + 12,
# 4_000_000_013, 5, 77, 123456) of the tiny batch cell: the program reads
# 0.0625 to 0.09375 (one to three bf16 steps of the logits), the int4
# control 6.96 to 9.67.
TINY_MAX_GAP = 0.5
# The share of layer 0's cache that may differ from the reference at the
# tiny size. On the CPU, seeds 11 and 2**31 + 12 of the tiny approx cell:
# the program reads 0; the program's exact int8 core serving it 0.485 and
# 0.499; the reference with the exact product 0.483 and 0.491; int4 0.99.
TINY_KV0_MISMATCH = 0.05


def tiny_cell(mix: str, config: str):
    """(Cell, ArchConfig) of ``config`` (``smollm-135m.int8`` or
    ``.approx``) under the scaled-down ``mix``."""
    import jax.numpy as jnp
    import harness
    from repro.configs import registry
    from repro.quant.quantize import for_lm
    conf = harness.load_json(BENCH / "configs" / f"{config}.json")
    conf.update(TINY)
    conf["check"] = {"served_tokens": 64, "token_budget": 2048}
    conf["limits"] = {"max_logit_gap": TINY_MAX_GAP,
                      "kv0_mismatch": TINY_KV0_MISMATCH}
    arch = dataclasses.replace(registry.reduced(conf["registry"]),
                               param_dtype=jnp.bfloat16,
                               quant=for_lm(conf["quant"]["backend"]))
    spec = harness.load_json(BENCH.parent / "BENCHMARK.json")
    name = next(w["name"] for w in spec["workloads"]
                if w["config"] == config and w["traffic"] == mix)
    cell = harness.Cell(
        name=name, chips=1, conf=conf, mix=MIXES[mix],
        end_to_end=[m for m in spec["end_to_end"]
                    if harness._applies(m, name)],
        per_layer=[m for m in spec["per_layer"]
                   if harness._applies(m, name)])
    return cell, arch


@pytest.fixture
def run_tiny(tmp_path):
    """Run a tiny cell through the harness, past its look for a chip."""
    import harness

    def run(mix, config="smollm-135m.int8", *, seed=2**31 + 7, seconds=3.0,
            trace=False, fault=None, control=False, backend=None):
        cell, arch = tiny_cell(mix, config)
        if backend is not None:
            from repro.quant.quantize import for_lm
            arch = dataclasses.replace(arch, quant=for_lm(backend))
        t = time.perf_counter()
        return harness.run_cell(cell, seed, seconds, trace, t_process=t,
                                log=lambda m: None, require_tpu=False,
                                arch=arch, trace_dir=str(tmp_path / "tr"),
                                fault=fault, control=control)
    return run

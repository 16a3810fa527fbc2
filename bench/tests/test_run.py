"""Whole runs of tiny cells on the CPU, past the look for a chip: the
window, its metrics and the check, and the check failing where the timed
path is broken underneath."""
import os
import subprocess
import sys

import pytest

from conftest import BENCH


@pytest.mark.parametrize("config", ["smollm-135m.int8",
                                    "smollm-135m.approx"])
def test_rehearsal_window_is_correct(run_tiny, config):
    res = run_tiny("batch", config)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    m = res["metrics"]
    assert m["out_tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert m["itl_p95_ms"]["value"] > 0
    assert "ttft_p95_ms" not in m
    for name in ("max_logit_gap", "kv0_mismatch"):
        assert 0.0 <= res["checks"][name]["value"] <= \
            res["checks"][name]["limit"]
    assert res["checks"]["kv0_mismatch"]["value"] == 0.0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["builds_in_window"] == 0


def test_rehearsal_traced_run_reads_host_metrics(run_tiny):
    res = run_tiny("batch", trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["host_ms_per_step"]["value"] > 0


def _decode_returns_state_unchanged(engine):
    dec = engine._decode

    def decode(params, pool, tok, pos):
        logits, _ = dec(params, pool, tok, pos)
        return logits, pool
    engine._decode = decode


def _token_altered_where_produced(engine):
    emit = engine._emit
    count = [0]

    def altered(req, tok):
        count[0] += 1
        if count[0] % 7 == 0:
            tok = (tok + 1) % engine.cfg.vocab
        emit(req, tok)
    engine._emit = altered


def _over_limit(res):
    return [n for n, c in res["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("fault", [_decode_returns_state_unchanged,
                                   _token_altered_where_produced])
def test_broken_timed_path_is_not_correct(run_tiny, fault):
    res = run_tiny("batch", fault=fault)
    assert res["correct"] is False, res["checks"]
    assert "max_logit_gap" in _over_limit(res)


def test_exact_product_in_the_multipliers_place_is_not_correct(run_tiny):
    """The approx cell served by the program's exact int8 core: the
    shortcut a faster program could take. Layer 0's cache shows it."""
    res = run_tiny("batch", "smollm-135m.approx", backend="int8_exact")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["kv0_mismatch"]["value"] > 0.3


@pytest.mark.parametrize("config,seed", [
    ("smollm-135m.int8", 11), ("smollm-135m.int8", 2**31 + 12),
    ("smollm-135m.int8", 4_000_000_013), ("smollm-135m.approx", 11),
    ("smollm-135m.approx", 2**31 + 12)])
def test_controls_fail_where_the_program_passes(run_tiny, config, seed):
    """Each control, the reference at int4 or (for the paper's multiplier)
    with the exact product in the program's place, reads a number above
    its limit; the program on the same sample reads within both."""
    res = run_tiny("batch", config, seed=seed, control=True)
    checks = res["checks"]
    assert res["correct"] is True, checks
    assert set(res["control"]) == ({"int4"} if config.endswith("int8")
                                   else {"int4", "exact_core"})
    for name, got in res["control"].items():
        assert any(got[n] > checks[n]["limit"]
                   for n in ("max_logit_gap", "kv0_mismatch")), (name, got)
    assert res["control"]["int4"]["max_logit_gap"] > \
        checks["max_logit_gap"]["limit"]


def test_cli_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "smollm-int8.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=BENCH.parent, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_cli_fails_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, the run
    exits non-zero and prints no result."""
    import shutil
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "smollm-int8.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout

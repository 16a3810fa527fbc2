"""The architecture seam: everything that depends on a model's layout is
``bench/arch/<architectures[0]>.py``.

* The Llama module reproduces, bit for bit, the weights, the reference's
  readings and the op count that the harness gave before it had the seam
  (readings pinned from that harness on the CPU at the tiny size).
* A configuration that names a new architecture is added as files alone.
* A dense model with an untied head and a cut in depth
  (``deepseek-coder-33b``) is taken through the same module.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import check
import harness
import ops
import weights as W
from conftest import BENCH, tiny_conf

CONFIGS = ("smollm-135m.int8", "smollm-135m.approx")

# sha256 of the tiny weight tree (``_tree_sha256``); the same for both
# files, whose sizes agree.
WEIGHTS_SHA256 = {
    3: "ecb565589728f9e570ad8c3550c9cc20d0ef2270d01c5144d4449bca0f17bdb0",
    2**32 + 17:
        "09ad64cf30230dc9570f1cae31d4707d670e0ab6656f87cb686efdbe4f5c21e1",
}
# sha256 (``_sha256``) of the reference's readings of ``_SEQ`` at the tiny
# size, seed 3, under each file's own core (int8: exact; approx:
# approx:proposed): sequence_stats' (best, at, argmax), and layer 0's k
# and v.
STATS_SHA256 = {
    "smollm-135m.int8":
        "2728001d3aef9e8127bfe2d8c6fec4dcbf661c3f52b50d784ce6fbda6e069a8b",
    "smollm-135m.approx":
        "9fb852bd67689ebf5279ac5c6972c6e7e31b956e4a8e82c3450aae4a5ba091c2",
}
KV0_SHA256 = {
    "smollm-135m.int8": {
        "k":
            "4c00e41d1369a32d9792abe275ba99fe4c68e5b76d3f7a5718b131e0009bbf15",
        "v":
            "98ec6d1b9b5ca04bc20b7f9ca742fd7a9eaefe77e6700ca5063322bb1b50138a",
    },
    "smollm-135m.approx": {
        "k":
            "132c8188c4d38129dc57f23982c505c0ca6a346310ff8aa2f1f2334574053afe",
        "v":
            "143afbb45dd9cc962fe070f1817befe1e1d75ab73a729c720c025f0be2f35220",
    },
}
_SEQ = (np.arange(40, dtype=np.int64) * 37 + 5) % 512


def _sha256(*arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()


def _tree_sha256(tree) -> str:
    m = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        m.update(jax.tree_util.keystr(path).encode())
        m.update(str(x.dtype).encode())
        m.update(str(x.shape).encode())
        m.update(x.tobytes())
    return m.hexdigest()


@pytest.mark.parametrize("seed", sorted(WEIGHTS_SHA256))
@pytest.mark.parametrize("config", CONFIGS)
def test_weights_are_the_pinned_trees(config, seed):
    assert _tree_sha256(W.make(tiny_conf(config), seed)) == \
        WEIGHTS_SHA256[seed]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_readings_are_the_pinned_ones(config):
    conf = tiny_conf(config)
    module = harness.arch_module(conf)
    w = W.make(conf, 3)
    best, at, arg = module.sequence_stats(w, conf, _SEQ.astype(np.int32))
    assert _sha256(best, at, arg) == STATS_SHA256[config]
    cache = module.layer0_cache(w, conf, _SEQ, np.arange(len(_SEQ)))
    assert {n: _sha256(a) for n, a in cache.items()} == KV0_SHA256[config]


@pytest.mark.parametrize("config", CONFIGS)
def test_full_size_op_count(config):
    conf = harness.load_json(BENCH / "configs" / f"{config}.json")
    assert ops.int8_ops_per_token(conf) == 268_959_744


def test_kv0_mismatch_is_the_share_over_all_leaves():
    ref = {"ckv": np.zeros((4, 8), np.float32),
           "kpe": np.zeros((4, 2), np.float32)}
    got = {"ckv": ref["ckv"].copy(), "kpe": ref["kpe"] + 1}
    got["ckv"][0, :3] = 1
    assert check._mismatch(got, ref) == (3 + 8) / 40


# ---------------------------------------------------------------------------
# A configuration of a new architecture, as files alone
# ---------------------------------------------------------------------------

RECORDING_MODULE = '''"""A Llama that records every call made to it."""
import harness

_llama = harness.arch_module({"architectures": ["LlamaForCausalLM"]})
TINY, CUTS = _llama.TINY, _llama.CUTS
CALLS = []


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(_llama, name)(*args, **kwargs)
    return call


fields, shapes, cache0, projection_weights, sequence_stats, layer0_cache = (
    _recorded(n) for n in ("fields", "shapes", "cache0",
                           "projection_weights", "sequence_stats",
                           "layer0_cache"))
'''

# The tiny rehearsal, traced (so that the op count behind mfu is read),
# run by the copy's own harness and conftest.
REHEARSAL = '''
import json, sys, time
sys.path[:0] = ["bench/tests", "bench"]
from conftest import tiny_cell
import harness
cell, arch = tiny_cell("batch", "tiny-recorded", "smollm-int8.batch")
res = harness.run_cell(cell, 2**31 + 7, 3.0, True,
                       t_process=time.perf_counter(), log=lambda m: None,
                       require_tpu=False, arch=arch, trace_dir=sys.argv[1])
module = harness.arch_module(cell.conf)
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "metrics": sorted(res["metrics"]),
                  "module": module.__file__, "calls": module.CALLS}))
'''


def _files(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_architecture_is_added_as_files_alone(tmp_path):
    checkout = tmp_path / "checkout"
    shutil.copytree(BENCH, checkout / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", checkout)
    before = _files(checkout)
    conf = harness.load_json(BENCH / "configs" / "smollm-135m.int8.json")
    conf.update(harness.arch_module(conf).TINY,
                architectures=["RecordedLlamaForCausalLM"])
    added = {"bench/configs/tiny-recorded.json": json.dumps(conf, indent=1),
             "bench/arch/RecordedLlamaForCausalLM.py": RECORDING_MODULE}
    for rel, text in added.items():
        (checkout / rel).write_text(text)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=str(BENCH.parent / "src"))
    p = subprocess.run([sys.executable, "-c", REHEARSAL,
                        str(tmp_path / "trace")], capture_output=True,
                       text=True, env=env, cwd=checkout, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["module"] == str(
        checkout / "bench" / "arch" / "RecordedLlamaForCausalLM.py")
    calls = set(res["calls"])
    # field check, weights (program and reference), cache leaves, op count
    # and both halves of the reference
    assert calls == {"fields", "shapes", "cache0", "projection_weights",
                     "sequence_stats", "layer0_cache"}, calls
    assert res["calls"].count("shapes") == 2
    assert "mfu" in res["metrics"]
    after = _files(checkout)
    assert set(after) == set(before) | set(added)
    assert {k: after[k] for k in before} == before


# ---------------------------------------------------------------------------
# An untied head and a cut in depth
# ---------------------------------------------------------------------------

def _coder(**changes):
    """deepseek-coder-33b (arXiv:2401.14196; a Llama with an untied head)
    in the Llama module's keys, cut to 6 of its 62 layers; the engine,
    quantization and check of the smollm int8 file."""
    conf = harness.load_json(BENCH / "configs" / "smollm-135m.int8.json")
    conf.update(registry="deepseek-coder-33b", hidden_size=7168,
                intermediate_size=19200, num_hidden_layers=6,
                num_attention_heads=56, num_key_value_heads=8,
                vocab_size=32256, max_position_embeddings=16384,
                rope_theta=100000.0, rms_norm_eps=1e-06,
                tie_word_embeddings=False, reduced=["num_hidden_layers"])
    conf.update(changes)
    return conf


def test_cut_in_depth_reaches_the_registry():
    cfg = harness.arch_config(_coder())
    assert cfg.n_layers == 6
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.dh,
            cfg.vocab, cfg.tied_embeddings) == (7168, 19200, 56, 8, 128,
                                                32256, False)
    assert "lm_head" in harness.arch_module(_coder()).shapes(_coder())


@pytest.mark.parametrize("changes", [
    {"reduced": []},                       # the cut in depth, not listed
    {"intermediate_size": 19456},          # a width, never a cut
    {"tie_word_embeddings": True}])
def test_unlisted_difference_raises(changes):
    with pytest.raises(ValueError, match="differs from the configuration"):
        harness.arch_config(_coder(**changes))


def _head_is_the_embedding(engine):
    engine.params = {**engine.params, "lm_head": engine.params["embed"]}


@pytest.mark.parametrize("fault", [None, _head_is_the_embedding],
                         ids=["sound", "head_is_the_embedding"])
def test_untied_head_rehearsal(run_tiny, fault):
    res = run_tiny("batch", _coder(), workload="smollm-int8.batch",
                   fault=fault)
    assert res["correct"] is (fault is None), res["checks"]
    if fault is not None:
        gap = res["checks"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]

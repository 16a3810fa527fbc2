"""The Engine's profiler spans and the model step's named scopes.

``Engine.step`` opens one ``serve.*`` host span per phase (per step, per
admission, per retirement; never per slot or page), and the jitted model
step tags its ops with ``qmm.*``, ``attn``, ``lm_head``, ``layer_scan``
and ``layer`` scopes. Spans
and scopes are metadata: tokens are the same with the profiler on or off.
"""
import ast
import dataclasses
import glob
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer_lm as TLM
from repro.quant.quantize import for_lm
from repro.serve import Engine, ServeRequest, engine as engine_mod
from repro.serve import paging

# (prompt length, max_new): the third finishes at admission, so one
# retirement runs inside serve.admit; the rest retire while sampling
LENGTHS = [(12, 6), (5, 4), (9, 1), (16, 5), (7, 3), (14, 6)]
SPANS = ("serve.step", "serve.admit", "serve.match", "serve.gather",
         "serve.prefill", "serve.write_slot", "serve.decode", "serve.logits",
         "serve.sample", "serve.retire", "serve.publish", "serve.store_pages")
# each span and the spans it may open inside
PARENTS = {"serve.admit": ("serve.step",),
           "serve.match": ("serve.admit",),
           "serve.gather": ("serve.admit",),
           "serve.prefill": ("serve.admit",),
           "serve.write_slot": ("serve.admit",),
           "serve.decode": ("serve.step",),
           "serve.logits": ("serve.step",),
           "serve.sample": ("serve.step",),
           "serve.retire": ("serve.admit", "serve.sample"),
           "serve.publish": ("serve.retire",),
           "serve.store_pages": ("serve.retire",)}


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(
        registry.reduced("smollm-135m", n_layers=2, d_model=64, n_heads=4,
                         n_kv_heads=2, d_ff=128, vocab=64, vocab_pad=64,
                         head_dim=16),
        quant=for_lm("int8_exact"))
    return cfg, TLM.init(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, rid0=0):
    eng = Engine(cfg, params, slots=4, max_len=32, page_size=4)
    rng = np.random.default_rng(3)
    for i, (n, m) in enumerate(LENGTHS):
        eng.submit(ServeRequest(rid=rid0 + i,
                                prompt=rng.integers(0, cfg.vocab, n),
                                max_new=m))
    return eng


def _run(eng):
    while eng.step():
        pass
    return {r.rid: list(r.output) for r in eng.completed}


def _host_spans(trace_dir):
    """[(name, start, end)] of the serve.* host spans, by start."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith("serve."))
    return sorted(out, key=lambda x: (x[1], -x[2]))


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """A tiny Engine run start to finish under the profiler: (tokens,
    serve.* spans, decode steps, the Engine), after an untraced run of the
    same requests that compiles every program."""
    cfg, params = tiny
    _run(_serve(cfg, params))
    eng = _serve(cfg, params)
    d = str(tmp_path_factory.mktemp("trace"))
    steps0 = eng.decode_steps
    with jax.profiler.trace(d):
        tokens = _run(eng)
    return tokens, _host_spans(d), eng.decode_steps - steps0, eng


def _parent(spans, i):
    """Name of the innermost span holding spans[i] (spans by start)."""
    name, s, e = spans[i]
    for n, ps, pe in reversed(spans[:i]):
        if ps <= s and e <= pe:
            return n
    return None


def test_every_span_is_recorded_and_nested(traced):
    _, spans, _, _ = traced
    assert {n for n, _, _ in spans} == set(SPANS)
    for i, (name, _, _) in enumerate(spans):
        parent = _parent(spans, i)
        if name == "serve.step":
            assert parent is None
        else:
            assert parent in PARENTS[name], (name, parent)
    retire_parents = {_parent(spans, i) for i, (n, _, _) in enumerate(spans)
                      if n == "serve.retire"}
    assert retire_parents == {"serve.admit", "serve.sample"}


def test_spans_open_per_step_admission_and_retirement(traced):
    _, spans, decode_steps, eng = traced
    count = {n: sum(1 for s in spans if s[0] == n) for n in SPANS}
    assert count["serve.decode"] == decode_steps
    for n in ("serve.logits", "serve.sample"):
        assert count[n] == decode_steps
    assert count["serve.admit"] == count["serve.step"]
    for n in ("serve.match", "serve.gather", "serve.prefill",
              "serve.write_slot"):
        assert count[n] == len(LENGTHS)            # one per admission
    assert count["serve.retire"] == len(LENGTHS)   # one per retirement
    assert count["serve.publish"] == len(LENGTHS)


def test_tokens_are_the_same_with_the_profiler_on(tiny, traced):
    cfg, params = tiny
    assert _run(_serve(cfg, params)) == traced[0]


def test_decode_program_carries_the_scopes(traced):
    eng = traced[3]
    text = eng._decode.lower(eng.params, eng.pool,
                             jnp.zeros((eng.slots, 1), jnp.int32),
                             jnp.zeros((eng.slots,), jnp.int32)
                             ).compile().as_text()
    for scope in ("qmm.core", "qmm.wquant", "qmm.xquant", "qmm.dequant",
                  "attn", "lm_head", "layer_scan", "layer"):
        assert f"/{scope}/" in text, scope


def test_spans_take_no_arguments_and_paging_stays_jax_free():
    """Every TraceAnnotation in the Engine is named by one literal
    ``serve.*`` string and nothing else; serve/paging.py imports no
    jax."""
    tree = ast.parse(inspect.getsource(engine_mod))
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and getattr(n.func, "attr", None) == "TraceAnnotation"]
    assert {c.args[0].value for c in calls} == set(SPANS)
    for c in calls:
        assert len(c.args) == 1 and not c.keywords
    imports = [a.name for n in ast.walk(ast.parse(inspect.getsource(paging)))
               if isinstance(n, (ast.Import, ast.ImportFrom))
               for a in n.names] + [
        n.module for n in ast.walk(ast.parse(inspect.getsource(paging)))
        if isinstance(n, ast.ImportFrom) and n.module]
    assert not any(i.split(".")[0] == "jax" for i in imports), imports

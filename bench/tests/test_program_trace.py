"""bench/program_trace.py on hand-made events, a hand-encoded trace, a
trace recorded on a TPU v5e (``record_serve_trace.py``) and a tiny traced
run of the harness."""
import gzip
import re

import pytest

import program_trace as P
from conftest import BENCH

DATA = BENCH / "tests" / "data"
RECORDED = DATA / "serve.xplane.pb.gz"
MS = 1_000_000


def _ev():
    """One step: an admission holding a prefill, a decode, the logits, and
    sampling holding a retirement (publish, then the page store). The
    prefill program (1), an eager scatter and the decode program (2) run
    on the device, on the host's clock; in the decode program a layer
    ``while`` holds an attention ``while`` (holding a weight quantization)
    and the integer core."""
    serve = [("serve.step", 0, 100), ("serve.admit", 0, 40),
             ("serve.prefill", 10, 30), ("serve.decode", 50, 70),
             ("serve.logits", 70, 80), ("serve.sample", 80, 100),
             ("serve.retire", 85, 95), ("serve.publish", 86, 90),
             ("serve.store_pages", 90, 94)]
    ops = [("fusion.1", 12, 28), ("scatter.1", 31, 35), ("while.5", 52, 68),
           ("while.6", 53, 61), ("fusion.2", 54, 58), ("dot.3", 61, 67)]
    modules = [("jit__lambda(1)", 12, 28), ("jit_scatter(3)", 31, 35),
               ("jit__lambda(2)", 52, 68)]
    ms = lambda xs: [(n, s * MS, e * MS) for n, s, e in xs]  # noqa: E731
    return {"serve": ms(serve), "bench": [],
            "devices": {"/device:TPU:0": {"ops": ms(ops),
                                          "modules": ms(modules)}},
            "scopes": {"jit__lambda(1)": {"fusion.1": "qmm.core"},
                       "jit__lambda(2)": {"while.6": "attn",
                                          "fusion.2": "qmm.wquant",
                                          "dot.3": "qmm.core"}}}


def test_host_spans_less_the_spans_inside():
    ev = _ev()
    assert P.steps(ev) == 1
    got = {m: P.span_ms_per_step(ev, *a) for m, a in P.HOST_METRICS.items()}
    assert got == pytest.approx({"admit_ms_per_step": 20.0,
                                 "publish_ms_per_step": 4.0,
                                 "logits_ms_per_step": 10.0,
                                 "sample_ms_per_step": 10.0})


def test_self_time_under_nested_while():
    ev = _ev()
    d = P.device_by_scope(ev)
    assert d["decode_runs"] == 1
    assert d["decode_ms"] == pytest.approx(16.0)
    # while.5: 16 - 8 (while.6) - 6 (dot.3); while.6: 8 - 4 (fusion.2)
    assert d["scope_ms"] == pytest.approx({"unscoped": 2.0, "attn": 4.0,
                                           "qmm.wquant": 4.0,
                                           "qmm.core": 6.0})
    assert sum(d["scope_ms"].values()) == pytest.approx(d["decode_ms"])
    assert P.scope_ms(ev, "attn") == pytest.approx(4.0)
    assert P.scope_ms(ev, "qmm.xquant") == 0.0
    assert P.eager_ms_per_step(ev) == pytest.approx(4.0)


def test_idle_charged_to_the_innermost_serve_span():
    idle = P.idle_by_span(_ev())
    # busy [12, 28], [31, 35], [52, 68] of [0, 100]
    assert idle == pytest.approx({
        "serve.admit": 0.016, "serve.prefill": 0.004, "serve.step": 0.010,
        "serve.decode": 0.004, "serve.logits": 0.010, "serve.sample": 0.010,
        "serve.retire": 0.002, "serve.publish": 0.004,
        "serve.store_pages": 0.004})


def test_a_trace_without_the_programs_spans_reads_nothing():
    ev = _ev()
    ev["serve"], ev["scopes"] = [], {"jit__lambda(2)": {}}
    ev["bench"] = [("bench.step", 0, 100 * MS)]
    assert P.steps(ev) == 0
    for m, a in P.HOST_METRICS.items():
        assert P.span_ms_per_step(ev, *a) is None
    assert P.device_by_scope(ev) is None
    assert P.scope_ms(ev, "attn") is None
    assert P.eager_ms_per_step(ev) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(<lambda>)/while/body/closed_call/qmm.core/dot_general",
     "qmm.core"),
    ("jit(<lambda>)/while/body/closed_call/attn/while/body/mul", "attn"),
    ("jit(<lambda>)/lm_head/qmm.dequant/mul", "qmm.dequant"),
    ("reshape;qmm.xquant/reshape", "qmm.xquant"),
    ("jit(<lambda>)/while/body/dynamic_slice", None)])
def test_innermost_scope(op_name, scope):
    assert P.innermost_scope(op_name) == scope


def _msg(*fields):
    """Protocol-buffer bytes of (field, value) pairs: ints as varints,
    str and bytes length-delimited."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_program_scopes_from_the_metadata_plane():
    inst = lambda name, op: _msg((1, name), (7, _msg((2, op))))  # noqa
    hlo = _msg((1, _msg((1, "jit__lambda"), (3, _msg(
        (1, "main"), (2, inst("fusion.1", "jit(f)/attn/mul")),
        (2, inst("dot.2", "jit(f)/qmm.core/dot_general")),
        (2, inst("copy.3", "jit(f)/copy")))))))
    plane = _msg(
        (1, 5), (2, "/host:metadata"),
        (4, _msg((1, 7), (2, _msg((1, 7), (2, "jit__lambda(7)"),
                                  (5, _msg((1, 1), (6, hlo))))))),
        (4, _msg((1, 8), (2, _msg((1, 8), (2, "jit_scatter(8)"),
                                  (5, _msg((1, 1), (6, hlo))))))),
        (5, _msg((1, 1), (2, _msg((1, 1), (2, "Hlo Proto"))))))
    xspace = _msg((1, _msg((2, "/host:CPU"))), (1, plane))
    assert P.program_scopes(memoryview(xspace)) == {
        "jit__lambda(7)": {"fusion.1": "attn", "dot.2": "qmm.core"}}


# ---- the recorded trace ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return P.load(str(RECORDED))


def test_recorded_trace_has_every_span(recorded):
    names = {n for n, _, _ in recorded["serve"]}
    assert names == {"serve.step", "serve.admit", "serve.match",
                     "serve.gather", "serve.prefill", "serve.write_slot",
                     "serve.decode", "serve.logits", "serve.sample",
                     "serve.retire", "serve.publish", "serve.store_pages"}
    for m, a in P.HOST_METRICS.items():
        assert P.span_ms_per_step(recorded, *a) > 0, m


def test_recorded_decode_time_by_scope(recorded):
    d = P.device_by_scope(recorded)
    assert d["decode_runs"] == len(P.spans(recorded, "serve.decode"))
    scopes = d["scope_ms"]
    for s in ("qmm.core", "qmm.wquant", "qmm.xquant", "attn"):
        assert scopes.get(s, 0) > 0, s
    # self times nest inside the program: their sum is at most its time
    assert 0.5 * d["decode_ms"] < sum(scopes.values()) <= d["decode_ms"]
    assert P.eager_ms_per_step(recorded) > 0
    idle = P.idle_by_span(recorded)
    assert set(idle) <= {n for n, _, _ in recorded["serve"]} | {"none"}


def test_recorded_scopes_match_the_compiled_text(recorded):
    """The scope map read from the trace's HloProto equals the one in the
    decode program's compiled HLO text, recorded beside it."""
    text = gzip.decompress((DATA / "serve.decode.hlo.txt.gz").read_bytes()
                           ).decode()
    from_text = {}
    for name, op in re.findall(
            r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{op_name="([^"]*)"',
            text, re.M):
        scope = P.innermost_scope(op)
        if scope:
            from_text[name] = scope
    dev = recorded["devices"][sorted(recorded["devices"])[0]]
    decode = [n for n, k in P._launch_kinds(recorded, dev).items()
              if k == "decode"]
    assert len(decode) == 1
    assert recorded["scopes"][decode[0]] == from_text


# ---- the harness ------------------------------------------------------------

def test_rehearsal_traced_run_reads_program_spans(run_tiny):
    """A tiny traced run on the CPU: the host metrics read numbers; the
    device metrics read nothing, as the CPU trace has no device plane."""
    res = run_tiny("batch", trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    for name in ("admit_ms_per_step", "logits_ms_per_step",
                 "sample_ms_per_step"):
        assert m[name]["value"] > 0, name
    assert m["publish_ms_per_step"]["value"] >= 0
    for name in ("attn_ms", "wquant_ms", "core_ms", "eager_ms_per_step"):
        assert name not in m

"""bench/trace.py on a small recorded trace and on hand-made events."""
import pytest

import trace as T
from conftest import BENCH

RECORDED = BENCH / "tests" / "data" / "probe.xplane.pb"


def _ev():
    # one device; host spans: a step [0, 100] holding a prefill call
    # [10, 40] and a decode call [60, 90]; a wait [100, 150]
    ms = 1_000_000
    return {"devices": {"/device:TPU:0": {
        "ops": [("fusion.1", 12 * ms, 30 * ms), ("fusion.1", 25 * ms,
                                                   38 * ms),
                ("dot.2", 62 * ms, 88 * ms)],
        "modules": [("jit__lambda", 12 * ms, 38 * ms),
                    ("jit__lambda", 62 * ms, 88 * ms)]}},
        "host": [("bench.step", 0, 100 * ms),
                 ("bench.prefill", 10 * ms, 40 * ms),
                 ("bench.decode", 60 * ms, 90 * ms),
                 ("bench.wait", 100 * ms, 150 * ms)]}


def test_busy_union_idle_and_attribution():
    r = T.reduce(_ev())
    assert r["window_s"] == pytest.approx(0.150)
    assert r["busy_s"] == pytest.approx(0.026 + 0.026)   # overlap merged
    assert r["program_s"]["prefill"] == [pytest.approx(0.026)]
    assert r["program_s"]["decode"] == [pytest.approx(0.026)]
    ops = dict(r["top_ops"])
    assert ops["fusion.1"] == pytest.approx(0.018 + 0.013)
    assert ops["dot.2"] == pytest.approx(0.026)
    idle = dict(r["idle_by_span"])
    # idle [0,12]: 10 ms in the step, 2 ms in prefill; [38,62]: 2 ms in
    # prefill, 20 ms in the step, 2 ms in decode; [88,150]: 2 ms in
    # decode, 10 ms in the step, 50 ms waiting
    assert idle["bench.step"] == pytest.approx(0.010 + 0.020 + 0.010)
    assert idle["bench.prefill"] == pytest.approx(0.004)
    assert idle["bench.decode"] == pytest.approx(0.004)
    assert idle["bench.wait"] == pytest.approx(0.050)
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_union():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_recorded_trace():
    """A trace recorded on a TPU v5e: three steps, each a prefill call and
    a decode call, then a wait (the harness's span names)."""
    ev = T.load(str(RECORDED))
    assert ev["devices"], "no device plane"
    r = T.reduce(ev)
    assert len(r["program_s"]["prefill"]) == 3
    assert len(r["program_s"]["decode"]) == 3
    assert 0 < r["busy_s"] < r["window_s"]
    assert dict(r["idle_by_span"]).get("bench.wait", 0) > 0.002

"""The generators: schedules and lengths from a seed."""
import numpy as np
import pytest

import harness
from conftest import BENCH


def _traffic(mix_name, seed, seconds=30.0, vocab=49152, slots=32):
    mix = harness.load_json(BENCH / "traffic" / f"{mix_name}.json")
    gen = harness.load_module(BENCH / "gen" / f"{mix['kind']}.py")
    return mix, gen.Traffic(mix, seed, vocab, slots, seconds)


def _drain_closed(t, n):
    out = []
    while len(out) < n:
        out += t.poll(0.0, 0)
    return out[:n]


def test_closed_same_sizes_any_seed_and_reproducible():
    mix, a = _traffic("batch", 5)
    _, b = _traffic("batch", 2**33 + 5)
    _, a2 = _traffic("batch", 5)
    n = 32 + mix["pool"]        # the staggered first slots, then one set
    ra, rb, ra2 = (_drain_closed(t, n) for t in (a, b, a2))
    assert sorted(len(r["prompt"]) for r in ra) == \
        sorted(len(r["prompt"]) for r in rb)
    assert sorted(r["max_new"] for r in ra[32:]) != []
    assert [len(r["prompt"]) for r in ra] != [len(r["prompt"]) for r in rb]
    assert all((x["prompt"] == y["prompt"]).all() and
               x["max_new"] == y["max_new"] for x, y in zip(ra, ra2))
    p, o = mix["prompt"], mix["output"]
    for r in ra:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert 1 <= r["max_new"] <= o["max"]
    # the first slots run to a share of their draw, spread over (0, 1]
    assert min(r["max_new"] for r in ra[:32]) < o["min"]
    assert all(r["max_new"] >= o["min"] for r in ra[32:])


def test_closed_keeps_depth_outstanding():
    mix, t = _traffic("batch", 1)
    assert len(t.poll(0.0, 0)) == mix["depth"]
    assert len(t.poll(1.0, mix["depth"])) == 0
    assert len(t.poll(1.0, mix["depth"] - 3)) == 3


@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_page_counts_cover_every_request(seed):
    _, t = _traffic("batch", seed)
    store, gather = t.page_counts(8)
    reqs = _drain_closed(t, 300)
    assert {(len(r["prompt"]) + r["max_new"] - 1) // 8 for r in reqs} <= store
    assert gather == set()

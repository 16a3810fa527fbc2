"""Reduce a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a small
recorded trace (``bench/tests/data/``):

1. ``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   returns plain event lists: device operations and program executions
   (per device plane), and the benchmark's host spans (``bench.*``).
2. ``reduce(ev)`` works out, over the traced stretch:
   - busy time: the union of the intervals in which an operation ran on
     each device, averaged over the devices;
   - device time of each execution of the engine's compiled programs
     (``jit__lambda``), attributed in order to the host spans
     (``bench.prefill`` / ``bench.decode``) that the benchmark put around
     the calls that launched them, each waited on before the next;
   - the top device operations by total time;
   - idle time on the device, split over the innermost host span open at
     each moment (``bench.wait``: waiting for arrivals; ``bench.step``
     outside any call: the engine's host work), summed by span.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

HOST_SPANS = ("bench.prefill", "bench.decode", "bench.step", "bench.wait")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PROGRAM = "jit__lambda"      # the Engine's jitted prefill and decode


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{'devices': {plane: {'ops': [(name, start, end)], 'modules': [...]}},
    'host': [(name, start, end)]}, times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    d[key].extend((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _innermost(host, starts, t):
    """Name of the latest-starting host span that contains ``t``: spans
    nest, so that is the innermost."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        name, s, e = host[i]
        if e >= t:
            return name
        if t - s > 60e9:
            break
    return "none"


def _overlap(gaps, segments):
    """(segment name, overlap) of sorted gaps with sorted segments."""
    i = 0
    for a, b in gaps:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            yield name, min(b, e) - max(a, s)
            j += 1


def reduce(ev: dict) -> dict:
    """Busy seconds, per-program device seconds, top ops and idle gaps.
    The stretch is from the first to the last host span."""
    host = sorted(ev["host"], key=lambda x: x[1])
    if not ev["devices"] or not host:
        return {}
    starts = [s for _, s, _ in host]
    lo, hi = starts[0], max(e for _, _, e in host)
    bounds = sorted({b for _, s, e in host for b in (s, e)})
    segments = [(a, b, _innermost(host, starts, (a + b) / 2))
                for a, b in zip(bounds, bounds[1:]) if b > a]
    busy, ops, calls, idle = [], defaultdict(float), defaultdict(list), \
        defaultdict(float)
    launches = [(n.split(".")[1], s, e) for n, s, e in host
                if n in ("bench.prefill", "bench.decode")]
    for dev in ev["devices"].values():
        progs = sorted((s, e) for name, s, e in dev["modules"]
                       if name.startswith(PROGRAM))
        pairs = list(zip(launches, progs))
        # device and host clocks differ by an offset; no program starts
        # before the call that launched it
        shift = max([0] + [hs - s for (_, hs, _), (s, _) in pairs])
        for (kind, _, _), (s, e) in pairs:
            calls[kind].append((e - s) * 1e-9)
        inside = [(name, max(s + shift, lo), min(e + shift, hi))
                  for name, s, e in dev["ops"]
                  if e + shift > lo and s + shift < hi]
        merged = union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in inside:
            ops[op_name(name)] += (e - s) * 1e-9
        gaps, prev = [], lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for name, t in _overlap(gaps, segments):
            idle[name] += t * 1e-9
    n = len(ev["devices"])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) * 1e-9 / n,
            "program_s": dict(calls),
            "top_ops": sorted(ops.items(), key=lambda x: -x[1])[:10],
            "idle_by_span": sorted(((k, v / n) for k, v in idle.items()),
                                   key=lambda x: -x[1])[:10]}

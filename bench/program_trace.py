"""Per-layer numbers from the program's own spans and scopes in a trace.

The program marks its work in two ways, both recorded by the profiler
that a ``--trace 1`` run starts:

- host spans (``jax.profiler.TraceAnnotation``) named ``serve.*`` inside
  ``repro.serve.Engine``: ``serve.step`` around each step; ``serve.admit``
  (holding ``serve.match``, ``serve.gather``, ``serve.prefill``,
  ``serve.write_slot``); ``serve.decode``; ``serve.logits``;
  ``serve.sample``; ``serve.retire`` (holding ``serve.publish`` and
  ``serve.store_pages``);
- named scopes (``jax.named_scope``) in the jitted model step, which land
  in each HLO instruction's ``metadata={op_name=...}``: ``qmm.wquant``,
  ``qmm.xquant``, ``qmm.core``, ``qmm.dequant`` (every projection),
  ``attn`` (attention over the cache), ``lm_head``, ``layer_scan`` (the
  layer loop's slicing of the stacked weights and cache, and its
  write-back of the cache) and ``layer`` (norms, residuals, activations).

``load(path)`` reads the ``.xplane.pb`` once per process and returns the
host spans, the device operations and modules, and, for each compiled
program, a map from instruction name to its innermost scope. The HLO of
every live program is in the trace itself (the profiler's
``/host:metadata`` plane holds one ``HloProto`` per program), so nothing
is read from the process that ran it. The reductions below turn that into
per-layer numbers. A trace of a program that has none of these spans or
scopes gives ``None`` from each, never an error.

    python3 bench/program_trace.py <trace dir>

prints every number, the coverage of the spans and scopes, and the idle
device time by the innermost span open at each moment.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import sys
from collections import defaultdict
from pathlib import Path

import trace as T

SCOPES = ("qmm.wquant", "qmm.xquant", "qmm.core", "qmm.dequant", "attn",
          "lm_head", "layer_scan", "layer")
PROGRAM = T.PROGRAM          # the Engine's jitted prefill and decode
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


# ---------------------------------------------------------------------------
# Protocol-buffer wire format: just enough to read the HLO the profiler
# stores (XSpace > XPlane > event metadata > stats > HloProto)
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields and fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} is not read here")
        yield field, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def innermost_scope(op_name: str):
    """The last of ``SCOPES`` on an op_name path, or None. XLA joins the
    op_names of merged instructions with ';'."""
    for part in reversed(op_name.replace(";", "/").split("/")):
        if part in SCOPES:
            return part
    return None


def _hlo_scopes(hlo_proto) -> dict:
    """{instruction name: innermost scope} over every computation of one
    HloProto; instructions with no scope are left out. Field numbers:
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1 and
    .metadata 7; OpMetadata.op_name 2."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            for f3, inst in _fields(comp):
                if f3 != 2:
                    continue
                name, scope = None, None
                for f4, v in _fields(inst):
                    if f4 == 1:
                        name = _text(v)
                    elif f4 == 7:
                        for f5, w in _fields(v):
                            if f5 == 2:
                                scope = innermost_scope(_text(w))
                if name and scope:
                    out[name] = scope
    return out


def program_scopes(xspace) -> dict:
    """{program name as the device trace gives it: {instruction: scope}},
    from the HloProto stats of the metadata plane. Field numbers: XSpace
    .planes 1; XPlane.name 2, .event_metadata 4 (map: key 1, value 2),
    .stat_metadata 5; XEventMetadata.name 2, .stats 5; XStatMetadata.name
    2; XStat.metadata_id 1, .bytes_value 6."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        parts = defaultdict(list)
        for g, v in _fields(plane):
            if g in (2, 4, 5):
                parts[g].append(v)
        if not parts[2] or _text(parts[2][0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in parts[5]:
            meta = dict(_fields(entry)).get(2)
            if meta is not None:
                m = dict(_fields(meta))
                stat_names[m.get(1)] = _text(m.get(2, b""))
        for entry in parts[4]:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            name, protos = None, []
            for g, v in _fields(meta):
                if g == 2:
                    name = _text(v)
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT \
                            and 6 in stat:
                        protos.append(stat[6])
            if name and name.startswith(PROGRAM):
                scopes = {}
                for p in protos:
                    scopes.update(_hlo_scopes(p))
                out[name] = scopes
    return out


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{'serve': [(name, start, end)] the program's serve.* spans,
    'bench': [...] the benchmark's bench.* spans, 'devices': {plane:
    {'ops': [(instruction, start, end)], 'modules': [(name, start,
    end)]}}, 'scopes': {program: {instruction: scope}}}; times in ns,
    each list sorted by start. A path ending in .gz is read unzipped."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    serve, bench, devices = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    d[key].extend((T.op_name(e.name) if key == "ops"
                                   else e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        serve.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name.startswith("bench."):
                        bench.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    for d in devices.values():
        d["ops"].sort(key=lambda x: (x[1], -x[2]))
        d["modules"].sort(key=lambda x: x[1])
    return {"serve": sorted(serve, key=lambda x: x[1]),
            "bench": sorted(bench, key=lambda x: x[1]),
            "devices": devices,
            "scopes": program_scopes(memoryview(raw))}


def for_run(run):
    """The loaded trace of a harness run, or None where it has none."""
    if not run.win.trace_dir:
        return None
    try:
        return load(T.find(run.win.trace_dir))
    except FileNotFoundError:
        return None


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def spans(ev, name):
    return [(s, e) for n, s, e in ev["serve"] if n == name]


def steps(ev) -> int:
    return len(spans(ev, "serve.step"))


def span_ms_per_step(ev, name, minus=()):
    """Wall time of the ``name`` spans less that of the ``minus`` spans
    inside them, per ``serve.step``, in ms; None where no step was
    traced."""
    n = steps(ev)
    if not n:
        return None
    outer = spans(ev, name)
    total = sum(e - s for s, e in outer)
    inner = sorted(x for m in minus for x in spans(ev, m))
    starts = [s for s, _ in inner]
    for s, e in outer:
        i = bisect.bisect_left(starts, s)
        while i < len(inner) and inner[i][0] < e:
            if inner[i][1] <= e:
                total -= inner[i][1] - inner[i][0]
            i += 1
    return 1e-6 * total / n


# ---------------------------------------------------------------------------
# Device time
# ---------------------------------------------------------------------------

def self_times(ops):
    """[(instruction, start, self ns)] of ops sorted by (start, -end). Ops
    on one line nest (a ``while`` holds its body's ops): an op's self time
    is its duration less that of the ops directly inside it."""
    out, stack = [], []          # stack of indices into out, with ends
    for name, s, e in ops:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent][2] -= min(e, stack[-1][1]) - s
        out.append([name, s, e - s])
        stack.append((len(out) - 1, e))
    return out


def _launch_kinds(ev, dev):
    """{program name: 'prefill' | 'decode'}: the Engine's launches
    (``serve.prefill`` / ``serve.decode`` spans) in order, against its
    programs' executions on the device in order (one stream, run in the
    order launched); each program takes the kind most of its executions
    got."""
    launches = [n.split(".")[1] for n, _, _ in ev["serve"]
                if n in ("serve.prefill", "serve.decode")]
    runs = [name for name, _, _ in dev["modules"]
            if name.startswith(PROGRAM)]
    votes = defaultdict(lambda: defaultdict(int))
    for kind, name in zip(launches, runs):
        votes[name][kind] += 1
    return {name: max(v, key=v.get) for name, v in votes.items()}


def device_by_scope(ev):
    """{'decode_runs': executions of the decode program, 'scope_ms': {scope
    or 'unscoped': decode self time per execution, ms}, 'decode_ms':
    device time per decode execution, 'eager_ms': device self time outside
    the prefill and decode programs, in total, ms}, from the first device;
    None where the trace has no device, no decode launch or no scope."""
    if not ev["devices"] or not ev["scopes"]:
        return None
    dev = ev["devices"][sorted(ev["devices"])[0]]
    kinds = _launch_kinds(ev, dev)
    runs = [(name, s, e) for name, s, e in dev["modules"]
            if name in kinds]
    decode = [(name, s, e) for name, s, e in runs
              if kinds[name] == "decode"]
    if not decode:
        return None
    starts = [s for _, s, _ in runs]
    by_scope, eager = defaultdict(float), 0.0
    for inst, s, t in self_times(dev["ops"]):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][2]:
            name = runs[i][0]
            if kinds[name] == "decode":
                scope = ev["scopes"].get(name, {}).get(inst, "unscoped")
                by_scope[scope] += t
        else:
            eager += t
    n = len(decode)
    return {"decode_runs": n,
            "decode_ms": 1e-6 * sum(e - s for _, s, e in decode) / n,
            "scope_ms": {k: 1e-6 * v / n for k, v in by_scope.items()},
            "eager_ms": 1e-6 * eager}


def scope_ms(ev, scope):
    """Decode self time under ``scope`` per decode execution, ms."""
    d = device_by_scope(ev)
    if d is None or not any(k in SCOPES for k in d["scope_ms"]):
        return None
    return d["scope_ms"].get(scope, 0.0)


def eager_ms_per_step(ev):
    """Device self time outside the prefill and decode programs per
    ``serve.step``, ms."""
    d = device_by_scope(ev)
    n = steps(ev)
    return d["eager_ms"] / n if d is not None and n else None


# ---------------------------------------------------------------------------
# Idle device time by the innermost host span
# ---------------------------------------------------------------------------

def idle_by_span(ev):
    """{span name: idle device seconds} over the stretch from the first to
    the last host span, each gap charged to the innermost ``bench.*`` or
    ``serve.*`` span open at that moment ('none' outside all), from the
    first device; the same reduction as ``trace.reduce``'s, over both
    kinds of span. Device and host clocks differ by an offset: no program
    starts before the ``serve.*`` span that launched it."""
    host = sorted(ev["bench"] + ev["serve"], key=lambda x: x[1])
    if not ev["devices"] or not host:
        return None
    dev = ev["devices"][sorted(ev["devices"])[0]]
    launches = [s for n, s, _ in ev["serve"]
                if n in ("serve.prefill", "serve.decode")]
    runs = [s for name, s, _ in dev["modules"] if name.startswith(PROGRAM)]
    shift = max([0] + [h - d for h, d in zip(launches, runs)])
    starts = [s for _, s, _ in host]
    lo, hi = starts[0], max(e for _, _, e in host)
    busy = T.union((max(s + shift, lo), min(e + shift, hi))
                   for _, s, e in dev["ops"]
                   if e + shift > lo and s + shift < hi)
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    bounds = sorted({b for _, s, e in host for b in (s, e)})
    segments = [(a, b, T._innermost(host, starts, (a + b) / 2))
                for a, b in zip(bounds, bounds[1:]) if b > a]
    idle = defaultdict(float)
    for name, t in T._overlap(gaps, segments):
        idle[name] += t * 1e-9
    return dict(idle)


def summary(ev) -> dict:
    """Every number this module reads, with the coverage checks."""
    d = device_by_scope(ev) or {}
    idle = idle_by_span(ev) or {}
    host = {m: span_ms_per_step(ev, *HOST_METRICS[m]) for m in HOST_METRICS}
    outside = sum(v for k, v in idle.items() if k in ("bench.step", "none"))
    scoped = sum(v for k, v in d.get("scope_ms", {}).items()
                 if k != "unscoped")
    return {"steps": steps(ev), "host_ms_per_step": host,
            "decode": d, "scoped_decode_share":
                scoped / d["decode_ms"] if d.get("decode_ms") else None,
            "idle_s": idle, "idle_outside_serve_share":
                outside / sum(idle.values()) if idle else None}


# the host metrics: (span, spans inside it that are left out)
HOST_METRICS = {
    "admit_ms_per_step": ("serve.admit", ("serve.prefill", "serve.retire")),
    "publish_ms_per_step": ("serve.publish", ()),
    "logits_ms_per_step": ("serve.logits", ()),
    "sample_ms_per_step": ("serve.sample", ("serve.retire",)),
}


if __name__ == "__main__":
    print(json.dumps(summary(load(T.find(sys.argv[1]))), indent=1))

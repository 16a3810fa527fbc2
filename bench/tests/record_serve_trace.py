"""Record the small trace that ``test_program_trace.py`` reads.

    python3 bench/tests/record_serve_trace.py [--out bench/tests/data]

Run on one TPU chip. A reduced SmolLM (two layers, d_model 128, vocab 512,
bf16, exact int8 core) is served through ``Engine`` (4 slots, max_len 64,
page size 8, prefix cache on). One set of requests compiles and runs every
program; a second set of the same lengths, other tokens, is served under
``jax.profiler`` with the Python tracer off: admissions, decode steps and
retirements, one of them at admission. Every prompt takes the same
prefill bucket. To keep the file small, the trace is pared before it is
written (``pare``): it keeps the device's op and module lines, the host
threads that hold ``serve.*`` spans, and, of the HLO the profiler stores
for every live program, each instruction's name and op_name in the
Engine's two programs; op events keep the instruction's name for their
text. Writes ``serve.xplane.pb.gz`` and the decode
program's compiled HLO text, ``serve.decode.hlo.txt.gz``, and prints what
``program_trace.summary`` reads from the trace.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

LENGTHS = [(12, 5), (15, 3), (9, 1), (16, 4), (11, 3)]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(f: int, v) -> bytes:
    """One protocol-buffer field: a varint, or length-delimited bytes."""
    if isinstance(v, int):
        return _varint(f << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else bytes(v)
    return _varint(f << 3 | 2) + _varint(len(v)) + v


def _message(fields) -> bytes:
    return b"".join(_field(f, v) for f, v in fields)


def _min_hlo(hlo) -> bytes:
    """An HloProto holding each instruction's name and op_name only."""
    import program_trace as P
    comps = []
    for f, module in P._fields(hlo):
        for f2, comp in (P._fields(module) if f == 1 else ()):
            if f2 != 3:
                continue
            insts = []
            for f3, inst in P._fields(comp):
                if f3 != 2:
                    continue
                d = dict(P._fields(inst))
                op = dict(P._fields(d.get(7, b""))).get(2, b"")
                insts.append((2, _message([(1, d.get(1, b"")),
                                           (7, _message([(2, op)]))])))
            comps.append((3, _message(insts)))
    return _message([(1, _message(comps))])


def pare(xspace) -> bytes:
    """The XSpace less what the tests do not read (module docstring)."""
    import program_trace as P
    import trace as T
    planes = []
    for f, plane in P._fields(xspace):
        if f != 1:
            continue
        fields = list(P._fields(plane))
        name = next((P._text(v) for g, v in fields if g == 2), "")
        meta = {}                           # id: (name, stats)
        for g, v in fields:
            if g == 4:
                entry = dict(P._fields(v))
                m = list(P._fields(entry[2]))
                meta[entry[1]] = (next((P._text(w) for h, w in m if h == 2),
                                       ""), [w for h, w in m if h == 5])
        keep = []
        for g, v in fields:
            if g == 3:                      # lines
                line = list(P._fields(v))
                line_name = next((P._text(w) for h, w in line if h == 2),
                                 "")
                events = {dict(P._fields(w)).get(1) for h, w in line
                          if h == 4}
                if name.startswith("/device:") and line_name not in (
                        "XLA Ops", "XLA Modules"):
                    continue
                if name.startswith("/host:") and not any(
                        meta[i][0].startswith(("serve.", "bench."))
                        for i in events):
                    continue
            elif g == 4:                    # event metadata
                i = dict(P._fields(v))[1]
                event, stats = meta[i]
                if name == P.METADATA_PLANE:
                    if not event.startswith(P.PROGRAM):
                        continue
                    stats = [(5, _message([(1, st[1]),
                                           (6, _min_hlo(st[6]))]))
                             for st in map(dict, map(P._fields, stats))
                             if 6 in st]
                    v = _message([(1, i), (2, _message(
                        [(1, i), (2, event)] + stats))])
                elif name.startswith("/device:"):
                    v = _message([(1, i), (2, _message(
                        [(1, i), (2, T.op_name(event))]))])
            keep.append((g, v))
        if name.startswith("/device:TPU:") or name in (
                "/host:CPU", P.METADATA_PLANE):
            planes.append((1, _message(keep)))
    return _message(planes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(BENCH / "tests" / "data"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import program_trace as P
    import trace as T
    from repro.configs import registry
    from repro.models import transformer_lm as TLM
    from repro.quant.quantize import for_lm
    from repro.serve import Engine, ServeRequest

    cfg = dataclasses.replace(
        registry.reduced("smollm-135m", n_layers=2, d_model=128, n_heads=4,
                         n_kv_heads=1, d_ff=256, vocab=512, vocab_pad=512,
                         head_dim=32),
        param_dtype=jnp.bfloat16, quant=for_lm("int8_exact"))
    params = jax.block_until_ready(TLM.init(cfg, jax.random.PRNGKey(0)))
    jax.clear_caches()
    eng = Engine(cfg, params, slots=4, max_len=64, page_size=8)
    rng = np.random.default_rng(0)

    def serve(first_rid):
        for i, (n, m) in enumerate(LENGTHS):
            eng.submit(ServeRequest(rid=first_rid + i,
                                    prompt=rng.integers(1, cfg.vocab, n),
                                    max_new=m))
        while eng.step():
            pass
        jax.block_until_ready(eng.pool)

    serve(0)
    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    serve(100)
    jax.profiler.stop_trace()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    xplane = out / "serve.xplane.pb.gz"
    xplane.write_bytes(gzip.compress(
        pare(memoryview(Path(T.find(str(tmp))).read_bytes())), 9))
    shutil.rmtree(tmp)
    hlo = eng._decode.lower(eng.params, eng.pool,
                            jnp.zeros((eng.slots, 1), jnp.int32),
                            jnp.zeros((eng.slots,), jnp.int32)
                            ).compile().as_text()
    (out / "serve.decode.hlo.txt.gz").write_bytes(
        gzip.compress(hlo.encode()))
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "bytes": {p.name: p.stat().st_size
                                for p in out.glob("serve.*.gz")},
                      "summary": P.summary(P.load(str(xplane)))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Trip-count-corrected HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts each while-loop body ONCE, which
undercounts scan-based programs by the trip count (layers x microbatches x
flash chunks here). This walker parses the post-SPMD HLO text, builds the
computation call graph (while bodies, calls, fusions, conditionals), infers
while trip counts from their condition computations, and accumulates:

  flops            — dot ops: 2 * prod(result_dims) * contraction size
                     (convolutions likewise; elementwise ignored: <1%)
  hbm_bytes        — per top-level op: result bytes + operand bytes of
                     fusion/dot/collective ops (fusion-internal traffic
                     stays in registers/VMEM and is not counted)
  collective_bytes — per collective op: result bytes, by collective kind

All numbers are per-device (post-SPMD shapes) and execution-count weighted.
Validated against an unrolled lowering in tests/test_hlo_costs.py.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
               "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
               "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
               "c128": 16, "token": 0, "opaque": 0}

_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+) \(.*\) -> .*\{")
_WHILE = re.compile(r"while\(.*?\), condition=%?([\w\.\-]+), "
                    r"body=%?([\w\.\-]+)")
_CALLS = re.compile(r"(?:calls=|to_apply=)%?([\w\.\-]+)")
_CONST_INT = re.compile(r"s32\[\](?:\{[^}]*\})? constant\((\d+)\)")
_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def _shape_bytes(stype: str, dims: str) -> int:
    n = DTYPE_BYTES.get(stype, 4)
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _first_shape(line: str) -> Tuple[Optional[str], Optional[str]]:
    m = _SHAPE.search(line)
    return (m.group(1), m.group(2)) if m else (None, None)


def _all_shapes(seg: str) -> List[Tuple[str, str]]:
    return _SHAPE.findall(seg)


class HloCost:
    def __init__(self, hlo: str):
        self._symcache: Dict[str, Dict[str, Tuple[str, str]]] = {}
        self.computations = self._split(hlo)
        self.trip = {}            # body computation -> trip count
        self._find_trips()
        self.flops = 0.0
        self.flops_int8 = 0.0        # dots with int8 operands (2x MXU rate)
        self.hbm_bytes = 0.0
        self.hbm_bytes_dots = 0.0    # dot/conv io only (TPU-fusion lower
                                     # bound: elementwise chains fuse away)
        self.collectives: Dict[str, float] = defaultdict(float)
        entry = self._entry_name(hlo)
        self._walk(entry, 1.0, set(), True)

    # -- parsing ----------------------------------------------------------
    def _split(self, hlo: str) -> Dict[str, List[str]]:
        comps: Dict[str, List[str]] = {}
        cur = None
        for line in hlo.splitlines():
            m = _COMP_HDR.match(line.strip()) if not line.startswith(" ") \
                else None
            if m and ("{" in line):
                cur = m.group(1)
                comps[cur] = []
            elif cur is not None:
                if line.startswith("}"):
                    cur = None
                else:
                    comps[cur].append(line)
        return comps

    def _entry_name(self, hlo: str) -> str:
        for line in hlo.splitlines():
            if line.startswith("ENTRY "):
                m = re.match(r"ENTRY %?([\w\.\-]+)", line)
                if m:
                    return m.group(1)
        return next(iter(self.computations))

    def _find_trips(self):
        for comp, lines in self.computations.items():
            for line in lines:
                m = _WHILE.search(line)
                if not m:
                    continue
                cond, body = m.groups()
                n = 0
                for cline in self.computations.get(cond, []):
                    for c in _CONST_INT.findall(cline):
                        n = max(n, int(c))
                self.trip[body] = max(n, 1)

    # -- walking ----------------------------------------------------------
    def _symtab(self, comp: str) -> Dict[str, Tuple[str, str]]:
        """op name -> (dtype, dims) of its result, within one computation."""
        if comp in self._symcache:
            return self._symcache[comp]
        tab: Dict[str, Tuple[str, str]] = {}
        for line in self.computations.get(comp, []):
            m = re.match(r"\s*(?:ROOT )?%([\w\.\-]+) = (\w+)\[([\d,]*)\]",
                         line)
            if m:
                tab[m.group(1)] = (m.group(2), m.group(3))
        self._symcache[comp] = tab
        return tab

    @staticmethod
    def _operands(ls: str) -> List[str]:
        m = re.search(r"[\w\-]+\(([^)]*)\)", ls[ls.index("=") + 1:]
                      if "=" in ls else ls)
        if not m:
            return []
        return re.findall(r"%([\w\.\-]+)", m.group(1))

    def _walk(self, comp: str, mult: float, stack, top: bool = True):
        """`top` marks computations whose tensors live in HBM (entry, while
        bodies/conds, call/conditional branches). Fusion/reduce/sort/scatter
        callees are walked only for flops/collectives — their intermediate
        traffic stays in VMEM/registers."""
        if comp not in self.computations or comp in stack:
            return
        stack = stack | {comp}
        for line in self.computations[comp]:
            ls = line.strip()
            if not ls.startswith("%") and not ls.startswith("ROOT"):
                continue
            m = _WHILE.search(ls)
            if m:
                cond, body = m.groups()
                self._walk(body, mult * self.trip.get(body, 1), stack, top)
                self._walk(cond, mult * self.trip.get(body, 1), stack, top)
                continue
            op = self._opcode(ls)
            if op in ("call", "conditional"):
                for callee in _CALLS.findall(ls):
                    self._walk(callee, mult, stack, top)
            elif op in ("fusion", "map", "reduce", "sort", "scatter",
                        "custom-call", "reduce-window", "select-and-scatter"):
                for callee in _CALLS.findall(ls):
                    self._walk(callee, mult, stack, False)
            self._account(ls, op, mult, self._symtab(comp), top)

    def _opcode(self, ls: str) -> str:
        m = re.search(r"=\s+(?:\w+\[[\d,]*\](?:\{[^}]*\})?\s+|\([^)]*\)\s+)?"
                      r"([\w\-]+)\(", ls)
        return m.group(1) if m else ""

    def _account(self, ls: str, op: str, mult: float, symtab, top: bool):
        if op in _COLL:
            st, dims = _first_shape(ls)
            if st:
                self.collectives[op] += mult * _shape_bytes(st, dims)
                if top:
                    self.hbm_bytes += 2 * mult * _shape_bytes(st, dims)
            return
        if op == "dot":
            f = mult * self._dot_flops(ls, symtab)
            ops_ = self._operands(ls)
            if ops_ and symtab.get(ops_[0], ("", ""))[0] in ("s8", "u8"):
                self.flops_int8 += f
            else:
                self.flops += f
            if top:
                io = mult * self._io_bytes(ls, symtab)
                self.hbm_bytes += io
                self.hbm_bytes_dots += io
            return
        if op == "convolution":
            self.flops += mult * self._conv_flops(ls, symtab)
            if top:
                io = mult * self._io_bytes(ls, symtab)
                self.hbm_bytes += io
                self.hbm_bytes_dots += io
            return
        if top and op in ("fusion", "transpose", "copy",
                          "scatter", "gather", "dynamic-update-slice",
                          "dynamic-slice", "reduce", "sort", "concatenate",
                          "slice", "pad", "select", "add", "multiply",
                          "convert", "exponential", "divide", "subtract",
                          "maximum", "rsqrt", "tanh"):
            self.hbm_bytes += mult * self._io_bytes(ls, symtab)

    def _io_bytes(self, ls: str, symtab, result_only: bool = False) -> float:
        st, dims = _first_shape(ls)
        if st is None:
            return 0.0
        total = _shape_bytes(st, dims)
        if not result_only:
            for name in self._operands(ls)[:8]:
                if name in symtab:
                    total += _shape_bytes(*symtab[name])
        return float(total)

    def _dot_flops(self, ls: str, symtab) -> float:
        st, dims = _first_shape(ls)
        ops = self._operands(ls)
        if st is None or not ops or ops[0] not in symtab:
            return 0.0
        res = [int(x) for x in dims.split(",") if x]
        lhs = [int(x) for x in symtab[ops[0]][1].split(",") if x]
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ls)
        k = 1
        if m:
            for d in m.group(1).split(","):
                if d:
                    k *= lhs[int(d)]
        out = 1
        for d in res:
            out *= d
        return 2.0 * out * k

    def _conv_flops(self, ls: str, symtab) -> float:
        st, dims = _first_shape(ls)
        ops = self._operands(ls)
        if st is None or len(ops) < 2 or ops[1] not in symtab:
            return 0.0
        res = [int(x) for x in dims.split(",") if x]
        ker = [int(x) for x in symtab[ops[1]][1].split(",") if x]
        out = 1
        for d in res:
            out *= d
        kflop = 1
        for d in ker:
            kflop *= d
        cout = res[-1] if res else 1
        return 2.0 * out * (kflop / max(cout, 1))

    def summary(self) -> Dict:
        return {"flops": self.flops, "flops_int8": self.flops_int8,
                "hbm_bytes": self.hbm_bytes,
                "collective_bytes": dict(self.collectives)}


def builtin_cost_analysis(compiled) -> Dict:
    """XLA's own cost analysis of a compiled program, as a flat dict."""
    return dict(compiled.cost_analysis())

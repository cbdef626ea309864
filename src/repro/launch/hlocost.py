"""Trip-count-aware HLO cost model.

``compiled.cost_analysis()`` counts every computation ONCE — a scan over
64 layers reports 1/64th of the real flops.  The scheduled HLO, however,
annotates every while op with ``backend_config={"known_trip_count":{"n":N}}``,
so we walk the module ourselves:

  * multiplicity(entry) = 1; a while op inside a computation with
    multiplicity m executes its body with multiplicity m * trip_count
    (nested scans multiply);
  * flops: counted for ``dot`` ops as 2 * prod(output) * prod(contracted
    lhs dims) * multiplicity (elementwise flops are <5% for these models
    and are ignored);
  * HBM bytes: for traffic-bearing ops (fusion, dot, copy, gather/scatter,
    dynamic-(update-)slice, reduce, transpose, collectives) we charge
    operand + result bytes * multiplicity.  Loop-invariant weights streamed
    each iteration are real HBM traffic and are correctly charged per trip;
  * collective bytes: result-shape bytes * multiplicity per collective op,
    reported by kind.

This is the flops/bytes source for :mod:`repro.perf.profile`'s
per-op cost harvest (cross-checkable against the analytic models in
:mod:`benchmarks.roofline`, DESIGN §8.2); raw cost_analysis numbers
are also recorded for reference.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u64": 8, "s64": 8,
               "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
               "f8e4m3": 1, "token": 0, "s4": 1, "u4": 1}

_SHAPE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_TRIP = re.compile(r'"known_trip_count":\{"n":"?(\d+)"?\}')
_CALLED = re.compile(r"(?:body|condition|to_apply|branch_computations|called_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")

TRAFFIC_OPS = {
    "fusion", "dot", "copy", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "reduce", "transpose", "convolution",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "all-gather-start", "all-reduce-start",
    "reduce-scatter-start", "all-to-all-start", "collective-permute-start",
    "reduce-window", "select-and-scatter", "sort", "concatenate", "pad",
    "slice", "reverse", "cholesky", "triangular-solve", "rng",
}
COLLECTIVES = {
    "all-gather": "all-gather", "all-gather-start": "all-gather",
    "all-reduce": "all-reduce", "all-reduce-start": "all-reduce",
    "reduce-scatter": "reduce-scatter", "reduce-scatter-start": "reduce-scatter",
    "all-to-all": "all-to-all", "all-to-all-start": "all-to-all",
    "collective-permute": "collective-permute",
    "collective-permute-start": "collective-permute",
}
SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "reshape", "broadcast", "iota", "after-all", "partition-id",
            "replica-id", "while", "conditional", "call", "custom-call",
            "bitcast-convert", "convert", "compare", "add", "multiply",
            "subtract", "divide", "select", "exponential", "tanh", "negate",
            "maximum", "minimum", "rsqrt", "sqrt", "log", "and", "or", "not",
            "clamp", "floor", "ceil", "sign", "abs", "power", "remainder",
            "all-gather-done", "all-reduce-done", "reduce-scatter-done",
            "all-to-all-done", "collective-permute-done", "optimization-barrier",
            "get-dimension-size", "rng-bit-generator", "domain", "send",
            "recv", "send-done", "recv-done", "infeed", "outfeed", "map",
            "exponential-minus-one", "log-plus-one", "atan2", "cosine", "sine"}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str):
    m = _SHAPE.search(type_str)
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


class Instruction:
    __slots__ = ("name", "rtype", "op", "line")

    def __init__(self, name, rtype, op, line):
        self.name, self.rtype, self.op, self.line = name, rtype, op, line


def parse_module(hlo: str) -> Dict[str, List[Instruction]]:
    comps: Dict[str, List[Instruction]] = {}
    cur = None
    entry = None
    for line in hlo.splitlines():
        if not line.strip():
            continue
        if not line.startswith(" ") and ("->" in line) and "{" in line:
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.lstrip().startswith("ENTRY"):
                    entry = cur
                continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if m:
            comps[cur].append(Instruction(m.group(1), m.group(2),
                                          m.group(3), line))
    comps["__entry__"] = comps.get(entry, [])
    comps["__entry_name__"] = entry  # type: ignore
    return comps


def _dot_flops(instr: Instruction, symtab: Dict[str, Tuple[str, tuple]]) -> float:
    _, out_dims = _shape_dims(instr.rtype)
    out_n = 1
    for d in out_dims:
        out_n *= d
    # lhs operand: shape literals carry commas ("f32[64,64]{1,0} %name"),
    # so match the first inline shape (or fall back to the symbol table)
    # rather than splitting the argument list on ","
    lhs_dims = None
    ops = re.search(rf"{re.escape(instr.op)}\((.*?)\)", instr.line)
    if ops:
        args = ops.group(1)
        shape = _SHAPE.search(args)
        if shape:
            lhs_dims = tuple(int(d) for d in shape.group(2).split(",") if d)
        else:
            names = re.findall(r"%([\w.\-]+)", args)
            if names and names[0] in symtab:
                lhs_dims = symtab[names[0]][1]
    contract = 1
    mm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.line)
    if mm and lhs_dims:
        for idx in mm.group(1).split(","):
            if idx:
                i = int(idx)
                if i < len(lhs_dims):
                    contract *= lhs_dims[i]
    return 2.0 * out_n * max(contract, 1)


def analyze(hlo: str) -> Dict[str, float]:
    comps = parse_module(hlo)
    entry = comps.pop("__entry_name__")
    comps.pop("__entry__")

    # per-computation instruction symbol tables
    symtabs = {}
    for cname, instrs in comps.items():
        symtabs[cname] = {i.name: _shape_dims(i.rtype) for i in instrs}

    # trip count of the while loop DIRECTLY enclosing each computation —
    # used to de-amortize stacked scan buffers (see below)
    own_trip: Dict[str, float] = {}
    for cname, instrs in comps.items():
        for instr in instrs:
            if instr.op == "while":
                t = _TRIP.search(instr.line)
                trip = float(t.group(1)) if t else 1.0
                bodym = re.search(r"body=%?([\w.\-]+)", instr.line)
                if bodym:
                    own_trip[bodym.group(1)] = max(
                        own_trip.get(bodym.group(1), 1.0), trip)

    # multiplicities via BFS from entry
    mult = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    # iterate to fixpoint over call graph (it is a DAG)
    changed = True
    passes = 0
    while changed and passes < 50:
        changed = False
        passes += 1
        mult2 = defaultdict(float)
        mult2[entry] = 1.0
        for cname in list(comps):
            m = mult[cname] if cname in mult else 0.0
            if m == 0.0:
                continue
            for instr in comps[cname]:
                called = _CALLED.findall(instr.line)
                if not called:
                    continue
                factor = m
                if instr.op == "while":
                    t = _TRIP.search(instr.line)
                    trip = float(t.group(1)) if t else 1.0
                    bodym = re.search(r"body=%?([\w.\-]+)", instr.line)
                    condm = re.search(r"condition=%?([\w.\-]+)", instr.line)
                    if bodym:
                        mult2[bodym.group(1)] += m * trip
                    if condm:
                        mult2[condm.group(1)] += m * (trip + 1)
                    continue
                if instr.op == "fusion":
                    continue  # fusion subcomputation = internal, no HBM
                for group in called:
                    for cal in group.split(","):
                        mult2[cal.strip().lstrip("%")] += factor
        if dict(mult2) != dict(mult):
            mult = mult2
            changed = True

    flops = 0.0
    bytes_ = 0.0
    coll = defaultdict(float)
    for cname, instrs in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        trip = own_trip.get(cname, 1.0)
        symtab = symtabs[cname]

        def tensor_bytes(dt, dims):
            """Bytes of one tensor; a leading dim equal to the enclosing
            loop's trip count marks a stacked scan buffer (xs/ys or saved
            residuals) of which each iteration touches ONE slice."""
            n = 1
            for d in dims:
                n *= d
            b = n * DTYPE_BYTES.get(dt, 4)
            if trip > 1 and dims and float(dims[0]) == trip:
                b /= trip
            return b

        def operand_tensors(instr):
            ops = re.search(rf"{re.escape(instr.op)}\((.*?)\)(?:,|$)",
                            instr.line)
            out = []
            if ops:
                for opnd in ops.group(1).split(","):
                    nm = opnd.strip().split(" ")[-1].lstrip("%")
                    if nm in symtab:
                        out.append(symtab[nm])
            return out

        for instr in instrs:
            if instr.op in SKIP_OPS:
                continue
            if instr.op == "dot":
                flops += m * _dot_flops(instr, symtab)
            if instr.op in TRAFFIC_OPS:
                operands = operand_tensors(instr)
                rdt, rdims = _shape_dims(instr.rtype)
                if instr.op in ("dynamic-slice", "slice", "gather"):
                    # reads only the slice it produces
                    tb = 2 * tensor_bytes(rdt, rdims)
                elif instr.op in ("dynamic-update-slice", "scatter"):
                    # in-place: read+write of the update operand only
                    upd = operands[1] if len(operands) > 1 else (rdt, rdims)
                    tb = 2 * tensor_bytes(*upd)
                else:
                    tb = sum(tensor_bytes(*o) for o in operands)
                    for sdt, sdims in _SHAPE.findall(instr.rtype):
                        dims = tuple(int(d) for d in sdims.split(",") if d)
                        tb += tensor_bytes(sdt, dims)
                bytes_ += m * tb
            if instr.op in COLLECTIVES:
                coll[COLLECTIVES[instr.op]] += m * _shape_bytes(instr.rtype)
    return {
        "flops": flops,
        "bytes": bytes_,
        "collectives": dict(coll),
        "collective_bytes": sum(coll.values()),
    }

"""While-aware HLO cost analysis (flops / bytes / collectives).

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE — with
scan-over-layers (and microbatch accumulation scans) that undercounts by the
trip count.  This module parses the post-optimization HLO text into its
computation graph, computes per-computation costs bottom-up, and multiplies
through while-loop trip counts (recovered from the loop-condition constant),
giving exact totals for scanned programs:

    flops        2 * prod(result dims) * prod(contracting dims) per dot
                 (convolutions likewise; elementwise flops are ignored —
                 <1% for transformer workloads, cross-checked against
                 XLA cost_analysis on unrolled modules in tests)
    bytes        operands-read + outputs-written per instruction, with
                 gather/slice reading only output-sized data (XLA's model)
    collectives  per-kind wire bytes per chip (ring estimates), trip-scaled

This is also the §Perf profiling tool: ``collective_schedule`` lists every
collective with its computation path, shape and wire bytes.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
                "f8e4m3": 1, "f8e5m2fnuz": 1, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(")
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a flat dict ({} when XLA gives
    none)."""
    return dict(compiled.cost_analysis() or {})


def shape_elems_bytes(type_str: str) -> tuple[int, int]:
    """Total (elements, bytes) over all array shapes in a type string."""
    elems = tot = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        tot += n * _DTYPE_BYTES[dt]
    return elems, tot


def shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    line: str
    called: list[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    shapes: dict[str, str] = field(default_factory=dict)   # instr -> type


def parse_module(hlo: str) -> tuple[dict[str, Computation], str]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line or line.lstrip().startswith("//"):
            continue
        if not line.startswith(" ") and line.endswith("{") and "->" in line:
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                if line.strip().startswith("ENTRY"):
                    entry = cur.name
                continue
        if line.strip() == "}":
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        called = []
        mc = _CALLED_RE.findall(line)
        for grp in mc:
            for c in grp.split(","):
                called.append(c.strip().lstrip("%"))
        instr = Instr(name, type_str, op, line, called)
        cur.instrs.append(instr)
        cur.shapes[name] = type_str
    if entry is None and comps:
        entry = list(comps)[-1]
    return comps, entry


def _operand_names(line: str, op: str) -> list[str]:
    """Operand instruction names inside op(...) — %-prefixed identifiers."""
    lparen = line.find(op + "(")
    if lparen < 0:
        return []
    seg = line[lparen + len(op) + 1:]
    depth, out, cur_tok = 1, [], []
    for ch in seg:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        cur_tok.append(ch)
    args = "".join(cur_tok)
    return re.findall(r"%([\w\.\-]+)", args)


def _dot_flops(comp: Computation, ins: Instr) -> float:
    out_elems, _ = shape_elems_bytes(ins.type_str)
    mc = _CONTRACT_RE.search(ins.line)
    ops = _operand_names(ins.line, ins.op)
    if not mc or not ops:
        return 2.0 * out_elems           # fallback
    lhs_type = comp.shapes.get(ops[0])
    if lhs_type is None:
        return 2.0 * out_elems
    dims = shape_dims(lhs_type)
    k = 1
    for d in mc.group(1).split(","):
        if d and int(d) < len(dims):
            k *= dims[int(d)]
    return 2.0 * out_elems * k


def _collective_wire(ins: Instr, n_chips: int,
                     pod_size: int = 256) -> tuple[str, float, bool]:
    """Returns (kind, wire_bytes_per_chip, crosses_pod).

    A collective crosses the pod boundary (DCI links, far slower than ICI)
    when its replica group mixes device ids from different pods."""
    kind = ins.op.replace("-start", "")
    _, R = shape_elems_bytes(ins.type_str)
    g = n_chips
    cross = n_chips > pod_size
    mg = _GROUPS_RE.search(ins.line)
    if mg:
        ids = [int(x) for x in mg.group(1).split(",") if x.strip()]
        g = len(ids)
        cross = len({i // pod_size for i in ids}) > 1
    else:
        mg2 = _GROUPS_V2_RE.search(ins.line)
        if mg2:
            g = int(mg2.group(2))
            cross = n_chips > pod_size and g > pod_size
    g = max(g, 1)
    if kind == "all-gather":
        wire = R * (g - 1) / g
    elif kind == "all-reduce":
        wire = 2 * R * (g - 1) / g
    elif kind == "reduce-scatter":
        wire = R * (g - 1)
    elif kind == "all-to-all":
        wire = R * (g - 1) / g
    else:  # collective-permute
        wire = R
    return kind, wire, cross


def _trip_count(comps: dict[str, Computation], cond_name: str) -> int:
    """Trip count from the loop condition's comparison constant."""
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    consts = []
    for ins in cond.instrs:
        for m in _CONST_RE.finditer(ins.line):
            consts.append(int(m.group(1)))
    return max(consts) if consts else 1


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "copy", "after-all", "custom-call"}


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    convert_bytes: float = 0.0    # dtype-convert traffic: real on the CPU
    #                               backend (no native bf16 matmul), fused
    #                               away on TPU — reported separately so the
    #                               roofline can use TPU-native bytes.
    coll: dict = field(default_factory=dict)       # kind -> [count, wire]
    schedule: list = field(default_factory=list)   # (path, kind, wire, shape)

    def add(self, other: "Cost", scale: float, path: str,
            with_bytes: bool = True):
        self.flops += scale * other.flops
        if with_bytes:
            self.bytes += scale * other.bytes
            self.convert_bytes += scale * other.convert_bytes
        for k, (c, w) in other.coll.items():
            e = self.coll.setdefault(k, [0, 0.0])
            e[0] += int(scale * c)
            e[1] += scale * w
        for (p, k, w, sh) in other.schedule:
            self.schedule.append((f"{path}/{p}" if p else path, k,
                                  scale * w, sh))


def analyze_hlo(hlo: str, n_chips: int, *, while_trips: bool = True) -> dict:
    """Cost totals for one HLO module.

    ``while_trips=False`` counts every while body once — XLA
    ``cost_analysis`` semantics, useful to validate the per-instruction
    model against XLA on modules where the compiler introduced its own
    loops; the default multiplies through recovered trip counts (the whole
    point of this module).
    """
    comps, entry = parse_module(hlo)
    memo: dict[str, Cost] = {}

    def cost_of(name: str) -> Cost:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        c = Cost()
        memo[name] = c                       # break accidental cycles
        if comp is None:
            return c
        for ins in comp.instrs:
            if ins.op == "while":
                body = cond = None
                mb = re.search(r"body=%?([\w\.\-]+)", ins.line)
                mcnd = re.search(r"condition=%?([\w\.\-]+)", ins.line)
                if mb:
                    body = mb.group(1)
                if mcnd:
                    cond = mcnd.group(1)
                trips = _trip_count(comps, cond) \
                    if cond and while_trips else 1
                if body:
                    c.add(cost_of(body), trips, f"while[{trips}]:{body}")
                continue
            if ins.op in ("fusion", "call", "conditional", "map", "reduce",
                          "reduce-window", "sort", "scatter",
                          "select-and-scatter"):
                # flops/collectives of fused sub-computations count; their
                # internal traffic does NOT (fusion keeps it on-chip).
                for sub in ins.called:
                    c.add(cost_of(sub), 1.0, sub, with_bytes=False)
            if ins.op == "dot":
                c.flops += _dot_flops(comp, ins)
            elif ins.op == "convolution":
                out_elems, _ = shape_elems_bytes(ins.type_str)
                c.flops += 2.0 * out_elems  # lower bound (no window parse)
            base = ins.op.replace("-start", "")
            if base in COLLECTIVE_OPS and not ins.op.endswith("-done"):
                kind, wire, cross = _collective_wire(ins, n_chips)
                key = kind + ("/cross-pod" if cross else "")
                e = c.coll.setdefault(key, [0, 0.0])
                e[0] += 1
                e[1] += wire
                c.schedule.append(("", key, wire, ins.type_str[:48]))
            # bytes: operands read + output written
            if ins.op in _SKIP_BYTES_OPS:
                continue
            _, out_b = shape_elems_bytes(ins.type_str)
            if ins.op in ("gather", "dynamic-slice"):
                add_b = 2 * out_b          # output-sized read + write
            elif ins.op in ("dynamic-update-slice",):
                add_b = 3 * out_b
            else:
                opers = _operand_names(ins.line, ins.op)
                rb = 0
                for o in opers:
                    t = comp.shapes.get(o)
                    if t:
                        rb += shape_elems_bytes(t)[1]
                add_b = rb + out_b
            c.bytes += add_b
            if ins.op == "convert" or (ins.op == "fusion"
                                       and "convert" in ins.name):
                c.convert_bytes += add_b
        return c

    total = cost_of(entry)
    coll_total = sum(w for _, (cnt, w) in total.coll.items())
    cross_total = sum(w for k, (cnt, w) in total.coll.items()
                      if k.endswith("/cross-pod"))
    return {
        "flops": total.flops,
        "bytes": total.bytes,
        "convert_bytes": total.convert_bytes,
        "collectives": {k: {"count": cnt, "wire_bytes_per_chip": w}
                        for k, (cnt, w) in total.coll.items()},
        "wire_bytes_per_chip": coll_total,
        "cross_pod_bytes_per_chip": cross_total,
        "schedule": sorted(total.schedule, key=lambda t: -t[2])[:40],
    }

"""Loop-aware analysis of compiled (post-SPMD, per-device) HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified in
tests/test_hlo_analysis.py), which under-reports FLOPs/bytes by the scan trip
count — fatal for models that lax.scan over layers. This module re-derives
loop-complete statistics directly from the HLO text:

  * computations are parsed into instruction lists with a symbol table
    (instruction name -> shape);
  * the call graph (fusion ``calls=``, ``to_apply=``, while ``body=`` /
    ``condition=``) propagates an execution-count multiplier; while trip
    counts are read from the loop-condition computation's bound constant;
  * FLOPs: 2 x |output| x |contracted dims| for every ``dot``;
  * HBM traffic: per scope-level instruction, output + operand bytes
    (fusions are XLA:CPU/TPU's codegen units, so computation-scope operands/
    results approximate materialised buffers);
  * collective bytes: output bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, times multiplier.

All numbers are PER-DEVICE (the SPMD program is per-device).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_SKIP_TRAFFIC = ("parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast", "while", "conditional", "call", "after-all",
                 "partition-id", "replica-id")

_SHAPE_TOK = re.compile(r"(\w+)\[([\d,]*)\]")
# one operand inside an instruction's argument list. Older XLA prints
# ``dot(%a, %b)``; this container's XLA prints typed operands
# ``dot(f32[128,128]{1,0} %a, ...)`` — the inline shape is captured as a
# fallback for names missing from the symbol table.
_OPERAND = re.compile(r"(?:([\w]+\[[\d,]*\](?:\{[^}]*\})?)\s+)?%([\w.\-]+)")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:[\w]+\[[\d,]*\]\S*))\s+([\w\-]+)\((.*)$")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")


def _shape_elems_bytes(shape_str: str) -> tuple[int, int]:
    elems = bytes_ = 0
    for dt, dims in _SHAPE_TOK.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        bytes_ += n * _DTYPE_BYTES[dt]
    return elems, bytes_


def _shape_dims(shape_str: str) -> tuple[str, list[int]]:
    m = _SHAPE_TOK.search(shape_str)
    if not m:
        return "", []
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims


@dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str


def _operands(ins: Instr) -> list[tuple[str, str | None]]:
    """(name, inline_shape_or_None) per operand of the instruction, robust
    to both bare (``%a``) and typed (``f32[..]{..} %a``) dump formats."""
    return [(m.group(2), m.group(1))
            for m in _OPERAND.finditer(ins.rest.split(")")[0])]


def _operand_shape(comp: "Computation", name: str, inline: str | None) -> str:
    return comp.symtab.get(name) or inline or ""


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    symtab: dict = field(default_factory=dict)   # value name -> shape str
    calls: list = field(default_factory=list)    # (callee, kind) kind in {call, body, cond}


def _trip_count(comps, cond_name: str) -> int:
    """Bound constant in the loop condition computation (lax.scan canonical:
    induction var starts at 0, compared LT against the trip bound). Falls
    back to 1 (the cost_analysis behaviour) when no bound is found."""
    vals = []
    seen: set = set()
    stack = [cond_name]
    while stack:
        c = stack.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ins in comps[c].instrs:
            if ins.op == "constant" and "s32" in ins.shape:
                m = re.match(r"(\d+)\)", ins.rest)
                if m:
                    vals.append(int(m.group(1)))
        stack.extend(cal for cal, _ in comps[c].calls)
    return max(vals) if vals else 1


def parse_into(comps, text):
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if line.endswith("{"):
            hdr = _COMP_HDR.match(line)
            if hdr:
                cur = Computation(hdr.group(1))
                comps[cur.name] = cur
                for pname, pshape in re.findall(
                        r"([\w.\-]+):\s*((?:\([^)]*\))|[\w\[\]{},]+)",
                        hdr.group(2)):
                    cur.symtab[pname] = pshape
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        cur.instrs.append(Instr(name, shape, op, rest))
        cur.symtab[name] = shape
        kind = "fusion" if op == "fusion" else "call"
        for callee in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", rest):
            cur.calls.append((callee, kind))
        wb = re.search(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)", rest)
        if wb:
            cur.calls.append((wb.group(1), "cond"))
            cur.calls.append((wb.group(2), "body"))


def _entry_name(comps, text) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    if m:
        return m.group(1)
    return next(iter(comps))


def _multipliers(comps, text) -> dict[str, float]:
    """Execution count per computation, propagated through fusions/whiles."""
    mult: dict[str, float] = defaultdict(float)
    entry = _entry_name(comps, text)

    def visit(cname: str, k: float, depth=0):
        if cname not in comps or depth > 64:
            return
        mult[cname] += k
        comp = comps[cname]
        # group while edges: body gets k * trip
        for ins in comp.instrs:
            if ins.op == "while":
                wb = re.search(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)",
                               ins.rest)
                if wb:
                    trip = _trip_count(comps, wb.group(1))
                    visit(wb.group(1), k * (trip + 1), depth + 1)
                    visit(wb.group(2), k * trip, depth + 1)
        for callee, kind in comp.calls:
            if kind in ("call", "fusion"):
                visit(callee, k, depth + 1)

    visit(entry, 1.0)
    return mult


def _dot_flops(comp: Computation, ins: Instr) -> float:
    _, out_dims = _shape_dims(ins.shape)
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    mm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
    if not mm:
        return 2.0 * out_elems  # dot with no contraction info
    cdims = [int(x) for x in mm.group(1).split(",") if x]
    ops = _operands(ins)
    contract = 1
    if ops:
        _, ldims = _shape_dims(_operand_shape(comp, *ops[0]))
        for c in cdims:
            if c < len(ldims):
                contract *= ldims[c]
    return 2.0 * out_elems * contract


def _operand_bytes(comp: Computation, ins: Instr) -> list[int]:
    out = []
    for name, inline in _operands(ins):
        shape = _operand_shape(comp, name, inline)
        if shape:
            out.append(_shape_elems_bytes(shape)[1])
    return out


def _fusion_traffic(comps, comp: Computation, ins: Instr) -> float:
    """Traffic of a fusion = output + per-parameter actual reads, with two
    in-place patterns discounted:
      * a parameter consumed ONLY by slicing ops (lax.scan stacked-weight
        reads) moves just the slices, not the whole buffer;
      * a parameter that is ONLY the target of dynamic-update-slice (scan
        carry accumulators — saved activations) aliases in place: traffic is
        the update region, and the fusion's big output buffer likewise."""
    _, ob = _shape_elems_bytes(ins.shape)
    m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
    if not m or m.group(1) not in comps:
        return ob + sum(_operand_bytes(comp, ins))
    callee = comps[m.group(1)]
    defined = {i.name for i in callee.instrs if i.op != "parameter"}
    params = [i.name for i in callee.instrs if i.op == "parameter"]
    params += [p for p in callee.symtab
               if p not in defined and p not in params]
    total = 0.0
    inplace_out = 0.0
    for p in params:
        pb = _shape_elems_bytes(callee.symtab[p])[1]
        uses = [i for i in callee.instrs
                if re.search(r"%" + re.escape(p) + r"\b", i.rest)]

        def first_opnd(u):
            ops = _operands(u)
            return ops[0][0] if ops else None

        if uses and all(u.op in ("dynamic-slice", "slice", "gather") and
                        first_opnd(u) == p for u in uses):
            total += sum(_shape_elems_bytes(u.shape)[1] for u in uses)
        elif uses and all(u.op == "dynamic-update-slice" and
                          first_opnd(u) == p for u in uses):
            # in-place accumulator: charge write of the update region(s)
            for u in uses:
                ops = _operands(u)
                upd = (_shape_elems_bytes(
                    _operand_shape(callee, *ops[1]))[1]
                       if len(ops) > 1 else 0)
                total += 2 * upd
                inplace_out += pb
        else:
            total += pb
    # if every output byte is an in-place-aliased accumulator, don't charge
    # the full output buffer again
    if inplace_out >= ob:
        return total
    return total + ob


def _instr_traffic(comp: Computation, ins: Instr) -> float:
    """HBM bytes moved by one scope-level instruction.

    Slicing/gather ops read only the slice (≈ output bytes), NOT the whole
    source buffer; in-place update ops move ~2x the update. Everything else
    reads its operands once and writes its output (the fusion contract)."""
    _, ob = _shape_elems_bytes(ins.shape)
    op = ins.op
    if op in ("dynamic-slice", "slice", "gather", "broadcast", "iota",
              "concatenate", "reshape", "transpose", "reverse"):
        return 2.0 * ob
    if op in ("dynamic-update-slice", "scatter"):
        opb = _operand_bytes(comp, ins)
        upd = opb[1] if len(opb) > 1 else ob
        return 2.0 * min(upd, ob)
    if op == "pad":
        return 2.0 * ob
    return ob + sum(_operand_bytes(comp, ins))


def _instr_traffic_full(comps, comp: Computation, ins: Instr) -> float:
    if ins.op == "fusion":
        return _fusion_traffic(comps, comp, ins)
    return _instr_traffic(comp, ins)


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (XLA's per-device
    estimate of flops and bytes)."""
    return dict(compiled.cost_analysis())


def analyze_hlo(text: str) -> dict:
    comps: dict[str, Computation] = {}
    parse_into(comps, text)
    mult = _multipliers(comps, text)

    # computations reachable through a fusion edge are codegen bodies —
    # their internals don't touch HBM (no separate traffic accounting)
    fused: set = set()
    stack = [c for comp in comps.values()
             for c, kind in comp.calls if kind == "fusion"]
    while stack:
        c = stack.pop()
        if c in fused or c not in comps:
            continue
        fused.add(c)
        stack.extend(cal for cal, _ in comps[c].calls)

    flops = 0.0
    traffic = 0.0
    coll = defaultdict(lambda: {"count": 0.0, "bytes": 0.0})
    for cname, comp in comps.items():
        k = mult.get(cname, 0.0)
        if k == 0.0:
            continue
        for ins in comp.instrs:
            if ins.op == "dot":
                flops += k * _dot_flops(comp, ins)
            if ins.op not in _SKIP_TRAFFIC and cname not in fused:
                traffic += k * _instr_traffic_full(comps, comp, ins)
            for kind in COLLECTIVES:
                if ins.op == kind or (ins.op.startswith(kind) and
                                      not ins.op.endswith("-start")):
                    _, b = _shape_elems_bytes(ins.shape)
                    coll[kind]["count"] += k
                    coll[kind]["bytes"] += k * b
                    break

    total_coll = sum(v["bytes"] for v in coll.values())
    return {
        "flops": flops,
        "traffic_bytes": traffic,
        "collectives": {k: dict(v) for k, v in coll.items()},
        "collective_bytes": total_coll,
        "n_computations": len(comps),
    }

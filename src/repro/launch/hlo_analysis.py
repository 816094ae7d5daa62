"""Post-optimization HLO analysis: collective bytes + roofline terms.

``compiled.cost_analysis()`` reports FLOPs/bytes with every ``while``
(scan) body counted ONCE, and collective traffic
not at all.  This module parses the per-device SPMD HLO text:

* splits it into computations,
* builds the call graph (while bodies, conditionals, called computations),
* extracts each while loop's trip count from its condition computation
  (``compare(counter, constant), direction=LT`` pattern),
* sums collective bytes with per-op wire-cost models, multiplying ops
  inside loop bodies by the enclosing trip counts,
* converts to the three roofline terms with the v5e constants.

All sizes in the SPMD module are already per-device, so "bytes" here are
per-chip wire bytes; the collective term is bytes / link_bw.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# v5e-like hardware constants (per the brief)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every array shape in an HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    bytes_wire: int            # per-device wire bytes (cost model applied)
    bytes_payload: int
    group_size: int
    computation: str
    multiplier: int = 1


@dataclasses.dataclass
class HloReport:
    collectives: list[CollectiveOp]
    trip_counts: dict[str, int]
    dot_flops: float = 0.0       # scan-corrected MXU flops (dots only)
    dot_bytes: float = 0.0       # scan-corrected dot operand+result bytes
    # CPU-backend artifact: FloatNormalization hoists bf16->f32 converts
    # of whole parameter stacks out of loops (no bf16 dot on CPU). A TPU
    # build keeps bf16 MXU dots, so these buffers don't exist there.
    f32_param_convert_bytes: float = 0.0

    @property
    def total_wire_bytes(self) -> float:
        return sum(c.bytes_wire * c.multiplier for c in self.collectives)

    def by_kind(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for c in self.collectives:
            out[c.kind] += c.bytes_wire * c.multiplier
        return dict(out)


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """Split module text into computations by column-0 indentation.

    Computation definitions start at column 0 (``%name (params...) ->``,
    possibly wrapped over several lines); instructions are indented; the
    closing ``}`` is at column 0.  Wrapped header lines land in the body
    but never match an instruction pattern, so they are harmless.
    """
    comps: dict[str, list[str]] = {}
    body: list[str] | None = None
    for line in hlo.splitlines():
        if not line.strip():
            continue
        if line.startswith("}"):
            body = None
            continue
        if not line.startswith(" "):
            m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if m:
                body = []
                comps[m.group(1)] = body
                continue
        if body is not None:
            s = line.strip()
            if s and not s.startswith("//"):
                body.append(s)
    return comps


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:  # iota-style [groups, size]
        return int(m.group(2))
    return default


def _wire_bytes(kind: str, payload: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * payload * (g - 1) / g
    if kind == "all-gather":
        return payload * (g - 1) / g          # payload = gathered result
    if kind == "reduce-scatter":
        return payload * (g - 1)              # payload = scattered result
    if kind == "all-to-all":
        return payload * (g - 1) / g
    if kind == "collective-permute":
        return float(payload)
    return float(payload)


def analyze_hlo(hlo: str, *, num_devices: int,
                default_trip: int = 1) -> HloReport:
    comps = _split_computations(hlo)

    # --- trip counts: map while-op body/condition computations ------------
    trip_of_body: dict[str, int] = {}
    for cname, lines in comps.items():
        for line in lines:
            if " while(" in line:
                mb = re.search(r"body=%?([\w\.\-]+)", line)
                mc = re.search(r"condition=%?([\w\.\-]+)", line)
                trip = default_trip
                if mc and mc.group(1) in comps:
                    consts = [int(x) for x in re.findall(
                        r"constant\((\d+)\)", "\n".join(comps[mc.group(1)]))]
                    if consts:
                        trip = max(consts)
                if mb:
                    trip_of_body[mb.group(1)] = max(trip, 1)

    # --- call-graph multipliers (nested whiles multiply) -------------------
    multiplier: dict[str, int] = defaultdict(lambda: 1)

    def propagate(name: str, mult: int, seen: frozenset):
        if name in seen or name not in comps:
            return
        multiplier[name] = max(multiplier[name], mult)
        for line in comps[name]:
            for ref in re.findall(
                    r"(?:body|condition|to_apply|calls)=%?([\w\.\-]+)", line):
                child_mult = mult * trip_of_body.get(ref, 1) \
                    if ref in trip_of_body else mult
                propagate(ref, child_mult, seen | {name})

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line)
            if m:
                entry = m.group(1)
        if entry:
            break
    roots = [entry] if entry and entry in comps else list(comps)
    for r in roots:
        propagate(r, 1, frozenset())

    # --- scan-corrected dot flops/bytes ------------------------------------
    # Operands carry no inline types in optimized HLO, so first build a
    # per-computation symbol table (%name -> type string).
    dot_flops = 0.0
    dot_bytes = 0.0
    def_re = re.compile(r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+"
                        r"([\w\-]+)\(")
    dot_args_re = re.compile(r"\sdot\(([^)]*)\)")
    lcd_re = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
    for cname, lines in comps.items():
        mult = multiplier.get(cname, 1)
        symtab: dict[str, str] = {}
        for line in lines:
            md = def_re.match(line)
            if md:
                symtab[md.group(1)] = md.group(2)

        def _operand_types(arg_str):
            """Type string per operand.  Older HLO printers inline the
            operand type (``dot(f32[8,16]{1,0} %a, ...)``); newer ones
            print bare names resolved through the symbol table.  Args
            are split on top-level commas only (shapes contain commas).
            """
            parts, depth, cur = [], 0, []
            for chx in arg_str:
                if chx in "[{(":
                    depth += 1
                elif chx in "]})":
                    depth -= 1
                if chx == "," and depth == 0:
                    parts.append("".join(cur))
                    cur = []
                else:
                    cur.append(chx)
            if cur:
                parts.append("".join(cur))
            types = []
            for p in parts:
                p = p.strip()
                if not p:
                    continue
                if " " in p:                      # inline "type %name"
                    types.append(p.rsplit(None, 1)[0])
                else:
                    types.append(symtab.get(p.lstrip("%"), ""))
            return types

        for line in lines:
            md = def_re.match(line)
            if md is None or md.group(3) != "dot":
                continue
            result_type = md.group(2)
            result_shapes = _SHAPE_RE.findall(result_type)
            ma = dot_args_re.search(line)
            if not result_shapes or ma is None:
                continue
            op_types = _operand_types(ma.group(1))
            lhs_type = op_types[0] if op_types else ""
            lhs_shapes = _SHAPE_RE.findall(lhs_type)
            if not lhs_shapes:
                continue
            res_dims = [int(d) for d in result_shapes[0][1].split(",") if d]
            lhs_dims = [int(d) for d in lhs_shapes[0][1].split(",") if d]
            mc = lcd_re.search(line)
            contract = 1
            if mc and mc.group(1):
                for idx in mc.group(1).split(","):
                    contract *= lhs_dims[int(idx)]
            res_n = 1
            for d in res_dims:
                res_n *= d
            dot_flops += 2.0 * res_n * contract * mult
            op_bytes = sum(_shape_bytes(t) for t in op_types)
            dot_bytes += (_shape_bytes(result_type) + op_bytes) * mult

    # --- CPU float-normalization artifact ----------------------------------
    # Only count hoisted converts in the ENTRY computation whose operand
    # is a true module parameter: those are weight stacks promoted to f32
    # because the CPU backend has no bf16 dot; they are live together at
    # the loop boundary (they feed the while tuple).
    f32_conv_bytes = 0.0
    conv_re = re.compile(
        r"=\s*(f32\[[0-9,]*\])[^ ]*\s+(?:fusion|convert)\((%?param[\w\.\-]*)\)")
    entry_name = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line)
            if m:
                entry_name = m.group(1)
            break
    if entry_name in comps:
        lines = comps[entry_name]
        symtab: dict[str, tuple[str, str]] = {}
        for line in lines:
            md = def_re.match(line)
            if md:
                symtab[md.group(1)] = (md.group(2), md.group(3))
        for line in lines:
            m = conv_re.search(line)
            if m is None:
                continue
            operand = m.group(2).lstrip("%")
            op_type, op_code = symtab.get(operand, ("", ""))
            if op_code != "parameter" or "bf16[" not in op_type:
                continue
            res_b = _shape_bytes(m.group(1))
            if res_b == 2 * _shape_bytes(op_type):
                f32_conv_bytes += res_b

    # --- collect collectives ----------------------------------------------
    ops: list[CollectiveOp] = []
    for cname, lines in comps.items():
        for line in lines:
            for kind in COLLECTIVES:
                token = f" {kind}("
                start_token = f"{kind}-start("
                if token in line or start_token in line:
                    # result type(s): text between '=' and the op name
                    m = re.search(r"=\s*(.*?)\s*" + kind, line)
                    if not m:
                        continue
                    payload = _shape_bytes(m.group(1))
                    g = _group_size(line, num_devices)
                    ops.append(CollectiveOp(
                        kind=kind,
                        bytes_wire=int(_wire_bytes(kind, payload, g)),
                        bytes_payload=payload,
                        group_size=g,
                        computation=cname,
                        multiplier=multiplier.get(cname, 1),
                    ))
                    break
    return HloReport(collectives=ops, trip_counts=trip_of_body,
                     dot_flops=dot_flops, dot_bytes=dot_bytes,
                     f32_param_convert_bytes=f32_conv_bytes)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    wire_bytes: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """compute_term / max(all terms): 1.0 = perfectly compute-bound."""
        t = self.bound_time_s
        return self.compute_s / t if t > 0 else 0.0


def roofline_terms(*, hlo_flops: float, hlo_bytes: float,
                   wire_bytes: float) -> RooflineTerms:
    """All inputs are PER-DEVICE quantities (SPMD module values)."""
    return RooflineTerms(
        compute_s=hlo_flops / PEAK_FLOPS,
        memory_s=hlo_bytes / HBM_BW,
        collective_s=wire_bytes / ICI_BW,
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        wire_bytes=wire_bytes,
    )

"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh) cell:
  compute term    = HLO_FLOPs_per_chip / peak_FLOPs
  memory term     = HLO_bytes_per_chip / HBM_bw
  collective term = estimated per-chip link bytes / link_bw

`cost_analysis()` reports the SPMD-partitioned (per-device) module, so
terms divide by per-chip peaks directly.  Collective bytes are NOT in
cost_analysis: we parse the optimized HLO and sum operand/output sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
with ring-transfer multipliers (all-reduce counts 2x its operand, an
all-gather counts its full output).

Per-chip peaks come from :data:`PEAKS`, keyed by the ``device_kind``
JAX reports; a kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

import re
from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    link_bw: float           # ICI bytes/s per link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect over 4 links
# (50 GB/s each).  JAX reports a v5e chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Published per-chip peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64"
                       r"|u64|c64|c128)\[([0-9,]*)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


# ---------------------------------------------------------------------------
# HLO mini cost model with while-loop trip-count multipliers.
#
# XLA's cost_analysis() counts a while body's ops ONCE, so a scanned
# 64-layer transformer under-reports flops/bytes/collectives by ~64x.
# We re-derive costs from the optimized HLO text: computations are
# traversed from ENTRY through while bodies, each with a multiplier =
# product of enclosing trip counts (parsed from `known_trip_count` or the
# `constant(K)` in the loop condition).  FLOPs come from dot ops
# (2 * |out| * contraction); HBM bytes from fusion/op boundary operand +
# output sizes; collective bytes from ring-transfer estimates.
# ---------------------------------------------------------------------------

_OP_LINE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_SKIP_OPS = ("parameter(", "constant(", "get-tuple-element(", "tuple(",
             "bitcast(", "after-all(", "partition-id(", "replica-id(",
             "iota(")


class _HloModule:
    def __init__(self, text: str):
        self.comps: dict[str, list[str]] = {}
        self.entry: str | None = None
        cur = None
        for line in text.splitlines():
            m = _COMP_HEADER.match(line)
            if m:
                cur = m.group(2)
                self.comps[cur] = []
                if m.group(1):
                    self.entry = cur
                continue
            if line.startswith("}"):
                cur = None
                continue
            if cur is not None and line.strip().startswith(("%", "ROOT")):
                self.comps[cur].append(line.strip())
        # symbol table: op name -> (dtype, dims) of its output
        self.shapes: dict[str, list[tuple[str, str]]] = {}
        for ops in self.comps.values():
            for line in ops:
                m = _OP_LINE.match(line)
                if not m:
                    continue
                name, rhs = m.group(2), m.group(3)
                paren = rhs.find("(")
                head = rhs if paren < 0 else rhs[:paren]
                self.shapes[name] = _SHAPE_RE.findall(head)

    def _op_bytes(self, name: str) -> int:
        return sum(_shape_bytes(d, s) for d, s in self.shapes.get(name, []))

    def trip_count(self, while_line: str, cond_name: str) -> int:
        m = re.search(r'known_trip_count=\{"?n"?[:=]"?(\d+)"?\}', while_line)
        if m:
            return int(m.group(1))
        best = 1
        for line in self.comps.get(cond_name, []):
            for c in re.findall(r"constant\((\d+)\)", line):
                best = max(best, int(c))
        return best

    def walk(self):
        """Yield (op_line, multiplier) over ENTRY + (nested) while bodies."""
        if self.entry is None:
            return
        stack = [(self.entry, 1.0)]
        seen = set()
        while stack:
            comp, mult = stack.pop()
            if comp in seen:
                continue
            seen.add(comp)
            for line in self.comps.get(comp, []):
                yield line, mult
                if re.search(r"\bwhile\(", line):
                    mb = re.search(r"body=%?([\w.\-]+)", line)
                    mc = re.search(r"condition=%?([\w.\-]+)", line)
                    if mb and mc:
                        k = self.trip_count(line, mc.group(1))
                        stack.append((mb.group(1), mult * k))
                mcall = re.search(r"\bcall\(.*to_apply=%?([\w.\-]+)", line)
                if mcall:
                    stack.append((mcall.group(1), mult))


def parse_hlo_costs(text: str) -> dict:
    """Loop-aware flops / HBM bytes / collective bytes from optimized HLO."""
    mod = _HloModule(text)
    flops = 0.0
    hbm_bytes = 0.0
    coll = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    whiles = []
    for line, mult in mod.walk():
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, rhs = m.group(2), m.group(3)
        if re.search(r"\bwhile\(", rhs):
            mc = re.search(r"condition=%?([\w.\-]+)", rhs)
            if mc:
                whiles.append({"op": name,
                               "trips": mod.trip_count(rhs, mc.group(1)),
                               "mult": mult})
            continue
        if any(s in rhs for s in _SKIP_OPS):
            continue
        paren = rhs.find("(")
        if paren < 0:
            continue
        out_b = sum(_shape_bytes(d, s)
                    for d, s in _SHAPE_RE.findall(rhs[:paren]))
        # operand bytes via symbol table
        stop = rhs.find("),")
        op_args = re.findall(r"%([\w.\-]+)",
                             rhs[paren:stop + 1 if stop > 0 else None])
        in_b = sum(mod._op_bytes(o) for o in op_args)
        # Sliced reads/writes touch only the slice, not the full operand:
        # counting the (L, ...) layer stack per scan iteration would
        # overstate traffic by ~L x.
        if re.search(r"\bdynamic-slice\(", rhs) or \
                re.search(r"\bgather\(", rhs):
            traffic = 2.0 * out_b
        elif re.search(r"\bdynamic-update-slice\(", rhs):
            upd = mod._op_bytes(op_args[1]) if len(op_args) > 1 else out_b
            traffic = 2.0 * upd
        elif re.search(r"\bscatter\(", rhs):
            upd = mod._op_bytes(op_args[-1]) if op_args else out_b
            traffic = 2.0 * upd
        else:
            traffic = out_b + in_b
        hbm_bytes += mult * traffic
        # dot flops
        if re.search(r"\bdot\(", rhs):
            mdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
            lhs_ref = op_args[0] if op_args else None
            contract = 1
            if mdims and lhs_ref and mod.shapes.get(lhs_ref):
                dims_str = mod.shapes[lhs_ref][0][1]
                lhs_dims = [int(x) for x in dims_str.split(",")] if dims_str \
                    else []
                for ci in mdims.group(1).split(","):
                    if ci != "" and int(ci) < len(lhs_dims):
                        contract *= lhs_dims[int(ci)]
            out_elems = out_b
            shp = _SHAPE_RE.findall(rhs[:paren])
            if shp:
                d, s = shp[0]
                n = 1
                if s:
                    for x in s.split(","):
                        n *= int(x)
                out_elems = n
            flops += mult * 2.0 * out_elems * contract
        # collectives
        for c in _COLLECTIVES:
            if re.search(rf"\b{c}(-start)?\(", rhs):
                if c == "all-gather":
                    b = out_b
                elif c == "all-reduce":
                    b = 2.0 * in_b
                else:
                    b = in_b
                coll[c] += mult * b
                counts[c] += 1
                break
    coll_total = sum(coll.values())
    return {"flops": flops, "hbm_bytes": hbm_bytes,
            "collective_bytes": coll_total, "collective_breakdown": coll,
            "collective_counts": counts, "while_loops": whiles}


def collective_bytes(hlo_text: str) -> dict:
    """Estimated per-chip link bytes by collective type."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in _COLLECTIVES:
            # match "  <shape> all-gather(" or "all-gather-start("
            if re.search(rf"\b{c}(-start)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        shapes = _SHAPE_RE.findall(rhs)
        if not shapes:
            continue
        # first shape token(s) before the op name are the OUTPUT shape;
        # tokens inside parens are operands.  Ring-transfer estimates:
        paren = rhs.index("(")
        out_shapes = _SHAPE_RE.findall(rhs[:paren])
        in_shapes = _SHAPE_RE.findall(rhs[paren:])
        out_b = sum(_shape_bytes(d, s) for d, s in out_shapes)
        in_b = sum(_shape_bytes(d, s) for d, s in in_shapes)
        if op == "all-gather":
            b = out_b                       # gather the full output
        elif op == "all-reduce":
            b = 2.0 * in_b                  # reduce-scatter + all-gather
        elif op == "reduce-scatter":
            b = in_b
        else:                               # all-to-all, collective-permute
            b = in_b
        out[op] += float(b)
        counts[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   device_kind: str) -> dict:
    peak = peaks_for(device_kind)
    compute_s = flops / peak.flops
    memory_s = bytes_accessed / peak.hbm_bw
    collective_s = coll_bytes / peak.link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(compute_s, memory_s, collective_s)
    terms["dominant"] = dom
    terms["step_time_bound_s"] = bound
    terms["roofline_fraction"] = compute_s / bound if bound > 0 else 0.0
    return terms


def analyze(compiled, lowered_text: str | None, model_flops: float,
            n_chips: int, device_kind: str) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):                      # older jax returns [dict]
        cost = cost[0]
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:                          # pragma: no cover
        mem_info = {"error": str(e)}
    text = lowered_text or compiled.as_text()
    parsed = parse_hlo_costs(text)
    # loop-corrected per-chip numbers (cost_analysis counts while bodies
    # once; our parser multiplies by trip counts)
    flops = max(parsed["flops"], raw_flops)
    byts = max(parsed["hbm_bytes"], raw_bytes)
    coll_total = parsed["collective_bytes"]
    terms = roofline_terms(flops, byts, coll_total, device_kind)
    useful = model_flops / (flops * n_chips) if flops > 0 else 0.0
    return {
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": byts,
        "collective_bytes_per_chip": coll_total,
        "collective_breakdown": parsed["collective_breakdown"],
        "collective_counts": parsed["collective_counts"],
        "while_loops": parsed["while_loops"][:16],
        "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes},
        "memory_analysis": mem_info,
        "model_flops": model_flops,
        "useful_compute_fraction": useful,
        **terms,
    }

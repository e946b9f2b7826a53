"""Loop-aware HLO cost accounting — the roofline's measurement layer.

XLA's `compiled.cost_analysis()` counts while-loop bodies ONCE (verified:
a lax.scan of L layers reports 1 layer of FLOPs), and naive text greps
under-count collectives the same way.  Since every model here runs depth
under lax.scan, the roofline needs a call-graph walk:

  total(comp) = own_ops(comp) + Σ_child total(child) * multiplicity(child)

where multiplicity is the while op's `known_trip_count` backend_config
(emitted by XLA for counted loops), 1 for calls/fusions, and max() over
conditional branches.  Per computation we account:

  * dot FLOPs: 2 * numel(result) * prod(contracted dims)  (shapes resolved
    through a per-computation symbol table),
  * collective bytes: output bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (+ -start forms),
  * HBM-traffic proxy: output bytes of top-level ops in non-fusion
    computations (fusion internals are not materialized).

All numbers are PER DEVICE (the HLO module is the per-partition SPMD
program), matching memory_analysis()'s convention.
"""

from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s+->\s+.*\{")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """All 'dtype[dims]' occurrences in a type string."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append((dt, dims))
    return out


def _bytes_of(type_str: str) -> int:
    total = 0
    for dt, dims in _parse_shapes(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _numel_first(type_str: str) -> Tuple[Optional[Tuple[int, ...]], int]:
    shapes = _parse_shapes(type_str)
    if not shapes:
        return None, 0
    dt, dims = shapes[0]
    n = 1
    for d in dims:
        n *= d
    return dims, n


@dataclasses.dataclass
class _Comp:
    name: str
    is_entry: bool = False
    flops: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    coll_counts: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    out_bytes: float = 0.0  # top-level op output bytes (HBM proxy)
    called_via_fusion: bool = False
    children: List[Tuple[str, float]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class HLOCost:
    flops: float
    collective_bytes: float
    collective_by_op: Dict[str, float]
    collective_counts: Dict[str, float]
    hbm_bytes_proxy: float

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def analyze_hlo(text: str) -> HLOCost:
    comps: Dict[str, _Comp] = {}
    cur: Optional[_Comp] = None
    symbols: Dict[str, Tuple[int, ...]] = {}
    fusion_called: set = set()

    for raw in text.splitlines():
        line = raw.rstrip()
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            cur = _Comp(name=hdr.group(2), is_entry=bool(hdr.group(1)))
            comps[cur.name] = cur
            symbols = {}
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue

        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.group(1), m.group(2), m.group(3)
        dims, numel = _numel_first(type_str)
        if dims is not None:
            symbols[name] = dims

        cur.out_bytes += _bytes_of(type_str)

        # -- dots ------------------------------------------------------------
        if op == "dot":
            cm = _CONTRACT_RE.search(line)
            k = 1
            if cm:
                args = line.split("dot(", 1)[1]
                ops_m = _OPERAND_RE.findall(args.split(")", 1)[0])
                lhs_shape = symbols.get(ops_m[0]) if ops_m else None
                if lhs_shape is not None:
                    for d in cm.group(1).split(","):
                        if d and int(d) < len(lhs_shape):
                            k *= lhs_shape[int(d)]
            cur.flops += 2.0 * numel * k

        # -- collectives -----------------------------------------------------
        base = None
        for c in COLLECTIVE_OPS:
            if op == c or op == c + "-start":
                base = c
                break
        if base is not None:
            nbytes = _bytes_of(type_str)
            cur.coll_bytes[base] += nbytes
            cur.coll_counts[base] += 1

        # -- call graph --------------------------------------------------------
        if op == "while":
            body = cond = None
            bm = re.search(r"body=%([\w\.\-]+)", line)
            cm2 = re.search(r"condition=%([\w\.\-]+)", line)
            tm = _TRIP_RE.search(line)
            trips = float(tm.group(1)) if tm else 1.0
            if bm:
                cur.children.append((bm.group(1), trips))
            if cm2:
                cur.children.append((cm2.group(1), trips + 1))
        elif op in ("fusion", "call", "custom-call", "reduce", "sort", "map",
                    "reduce-window", "scatter", "select-and-scatter",
                    "all-reduce", "reduce-scatter"):
            for child in _CALLS_RE.findall(line):
                cur.children.append((child, 1.0))
                if op == "fusion":
                    fusion_called.add(child)
        elif op == "conditional":
            bm = _BRANCHES_RE.search(line)
            if bm:
                branches = _OPERAND_RE.findall(bm.group(1))
                for b in branches:
                    # max-cost semantics approximated by weighting one full
                    # visit per branch then taking max at aggregation time is
                    # complex; weight each branch by 1 (upper bound).
                    cur.children.append((b, 1.0))

    for fname in fusion_called:
        if fname in comps:
            comps[fname].called_via_fusion = True

    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return HLOCost(0.0, 0.0, {}, {}, 0.0)

    memo: Dict[str, Tuple[float, Dict[str, float], Dict[str, float], float]] = {}
    visiting: set = set()

    def walk(name: str):
        if name in memo:
            return memo[name]
        if name not in comps or name in visiting:
            return (0.0, {}, {}, 0.0)
        visiting.add(name)
        c = comps[name]
        flops = c.flops
        coll = dict(c.coll_bytes)
        counts = dict(c.coll_counts)
        obytes = 0.0 if c.called_via_fusion else c.out_bytes
        for child, mult in c.children:
            cf, cc, cn, cb = walk(child)
            flops += mult * cf
            for k2, v in cc.items():
                coll[k2] = coll.get(k2, 0.0) + mult * v
            for k2, v in cn.items():
                counts[k2] = counts.get(k2, 0.0) + mult * v
            obytes += mult * cb
        visiting.discard(name)
        memo[name] = (flops, coll, counts, obytes)
        return memo[name]

    flops, coll, counts, obytes = walk(entry.name)
    return HLOCost(
        flops=flops,
        collective_bytes=sum(coll.values()),
        collective_by_op=coll,
        collective_counts=counts,
        hbm_bytes_proxy=obytes,
    )


# -- legacy helper (entry-level only; kept for comparison) --------------------


def collective_bytes(hlo_text: str):
    cost = analyze_hlo(hlo_text)
    return cost.collective_bytes, cost.collective_by_op, cost.collective_counts


def flops_and_bytes(cost: dict) -> Tuple[float, float]:
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    return flops, nbytes


_ALIAS_BLOCK_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*,\s*\w+=",
                             re.DOTALL)
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([\d,\s]*)\}\s*,\s*([\w-]+)\s*\)"
)


def donation_aliases(compiled):
    """Parse the compiled module's ``input_output_alias`` table (the record
    XLA emits when buffer donation succeeded).

    Returns a list of (output_index, param_number, param_index, kind)
    tuples — empty when nothing is aliased, i.e. when every donated input
    would still be copied.  This is the no-copy assertion the tiered PQ's
    donated step paths are pinned with (donated carries must alias through,
    otherwise each step pays a full O(S*C) state copy)."""
    text = compiled.as_text()
    m = _ALIAS_BLOCK_RE.search(text)
    if not m:
        return []
    return [
        (tuple(int(x) for x in out.split(",") if x.strip()),
         int(param),
         tuple(int(x) for x in pidx.split(",") if x.strip()),
         kind)
        for out, param, pidx, kind in _ALIAS_ENTRY_RE.findall(m.group(1))
    ]

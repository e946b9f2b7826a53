"""Device time by layer and by kernel arm, from a profiler trace.

The fused window names its layers with `jax.named_scope` (the `pq.*` scopes
of `repro.obs.profiling.LAYER_SCOPES`) and each kernel dispatch with a
`kernel.<kernel>.<arm>` scope. On the device those names exist only as the
`op_name` of each HLO instruction, and the profiler embeds each program's
optimised HLO in the trace: the `/host:metadata` plane holds one event
metadata per program, named as that program's "XLA Modules" events, with
the serialized `HloProto` under the stat `Hlo Proto`. This module

* decodes those protos with a small protobuf wire reader (no generated
  message classes are installed, and none are needed) into
  {program: {instruction: op_name}}; a fusion with no `op_name` takes its
  fused computation's root instruction's, or where the root has none (a
  bitcast), that of the nearest operand of the root that has one;
* assigns each "XLA Ops" event of a device line to the "XLA Modules" event
  that encloses it, and so to its `op_name`;
* counts each op's own time inside the traced window (as `trace_reduce`
  counts it by opcode) under its layer, the innermost `pq.*` component of
  its `op_name` or `(unscoped)`, under its innermost `kernel.*` component,
  and under `pq.X` too when its layer is a branch scope `pq.X.Y`; seconds,
  averaged over the device planes.

Like `trace_reduce`, it reads only what the profiler wrote and imports
nothing from the program.

The harness reduces each traced segment with it
(`harness.reduce_trace`), and the readers of `bench/metrics/` take their
shares through `share`. Run on the chip, it prints one cell's traced
segment by scope and by instruction:

    python3 bench/trace_scopes.py --workload pq1m.table3 --seed 7 --seconds 51

That is `bench/run.py --trace 1` (`harness.run_cell`): set-up, `--seconds` of
untraced windows, a traced segment of `harness.TRACE_SECONDS` and the
check. The last line of stdout is one JSON object: seconds by scope, the
layer shares of busy time, the heaviest instructions (program, name,
opcode, executions, seconds, op_name), the set-up by phase, and how long
each reduction of the trace took.
"""

from __future__ import annotations

import bisect
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if not __package__:  # run as a script: the repo root and src/ on the path
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.trace_reduce import (DEVICE_PREFIX, HOST_PLANE, MODULES_LINE,
                                OPS_LINE, _clip, opcode_of, self_times)

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
UNSCOPED = "(unscoped)"
LAYER_PREFIX = "pq."
KERNEL_PREFIX = "kernel."

# Per-layer shares of device busy time, in %: metric name -> the scopes
# whose own time it sums. The first six and `unscoped_busy_pct` partition
# busy time; `windowed_merge_busy_pct` cuts across the layers.
SHARES = {
    "decide_busy_pct": ("pq.decide",),
    "eliminate_busy_pct": ("pq.presort", "pq.eliminate"),
    "insert_busy_pct": ("pq.insert",),
    "refill_busy_pct": ("pq.refill",),
    "compact_busy_pct": ("pq.compact",),
    "schedule_busy_pct": ("pq.schedule",),
    "windowed_merge_busy_pct": ("kernel.windowed_merge.",),
    "unscoped_busy_pct": (UNSCOPED,),
}

Attributed = Tuple[str, str, str, str, float]  # program, instruction,
# opcode, op_name, own seconds (averaged over devices)


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None) -> Iterator[tuple]:
    """(field number, value) of each field of the message in buf[lo:hi]:
    an int for a varint, a (start, end) slice for a length-delimited
    field, the raw bytes for a fixed-width one."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, v) -> List[int]:
    """A repeated int64 field's values: packed (a slice) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


# ---------------------------------------------------------------------------
# the programs' HLO, from the metadata plane
# ---------------------------------------------------------------------------

# Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry
# key 1, value 2), .stat_metadata 5 (same); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5, .root_id 6;
# HloInstructionProto.name 1, .opcode 2, .metadata 7, .id 35, .operand_ids 36,
# .called_computation_ids 38; OpMetadata.op_name 2.


def _hlo_op_names(buf, span) -> Dict[str, str]:
    """{instruction name: op_name} of one serialized HloProto. A fusion
    with no op_name takes its fused computation's root's; where the root
    has none either (a bitcast, a tuple), the nearest instruction that has
    one, walking back from the root through operands."""
    module = next(v for f, v in _fields(buf, *span) if f == 1)
    # instruction id -> [name, opcode, op_name, operand ids, called ids]
    instrs: Dict[int, list] = {}
    roots: Dict[int, int] = {}  # computation id -> root instruction id
    for f, comp in _fields(buf, *module):
        if f != 3:
            continue
        cid = root = None
        for g, v in _fields(buf, *comp):
            if g == 5:
                cid = v
            elif g == 6:
                root = v
            elif g == 2:
                ins = ["", "", "", [], []]
                iid = None
                for h, w in _fields(buf, *v):
                    if h == 1:
                        ins[0] = _text(buf, w)
                    elif h == 2:
                        ins[1] = _text(buf, w)
                    elif h == 7:
                        ins[2] = next((_text(buf, x) for k, x in
                                       _fields(buf, *w) if k == 2), "")
                    elif h == 35:
                        iid = w
                    elif h == 36:
                        ins[3] += _ints(buf, w)
                    elif h == 38:
                        ins[4] += _ints(buf, w)
                instrs[iid] = ins
        roots[cid] = root

    def named_from(iid) -> str:
        todo, seen = [iid], set()
        while todo:
            i = todo.pop(0)
            if i in seen or i not in instrs:
                continue
            seen.add(i)
            if instrs[i][2]:
                return instrs[i][2]
            todo += instrs[i][3]
        return ""

    out = {}
    for name, opcode, op_name, _, calls in instrs.values():
        if not op_name and opcode == "fusion" and calls:
            op_name = named_from(roots.get(calls[0]))
        out[name] = op_name
    return out


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op_name}} of every program whose HLO the
    profiler embedded in a serialized XSpace."""
    buf = memoryview(xspace)
    plane = None
    for f, v in _fields(buf):
        if f == 1 and any(g == 2 and _text(buf, w) == METADATA_PLANE
                          for g, w in _fields(buf, *v)):
            plane = v
            break
    if plane is None:
        return {}
    events, hlo_stat = [], None
    for f, v in _fields(buf, *plane):
        if f == 4:
            events += [w for g, w in _fields(buf, *v) if g == 2]
        elif f == 5:
            meta = next(w for g, w in _fields(buf, *v) if g == 2)
            sid = name = None
            for g, w in _fields(buf, *meta):
                if g == 1:
                    sid = w
                elif g == 2:
                    name = _text(buf, w)
            if name == HLO_STAT:
                hlo_stat = sid
    out = {}
    for ev in events:
        name, proto = None, None
        for g, w in _fields(buf, *ev):
            if g == 2:
                name = _text(buf, w)
            elif g == 5:
                stat = dict(_fields(buf, *w))
                if stat.get(1) == hlo_stat and 6 in stat:
                    proto = stat[6]
        if name is not None and proto is not None:
            out[name] = _hlo_op_names(buf, proto)
    return out


# ---------------------------------------------------------------------------
# device ops -> op_name -> scope
# ---------------------------------------------------------------------------


def instruction_of(event_name: str) -> str:
    """The HLO instruction an "XLA Ops" event ran: the event's name is the
    instruction's text (`%fusion.8 = s32[..] fusion(..)`) or its name."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def scopes_of(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """(layer, kernel): the innermost `pq.*` and `kernel.*` components of
    an `op_name` path, None where there is none."""
    parts = op_name.split("/")
    layer = next((p for p in reversed(parts)
                  if p.startswith(LAYER_PREFIX)), None)
    kernel = next((p for p in reversed(parts)
                   if p.startswith(KERNEL_PREFIX)), None)
    return layer, kernel


def attribute(planes, op_names: Dict[str, Dict[str, str]],
              window_span: str) -> List[Attributed]:
    """Each device op inside the traced window, with its program (the
    "XLA Modules" event enclosing it on its plane), opcode, op_name and own
    time in seconds over the number of device planes."""
    planes = list(planes)
    window = next(((float(e.start_ns), float(e.start_ns + e.duration_ns))
                   for p in planes if p.name == HOST_PLANE
                   for line in p.lines for e in line.events
                   if e.name == window_span), None)
    if window is None:
        raise ValueError(f"no {window_span!r} span in the trace")
    per_plane = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in (lines[MODULES_LINE].events
                                         if MODULES_LINE in lines else ()))
        starts = [m[0] for m in mods]
        ops = []
        for e in lines[OPS_LINE].events:
            s, d = float(e.start_ns), float(e.duration_ns)
            c = _clip(s, s + d, *window)
            if not c:
                continue
            k = bisect.bisect_right(starts, s) - 1
            program = mods[k][2] if k >= 0 and s <= mods[k][1] else ""
            ops.append((c[0], c[1], (program, e.name)))
        per_plane.append(ops)
    if not per_plane:
        raise ValueError("no device operations in the trace")
    n = len(per_plane)
    out = []
    for ops in per_plane:
        for _, _, (program, event), own in self_times(ops):
            instr = instruction_of(event)
            out.append((program, instr, opcode_of(event),
                        op_names.get(program, {}).get(instr, ""),
                        own * 1e-9 / n))
    return out


def by_scope(attributed: Sequence[Attributed]) -> Dict[str, float]:
    """Own seconds by layer scope (`(unscoped)` for none), by the parent
    `pq.X` of a branch scope `pq.X.Y`, and by kernel scope."""
    out: Dict[str, float] = {}
    for _, _, _, op_name, own in attributed:
        layer, kernel = scopes_of(op_name)
        keys = [layer or UNSCOPED]
        if layer and layer.count(".") > 1:
            keys.append(".".join(layer.split(".")[:2]))
        if kernel:
            keys.append(kernel)
        for k in keys:
            out[k] = out.get(k, 0.0) + own
    return out


def layers(scopes: Dict[str, float]) -> Dict[str, float]:
    """The entries of `by_scope` that partition busy time: each layer
    `pq.X` (branch scopes rolled up) and `(unscoped)`."""
    return {k: v for k, v in scopes.items()
            if k == UNSCOPED or (k.startswith(LAYER_PREFIX)
                                 and k.count(".") == 1)}


def shares(scopes: Dict[str, float], busy_s: float) -> Dict[str, float]:
    """`SHARES` in % of busy time; empty for a trace of a program that
    names no `pq.*` scope, since there is nothing to divide."""
    if busy_s <= 0 or not any(k.startswith(LAYER_PREFIX) for k in scopes):
        return {}
    return {name: 100.0 * sum(v for k, v in scopes.items()
                              if any(k == p or (p.endswith(".")
                                                and k.startswith(p))
                                     for p in prefixes)) / busy_s
            for name, prefixes in SHARES.items()}


def share(view, name: str) -> Optional[float]:
    """`SHARES[name]` of a run's traced segment, for a reader of
    `bench/metrics/`: None without a trace, or where the trace names no
    `pq.*` scope."""
    if view.trace is None:
        return None
    return shares(view.scopes, view.trace.busy_s).get(name)


# ---------------------------------------------------------------------------
# one cell's traced segment, on the chip
# ---------------------------------------------------------------------------


def measure(workload: str, seed: int, seconds: float) -> dict:
    """A traced benchmark run of the cell (`harness.run_cell`), printed by
    scope and by instruction."""
    import json

    from bench import harness as H

    line, view = H.run_cell(workload, seed, seconds, True)
    red = view.trace
    heavy: Dict[tuple, list] = {}  # (program, instr, opcode, op_name) ->
    # [executions, seconds]
    for program, instr, opcode, op_name, own in view.scope_ops:
        h = heavy.setdefault((program.split("(")[0], instr, opcode, op_name),
                             [0, 0.0])
        h[0] += 1
        h[1] += own
    out = {
        "workload": workload, "seed": seed, "correct": line["correct"],
        "device": line["device"], "windows": len(view.dispatch_s),
        "setup": view.setup,
        "busy_s": red.busy_s, "window_s": red.window_s,
        "idle_pct": red.idle_pct,
        "by_scope": dict(sorted(view.scopes.items(), key=lambda kv: -kv[1])),
        "shares_pct": shares(view.scopes, red.busy_s),
        "layer_sum_pct": 100.0 * sum(layers(view.scopes).values())
        / red.busy_s,
        "heaviest_ops": [[*k[:3], n, t, k[3]] for k, (n, t) in sorted(
            heavy.items(), key=lambda kv: -kv[1][1])[:25]],
        "trace_cost": view.trace_cost,
    }
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="untraced windows before the traced segment")
    args = ap.parse_args(argv)

    from bench.harness import NoChip, use_compile_cache

    use_compile_cache()

    try:
        measure(args.workload, args.seed, args.seconds)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

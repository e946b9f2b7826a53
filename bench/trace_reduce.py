"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

* The traced window is the host annotation named by the harness
  (`bench.window`), read from the host plane on the trace's own clock.
* Device operations are the events of each device plane's "XLA Ops" line.
  Busy time is the union of their intervals inside the window, averaged
  over the device planes; idle time is the rest of the window.
* Each operation is grouped by its HLO opcode, read from the text of the
  instruction and not from its name, so that a renamed fusion keeps its
  group. The ops of a loop's or a conditional's body are nested inside
  that op's event; each op counts its own time, without its body's.
* Each execution of a compiled program is an event of the "XLA Modules"
  line; their device durations are kept by program name.
* Each idle gap is labelled by the host span (`generate`, `dispatch`,
  `readback`) that overlaps it most, or `other`.

Nothing here imports the program; it reads only what the profiler wrote.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    by_opcode: Dict[str, float]  # device seconds, averaged over devices
    gaps: List[Tuple[str, float]]  # (host span, seconds), longest first
    devices: int
    modules: Dict[str, List[float]]  # program name -> execution seconds

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        ops = sorted(self.by_opcode.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def opcode_of(name: str) -> str:
    """Opcode of a device operation from its event name, which on the TPU
    is the HLO instruction's text: `%sort.10 = (s32[..], ..) sort(...)`.
    A fusion is keyed by its kind as well (`fusion:kLoop`), since fusions
    of every kind share the opcode. A name that is not instruction text
    is kept without its `.N` suffix."""
    eq = name.find(" = ")
    if not name.startswith("%") or eq < 0:
        return re.sub(r"[.\d]+$", "", name) or name
    i = eq + 3
    if name.startswith("(", i):  # a tuple shape: skip to its closing paren
        depth = 0
        for j in range(i, len(name)):
            depth += {"(": 1, ")": -1}.get(name[j], 0)
            if depth == 0:
                i = j + 1
                break
    else:
        i = name.find(" ", i)
    m = re.match(r"\s*([a-z][a-z0-9\-]*)\(", name[i:])
    if not m:
        return name[:eq].lstrip("%").split(".")[0]
    op = m.group(1)
    if op == "fusion":
        kind = re.search(r"kind=(k\w+)", name)
        op += f":{kind.group(1)}" if kind else ""
    return op


def self_times(ops: Sequence[Tuple[float, float, str]]):
    """(start, end, opcode, self time) of each operation of one line. The
    line nests a loop's or a conditional's body inside its event, so an
    operation's own time is its duration less that of the operations
    directly inside it."""
    out = []
    stack: List[list] = []
    for s, e, op in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and s >= stack[-1][1]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([s, e, op, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce_profile(planes, window_span: str,
                   host_spans: Sequence[str]) -> Reduction:
    """`planes`: the planes of a `jax.profiler.ProfileData`."""
    spans: List[Tuple[str, float, float]] = []
    window = None
    device_ops = []  # per device plane: [(start, end, opcode)]
    modules: Dict[str, List[float]] = {}
    planes = list(planes)
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if e.name == window_span and window is None:
                    window = (s, s + d)
                elif e.name in host_spans:
                    spans.append((e.name, s, s + d))
    if window is None:
        raise ValueError(f"no {window_span!r} span in the trace")
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for e in line.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    if _clip(s, s + d, *window):
                        modules.setdefault(e.name, []).append(d * 1e-9)
            elif line.name == OPS_LINE:
                device_ops.append([
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     opcode_of(e.name)) for e in line.events])
    if not device_ops:
        raise ValueError("no device operations in the trace")
    lo, hi = window
    n = len(device_ops)
    busy = 0.0
    by_opcode: Dict[str, float] = {}
    gaps: List[Interval] = []
    for ops in device_ops:
        inside = [(c[0], c[1], op) for s, e, op in ops
                  if (c := _clip(s, e, lo, hi))]
        for _, _, op, own in self_times(inside):
            by_opcode[op] = by_opcode.get(op, 0.0) + own / n
        inside = [(s, e) for s, e, _ in inside]
        merged = union(inside)
        busy += sum(b - a for a, b in merged) / n
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    # host spans run one after another on one thread: sorted by start,
    # they are sorted by end too, so the spans over a gap are a run of them
    spans.sort(key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    ends = [sp[2] for sp in spans]
    labelled = []
    for a, b in gaps:
        best, label = 0.0, "other"
        for name, s, e in spans[bisect.bisect_right(ends, a):
                                bisect.bisect_left(starts, b)]:
            c = _clip(s, e, a, b)
            if c and c[1] - c[0] > best:
                best, label = c[1] - c[0], name
        labelled.append((label, (b - a) * 1e-9))
    labelled.sort(key=lambda g: -g[1])
    return Reduction(busy_s=busy * 1e-9, window_s=(hi - lo) * 1e-9,
                     by_opcode={k: v * 1e-9 for k, v in by_opcode.items()},
                     gaps=labelled, devices=n, modules=modules)


def xplane_in(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(found)}")
    return found[0]


def reduce_file(path: str, window_span: str,
                host_spans: Sequence[str]) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path).planes, window_span,
                          host_spans)

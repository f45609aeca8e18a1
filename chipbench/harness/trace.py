"""Reduce a profiler trace (``.xplane.pb``) of the window to what the
metrics read: the device's busy time (the union of the intervals in
which an operation ran), the operations that took most time, the
longest idle gaps with the host span that covers most of each, and the
device time of each program execution.

Device planes are ``/device:TPU:<id>``; their ``XLA Ops`` line holds one
event per operation run, ``XLA Modules`` one per program execution.
The window is the host span named by the caller.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
TOP = 10
OPCODE = re.compile(r"\}? ?([a-z][a-z0-9-]*)\(")


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                              # mean over the chips
    ops: List[Tuple[str, float]]               # seconds, mean over chips
    idle: List[Tuple[str, float]]              # longest gaps, labelled
    modules: Dict[str, List[float]]            # program -> seconds each

    def module_times(self, prefix: str) -> List[float]:
        return [t for name, ts in self.modules.items()
                if name.startswith(prefix) for t in ts]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle[:TOP]]}


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def _short(op: str) -> str:
    """An HLO op's name and opcode, without its shapes: the trace names
    each op by its whole instruction text."""
    name, _, rest = op.partition(" = ")
    m = OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else op[:120]


def _label(gap: Tuple[int, int], spans: List[Tuple[str, int, int]]) -> str:
    best, cover = "unattributed", 0
    for name, a, b in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > cover:
            best, cover = name, c
    return best


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce(trace_dir: str, window: str,
           device_ids: Optional[Sequence[int]] = None) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_file(trace_dir))
    host_spans: List[Tuple[str, int, int]] = []
    lo = hi = None
    devices = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if device_ids is None or int(m.group(1)) in device_ids:
                devices.append(plane)
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if not name.startswith(SPAN_PREFIX):
                    continue
                if name == SPAN_PREFIX + window:
                    lo, hi = a, b
                else:
                    host_spans.append((name[len(SPAN_PREFIX):], a, b))
    if lo is None:
        raise ValueError(f"no span {SPAN_PREFIX + window} in the trace")
    if not devices:
        raise ValueError("no device plane in the trace")
    busy_total, op_time, modules, idle = 0, {}, {}, []
    for k, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        spans = []
        for name, a, b in (_events(ops) if ops is not None else ()):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                spans.append((a, b))
                op = _short(name)
                op_time[op] = op_time.get(op, 0) + (b - a)
        busy = union(spans)
        busy_total += sum(b - a for a, b in busy)
        if MODULES_LINE in lines:
            for name, a, b in _events(lines[MODULES_LINE]):
                if a >= lo and b <= hi:
                    modules.setdefault(name, []).append((b - a) / 1e9)
        if k == 0:
            idle = sorted(((_label(g, host_spans), (g[1] - g[0]) / 1e9)
                           for g in gaps(busy, lo, hi)),
                          key=lambda x: -x[1])
    n = len(devices)
    ops_sorted = sorted(((name, t / n / 1e9) for name, t in op_time.items()),
                        key=lambda x: -x[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
                   ops=ops_sorted, idle=idle[:TOP], modules=modules)

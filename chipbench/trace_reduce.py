"""Reduce a profiler trace to the device's busy and idle time.

The harness wraps its own host calls in ``jax.profiler.TraceAnnotation``
spans named ``chipbench.*``; the profiler records them on the same clock as
the device's operations.  The reduction works on flat event records
``(plane, line, name, start_ns, duration_ns)``:

* the traced window runs from the first harness span's start to the last
  one's end;
* a device's busy time is the length of the union of its operation
  intervals (line ``XLA Ops`` of each ``/device:TPU:*`` plane) inside the
  window, and the idle share is 1 minus busy over the window;
* the top operations are the ones that do the work: control flow
  (``while``, ``conditional``, ``call``), which holds other operations,
  is left out of them;
* each idle gap is named after the harness span that overlaps it most,
  which says what the host was doing while the device waited;
* collective time per device is the summed duration, inside the window, of
  its all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute operations, told by the opcode of their HLO text (a
  TPU trace names an op after the JAX operation it came from:
  ``%psum_invariant.3 = f32[...] all-reduce(...)``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_COLLECTIVE_OP = re.compile(r"[\s)}](?:%s)(?:-start|-done)?\(" %
                            "|".join(COLLECTIVES))
# ops that hold other ops on the same line (a round's scan is one
# ``while``): they count towards busy time, not towards the top ops
CONTROL_FLOW = ("while", "conditional", "call")

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over devices
    busy_s_per_device: Dict[str, float]
    top_ops: List[Tuple[str, float]]   # (op name, seconds per device)
    idle_gaps: List[Tuple[str, float]]  # (what the host did, seconds)
    collective_s: Dict[str, float]

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(merged, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in merged if e > t0 and s < t1]


def reduce_events(events: Iterable[Event],
                  top: int = 10) -> Optional[TraceSummary]:
    """Reduce flat events (times in ns) to a :class:`TraceSummary`; None
    when no device operation was recorded (a host-only backend)."""
    spans, ops = [], defaultdict(list)
    for plane, line, name, start, dur in events:
        if _is_device(plane):
            if line == OPS_LINE:
                ops[plane].append((op_name(name), start, start + dur,
                                   is_collective(name)))
        elif name.startswith(SPAN_PREFIX):
            spans.append((name, start, start + dur))
    if not ops:
        return None
    if spans:
        t0, t1 = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    else:
        t0 = min(s for evs in ops.values() for _, s, _ in evs)
        t1 = max(e for evs in ops.values() for _, _, e in evs)
    busy, gaps = {}, []
    per_op, coll = defaultdict(float), {}
    for plane, evs in ops.items():
        merged = _clip(union((s, e) for _, s, e, _ in evs), t0, t1)
        busy[plane] = sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_label(spans, gs, ge), (ge - gs) * 1e-9))
        coll[plane] = 0.0
        for name, s, e, collective in evs:
            if e > t0 and s < t1:
                if not name.startswith(CONTROL_FLOW):
                    per_op[name] += (min(e, t1) - max(s, t0)) * 1e-9 / len(ops)
                if collective:
                    coll[plane] += (min(e, t1) - max(s, t0)) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(t1 - t0) * 1e-9,
        busy_s=sum(busy.values()) / len(busy),
        busy_s_per_device=busy,
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top], collective_s=coll)


def is_collective(name: str) -> bool:
    """Whether a device op is a collective: by the opcode of its HLO text,
    or by its name where the trace gives no HLO text."""
    _, sep, rhs = name.partition(" = ")
    if sep:
        return bool(_COLLECTIVE_OP.search(rhs))
    return any(c in name for c in COLLECTIVES)


def op_name(name: str) -> str:
    """A device op's short name: the HLO instruction name and its first
    result shape (TPU traces name each op by its whole HLO text)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _SHAPE.search(rhs)
    return lhs.lstrip("%") + (f" {m.group(0)}" if m else "")


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9][0-9,]*\]")


def _label(spans, gs, ge) -> str:
    best, name = 0.0, "host outside the harness spans"
    for n, s, e in spans:
        overlap = min(e, ge) - max(s, gs)
        if overlap > best:
            best, name = overlap, n
    return f"idle in {name}"


def profile_events(path: str) -> List[Event]:
    """Flat events of one ``.xplane.pb`` file, read with JAX alone."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]

"""Reduce a profiler trace to device busy time, time per operation and
idle gaps attributed to the harness's host spans.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes
into plain events; everything after it is arithmetic on ``(name, start,
end)`` tuples in nanoseconds, so that it can be checked on a recorded
trace.  Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are the harness's own
``TraceAnnotation`` events (names starting ``bench.``) on the host plane.

The device's clock in a TPU trace runs about a millisecond apart from the
host's (on a v5e the device's events read some 1.2 ms early), so a gap
shorter than that may be put down to the span next to the right one.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class Event(NamedTuple):
    name: str
    start: int   # ns
    end: int     # ns


class Summary(NamedTuple):
    """What the per-layer readers take from one traced window."""

    window_s: float                 # length of the traced window
    busy_s: float                   # busy union, averaged over the devices
    op_s: Dict[str, float]          # device seconds per op name, summed
    gaps: List[Tuple[str, float]]   # idle gaps, longest first: (span, s)
    idle_by_span: Dict[str, float]  # idle seconds per host span


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """The trace names an op by its HLO text; keep its name and its result
    type without the layout (``'%fusion.1 = f32[12,41]{1,0:T(8,128)}
    fusion(...)'`` becomes ``'fusion.1 f32[12,41]'``)."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    typ = rest.split(" ", 1)[0]
    if not sep or typ.startswith("("):
        return name
    return f"{name} {re.sub(r'{[^}]*}', '', typ)}"


def read_xplane(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """``({device plane: ops}, host spans)`` of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(op_name(e.name), int(e.start_ns),
                                     int(e.start_ns + e.duration_ns))
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Event(e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of intervals, as sorted disjoint ``(start, end)`` pairs."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def idle_gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no busy interval covers
    (``busy`` merged and clipped)."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_labeller(spans: Sequence[Event]):
    """``label(t)``: the innermost harness span open at ``t``.  Spans
    inside the window do not overlap one another (the harness opens them
    one after another), so the one that opened last before ``t`` is the
    only candidate; where it has closed, the window's label stands."""
    inner = sorted((sp for sp in spans if sp.name != WINDOW_SPAN),
                   key=lambda sp: sp.start)
    starts = [sp.start for sp in inner]

    def label(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and inner[i].end > t:
            return inner[i].name
        return WINDOW_SPAN
    return label


def summarize(devices: Dict[str, List[Event]], spans: Sequence[Event],
              top: int = 10) -> Summary:
    """Reduce one trace over its window (the ``bench.window`` span)."""
    windows = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    lo, hi = windows[0].start, windows[0].end
    label_at = span_labeller(spans)
    busy_total = 0
    op_ns: Dict[str, int] = {}
    gaps: List[Tuple[str, int]] = []
    idle_by: Dict[str, int] = {}
    for ops in devices.values():
        busy = merge(clip([(e.start, e.end) for e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for ev in ops:
            d = min(ev.end, hi) - max(ev.start, lo)
            if d > 0:
                op_ns[ev.name] = op_ns.get(ev.name, 0) + d
        for s, e in idle_gaps(busy, lo, hi):
            label = label_at((s + e) // 2)
            gaps.append((label, e - s))
            idle_by[label] = idle_by.get(label, 0) + (e - s)
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        op_s={k: v * 1e-9 for k, v in sorted(op_ns.items(),
                                             key=lambda kv: -kv[1])},
        gaps=[(lab, d * 1e-9) for lab, d in gaps[:top]],
        idle_by_span={k: v * 1e-9 for k, v in idle_by.items()})


def kernel_seconds(summary: Summary, kernels: Sequence[str]) -> float:
    """Device seconds of the ops named for one of ``kernels`` (the op is
    named for its kernel, with a ``.<n>`` suffix where there are several)."""
    return sum(sec for name, sec in summary.op_s.items()
               if name.split(" ")[0].split(".")[0] in kernels)

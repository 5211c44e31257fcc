"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* The traced window runs from the start of the first benchmark
  annotation (``bench.call``, one around each call the window makes) to
  the end of the last, on the profiler's clock.
* A device is busy while any of its operations runs: the union of the
  intervals of the events on its ``XLA Ops`` line, clipped to the window.
  Its idle share is 1 minus busy over the window.
* The device operations that took most time, by self time (a loop op
  without the ops of its body), summed by name over the devices; an
  operation inside a loop counts each time it runs.
* The longest idle gaps of the busiest device, each labelled by the
  shortest host event that covers its midpoint: what the host was doing
  while the device waited.

Times are in seconds.  Nothing here reads the program.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION = "bench.call"

Interval = Tuple[int, int]      # [start_ns, end_ns)


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered_ns(merged: List[Interval], lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` that the disjoint ``merged`` intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


@dataclass
class Reduction:
    window: Interval                          # traced window, ns
    annotations: List[Interval]               # one per benchmark call
    busy: Dict[str, List[Interval]]           # device -> merged busy
    ops: List[Tuple[str, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device: Optional[str] = None) -> float:
        """Busy seconds of one device, or the mean over the devices."""
        if device is not None:
            return covered_ns(self.busy[device], *self.window) / 1e9
        if not self.busy:
            return 0.0
        return sum(self.busy_s(d) for d in self.busy) / len(self.busy)

    def busiest(self) -> Optional[str]:
        return max(self.busy, key=self.busy_s) if self.busy else None

    def busy_in(self, lo: int, hi: int, device: Optional[str] = None
                ) -> float:
        """Busy seconds of ``device`` (default: the busiest) in [lo, hi)."""
        device = device or self.busiest()
        return covered_ns(self.busy[device], lo, hi) / 1e9 if device else 0.0


def _events(line):
    for ev in line.events:
        s = int(ev.start_ns)
        yield ev.name, s, s + int(ev.duration_ns)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(events: List[Tuple[str, int, int]], lo: int, hi: int
               ) -> Dict[str, int]:
    """Nanoseconds in ``[lo, hi)`` per op name that no op nested inside it
    covers (a loop's body ops are nested inside the loop op)."""
    out: Dict[str, int] = {}
    stack: List[list] = []                 # [name, start, end, child_ns]

    def close(item):
        name, s, e, child = item
        own = max(0, min(e, hi) - max(s, lo)) - child
        out[name] = out.get(name, 0) + max(own, 0)

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            parent_end = min(stack[-1][2], e, hi)
            stack[-1][3] += max(0, parent_end - max(s, lo))
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def reduce_profile(profile, annotation: str = ANNOTATION,
                   top: int = 10) -> Optional[Reduction]:
    """``profile`` is a ``jax.profiler.ProfileData``; ``None`` when the trace
    holds no benchmark annotation."""
    host_events: List[Tuple[str, int, int]] = []
    ann: List[Interval] = []
    device_ops: Dict[str, List[Tuple[str, int, int]]] = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (op_name(n), s, e) for n, s, e in _events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == annotation:
                        ann.append((s, e))
                    else:
                        host_events.append((name, s, e))
    if not ann:
        return None
    ann.sort()
    lo, hi = ann[0][0], max(e for _, e in ann)
    busy = {d: union(clip([(s, e) for _, s, e in evs], lo, hi))
            for d, evs in device_ops.items()}
    red = Reduction(window=(lo, hi), annotations=ann, busy=busy)
    per_op: Dict[str, int] = {}
    for evs in device_ops.values():
        for name, ns in self_times(evs, lo, hi).items():
            per_op[name] = per_op.get(name, 0) + ns
    red.ops = [(n, ns / 1e9) for n, ns in
               sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
               if ns > 0]
    dev = red.busiest()
    if dev is not None:
        merged = busy[dev]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle.sort(key=lambda iv: iv[0] - iv[1])
        for s, e in idle[:top]:
            mid = (s + e) // 2
            cover = [(ev_e - ev_s, name) for name, ev_s, ev_e in host_events
                     if ev_s <= mid < ev_e]
            label = min(cover)[1] if cover else "no host event"
            red.gaps.append((label, (e - s) / 1e9))
    return red


def load(trace_dir: str):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return ProfileData.from_file(paths[0])

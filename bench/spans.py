"""The program's own spans in a cell's window, for the per-layer readers.

The program (``repro.telemetry.spans``) records each span with its
``span_id``, ``parent_id`` and ``root_id``; every span of one ``api.*``
call shares the call's ``root_id``.  A program whose records carry no
ids gives no calls here, so every reader built on this file reads
nothing there rather than failing.

Program spans are timed on the host's ``perf_counter``, the profile on
the profiler's clock.  ``idle_ms`` pairs the i-th traced ``bench.call``
annotation with the i-th root span of the window and moves that call's
spans onto the profiler's clock by the difference of the two starts.
The error is the benchmark's own Python between entering the annotation
and entering the call, which shifts the spans a little early.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence


def calls(spans: Sequence, verb: str) -> List:
    """The root spans named ``verb``, in start order ([] without ids)."""
    return sorted((r for r in spans if r.name == verb
                   and getattr(r, "parent_id", 0) is None),
                  key=lambda r: r.start_us)


def per_call(spans: Sequence, verb: str, names: Sequence[str],
             value: Callable = lambda r: r.dur_us / 1e3) -> Optional[float]:
    """Mean over the ``verb`` calls of ``value`` (default: milliseconds)
    summed over each call's spans named in ``names``; ``None`` when there
    is no call or no such span."""
    roots = calls(spans, verb)
    ids = {r.span_id for r in roots}
    found = [r for r in spans if r.name in names
             and getattr(r, "root_id", None) in ids]
    if not found:
        return None
    return sum(value(r) for r in found) / len(roots)


def idle_ms(ctx: dict, verb: str, names: Sequence[str]) -> Optional[float]:
    """Mean, over the traced calls, of the milliseconds inside each call's
    spans named in ``names`` during which the busiest device ran nothing.
    ``None`` without a trace or such spans, or when the pairing of
    annotations with calls fails: their counts differ, or a call's span
    does not fit inside its annotation."""
    red = ctx["trace"]
    if red is None or not red.busy:
        return None
    spans = ctx["window_spans"]
    roots = calls(spans, verb)
    ann = red.annotations
    if not ann or len(ann) != min(int(ctx["traced_items"]), len(roots)):
        return None
    per_root = {r.span_id: [] for r in roots[:len(ann)]}
    for r in spans:
        if r.name in names and getattr(r, "root_id", None) in per_root:
            per_root[r.root_id].append(r)
    if not any(per_root.values()):
        return None
    idle = []
    for (a0, a1), root in zip(ann, roots):
        shift = a0 - root.start_us * 1e3          # ns onto the profile
        if a0 + root.dur_us * 1e3 > a1:
            return None
        ms = 0.0
        for r in per_root[root.span_id]:
            lo = int(round(r.start_us * 1e3 + shift))
            hi = int(round((r.start_us + r.dur_us) * 1e3 + shift))
            ms += (hi - lo) / 1e6 - red.busy_in(lo, hi) * 1e3
        idle.append(ms)
    return sum(idle) / len(idle)

"""In-loop flight recorder: scan-safe structured event capture.

The lag twin's ``lax.scan`` is opaque once compiled -- end-of-run
aggregates cannot say *which* repack decision blew the SLO, or whether a
violation window overlapped a rebalance storm.  This module captures the
answer inside the scan, as pure data flow:

* :class:`TelemetryConfig` -- static, hashable knobs; rides inside
  ``LagSimConfig`` so it participates in jit / fleet compile-cache keys
  automatically.  ``None`` (or ``enabled=False``) is the recorder-free
  path: the engine emits the exact same jaxpr as before this module
  existed, so the goldens stay bit-identical.
* a fixed vector of per-step **channels** (migrations, the per-iteration
  Eq. 10 R-score, unreadable/storm partition counts, replica count,
  active-partition count, total lag and configurable lag quantiles),
  threaded as an extra scan output -- or, with ``ring`` set, written
  into a fixed-shape ring buffer carried through the scan so memory
  stays O(ring) on arbitrarily long simulations;
* :class:`TelemetryFrame` -- the recorded array bundle (a registered
  pytree; channel names are static aux data so they survive jit, vmap
  and stacking);
* :class:`CounterState` -- the custom-counter contract: a policy whose
  scan state is ``CounterState(counters, names, inner)`` gets its
  ``counters`` appended to every recorded step (the registry's policy
  protocol documents this);
* :func:`decode_events` / :class:`EventStream` -- host-side decoding of
  a frame into typed event records (scale decisions, migration bursts,
  rebalance-storm windows, partition births/deaths), with
  ``to_dataframe()`` / ``to_json()`` exporters.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static recorder knobs (hashable: part of the engine's jit key).

    ``lag_quantiles`` adds one ``lag_q{..}`` channel per entry (quantile
    of per-partition backlog over the *active* partitions).  ``ring``
    bounds recorder memory: ``None`` records every step (``T`` rows);
    an integer keeps only the last ``ring`` steps in a carried ring
    buffer (the flight-recorder mode for very long scans).

    The streaming-observability knobs ride here too: ``sketch`` carries
    online aggregators (``repro.telemetry.sketch``) through the scan,
    ``alerts`` evaluates a declarative rule set in-loop
    (``repro.telemetry.alerts``), and ``record_frames=False`` drops the
    per-step frame entirely -- sketches/alerts in O(1) memory with no
    O(T) history, the planet-scale monitoring mode.
    """

    enabled: bool = True
    lag_quantiles: Tuple[float, ...] = (0.5, 0.9, 0.99)
    ring: Optional[int] = None
    record_frames: bool = True
    sketch: Optional["Any"] = None       # telemetry.sketch.SketchConfig
    alerts: Optional["Any"] = None       # telemetry.alerts.AlertConfig

    def __post_init__(self) -> None:
        for q in self.lag_quantiles:
            if not 0.0 <= float(q) <= 1.0:
                raise ValueError(
                    f"lag_quantiles entries must be in [0, 1], got {q!r}")
        if self.ring is not None and int(self.ring) < 1:
            raise ValueError(
                f"ring={self.ring!r} must be a positive number of steps "
                f"(or None to record every step)")
        if self.ring is not None and not self.record_frames:
            raise ValueError(
                "ring is a frame-recorder mode; record_frames=False with "
                "ring set is contradictory (drop ring, or keep frames)")
        if self.sketch is not None:
            from . import sketch as _sketch
            if not isinstance(self.sketch, _sketch.SketchConfig):
                raise TypeError(
                    f"TelemetryConfig.sketch must be a SketchConfig, got "
                    f"{type(self.sketch).__name__}")
        if self.alerts is not None:
            from . import alerts as _alerts
            if not isinstance(self.alerts, _alerts.AlertConfig):
                raise TypeError(
                    f"TelemetryConfig.alerts must be an AlertConfig, got "
                    f"{type(self.alerts).__name__}")

    @property
    def base_channels(self) -> Tuple[str, ...]:
        """Channel names this config records, before custom counters."""
        return BASE_CHANNELS + tuple(
            f"lag_q{int(round(float(q) * 100)):02d}"
            for q in self.lag_quantiles)


#: the always-recorded channels (see ``record_step`` for definitions); a
#: run with a crash schedule adds ``orphaned`` after them
BASE_CHANNELS: Tuple[str, ...] = (
    "consumers",        # replicas billed this step
    "migrations",       # partitions whose owner changed (NEG never counts)
    "rscore",           # Eq. 10 of this step's reassignment: moved speed / C
    "unreadable",       # partitions blocked (migration downtime or storm)
    "storm_parts",      # partitions blocked by a control-plane warm-up storm
    "active_parts",     # partitions that exist this step (mask contract)
    "lag_total",        # total backlog after draining
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CounterState:
    """Custom-counter contract for policies.

    A policy builder that wants its own per-step counters in the
    recorded stream wraps its scan state as
    ``CounterState(counters=f32[K], names=(...), inner=state)`` and
    updates ``counters`` in ``step``.  The engine probes the state type
    after each step and appends ``counters`` to the channel vector;
    ``names`` (static) join the frame's channel names.
    """

    counters: jax.Array                        # f32[K]
    inner: Any                                 # the policy's own state
    names: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TelemetryFrame:
    """Recorded channels of one (or a batch of) simulated stream(s).

    ``channels`` is ``f32[..., R, K]`` where ``R`` is the number of
    recorded rows (``T``, or ``ring`` in ring mode) and ``K ==
    len(names)``; ``steps`` (``i32[..., R]``) is the absolute simulation
    step of each row (``-1``: slot never written, ring mode only);
    ``count`` (``i32[...]``) the total number of steps the recorder saw.
    """

    channels: jax.Array
    steps: jax.Array
    count: jax.Array
    names: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))

    def channel(self, name: str) -> np.ndarray:
        """One channel as ``[..., R]`` numpy, by name."""
        return np.asarray(self.channels)[..., self.names.index(name)]


# ---------------------------------------------------------------------------
# in-scan recording (called from lagsim.engine inside the scan body)
# ---------------------------------------------------------------------------

def record_step(tele: TelemetryConfig, *, speeds, new_lag, moved, blocked,
                storm, n_consumers, act_t, capacity, pstate, orphaned=None
                ) -> Tuple[jax.Array, Tuple[str, ...]]:
    """The per-step channel vector ``f32[K]`` and its (static) names.

    Pure ``jnp`` on values the engine already computes -- adding the
    recorder never changes the simulated trajectories, only the scan's
    outputs.  ``storm`` may be ``None`` (no control plane).  ``orphaned``
    (bool[N], the active partitions a crashed consumer left unread) adds
    the ``orphaned`` channel; ``None`` (no crash schedule) leaves it out.
    """
    n = speeds.shape[0]
    moved_speed = jnp.sum(jnp.where(moved, speeds, 0.0))
    if act_t is None:
        active_parts = jnp.float32(n)
        lag_for_q = new_lag
    else:
        active_parts = jnp.sum(act_t.astype(jnp.float32))
        # quantiles over existing partitions only: a dead partition's
        # forced-zero lag must not drag the distribution down
        lag_for_q = jnp.where(act_t, new_lag, jnp.nan)
    vals = [
        n_consumers.astype(jnp.float32),
        jnp.sum(moved.astype(jnp.float32)),
        moved_speed / jnp.float32(capacity),
        jnp.sum(blocked.astype(jnp.float32)),
        (jnp.float32(0.0) if storm is None
         else jnp.sum(storm.astype(jnp.float32))),
        active_parts,
        jnp.sum(new_lag),
    ]
    names = tele.base_channels
    if tele.lag_quantiles:
        qs = jnp.nanquantile(
            lag_for_q, jnp.asarray(tele.lag_quantiles, jnp.float32))
        # an all-dead step has no distribution; record 0, not NaN
        qs = jnp.where(jnp.isnan(qs), 0.0, qs)
        vals.extend(qs[i] for i in range(len(tele.lag_quantiles)))
    if orphaned is not None:
        vals.append(jnp.sum(orphaned.astype(jnp.float32)))
        names = names + ("orphaned",)
    if isinstance(pstate, CounterState):
        vals.extend(pstate.counters[i].astype(jnp.float32)
                    for i in range(len(pstate.names)))
        names = names + tuple(pstate.names)
    return jnp.stack(vals), names


def ring_init(tele: TelemetryConfig, k: int):
    """Initial ring-buffer carry ``(buf f32[ring, K], steps i32[ring])``."""
    r = int(tele.ring)
    return (jnp.zeros((r, k), jnp.float32), jnp.full((r,), -1, jnp.int32))


def ring_write(carry, tick, vec):
    """Write ``vec`` at slot ``tick % ring``; returns the new carry."""
    buf, steps = carry
    slot = tick % jnp.int32(buf.shape[0])
    return (buf.at[slot].set(vec), steps.at[slot].set(tick))


def frame_from_outputs(tele: TelemetryConfig, names: Tuple[str, ...],
                       channels: jax.Array, t_total: int) -> TelemetryFrame:
    """Frame for per-step (non-ring) recording: one row per scan step."""
    steps = jnp.broadcast_to(
        jnp.arange(t_total, dtype=jnp.int32), channels.shape[:-1])
    return TelemetryFrame(channels=channels, steps=steps,
                          count=jnp.int32(t_total), names=names)


def frame_from_ring(tele: TelemetryConfig, names: Tuple[str, ...],
                    carry, t_total: int) -> TelemetryFrame:
    """Frame for ring mode: the final buffer plus absolute step indices."""
    buf, steps = carry
    return TelemetryFrame(channels=buf, steps=steps,
                          count=jnp.int32(t_total), names=names)


# ---------------------------------------------------------------------------
# host-side decoding
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TelemetryEvent:
    """One decoded event.  ``kind`` is one of:

    * ``scale``       -- the consumer count changed (``from``/``to``);
    * ``migration``   -- >= 1 partition changed owner this step
      (``count``, ``rscore`` -- the paper's Eq. 10 price of the move);
    * ``storm``       -- a control-plane rebalance-storm window
      (``start``/``end`` steps, ``peak_parts`` concurrently blocked);
    * ``downtime``    -- a window with any partition unreadable
      (migration downtime and/or storm; ``start``/``end``,
      ``peak_parts``);
    * ``lifecycle``   -- the active-partition count changed: topic
      births/deaths under the variable-N mask (``delta``, ``active``).

    ``index`` locates the stream in a batched frame (e.g. ``(policy,
    stream)`` for a sweep; ``()`` for a single trace).
    """

    kind: str
    step: int
    index: Tuple[int, ...] = ()
    data: Dict[str, float] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "step": self.step,
                "index": list(self.index),
                "data": {k: (round(float(v), 6) if isinstance(v, float)
                             else v) for k, v in self.data.items()}}


def _windows(mask: np.ndarray, steps: np.ndarray, vals: np.ndarray
             ) -> List[Tuple[int, int, float]]:
    """Contiguous True runs -> [(start_step, end_step_inclusive, peak)]."""
    out = []
    start = None
    peak = 0.0
    for i, on in enumerate(mask):
        if on and start is None:
            start, peak = int(steps[i]), float(vals[i])
        elif on:
            peak = max(peak, float(vals[i]))
        elif start is not None:
            out.append((start, int(steps[i - 1]), peak))
            start = None
    if start is not None:
        out.append((start, int(steps[-1]), peak))
    return out


def decode_events(frame: TelemetryFrame) -> List[TelemetryEvent]:
    """Decode a frame (any leading batch shape) into typed event records,
    ordered by ``(index, step)``.  Ring-mode frames decode the surviving
    window; rows never written (``step == -1``) are skipped."""
    ch = np.asarray(frame.channels, np.float64)
    steps = np.asarray(frame.steps, np.int64)
    names = frame.names
    col = {nm: i for i, nm in enumerate(names)}
    events: List[TelemetryEvent] = []
    lead = ch.shape[:-2]
    for index in np.ndindex(*lead) if lead else [()]:
        c = ch[index]                       # [R, K]
        s = steps[index]                    # [R]
        order = np.argsort(s, kind="stable")  # ring mode: restore time order
        valid = s[order] >= 0
        c, s = c[order][valid], s[order][valid]
        if c.shape[0] == 0:
            continue
        cons = c[:, col["consumers"]]
        migs = c[:, col["migrations"]]
        rsc = c[:, col["rscore"]]
        act = c[:, col["active_parts"]]
        for t in np.flatnonzero(np.diff(cons) != 0):
            events.append(TelemetryEvent(
                "scale", int(s[t + 1]), index,
                {"from": float(cons[t]), "to": float(cons[t + 1])}))
        for t in np.flatnonzero(migs > 0):
            events.append(TelemetryEvent(
                "migration", int(s[t]), index,
                {"count": float(migs[t]), "rscore": float(rsc[t])}))
        for start, end, peak in _windows(c[:, col["storm_parts"]] > 0, s,
                                         c[:, col["storm_parts"]]):
            events.append(TelemetryEvent(
                "storm", start, index, {"end": float(end),
                                        "peak_parts": peak}))
        for start, end, peak in _windows(c[:, col["unreadable"]] > 0, s,
                                         c[:, col["unreadable"]]):
            events.append(TelemetryEvent(
                "downtime", start, index, {"end": float(end),
                                           "peak_parts": peak}))
        for t in np.flatnonzero(np.diff(act) != 0):
            events.append(TelemetryEvent(
                "lifecycle", int(s[t + 1]), index,
                {"delta": float(act[t + 1] - act[t]),
                 "active": float(act[t + 1])}))
    events.sort(key=lambda e: (e.index, e.step, e.kind))
    return events


def _require_pandas(caller: str):
    """Late pandas import with a degrade-gracefully error: pandas is an
    optional dependency (not in requirements.txt), and the exporters are
    conveniences, not core paths."""
    try:
        import pandas as pd
    except ImportError as exc:
        raise ImportError(
            f"{caller} needs pandas, which is an optional dependency and "
            f"is not installed in this environment.  Install pandas, or "
            f"use to_json()/decode_events() (stdlib + numpy only) instead."
        ) from exc
    return pd


@dataclasses.dataclass
class EventStream:
    """A decoded frame: typed events plus the raw per-step samples."""

    events: List[TelemetryEvent]
    frame: TelemetryFrame

    @classmethod
    def from_frame(cls, frame: TelemetryFrame) -> "EventStream":
        return cls(events=decode_events(frame), frame=frame)

    def counts(self) -> Dict[str, int]:
        """Events per kind -- the summary the BENCH ``telemetry`` blocks
        embed."""
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    def to_json(self) -> str:
        """Canonical JSON: channel names, event records, recorded-step
        count.  Floats round to 6 decimals so fixed-seed streams diff
        cleanly across runs."""
        return json.dumps({
            "channels": list(self.frame.names),
            "recorded_steps": int(np.max(np.asarray(self.frame.count))),
            "counts": self.counts(),
            "events": [e.as_dict() for e in self.events],
        }, indent=1, sort_keys=True)

    def to_dataframe(self):
        """The per-step samples as a tidy ``pandas.DataFrame`` (one row
        per recorded (index, step), one column per channel)."""
        pd = _require_pandas("EventStream.to_dataframe")
        ch = np.asarray(self.frame.channels, np.float64)
        steps = np.asarray(self.frame.steps, np.int64)
        lead = ch.shape[:-2]
        rows = []
        for index in np.ndindex(*lead) if lead else [()]:
            c, s = ch[index], steps[index]
            for r in range(c.shape[0]):
                if s[r] < 0:
                    continue
                row = {"step": int(s[r])}
                row.update({f"i{d}": int(v) for d, v in enumerate(index)})
                row.update({nm: float(c[r, k])
                            for k, nm in enumerate(self.frame.names)})
                rows.append(row)
        return pd.DataFrame(rows).sort_values(
            [c for c in rows[0] if c.startswith("i")] + ["step"]
        ).reset_index(drop=True) if rows else pd.DataFrame()

    def events_dataframe(self):
        """The decoded events as a ``pandas.DataFrame``."""
        pd = _require_pandas("EventStream.events_dataframe")
        return pd.DataFrame([
            {"kind": e.kind, "step": e.step, "index": e.index, **e.data}
            for e in self.events])


__all__ = [
    "BASE_CHANNELS",
    "CounterState",
    "EventStream",
    "TelemetryConfig",
    "TelemetryEvent",
    "TelemetryFrame",
    "decode_events",
]

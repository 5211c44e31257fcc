"""Closed-loop lag digital twin: one ``lax.scan`` per stream, vmapped over
the scenario batch.

``serving/simulation.py`` ticks one Python-object world at a time
(broker + JSON mailboxes + replica objects); this engine keeps only the
state that determines consumer-group lag -- per-partition backlog, the
assignment, and migration downtime -- and evolves it as pure arrays, so a
whole fleet of scenarios x policies compiles into a handful of XLA
programs.  Per step ``t``:

  1. each partition produces ``rate[t] * dt`` bytes of backlog;
  2. the policy (a bin-packing algorithm or a reactive baseline, see
     ``policies.py``) maps the current speeds / backlog / previous
     assignment to a new assignment and a consumer count;
  3. partitions whose owner changed become unreadable for
     ``migration_steps`` steps -- the paper's rebalancing cost (data
     cannot be read while a queue migrates) made physical;
  4. every consumer drains up to ``capacity * dt`` bytes from its
     readable partitions, proportionally to their backlog (shared-budget
     water-filling; the fused Pallas kernel in
     ``kernels/lag_update.py`` implements the same update).

The recorded trajectories (total/max lag, consumers, migrations,
unreadable partitions) feed the SLO metrics in ``metrics.py``.  A golden
test cross-validates the twin against ``serving/simulation.py`` on a
constant-rate scenario (tests/test_lagsim.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.lag_update import (lag_update_reference,
                                      lag_update_single)
from repro.lagsim.controlplane import (ControlPlaneConfig, ControlPlaneState,
                                       wrap_policy)
from repro.lagsim.fused import fused_mode, simulate_fused, sweep_fused
from repro.registry import make_policy
from repro.telemetry.alerts import AlertState, alert_init, alert_step
from repro.telemetry.record import (CounterState, TelemetryConfig,
                                    TelemetryFrame, frame_from_outputs,
                                    frame_from_ring, record_step, ring_init,
                                    ring_write)
from repro.telemetry.sketch import SketchState, sketch_init, sketch_update

NEG = -1


@dataclasses.dataclass(frozen=True)
class LagSimConfig:
    """Static knobs of the twin (hashable: one jit cache entry per config).

    ``capacity`` is the consumer drain rate in bytes/s (the paper's C),
    ``dt`` the seconds per step.  ``lag_threshold`` / ``slo_lag`` /
    ``max_consumers`` default to values derived from capacity and the
    partition count when left ``None`` (see ``resolve``).
    """

    capacity: float = 1.0
    dt: float = 1.0
    migration_steps: int = 2          # downtime steps for a moved partition
    lag_threshold: Optional[float] = None    # KEDA_LAG target (bytes)
    target_utilization: float = 0.75         # RATE_THRESHOLD target
    max_consumers: Optional[int] = None      # reactive clamp; default n
    scale_down_patience: int = 3             # stabilization window (steps)
    slo_lag: Optional[float] = None          # metrics threshold (bytes)
    use_kernel: bool = False                 # Pallas fused update in the scan
    fused_steps: int = 0              # K > 0: fused multi-step path (fused.py)
    fused_kernel: bool = False        # fused path launches kernels/loop_fused
    control_plane: Optional[ControlPlaneConfig] = None  # scaler friction
    telemetry: Optional[TelemetryConfig] = None  # in-loop flight recorder

    @property
    def telemetry_on(self) -> bool:
        """True when the in-loop recorder captures this config's runs."""
        return self.telemetry is not None and self.telemetry.enabled

    @property
    def slo_lag_or_default(self) -> float:
        """The metrics threshold; defaults to one consumer-step of drain."""
        return (self.slo_lag if self.slo_lag is not None
                else self.capacity * self.dt)

    def resolve(self, n: int) -> "LagSimConfig":
        """Fill derived defaults for an ``n``-partition workload."""
        if (self.control_plane is not None
                and not isinstance(self.control_plane, ControlPlaneConfig)):
            # one choke point hit by both the direct and the fleet path:
            # fail fast with a named error instead of a scan-deep crash
            raise ValueError(
                f"control_plane must be a ControlPlaneConfig (or None), got "
                f"{type(self.control_plane).__name__}; build one via "
                f"repro.api.ControlPlaneConfig(...)")
        if (self.telemetry is not None
                and not isinstance(self.telemetry, TelemetryConfig)):
            raise ValueError(
                f"telemetry must be a TelemetryConfig (or None), got "
                f"{type(self.telemetry).__name__}; build one via "
                f"repro.api.TelemetryConfig(...)")
        if int(self.fused_steps) < 0:
            raise ValueError(
                f"fused_steps must be >= 0 (0 disables the fused path), "
                f"got {self.fused_steps}")
        if self.fused_kernel and not self.fused_steps:
            raise ValueError(
                "fused_kernel=True requires fused_steps > 0: the megakernel "
                "block size is fused_steps (steps advanced per launch)")
        tele = self.telemetry
        if (tele is not None and tele.sketch is not None
                and tele.sketch.hist_max is None):
            # default histogram range: eight consumer-steps of drain per
            # partition covers any workload the SLO metrics call healthy
            tele = dataclasses.replace(
                tele, sketch=dataclasses.replace(
                    tele.sketch,
                    hist_max=8.0 * self.capacity * self.dt * n))
        return dataclasses.replace(
            self,
            lag_threshold=(self.lag_threshold if self.lag_threshold is not None
                           else 2.0 * self.capacity * self.dt),
            max_consumers=(self.max_consumers if self.max_consumers is not None
                           else n),
            slo_lag=self.slo_lag_or_default,
            telemetry=tele,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LagTrace:
    """Per-step trajectories of one simulated stream (axes ``[..., T]``).

    ``telemetry`` is the in-loop flight-recorder frame when the config's
    ``TelemetryConfig`` is on (``None`` otherwise -- the recorder-free
    path is bit-identical to the pre-telemetry engine)."""

    lag_total: jax.Array    # f32  total backlog after draining
    lag_max: jax.Array      # f32  worst single-partition backlog
    consumers: jax.Array    # i32  consumers billed this step
    migrations: jax.Array   # i32  partitions that changed owner
    unreadable: jax.Array   # i32  partitions in migration downtime
    telemetry: Optional[TelemetryFrame] = None  # recorder frame [.., R, K]
    sketch: Optional[SketchState] = None    # streaming aggregators (O(1))
    incidents: Optional[AlertState] = None  # in-loop alert/incident state


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LagSweepResult:
    """Stacked trajectories of a policy sweep, indexed ``[policy, stream, t]``."""

    lag_total: jax.Array    # f32[P, B, T]
    lag_max: jax.Array      # f32[P, B, T]
    consumers: jax.Array    # i32[P, B, T]
    migrations: jax.Array   # i32[P, B, T]
    unreadable: jax.Array   # i32[P, B, T]
    policies: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    telemetry: Optional[TelemetryFrame] = None  # frame [P, B, R, K]
    sketch: Optional[SketchState] = None    # aggregators, leading [P, B]
    incidents: Optional[AlertState] = None  # alert state, leading [P, B]

    def for_policy(self, name: str) -> LagTrace:
        p = self.policies.index(name.upper())
        pick = lambda obj: (None if obj is None else
                            jax.tree_util.tree_map(lambda a: a[p], obj))
        return LagTrace(self.lag_total[p], self.lag_max[p], self.consumers[p],
                        self.migrations[p], self.unreadable[p],
                        telemetry=pick(self.telemetry),
                        sketch=pick(self.sketch),
                        incidents=pick(self.incidents))


def _check_rates_shape(rates, n: int, what: str, array_name: str) -> None:
    """Satellite fix: a partition-count mismatch used to surface as an
    opaque broadcast error deep inside ``lax.scan``; fail fast instead,
    naming both shapes."""
    got = tuple(getattr(rates, "shape", np.shape(rates)))
    if got[-1:] != (n,):
        raise ValueError(
            f"{array_name} has shape {got}, but rates.shape[-1] gives the "
            f"policy n = {n} partitions to pack; {what}")


def _simulate(trace: jax.Array, initial_lag: jax.Array, policy: str,
              cfg: LagSimConfig, active: Optional[jax.Array] = None,
              record_assign: bool = False,
              valid: Optional[jax.Array] = None):
    """Unjitted core: ``trace`` f32[T, N] -> LagTrace of f32/i32[T].

    ``active`` (bool[T, N], optional) marks which partitions exist at each
    step.  A masked partition is *unreadable and empty*: it produces no
    backlog, is assigned to no consumer (``NEG``), drains no budget, and
    its recorded lag is exactly zero.  Deaths cost no migration (the
    consumer just stops reading); rebirths start with no sticky memory.

    With ``record_assign=True`` the per-step assignment ``i32[T, N]`` is
    recorded alongside the trace and a ``(LagTrace, assigns)`` pair is
    returned (regression goldens pin full trajectories this way).

    With ``cfg.telemetry`` on, the flight recorder threads a fixed-shape
    channel vector through the scan (an extra scan output, or a carried
    ring buffer when ``telemetry.ring`` is set) and the returned
    ``LagTrace.telemetry`` holds the recorded ``TelemetryFrame``.  The
    recorder only *reads* values the step already computes, so telemetry
    on/off never changes the simulated trajectories, and the off path
    emits the exact pre-telemetry jaxpr.

    ``telemetry.sketch`` / ``telemetry.alerts`` additionally carry
    streaming aggregators (``repro.telemetry.sketch``) and an in-loop
    alert evaluator (``repro.telemetry.alerts``) through the scan --
    O(1) observability state regardless of T.  ``valid`` (bool[T],
    optional, fleet-internal) gates sketch/alert updates on padded
    bucket steps so a padded run's observability state agrees with the
    direct run's (``repro.lagsim.metrics.agrees``).
    """
    n = trace.shape[1]
    if cfg.fused_steps and fused_mode(policy, cfg, n) == "fused":
        # heuristic family under fused_steps: the multi-step fused path
        # (repro.lagsim.fused) replaces the per-step scan
        return simulate_fused(trace, initial_lag, policy, cfg, active=active,
                              record_assign=record_assign, valid=valid)
    m = 2 * n + 2                       # packer bin-name universe
    cfg = cfg.resolve(n)
    cap_step = jnp.float32(cfg.capacity * cfg.dt)
    cp = cfg.control_plane
    # strict=False: the engine passes its uniform reactive knob set to every
    # policy; specs that do not declare a knob simply ignore it.  With a
    # control plane configured, its knobs join the set, so a REAL policy
    # family (which declares them and self-wraps) sees the same grid values
    # the engine uses to wrap a plain policy below.
    extra = {} if cp is None else cp.knobs()
    pol = make_policy(
        policy, n, jnp.float32(cfg.capacity), backend="jax", strict=False,
        lag_threshold=jnp.float32(cfg.lag_threshold),
        target_utilization=jnp.float32(cfg.target_utilization),
        max_consumers=cfg.max_consumers,
        scale_down_patience=cfg.scale_down_patience, **extra)
    init, policy_step = pol.init, pol.step
    if cp is not None and not getattr(policy_step, "_controlplane_wrapped",
                                      False):
        init, policy_step = wrap_policy(init, policy_step, cp)
    # the warm-up storm only exists behind a control plane; probing the
    # step marker keeps self-wrapped (REAL) policies storm-correct even
    # when cfg.control_plane is None
    has_cp = getattr(policy_step, "_controlplane_wrapped", False)
    tele = cfg.telemetry if cfg.telemetry_on else None
    frames_on = tele is not None and tele.record_frames
    sketch_on = tele is not None and tele.sketch is not None
    alerts_on = tele is not None and tele.alerts is not None
    ring_mode = frames_on and tele.ring is not None
    need_vec = frames_on or sketch_on
    tele_names: list = [None]        # filled at trace time by record_step

    def drain(lag, produced, assign, readable, act_t):
        if cfg.use_kernel:
            # rank-1 kernel entry: no lag[None] expand + [0] squeeze pair
            # in the jaxpr of every scanned step
            return lag_update_single(
                lag, produced, assign, readable.astype(jnp.int32),
                jnp.full((m,), cap_step, jnp.float32), active=act_t)
        return lag_update_reference(lag, produced, assign, readable,
                                    cap_step, m=m, active=act_t)

    def step(carry, xs):
        lag, assign, down, pstate = carry[:4]
        ci = 4
        if ring_mode:
            tick, rbuf = carry[4:6]
            ci = 6
        if sketch_on:
            sk = carry[ci]
            ci += 1
        if alerts_on:
            al = carry[ci]
        valid_t = None
        if active is None:
            if valid is None:
                rate_t, act_t = xs, None
            else:
                (rate_t, valid_t), act_t = xs, None
            produced = rate_t * jnp.float32(cfg.dt)
        else:
            if valid is None:
                rate_t, act_t = xs
            else:
                rate_t, act_t, valid_t = xs
            produced = jnp.where(act_t, rate_t * jnp.float32(cfg.dt), 0.0)
        observed = lag + produced       # backlog a lag-reactive scaler sees
        if active is None:
            new_assign, n_active, pstate = policy_step(
                rate_t, observed, assign, pstate)
        else:
            new_assign, n_active, pstate = policy_step(
                rate_t, observed, assign, pstate, act_t)
        # NEG never counts as a move: a dying partition hands off nothing
        moved = (assign >= 0) & (new_assign >= 0) & (new_assign != assign)
        down = jnp.where(moved, jnp.int32(cfg.migration_steps),
                         jnp.maximum(down - 1, 0))
        readable = (down == 0) & (new_assign >= 0)
        blocked = down > 0
        storm_mask = None
        if has_cp:
            # rebalance storm: partitions on a warming consumer are
            # unreadable while that consumer rejoins the group
            storm = pstate.warming > 0
            readable = readable & ~storm
            storm_mask = storm & (new_assign >= 0)
            blocked = blocked | storm_mask
        new_lag = drain(lag, produced, new_assign, readable, act_t)
        unreadable = blocked if act_t is None else (blocked & act_t)
        ys = (jnp.sum(new_lag), jnp.max(new_lag),
              n_active.astype(jnp.int32),
              jnp.sum(moved.astype(jnp.int32)),
              jnp.sum(unreadable.astype(jnp.int32)))
        if tele is not None and storm_mask is not None and act_t is not None:
            storm_mask = storm_mask & act_t
        if need_vec:
            vec, tele_names[0] = record_step(
                tele, speeds=rate_t, new_lag=new_lag, moved=moved,
                blocked=unreadable, storm=storm_mask, n_consumers=n_active,
                act_t=act_t, capacity=cfg.capacity, pstate=pstate)
            if frames_on and not ring_mode:
                ys = ys + (vec,)
        if record_assign:
            ys = ys + (new_assign,)
        new_carry = (new_lag, new_assign, down, pstate)
        if ring_mode:
            new_carry = new_carry + (tick + 1, ring_write(rbuf, tick, vec))
        if sketch_on:
            new_carry = new_carry + (
                sketch_update(tele.sketch, sk, vec, valid=valid_t),)
        if alerts_on:
            storm_ct = (jnp.float32(0.0) if storm_mask is None
                        else jnp.sum(storm_mask.astype(jnp.float32)))
            new_carry = new_carry + (alert_step(
                tele.alerts, al, lag_total=ys[0], consumers=n_active,
                unreadable=ys[4], storm_parts=storm_ct,
                slo_lag=cfg.slo_lag, valid=valid_t),)
        return new_carry, ys

    if active is None:
        xs = (trace.astype(jnp.float32) if valid is None
              else (trace.astype(jnp.float32), valid.astype(bool)))
    else:
        xs = ((trace.astype(jnp.float32), active.astype(bool))
              if valid is None
              else (trace.astype(jnp.float32), active.astype(bool),
                    valid.astype(bool)))
    carry0 = (initial_lag.astype(jnp.float32), jnp.full(n, NEG, jnp.int32),
              jnp.zeros(n, jnp.int32), init(n))
    if tele is not None:
        pstate0 = carry0[3]
        full_names = tele.base_channels + (
            tuple(pstate0.names) if isinstance(pstate0, CounterState) else ())
    if ring_mode:
        carry0 = carry0 + (jnp.int32(0), ring_init(tele, len(full_names)))
    if sketch_on:
        carry0 = carry0 + (sketch_init(tele.sketch, full_names),)
    if alerts_on:
        carry0 = carry0 + (alert_init(tele.alerts),)
    carry_end, ys = lax.scan(step, carry0, xs)
    tot, mx, cons, migs, unread = ys[:5]
    idx = 5
    frame = None
    if frames_on:
        t_total = trace.shape[0]
        if ring_mode:
            frame = frame_from_ring(tele, tele_names[0], carry_end[5],
                                    t_total)
        else:
            frame = frame_from_outputs(tele, tele_names[0], ys[idx], t_total)
            idx += 1
    ci = 6 if ring_mode else 4
    sk_state = None
    if sketch_on:
        sk_state = carry_end[ci]
        ci += 1
    al_state = carry_end[ci] if alerts_on else None
    out = LagTrace(lag_total=tot, lag_max=mx, consumers=cons,
                   migrations=migs, unreadable=unread, telemetry=frame,
                   sketch=sk_state, incidents=al_state)
    return (out, ys[idx]) if record_assign else out


@functools.partial(jax.jit,
                   static_argnames=("policy", "cfg", "record_assign"))
def _simulate_jit(trace, initial_lag, policy: str, cfg: LagSimConfig,
                  active=None, record_assign: bool = False, valid=None):
    return _simulate(trace, initial_lag, policy, cfg, active, record_assign,
                     valid)


def simulate_lag(trace: jax.Array, *, policy: str,
                 cfg: LagSimConfig = LagSimConfig(),
                 initial_lag: Optional[jax.Array] = None,
                 active: Optional[jax.Array] = None,
                 record_assign: bool = False):
    """Run one policy over one stream ``f32[T, N]`` -> ``LagTrace`` of [T].

    ``initial_lag`` (f32[N], default zeros) seeds the per-partition backlog
    -- e.g. to resume from a measured system state or to study spike
    recovery from a known excursion.  ``active`` (bool[T, N], optional)
    masks partitions that do not exist at a step: unreadable and empty
    (see ``_simulate``).  ``record_assign=True`` returns
    ``(LagTrace, assigns i32[T, N])`` instead of the trace alone.
    """
    trace = jnp.asarray(trace)
    if trace.ndim != 2:
        raise ValueError(
            f"trace must be f32[T, N] (one stream); got shape {trace.shape}")
    n = trace.shape[1]
    if initial_lag is None:
        initial_lag = jnp.zeros(n, jnp.float32)
    else:
        _check_rates_shape(
            initial_lag, n, "initial_lag must seed every partition's "
            f"backlog, shape ({n},)", "initial_lag")
    if active is not None:
        active = jnp.asarray(active)
        if active.shape != trace.shape:
            raise ValueError(
                f"active mask has shape {active.shape} but the rates trace "
                f"has shape {trace.shape}; the mask must name every "
                f"(step, partition) cell")
    return _simulate_jit(trace, jnp.asarray(initial_lag, jnp.float32),
                         policy.upper(), cfg, active,
                         record_assign=record_assign)


def _sweep_impl(policies: Tuple[str, ...], traces: jax.Array,
                cfg: LagSimConfig, active: Optional[jax.Array] = None,
                valid: Optional[jax.Array] = None) -> LagSweepResult:
    """Unjitted sweep core, shared by the module-level jit below and the
    fleet execution layer (``repro.fleet``), which jits it under its own
    bounded per-bucket cache.  ``valid`` (bool[B, T], fleet-internal)
    gates sketch/alert updates on padded bucket steps."""
    zero0 = jnp.zeros(traces.shape[2], jnp.float32)
    fused_fields = {}
    if cfg.fused_steps:
        # route the heuristic family through the fused multi-step path as
        # ONE family-batched run; everything else keeps the per-step scan
        # (fused_mode raises a named error for fused-incompatible configs)
        modes = {p: fused_mode(p, cfg, traces.shape[2]) for p in policies}
        group = tuple(p for p in policies if modes[p] == "fused")
        if group:
            fused_fields = sweep_fused(group, traces, cfg, active=active,
                                       valid=valid)

    def run_policy(p):
        if active is None and valid is None:
            return jax.vmap(lambda tr: _simulate(tr, zero0, p, cfg))(traces)
        if valid is None:
            return jax.vmap(
                lambda tr, ac: _simulate(tr, zero0, p, cfg, ac))(
                    traces, active)
        if active is None:
            return jax.vmap(
                lambda tr, va: _simulate(tr, zero0, p, cfg, valid=va))(
                    traces, valid)
        return jax.vmap(
            lambda tr, ac, va: _simulate(tr, zero0, p, cfg, ac, valid=va))(
                traces, active, valid)

    per_policy = [LagTrace(**fused_fields[p]) if p in fused_fields
                  else run_policy(p) for p in policies]
    for attr, what in (("telemetry", "telemetry channels"),
                       ("sketch", "sketch channels")):
        objs = [getattr(tr, attr) for tr in per_policy]
        if any(o is not None for o in objs):
            # stacking across policies needs one channel universe; fail
            # with names, not a cryptic treedef mismatch from tree_map
            per_names = {p: (None if o is None else o.names)
                         for p, o in zip(policies, objs)}
            if len(set(per_names.values())) != 1:
                raise ValueError(
                    f"policies in one sweep must record identical {what} "
                    f"(custom CounterState counters differ): "
                    f"{per_names}; sweep them separately via simulate_lag")
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_policy)
    return LagSweepResult(
        lag_total=stacked.lag_total, lag_max=stacked.lag_max,
        consumers=stacked.consumers, migrations=stacked.migrations,
        unreadable=stacked.unreadable, policies=policies,
        telemetry=stacked.telemetry, sketch=stacked.sketch,
        incidents=stacked.incidents)


@functools.partial(jax.jit, static_argnames=("policies", "cfg"))
def _sweep_jit(policies: Tuple[str, ...], traces: jax.Array,
               cfg: LagSimConfig, active=None, valid=None) -> LagSweepResult:
    return _sweep_impl(policies, traces, cfg, active, valid)


def sweep_lag(policies: Tuple[str, ...], traces: jax.Array,
              cfg: LagSimConfig = LagSimConfig(),
              active: Optional[jax.Array] = None) -> LagSweepResult:
    """Closed-loop sweep: every policy over a batch of streams f32[B, T, N].

    ``active`` (bool[B, T, N], optional) is the per-stream partition
    existence mask.  Each policy's scan is vmapped over the batch axis;
    with batch size 1 a row is bit-identical to ``simulate_lag`` on the
    single stream (tests/test_lagsim.py).  Names are case-normalized
    before the jit boundary so equivalent spellings share one
    compile-cache entry.
    """
    traces = jnp.asarray(traces)
    if traces.ndim != 3:
        raise ValueError(
            f"traces must be f32[B, T, N]; got shape {traces.shape}")
    if active is not None:
        active = jnp.asarray(active)
        if active.shape != traces.shape:
            raise ValueError(
                f"active mask has shape {active.shape} but the rates "
                f"traces have shape {traces.shape}; the mask must name "
                f"every (stream, step, partition) cell")
    return _sweep_jit(tuple(p.upper() for p in policies), traces, cfg,
                      active)

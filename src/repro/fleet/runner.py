"""Sharded fleet execution: shape-bucketed, device-parallel scenario runs.

The mask contract (``active: bool[T, N]``, see ``core/jaxpack.py`` and
``lagsim/engine.py``) makes *padding exact*: a padded partition is just an
inactive one (packs to ``NEG``, produces no backlog, opens no bin) and a
padded timestep is sliced off the trailing end of every trajectory.  This
module turns that into a production execution layer:

* **Bucketing** -- scenarios of heterogeneous shape ``(T_i, N_i)`` are
  padded up to the next configured bucket ``(T_b, N_b)`` and grouped, so
  a fleet of thousands of ragged scenarios compiles a handful of XLA
  programs instead of one per shape.
* **Bounded jit cache** -- one compiled executable per (verb, policy
  tuple, bucket, config) key, kept in an LRU of ``max_compile_cache``
  entries.  Churning shapes can never grow the cache without bound; the
  eviction/hit/miss counters are exported via ``FleetRunner.stats()``.
* **Batch sharding** -- the scenario (batch) axis is sharded across
  devices with ``jax.sharding.NamedSharding`` over a 1-D mesh; every
  per-scenario scan is independent, so the sharded result equals the
  single-device result exactly.  Works on CPU hosts via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI smoke
  asserts the equality) and on real multi-device backends unchanged.

``FleetRunner`` is the single execution path of the repo's drivers:
``repro.api.sweep`` / ``repro.api.simulate``, the lag-SLO benchmark and
``benchmarks/paper_eval.py`` all route through it.

Caveat: the stochastic ANNEAL policies draw their Gumbel noise over a
``(chains, N * M)`` plane, so *padding* N changes the PRNG stream and
therefore the (still valid) trajectories.  For every deterministic
policy (all 12 packers and both reactive baselines) padding, and for
every policy sharding, changes no decision; float fields agree to
rounding (``repro.lagsim.metrics.agrees``), since programs of other
shapes sum in other orders.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.jaxpack import _sweep_streams_impl
from repro.lagsim.engine import LagSimConfig, _sweep_impl, check_crash
from repro.lagsim.metrics import slo_summary
from repro.telemetry.alerts import (AlertConfig, AlertState, Incident,
                                    decode_incidents, incident_counts,
                                    incident_matrix)
from repro.telemetry.record import TelemetryFrame
from repro.telemetry.sketch import (SketchConfig, SketchState, SketchSummary,
                                    merge_summaries, summaries_from_state)
from repro.telemetry.spans import instant as _instant
from repro.telemetry.spans import span as _span


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Static knobs of a ``FleetRunner``.

    ``t_buckets`` / ``n_buckets``: ascending padded sizes; a scenario's
    ``T`` (``N``) is rounded up to the smallest bucket that holds it, or
    left exact when it exceeds every bucket (or when the tuple is empty
    -- the default, which never pads and buckets by exact shape).
    ``max_compile_cache``: LRU bound on live compiled executables.
    ``shard``: shard the batch axis across ``devices`` (default: all of
    ``jax.devices()``); the batch is padded with all-inactive dummy
    scenarios up to a device multiple, then sliced back.
    """

    t_buckets: Tuple[int, ...] = ()
    n_buckets: Tuple[int, ...] = ()
    max_compile_cache: int = 16
    shard: bool = True
    devices: Optional[Tuple[Any, ...]] = None

    def __post_init__(self):
        if self.max_compile_cache < 1:
            raise ValueError(
                f"max_compile_cache must be >= 1, got {self.max_compile_cache}")
        for name in ("t_buckets", "n_buckets"):
            b = getattr(self, name)
            if tuple(sorted(b)) != tuple(b):
                raise ValueError(f"{name} must be ascending, got {b}")


@dataclasses.dataclass
class FleetSweepResult:
    """Per-scenario packing traces, in input order (arrays ``[A, T_i]``)."""

    algorithms: Tuple[str, ...]
    bins: List[np.ndarray]          # i32[A, T_i]
    rscores: List[np.ndarray]       # f32[A, T_i]
    migrations: List[np.ndarray]    # i32[A, T_i]

    def stacked(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Stack a uniform-``T`` fleet into ``[A, B, T]`` arrays."""
        return (np.stack(self.bins, axis=1), np.stack(self.rscores, axis=1),
                np.stack(self.migrations, axis=1))


#: trajectory fields of ``FleetLagResult`` (the stackable [P, T_i] arrays;
#: ``policies`` is static and ``telemetry`` holds per-scenario frames)
_TRAJ_FIELDS = ("lag_total", "lag_max", "consumers", "migrations",
                "unreadable")


@dataclasses.dataclass
class FleetLagResult:
    """Per-scenario closed-loop trajectories, in input order ([P, T_i])."""

    policies: Tuple[str, ...]
    lag_total: List[np.ndarray]     # f32[P, T_i]
    lag_max: List[np.ndarray]       # f32[P, T_i]
    consumers: List[np.ndarray]     # i32[P, T_i]
    migrations: List[np.ndarray]    # i32[P, T_i]
    unreadable: List[np.ndarray]    # i32[P, T_i]
    #: per-scenario orphaned-partition counts ``i32[P, T_i]``, present iff
    #: the run carried a crash schedule
    orphaned: Optional[List[np.ndarray]] = None
    #: per-scenario recorder frames (channels ``[P, T_i, K]``), present
    #: iff the config's ``TelemetryConfig`` is on; decode each with
    #: ``EventStream.from_frame``
    telemetry: Optional[List[TelemetryFrame]] = None
    #: per-scenario final streaming-sketch states (leading ``[P]`` policy
    #: axis, numpy leaves) plus the per-scenario *resolved*
    #: ``SketchConfig`` (``hist_max`` filled at the scenario's true N) --
    #: padded bucket steps are valid-gated out, so a padded scenario's
    #: state agrees with a direct ``simulate_lag`` run's
    sketch: Optional[List[SketchState]] = None
    sketch_configs: Optional[List[SketchConfig]] = None
    #: per-scenario final alert states (leading ``[P]``); see
    #: :meth:`scenario_incidents`
    incidents: Optional[List[AlertState]] = None
    alert_config: Optional[AlertConfig] = None
    dt: float = 1.0

    def sketch_summaries(self, scenario: int
                         ) -> List[Tuple[Tuple[int, ...], SketchSummary]]:
        """Finalized ``[(policy_index,), SketchSummary]`` pairs for one
        scenario (requires the run's ``SketchConfig`` to have been on)."""
        if self.sketch is None:
            raise ValueError(
                "this fleet run carried no sketches; enable them via "
                "TelemetryConfig(sketch=SketchConfig(...))")
        return summaries_from_state(self.sketch[scenario],
                                    self.sketch_configs[scenario])

    def scenario_incidents(self, scenario: int) -> List[Incident]:
        """Decoded incidents for one scenario (``index`` = policy)."""
        if self.incidents is None:
            raise ValueError(
                "this fleet run carried no alerting; enable it via "
                "TelemetryConfig(alerts=AlertConfig(rules=default_rules()))")
        return decode_incidents(self.incidents[scenario], self.alert_config,
                                dt=self.dt)

    def stacked(self) -> Dict[str, np.ndarray]:
        """Stack a uniform-``T`` fleet into ``[P, B, T]`` arrays
        (``orphaned`` too, when the run carried a crash schedule)."""
        names = _TRAJ_FIELDS + (() if self.orphaned is None
                                else ("orphaned",))
        return {name: np.stack(getattr(self, name), axis=1)
                for name in names}

    def summarize(self, cfg: LagSimConfig,
                  stacked: Optional[Dict[str, np.ndarray]] = None
                  ) -> Dict[str, np.ndarray]:
        """SLO summary of a uniform-``T`` fleet under ``cfg`` (the single
        reduction ``lagsim.metrics`` defines; arrays ``[P, B]``).  Pass a
        precomputed ``stacked()`` dict to avoid re-stacking."""
        st = self.stacked() if stacked is None else stacked
        return slo_summary(st["lag_total"], st["consumers"],
                           st["migrations"],
                           slo_lag=cfg.slo_lag_or_default, dt=cfg.dt)


@dataclasses.dataclass
class FleetFitness:
    """One fitness-oracle evaluation for the adversarial scenario search
    (arrays ``[P, B]``: policy x scenario, in input order).

    ``fitness = violation_frac + incident_weight * incidents / T`` --
    the SLO-violation fraction plus (optionally) the per-step rate of
    burn/invariant incidents, so a genome is rewarded both for lag the
    SLO sees and for the pages it causes."""

    policies: Tuple[str, ...]
    violation_frac: np.ndarray      # f32[P, B]
    incidents: np.ndarray           # f32[P, B] total incidents per stream
    fitness: np.ndarray             # f32[P, B]
    incident_weight: float = 0.0


@dataclasses.dataclass
class FleetProgress:
    """One live observability snapshot, handed to the ``progress``
    callback of :meth:`FleetRunner.simulate` after each bucket group
    finishes (host-side only -- the compiled programs never see it).

    ``sketch`` is the merge of every finished scenario's summaries
    (``None`` until sketches exist, or when scenarios use different
    histogram edges and cannot merge); ``incidents`` the cumulative
    per-rule incident counts."""

    done: int                           # scenarios finished so far
    total: int                          # scenarios in this call
    bucket: str                         # bucket label just finished
    sketch: Optional[SketchSummary] = None
    incidents: Dict[str, int] = dataclasses.field(default_factory=dict)


def _round_up(x: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if b >= x:
            return b
    return x


class FleetRunner:
    """Bucketed, sharded executor for scenario fleets.

    One runner owns one bounded compile cache; share it across calls (the
    benchmarks keep a module-level runner) so repeated bucket shapes hit
    warm executables.
    """

    def __init__(self, config: FleetConfig = FleetConfig()):
        self.config = config
        # key -> (executable, bucket label); the label follows the entry
        # so its eviction is charged to the right bucket
        self._cache: "OrderedDict[Any, Tuple[Callable, str]]" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._bucket_counts: Dict[Tuple[int, int], int] = {}
        self._per_bucket: Dict[str, Dict[str, int]] = {}
        self._dispatched: set = set()

    # -- observability ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Snapshot: cache behaviour and scenarios executed per bucket.

        ``per_bucket`` breaks the global hit/miss/eviction counters down
        by padded bucket label (``"TxN"``).
        """
        return {
            "cache_entries": len(self._cache),
            "cache_hits": self._hits,
            "cache_misses": self._misses,
            "cache_evictions": self._evictions,
            "buckets": {f"{t}x{n}": c
                        for (t, n), c in sorted(self._bucket_counts.items())},
            "per_bucket": {b: dict(c)
                           for b, c in sorted(self._per_bucket.items())},
            "devices": len(self._devices()),
        }

    def reset(self) -> None:
        """Zero every counter (global and per-bucket) without dropping
        compiled executables -- warm cache, fresh statistics.  Use before
        a measured region; ``clear()`` drops the executables too."""
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._bucket_counts.clear()
        self._per_bucket.clear()

    def clear(self) -> None:
        self._cache.clear()
        self._dispatched.clear()

    # -- internals ----------------------------------------------------------

    def _devices(self) -> Tuple[Any, ...]:
        return (self.config.devices if self.config.devices is not None
                else tuple(jax.devices()))

    def _bucket_stats(self, bucket: str) -> Dict[str, int]:
        return self._per_bucket.setdefault(
            bucket, {"hits": 0, "misses": 0, "evictions": 0})

    def _compiled(self, key: Any, build: Callable[[], Callable],
                  args: Tuple[Any, ...], bucket: str) -> Callable:
        """Executable for ``key``, compiling ahead-of-time on a miss.

        The jitted builder is lowered and compiled *here* (jax AOT), not
        lazily on first call -- so ``fleet.trace_lower`` / ``fleet.compile``
        spans carry the true compile cost and the first ``fleet.dispatch``
        is a dispatch, nothing more (BENCH_fleet first-call times used to
        conflate the two).
        """
        entry = self._cache.get(key)
        if entry is not None:
            self._hits += 1
            self._bucket_stats(bucket)["hits"] += 1
            self._cache.move_to_end(key)
            return entry[0]
        self._misses += 1
        self._bucket_stats(bucket)["misses"] += 1
        _instant("fleet.cache_miss", bucket=bucket)
        fn = build()
        with _span("fleet.trace_lower", bucket=bucket):
            lowered = fn.lower(*args)
        with _span("fleet.compile", bucket=bucket):
            compiled = lowered.compile()
        while len(self._cache) >= self.config.max_compile_cache:
            _, (_, gone) = self._cache.popitem(last=False)
            self._evictions += 1
            self._bucket_stats(gone)["evictions"] += 1
            _instant("fleet.cache_evict", bucket=gone)
        self._cache[key] = (compiled, bucket)
        return compiled

    def _dispatch(self, key: Any, fn: Callable, args: Tuple[Any, ...],
                  bucket: str):
        """Run the executable under a ``fleet.dispatch`` span; the span's
        ``first`` arg marks the first dispatch of this cache key (still
        distinct from compile, which happened in ``_compiled``)."""
        first = key not in self._dispatched
        self._dispatched.add(key)
        with _span("fleet.dispatch", bucket=bucket, first=first):
            return jax.block_until_ready(fn(*args))

    def _normalize(self, scenarios, active) -> List[Tuple[jax.Array,
                                                          Optional[jax.Array]]]:
        """-> list of (speeds f32[T, N], active bool[T, N] | None)."""
        if hasattr(scenarios, "ndim") and getattr(scenarios, "ndim") == 3:
            sp = jnp.asarray(scenarios, jnp.float32)
            if active is not None:
                ac = jnp.asarray(active, bool)
                if ac.shape != sp.shape:
                    raise ValueError(
                        f"active mask has shape {ac.shape} but the scenario "
                        f"batch has shape {sp.shape}")
                return [(sp[b], ac[b]) for b in range(sp.shape[0])]
            return [(sp[b], None) for b in range(sp.shape[0])]
        if active is not None:
            raise ValueError(
                "pass per-scenario masks as (speeds, active) pairs when "
                "scenarios is a sequence")
        items: List[Tuple[jax.Array, Optional[jax.Array]]] = []
        for s in scenarios:
            if isinstance(s, tuple):
                sp, ac = s
                sp = jnp.asarray(sp, jnp.float32)
                ac = None if ac is None else jnp.asarray(ac, bool)
                if ac is not None and ac.shape != sp.shape:
                    raise ValueError(
                        f"scenario mask shape {ac.shape} != speeds shape "
                        f"{sp.shape}")
            else:
                sp, ac = jnp.asarray(s, jnp.float32), None
            if sp.ndim != 2:
                raise ValueError(
                    f"each scenario must be f32[T, N]; got shape {sp.shape}")
            items.append((sp, ac))
        return items

    def _group(self, items, extra_key=lambda sp, ac: ()):
        """Bucket scenarios: {(Tb, Nb, use_mask, *extra): [(idx, sp, ac)]}.

        ``use_mask`` is True as soon as any member needs padding or
        carries an explicit mask -- then every member gets one (all-True
        where absent), keeping the whole group under a single jaxpr.
        """
        groups: Dict[Any, List[Tuple[int, jax.Array, Optional[jax.Array]]]] = {}
        metas = []
        for idx, (sp, ac) in enumerate(items):
            t, n = sp.shape
            tb = _round_up(t, self.config.t_buckets)
            nb = _round_up(n, self.config.n_buckets)
            metas.append((idx, sp, ac, tb, nb))
        masked_buckets = {
            (tb, nb) for (_, sp, ac, tb, nb) in metas
            if ac is not None or (tb, nb) != sp.shape
        }
        for idx, sp, ac, tb, nb in metas:
            use_mask = (tb, nb) in masked_buckets
            key = (tb, nb, use_mask) + tuple(extra_key(sp, ac))
            groups.setdefault(key, []).append((idx, sp, ac))
            self._bucket_counts[(tb, nb)] = (
                self._bucket_counts.get((tb, nb), 0) + 1)
        return groups

    def _pad_and_stack(self, members, tb: int, nb: int, use_mask: bool,
                       n_dev: int, crashes=None):
        """-> (speeds [Bp, tb, nb], active [Bp, tb, nb] | None, crash
        [Bp, tb, 2nb + 2] | None); ``crashes`` are the scenarios' crash
        schedules by input index, padded with no crash on every axis."""
        sps, acs, crs = [], [], []
        for idx, sp, ac in members:
            t, n = sp.shape
            pad = ((0, tb - t), (0, nb - n))
            sps.append(jnp.pad(sp, pad))
            if use_mask:
                ac = jnp.ones((t, n), bool) if ac is None else ac
                acs.append(jnp.pad(ac, pad))        # pads with False
            if crashes is not None:
                crs.append(jnp.pad(crashes[idx], ((0, tb - t),
                                                  (0, 2 * (nb - n)))))
        n_pad = (-len(sps)) % n_dev
        for _ in range(n_pad):          # dummy scenarios for the shard grid
            sps.append(jnp.zeros((tb, nb), jnp.float32))
            if use_mask:
                acs.append(jnp.zeros((tb, nb), bool))
            if crashes is not None:
                crs.append(jnp.zeros((tb, 2 * nb + 2), bool))
        speeds = jnp.stack(sps)
        active = jnp.stack(acs) if use_mask else None
        crash = jnp.stack(crs) if crashes is not None else None
        return speeds, active, crash

    def _uniform_batch(self, scenarios, active, n_dev: int):
        """Fast-path probe: an already-stacked ``f32[B, T, N]`` batch that
        needs no bucket padding and no batch padding (B a device multiple)
        passes straight through, skipping the per-scenario unbatch /
        re-pad / re-stack round trip of the ragged path -- this is the
        common case of ``repro.api`` and the benchmark drivers."""
        if not (hasattr(scenarios, "ndim") and getattr(scenarios, "ndim") == 3):
            return None
        b, t, n = scenarios.shape
        if (_round_up(t, self.config.t_buckets) != t
                or _round_up(n, self.config.n_buckets) != n
                or b % n_dev):
            return None
        sp = jnp.asarray(scenarios, jnp.float32)
        ac = None
        if active is not None:
            ac = jnp.asarray(active, bool)
            if ac.shape != sp.shape:
                raise ValueError(
                    f"active mask has shape {ac.shape} but the scenario "
                    f"batch has shape {sp.shape}")
        self._bucket_counts[(t, n)] = self._bucket_counts.get((t, n), 0) + b
        return sp, ac

    def _device_put(self, *arrays):
        """The batch-leading ``arrays`` sharded over the ``("batch",)``
        mesh (``None`` stays ``None``)."""
        devices = self._devices()
        if not self.config.shard or len(devices) <= 1:
            return arrays
        mesh = Mesh(np.asarray(devices), ("batch",))
        sharding = NamedSharding(mesh, PartitionSpec("batch"))
        return tuple(None if a is None else jax.device_put(a, sharding)
                     for a in arrays)

    def _n_dev(self) -> int:
        devices = self._devices()
        return len(devices) if self.config.shard else 1

    # -- verbs --------------------------------------------------------------

    def _run_sweep(self, algorithms, speeds, act, capacity, tb: int, nb: int):
        speeds, act = self._device_put(speeds, act)
        key = ("sweep", algorithms, tb, nb, act is not None, speeds.shape[0])
        bucket = f"{tb}x{nb}"
        args = (speeds, jnp.float32(capacity), act)
        fn = self._compiled(key, lambda: jax.jit(functools.partial(
            _sweep_streams_impl, algorithms)), args, bucket)
        res = self._dispatch(key, fn, args, bucket)
        return (np.asarray(res.bins), np.asarray(res.rscores),
                np.asarray(res.migrations))

    def sweep(self, algorithms: Sequence[str], scenarios, capacity: float = 1.0,
              *, active=None) -> FleetSweepResult:
        """Run every algorithm over a fleet of scenarios.

        ``scenarios``: f32[B, T, N] (optionally with ``active`` bool
        [B, T, N]) or a sequence of ``f32[T_i, N_i]`` / ``(speeds,
        active)`` entries of heterogeneous shape.  Results come back
        sliced to each scenario's true ``(T_i,)`` length, in input order.
        """
        with _span("fleet.sweep", algorithms=len(algorithms)):
            return self._sweep(algorithms, scenarios, capacity, active)

    def _sweep(self, algorithms, scenarios, capacity, active
               ) -> FleetSweepResult:
        algorithms = tuple(a.upper() for a in algorithms)
        n_dev = self._n_dev()
        fast = self._uniform_batch(scenarios, active, n_dev)
        if fast is not None:
            speeds, act = fast
            b, t, n = speeds.shape
            bins, rs, migs = self._run_sweep(algorithms, speeds, act,
                                             capacity, t, n)
            return FleetSweepResult(
                algorithms=algorithms,
                bins=[bins[:, i] for i in range(b)],
                rscores=[rs[:, i] for i in range(b)],
                migrations=[migs[:, i] for i in range(b)])
        items = self._normalize(scenarios, active)
        out_bins: List[Optional[np.ndarray]] = [None] * len(items)
        out_rs: List[Optional[np.ndarray]] = [None] * len(items)
        out_migs: List[Optional[np.ndarray]] = [None] * len(items)
        for (tb, nb, use_mask), members in self._group(items).items():
            speeds, act, _ = self._pad_and_stack(members, tb, nb, use_mask,
                                                 n_dev)
            bins, rs, migs = self._run_sweep(algorithms, speeds, act,
                                             capacity, tb, nb)
            for slot, (idx, sp, _) in enumerate(members):
                t = sp.shape[0]
                out_bins[idx] = bins[:, slot, :t]
                out_rs[idx] = rs[:, slot, :t]
                out_migs[idx] = migs[:, slot, :t]
        return FleetSweepResult(algorithms=algorithms, bins=out_bins,
                                rscores=out_rs, migrations=out_migs)

    _SIM_FIELDS = _TRAJ_FIELDS

    def _run_sim(self, policies, speeds, act, rcfg, tb: int, nb: int,
                 valid=None, crash=None):
        speeds, act, crash = self._device_put(speeds, act, crash)
        # `valid is not None` is part of the key: the gated program takes
        # a third operand, so it must never share an executable with the
        # ungated one even at identical shapes; so is a crash schedule
        key = ("simulate", policies, tb, nb, act is not None,
               valid is not None, crash is not None, rcfg, speeds.shape[0])
        bucket = f"{tb}x{nb}"
        if crash is not None:
            args = (speeds, act, valid, crash)
            build = lambda: jax.jit(
                lambda tr, ac, va, cr: _sweep_impl(policies, tr, rcfg, ac,
                                                   va, cr))
        elif valid is None:
            args = (speeds, act)
            build = lambda: jax.jit(
                lambda tr, ac: _sweep_impl(policies, tr, rcfg, ac))
        else:
            args = (speeds, act, valid)
            build = lambda: jax.jit(
                lambda tr, ac, va: _sweep_impl(policies, tr, rcfg, ac, va))
        fn = self._compiled(key, build, args, bucket)
        res = self._dispatch(key, fn, args, bucket)
        fields = self._SIM_FIELDS + (() if crash is None else ("orphaned",))
        with _span("fleet.read") as counts:
            out = ({f: getattr(res, f) for f in fields}, res.telemetry,
                   res.sketch, res.incidents)
            # every leaf's copy starts before the first is awaited, so the
            # ~30 reads share one round trip instead of one each
            for leaf in jax.tree_util.tree_leaves(out):
                leaf.copy_to_host_async()
            arrays, tele, sk, inc = jax.tree_util.tree_map(np.asarray, out)
            if counts is not None:
                read = jax.tree_util.tree_leaves((arrays, tele, sk, inc))
                counts.update(arrays=len(read),
                              bytes=sum(a.nbytes for a in read))
                if crash is not None:
                    # orphaned partition-steps of the call (padding has none)
                    counts.update(orphaned=int(arrays["orphaned"].sum()))
        return arrays, tele, sk, inc

    @staticmethod
    def _scenario_frame(tele: TelemetryFrame, slot: int,
                        t: int) -> TelemetryFrame:
        """Slice one scenario's frame out of a batch frame, trimming the
        padded timesteps (the recorder ran tb steps; only the scenario's
        true first ``t`` are its history)."""
        return TelemetryFrame(
            channels=tele.channels[:, slot, :t],
            steps=tele.steps[:, slot, :t],
            count=np.minimum(tele.count[:, slot], t),
            names=tele.names)

    @staticmethod
    def _scenario_state(state, slot: int):
        """Slice one scenario's sketch/alert state (leading [P, B] axes)
        out of a batch; unlike frames there is no T axis to trim -- the
        padded steps never touched the state (valid gating)."""
        return jax.tree_util.tree_map(lambda a: a[:, slot], state)

    @staticmethod
    def _obs_on(cfg: LagSimConfig) -> bool:
        """True when the run carries scan-state observability (sketches
        or alerts) that bucket padding must valid-gate."""
        return cfg.telemetry_on and (cfg.telemetry.sketch is not None
                                     or cfg.telemetry.alerts is not None)

    def simulate(self, policies: Sequence[str], scenarios,
                 cfg: LagSimConfig = LagSimConfig(), *,
                 active=None, crash=None,
                 progress: Optional[Callable[[FleetProgress], None]] = None
                 ) -> FleetLagResult:
        """Closed-loop lag twin over a fleet of scenarios.

        The config is resolved at each scenario's *true* partition count
        (so e.g. the reactive ``max_consumers`` default clamps at the
        real N, not the padded bucket), which keeps padded runs exact.
        ``cfg.control_plane`` (scaler friction emulation) rides inside
        the hashable config, so it participates in bucket/compile-cache
        keys automatically and bucketing stays behavior-preserving.
        ``cfg.fused_steps`` / ``cfg.fused_kernel`` (the multi-step fused
        path, ``repro.lagsim.fused``) ride the same resolved config, so
        fused and unfused runs never share an executable and a padded
        fused run agrees with the direct one; an N-padded bucket above
        ``FUSED_MAX_PARTITIONS`` falls back to the per-step scan inside
        the same program.
        With ``cfg.telemetry`` on, the result carries one recorder frame
        per scenario (``FleetLagResult.telemetry``), sliced to true
        length like every other trajectory.  Streaming sketches/alerts
        ride the same config (``telemetry.sketch`` / ``telemetry.alerts``)
        and come back as per-scenario states; padded bucket steps are
        gated out of their updates, so padding stays exact for them too.

        ``crash`` is a schedule of hard consumer failures
        (``repro.lagsim.engine._simulate``): ``bool[B, T, 2N + 2]`` for a
        stacked batch, or one ``bool[T_i, 2N_i + 2]`` per scenario for a
        sequence.  It is padded with no crash on the batch, step and
        consumer-id axes, sharded like ``active``, and the result then
        carries ``orphaned``.

        ``progress`` (optional, host-side) is called after each bucket
        group with a :class:`FleetProgress` snapshot -- merged sketch
        summary and cumulative incident counts so far; this is what
        ``examples/live_dashboard.py`` streams.
        """
        with _span("fleet.simulate", policies=len(policies)):
            return self._simulate(policies, scenarios, cfg, active, progress,
                                  crash)

    @staticmethod
    def _crash_list(crash, items) -> Optional[List[jax.Array]]:
        """Per-scenario crash schedules, each checked against its
        scenario's ``(T_i, N_i)``."""
        if crash is None:
            return None
        if hasattr(crash, "ndim") and crash.ndim == 3:
            crash = [crash[b] for b in range(crash.shape[0])]
        crash = list(crash)
        if len(crash) != len(items):
            raise ValueError(
                f"crash holds {len(crash)} schedules for {len(items)} "
                f"scenarios; give one per scenario")
        for cr, (sp, _) in zip(crash, items):
            check_crash(cr, sp.shape)
        return [jnp.asarray(cr) for cr in crash]

    def _simulate(self, policies, scenarios, cfg: LagSimConfig,
                  active, progress=None, crash=None) -> FleetLagResult:
        if cfg.telemetry is not None and cfg.telemetry.ring is not None:
            raise ValueError(
                "TelemetryConfig.ring is not supported through FleetRunner: "
                "a ring holds the *last* ring steps, which for a T-padded "
                "scenario are padding, not history; use the full-history "
                "recorder (ring=None) here, or run simulate_lag directly "
                "for ring capture")
        policies = tuple(p.upper() for p in policies)
        alert_cfg = (cfg.telemetry.alerts if cfg.telemetry_on else None)
        n_dev = self._n_dev()
        fast = self._uniform_batch(scenarios, active, n_dev)
        if fast is not None:
            speeds, act = fast
            b, t, n = speeds.shape
            if crash is not None:
                crash = jnp.asarray(crash)
                check_crash(crash, speeds.shape)
            rcfg = cfg.resolve(n)
            arrays, tele, sk, inc = self._run_sim(policies, speeds, act,
                                                  rcfg, t, n, crash=crash)
            sk_cfg = None if rcfg.telemetry is None else rcfg.telemetry.sketch
            with _span("fleet.unpack", scenarios=b):
                result = FleetLagResult(policies=policies, **{
                    f: [arrays[f][:, i] for i in range(b)]
                    for f in arrays},
                    telemetry=None if tele is None else [
                        self._scenario_frame(tele, i, t) for i in range(b)],
                    sketch=None if sk is None else [
                        self._scenario_state(sk, i) for i in range(b)],
                    sketch_configs=None if sk is None else [sk_cfg] * b,
                    incidents=None if inc is None else [
                        self._scenario_state(inc, i) for i in range(b)],
                    alert_config=alert_cfg, dt=cfg.dt)
            if progress is not None:
                progress(self._progress_snapshot(result, b, b, f"{t}x{n}"))
            return result
        items = self._normalize(scenarios, active)
        crashes = self._crash_list(crash, items)
        obs_on = self._obs_on(cfg)
        fields = self._SIM_FIELDS + (() if crash is None else ("orphaned",))
        outs: Dict[str, List[Optional[np.ndarray]]] = {
            f: [None] * len(items) for f in fields}
        tele_out: List[Optional[TelemetryFrame]] = [None] * len(items)
        sk_out: List[Optional[SketchState]] = [None] * len(items)
        sk_cfg_out: List[Optional[SketchConfig]] = [None] * len(items)
        inc_out: List[Optional[AlertState]] = [None] * len(items)
        any_tele = any_sk = any_inc = False
        done = 0
        result = FleetLagResult(policies=policies, **outs,
                                telemetry=None, alert_config=alert_cfg,
                                dt=cfg.dt)
        groups = self._group(items,
                             extra_key=lambda sp, ac: (cfg.resolve(sp.shape[1]),))
        for (tb, nb, use_mask, rcfg), members in groups.items():
            speeds, act, cr = self._pad_and_stack(members, tb, nb, use_mask,
                                                  n_dev, crashes)
            valid = None
            if obs_on:
                # bool[B, T]: a scenario's true steps, False on T-padding
                # and on the all-dummy rows added for the shard grid
                rows = [np.arange(tb) < sp.shape[0] for _, sp, _ in members]
                rows += [np.zeros(tb, bool)] * (speeds.shape[0] - len(rows))
                valid = jnp.asarray(np.stack(rows))
            arrays, tele, sk, inc = self._run_sim(policies, speeds, act,
                                                  rcfg, tb, nb, valid, cr)
            sk_cfg = None if rcfg.telemetry is None else rcfg.telemetry.sketch
            with _span("fleet.unpack", scenarios=len(members)):
                for slot, (idx, sp, _) in enumerate(members):
                    t = sp.shape[0]
                    for f in fields:
                        outs[f][idx] = arrays[f][:, slot, :t]
                    if tele is not None:
                        any_tele = True
                        tele_out[idx] = self._scenario_frame(tele, slot, t)
                    if sk is not None:
                        any_sk = True
                        sk_out[idx] = self._scenario_state(sk, slot)
                        sk_cfg_out[idx] = sk_cfg
                    if inc is not None:
                        any_inc = True
                        inc_out[idx] = self._scenario_state(inc, slot)
            done += len(members)
            if progress is not None:
                result.sketch = sk_out if any_sk else None
                result.sketch_configs = sk_cfg_out if any_sk else None
                result.incidents = inc_out if any_inc else None
                progress(self._progress_snapshot(result, done, len(items),
                                                 f"{tb}x{nb}"))
        result.telemetry = tele_out if any_tele else None
        result.sketch = sk_out if any_sk else None
        result.sketch_configs = sk_cfg_out if any_sk else None
        result.incidents = inc_out if any_inc else None
        return result

    def fitness(self, policies: Sequence[str], scenarios,
                cfg: LagSimConfig = LagSimConfig(), *, active=None,
                incident_weight: float = 0.0) -> FleetFitness:
        """Fitness-batch entrypoint of the adversarial scenario search
        (``repro.scenarios.search``): one scenario batch -> per-(policy,
        scenario) SLO-violation fitness, arrays ``[P, B]``.

        Routes through :meth:`simulate`, so a search that keeps
        ``(B, T, N, cfg)`` constant across generations compiles its
        oracle once and dispatches a warm executable thereafter (the
        bounded LRU cache is the generation loop's flywheel).
        ``incident_weight > 0`` folds per-step incident counts into the
        fitness and requires ``cfg.telemetry.alerts`` to be on.
        """
        if incident_weight and not (cfg.telemetry_on
                                    and cfg.telemetry.alerts is not None):
            raise ValueError(
                "incident_weight > 0 needs alerting in the loop: pass a "
                "LagSimConfig with telemetry=TelemetryConfig(alerts="
                "AlertConfig(rules=default_rules()))")
        with _span("fleet.fitness", policies=len(policies)):
            res = self._simulate(tuple(p.upper() for p in policies),
                                 scenarios, cfg, active)
            stacked = res.stacked()
            summ = res.summarize(cfg, stacked=stacked)
            vf = np.asarray(summ["violation_frac"], np.float32)    # [P, B]
            steps = stacked["lag_total"].shape[-1]
            if res.incidents is not None:
                inc = np.stack([incident_matrix(st)
                                for st in res.incidents], axis=1)  # [P, B]
            else:
                inc = np.zeros_like(vf)
            fit = vf + np.float32(incident_weight) * inc / max(steps, 1)
            return FleetFitness(policies=res.policies, violation_frac=vf,
                                incidents=inc,
                                fitness=fit.astype(np.float32),
                                incident_weight=float(incident_weight))

    @staticmethod
    def _progress_snapshot(result: FleetLagResult, done: int, total: int,
                           bucket: str) -> FleetProgress:
        """Merge whatever has finished into one live snapshot."""
        merged = None
        if result.sketch is not None:
            summaries = []
            for i, st in enumerate(result.sketch):
                if st is not None:
                    summaries.extend(
                        s for _, s in summaries_from_state(
                            st, result.sketch_configs[i]))
            if summaries:
                try:
                    merged = merge_summaries(summaries)
                except ValueError:
                    merged = None       # heterogeneous edges: unmergeable
        counts: Dict[str, int] = {}
        if result.incidents is not None:
            for st in result.incidents:
                if st is not None:
                    for rule, c in incident_counts(st).items():
                        counts[rule] = counts.get(rule, 0) + c
        return FleetProgress(done=done, total=total, bucket=bucket,
                             sketch=merged, incidents=counts)

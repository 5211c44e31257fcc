"""Stable public facade of the reproduction.

One import gives the five verbs the paper's evaluation is made of, all
resolving policy names through ``repro.registry`` and all returning
versioned result dataclasses (``schema_version`` = ``API_VERSION``):

* ``pack``      -- one packing decision (any registered packer, either
                   backend) -> ``PackOutcome``;
* ``sweep``     -- every algorithm x a batch of speed streams through the
                   vmapped scan engine -> ``SweepOutcome``;
* ``simulate``  -- closed-loop lag twin: policies x traces with migration
                   downtime and SLO metrics -> ``SimulateOutcome``;
* ``optimize``  -- lambda-sweep annealed Pareto frontier of one instance
                   -> ``OptimizeOutcome``;
* ``evaluate``  -- the paper's Figs. 6-9 tables (CBS / avg R-score /
                   Pareto membership) on Eq. 11 streams -> ``EvaluateOutcome``;
* ``attack``    -- adversarial scenario search: evolve the workload
                   genome that maximizes one policy's SLO violation,
                   with a random-search baseline at equal evals
                   -> ``AttackOutcome``;
* ``replay``    -- run a versioned on-disk trace (``repro.scenarios``
                   format, or a ``Trace``) through the fleet path
                   -> ``ReplayOutcome``.

``sweep`` and ``simulate`` execute through the fleet layer
(``repro.fleet``): a shared ``default_fleet()`` runner buckets scenarios
by padded shape under a bounded compile cache and shards the batch axis
across available devices; both verbs take an optional ``active``
bool[B, T, N] partition mask (the variable-N contract) and an optional
``fleet=`` runner override.  ``FleetRunner`` / ``FleetConfig`` are
re-exported for callers that manage their own fleet.

Policy discovery re-exports the registry: ``list_policies``,
``make_policy``, ``get_spec``, ``packer_for``, ``PolicySpec``, ``Policy``.

``BenchReport`` is the shared envelope every ``BENCH_*.json`` is written
through (one schema across benchmark artifacts).  The CI API-surface step
runs ``selfcheck()``; the documented surface lives in README "Public
API" and is pinned by ``tests/test_api_surface.py``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from repro.registry import (
    BACKENDS,
    FAMILIES,
    PACKER_FAMILIES,
    Policy,
    PolicySpec,
    get_spec,
    list_policies,
    make_policy,
    packer_for,
)
from repro.telemetry.spans import Tracer, default_tracer, span, traced

#: schema version stamped on every result dataclass and BENCH_*.json
API_VERSION = 1

__all__ = [
    "AlertConfig",
    "AlertRule",
    "API_VERSION",
    "attack",
    "AttackOutcome",
    "BACKENDS",
    "BenchReport",
    "ControlPlaneConfig",
    "default_fleet",
    "default_tracer",
    "evaluate",
    "EvaluateOutcome",
    "EventStream",
    "FAMILIES",
    "FleetConfig",
    "FleetRunner",
    "FUSED_MAX_PARTITIONS",
    "FusedPathError",
    "get_spec",
    "Incident",
    "list_policies",
    "load_trace",
    "make_policy",
    "optimize",
    "OptimizeOutcome",
    "otlp_metrics_json",
    "pack",
    "PACKER_FAMILIES",
    "packer_for",
    "PackOutcome",
    "Policy",
    "PolicySpec",
    "prometheus_exposition",
    "replay",
    "ReplayOutcome",
    "save_trace",
    "SearchConfig",
    "SearchResult",
    "seed_trace",
    "selfcheck",
    "simulate",
    "SimulateOutcome",
    "SketchConfig",
    "SketchSummary",
    "span",
    "sweep",
    "SweepOutcome",
    "TelemetryConfig",
    "TelemetryFrame",
    "Trace",
    "Tracer",
    "use_compile_cache",
    "validate_exposition",
]

#: fleet re-exports resolve lazily (keeps ``import repro.api`` jax-free)
_FLEET_EXPORTS = ("FleetRunner", "FleetConfig")
#: lagsim re-exports resolve lazily for the same reason
_LAGSIM_EXPORTS = ("ControlPlaneConfig", "FUSED_MAX_PARTITIONS",
                   "FusedPathError")
#: in-loop recorder / sketch / alert / exporter re-exports resolve
#: lazily too (the exporters are jax-free but live behind
#: ``repro.telemetry``'s lazy map); the span half of telemetry is
#: stdlib-only and imported eagerly above
_TELEMETRY_EXPORTS = ("TelemetryConfig", "TelemetryFrame", "EventStream",
                      "SketchConfig", "SketchSummary", "AlertConfig",
                      "AlertRule", "Incident", "prometheus_exposition",
                      "validate_exposition", "otlp_metrics_json")
#: scenario-engine re-exports (trace format + adversarial search) --
#: lazy like the rest so ``import repro.api`` stays jax-free
_SCENARIO_EXPORTS = ("Trace", "SearchConfig", "SearchResult", "load_trace",
                     "save_trace", "seed_trace")


def __getattr__(name: str):
    if name in _FLEET_EXPORTS:
        from repro import fleet as _fleet

        return getattr(_fleet, name)
    if name in _LAGSIM_EXPORTS:
        from repro import lagsim as _lagsim

        return getattr(_lagsim, name)
    if name in _TELEMETRY_EXPORTS:
        from repro import telemetry as _telemetry

        return getattr(_telemetry, name)
    if name in _SCENARIO_EXPORTS:
        from repro import scenarios as _scenarios

        return getattr(_scenarios, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_DEFAULT_FLEET = None


def default_fleet():
    """The module-level ``FleetRunner`` every api verb routes through.

    One shared runner means one bounded compile cache across ``sweep`` /
    ``simulate`` calls, so repeated bucket shapes hit warm executables.
    Pass ``fleet=`` to a verb to use a differently-configured runner.
    """
    global _DEFAULT_FLEET
    if _DEFAULT_FLEET is None:
        from repro.fleet import FleetRunner

        _DEFAULT_FLEET = FleetRunner()
    return _DEFAULT_FLEET


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory (JAX
    reads it at import) and nothing else is set here.  Otherwise the cache
    lives at ``<checkout>/.jax_cache``: a fixed path, so a later process
    on the same checkout finds what an earlier one compiled.  Entry points
    call this before their first compile; importing the package never
    does, so tests run with the cache off.
    """
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.abspath(os.path.join(
            os.path.dirname(__file__), "..", "..", ".jax_cache"))
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# result dataclasses (the shared versioned schema)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackOutcome:
    """One packing decision."""

    algorithm: str
    backend: str
    capacity: float
    n_bins: int
    assignment: Dict[Any, int]        # pid -> consumer (bin name)
    loads: Dict[int, float]           # consumer -> assigned write speed
    rscore: Optional[float] = None    # Eq. 10 vs ``prev`` (None: no prev)
    schema_version: int = API_VERSION


@dataclasses.dataclass
class SweepOutcome:
    """Batched scenario sweep, axes ``[algorithm, stream, iteration]``."""

    algorithms: Tuple[str, ...]
    bins: np.ndarray                  # i32[A, B, T]
    rscores: np.ndarray               # f32[A, B, T]
    migrations: np.ndarray            # i32[A, B, T]
    schema_version: int = API_VERSION


@dataclasses.dataclass
class SimulateOutcome:
    """Closed-loop lag sweep: SLO metrics per policy x stream."""

    policies: Tuple[str, ...]
    metrics: Dict[str, np.ndarray]    # metric -> f64[P, B]
    lag_total: np.ndarray             # f32[P, B, T] raw trajectories
    consumers: np.ndarray             # i32[P, B, T]
    migrations: np.ndarray            # i32[P, B, T]
    #: per-scenario recorder frames (``TelemetryFrame``) when the config
    #: carries a ``TelemetryConfig``; decode with ``EventStream.from_frame``
    telemetry: Optional[List[Any]] = None
    #: per-scenario streaming-sketch summaries when ``telemetry.sketch``
    #: is on: ``sketches[scenario][policy]`` is a ``SketchSummary``
    #: (merge across scenarios with ``telemetry.sketch.merge_summaries``)
    sketches: Optional[List[List[Any]]] = None
    #: per-scenario decoded ``Incident`` lists (``index == (policy,)``)
    #: when ``telemetry.alerts`` is on
    incidents: Optional[List[List[Any]]] = None
    #: i32[P, B, T] active partitions left unread by a crashed consumer,
    #: when the call passed a ``crash`` schedule
    orphaned: Optional[np.ndarray] = None
    schema_version: int = API_VERSION


@dataclasses.dataclass
class OptimizeOutcome:
    """Annealed lambda-sweep Pareto frontier of one packing instance."""

    lambdas: List[float]
    per_lambda: List[Tuple[float, float]]   # best (bins, rscore) per lambda
    front: List[Tuple[float, float]]        # non-dominated set
    hypervolume: float
    heuristics: Dict[str, dict]             # name -> frontier metrics
    schema_version: int = API_VERSION


@dataclasses.dataclass
class EvaluateOutcome:
    """The paper's Figs. 6-9 tables over Eq. 11 delta-streams."""

    algorithms: Tuple[str, ...]
    deltas: Tuple[int, ...]
    cbs: Dict[int, Dict[str, float]]        # Eq. 12 per delta
    avg_rscore: Dict[int, Dict[str, float]]  # Eq. 13 per delta
    pareto: Dict[int, List[str]]            # front membership per delta
    schema_version: int = API_VERSION


@dataclasses.dataclass
class AttackOutcome:
    """Adversarial search result: the worst workload found for one
    policy, plus the random-search baseline at equal oracle evals
    (``baseline_fitness`` / ``beats_baseline`` are ``None`` when the
    baseline was skipped)."""

    policy: str
    family: str
    best_fitness: float
    best_violation_frac: float
    best_incidents: float
    witness_genome: List[float]
    witness_knobs: Dict[str, float]
    history: List[float]              # best-so-far fitness per generation
    evals: int
    generations_run: int
    seed: int
    baseline_fitness: Optional[float] = None
    beats_baseline: Optional[bool] = None
    #: the full ``repro.scenarios.SearchResult`` pair (search, baseline)
    search: Any = None
    baseline: Any = None
    schema_version: int = API_VERSION


@dataclasses.dataclass
class ReplayOutcome:
    """One on-disk trace replayed through the fleet path."""

    trace_name: str
    source: str
    shape: Tuple[int, int, int]       # (B, T, N) as simulated
    resampled: bool
    policies: Tuple[str, ...]
    metrics: Dict[str, np.ndarray]    # metric -> f64[P, B]
    #: full per-policy trajectories (the ``simulate`` result the replay
    #: reduces to metrics)
    result: Optional[SimulateOutcome] = None
    schema_version: int = API_VERSION


@dataclasses.dataclass
class BenchReport:
    """Shared envelope for ``BENCH_*.json`` artifacts.

    ``as_dict`` keeps each benchmark's historical top-level keys
    (``config`` / ``families`` / anything in ``extra``) and stamps the
    shared schema fields, so one schema covers every artifact without
    breaking row emitters that index into the dict.
    """

    kind: str                          # e.g. "lagsim", "opt"
    config: Dict[str, Any]
    families: Dict[str, Any]
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    schema_version: int = API_VERSION

    def as_dict(self) -> Dict[str, Any]:
        reserved = {"schema_version", "kind", "config", "families"}
        clash = reserved & set(self.extra)
        if clash:
            raise ValueError(
                f"BenchReport.extra must not shadow envelope keys: "
                f"{sorted(clash)}")
        return {
            "schema_version": self.schema_version,
            "kind": self.kind,
            "config": self.config,
            "families": self.families,
            **self.extra,
        }

    def write(self, path: str) -> Dict[str, Any]:
        out = self.as_dict()
        with open(path, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        return out


# ---------------------------------------------------------------------------
# the five verbs
# ---------------------------------------------------------------------------

@traced("api.pack")
def pack(speeds, capacity: float, *, algorithm: str = "BFD",
         prev: Optional[Mapping] = None, backend: str = "py") -> PackOutcome:
    """One packing decision with any registered packer.

    ``backend="py"``: ``speeds`` is a mapping pid -> write speed (or a
    sequence of (pid, speed)); ``prev`` maps pid -> previous consumer.
    ``backend="jax"``: ``speeds`` is f32[n], ``prev`` i32[n] (-1 =
    unassigned); pids are array indices.
    """
    fn = packer_for(algorithm, backend=backend)
    name = algorithm.upper()
    if backend == "py":
        speeds_of = dict(speeds)
        prev = dict(prev) if prev else None
        res = fn(speeds_of, capacity, prev=prev)
    else:
        import jax.numpy as jnp

        sp = np.asarray(speeds, np.float64)
        pv = (np.full(sp.shape[0], -1, np.int32) if prev is None
              else np.asarray(prev, np.int32))
        with span("pack.put"):
            args = (jnp.asarray(sp, jnp.float32), jnp.asarray(pv))
        with span("pack.run"):  # returns before the device finishes
            res = fn(*args, capacity)
        with span("pack.read") as counts:
            bin_of, n_bins, names, lds = (
                np.asarray(a)
                for a in (res.bin_of, res.n_bins, res.names, res.loads))
            if counts is not None:
                counts.update(arrays=4, bytes=int(
                    bin_of.nbytes + n_bins.nbytes + names.nbytes
                    + lds.nbytes))
    with span("pack.reply"):
        if backend == "py":
            assignment = dict(res.pid_to_bin)
            loads = {int(c): float(l) for c, l in res.loads.items()}
            n_bins = res.n_bins
        else:
            n_bins = int(n_bins)
            assignment = {int(j): int(c) for j, c in enumerate(bin_of)}
            loads = {int(c): float(l)
                     for c, l in zip(names[:n_bins], lds[:n_bins])}
            speeds_of = {int(j): float(w) for j, w in enumerate(sp)}
            prev = ({int(j): int(c) for j, c in enumerate(pv) if c >= 0}
                    if prev is not None else None)
        r = None
        if prev:
            from repro.core.rscore import rscore

            r = rscore(prev, assignment, speeds_of, capacity)
    return PackOutcome(algorithm=name, backend=backend,
                       capacity=float(capacity), n_bins=int(n_bins),
                       assignment=assignment, loads=loads, rscore=r)


@traced("api.sweep")
def sweep(traces, capacity: float = 1.0, *,
          algorithms: Optional[Sequence[str]] = None, active=None,
          fleet=None) -> SweepOutcome:
    """Every algorithm x a batch of streams ``f32[B, T, N]``, executed
    through the fleet layer (bucketed compile cache + batch-axis device
    sharding).  ``active`` (bool[B, T, N], optional) masks partitions
    that do not exist at a step (they pack to ``-1``)."""
    if algorithms is None:
        algorithms = list_policies(family=PACKER_FAMILIES, backend="jax")
    runner = fleet if fleet is not None else default_fleet()
    res = runner.sweep(tuple(algorithms), traces, capacity, active=active)
    bins, rscores, migrations = res.stacked()
    return SweepOutcome(algorithms=res.algorithms, bins=bins,
                        rscores=rscores, migrations=migrations)


@traced("api.simulate")
def simulate(traces, *, policies: Optional[Sequence[str]] = None,
             config=None, active=None, crash=None, fleet=None,
             control_plane=None, **cfg_overrides) -> SimulateOutcome:
    """Closed-loop lag twin over ``traces`` f32[B, T, N]: backlog, shared
    drain budgets and migration downtime per policy, reduced to SLO
    metrics (violation fraction, peak lag, time-to-drain,
    consumer-seconds, migrations).  Executes through the fleet layer;
    ``active`` (bool[B, T, N], optional) marks masked partitions as
    unreadable-and-empty.

    ``control_plane`` (a ``ControlPlaneConfig`` or a mapping of its
    knobs) runs every policy behind an emulated scaler control plane:
    polling, observation/actuation delay, cooldown, replica clamps, and
    the scale-event rebalance storm.  Inconsistent knobs raise a named
    ``ValueError`` before anything compiles.

    ``telemetry=TelemetryConfig(...)`` (a config override) turns on the
    in-loop observability surface: ``record_frames`` captures per-step
    frames (``.telemetry``), ``sketch=SketchConfig(...)`` streams O(1)
    whole-run aggregates (``.sketches``), and
    ``alerts=AlertConfig(rules=...)`` evaluates SLO burn-rate /
    lag-growth / storm / thrash rules in-loop (``.incidents``).  Export
    any of them with ``prometheus_exposition`` / ``otlp_metrics_json``.

    ``fused_steps=K`` (a config override) routes heuristic-family
    policies through the fused K-step engine (``repro.lagsim.fused``):
    bit-identical trajectories, sketch summaries and incidents, at a
    fraction of the unfused scan's dispatch cost.  Optimizer policies
    and control-plane-wrapped configs raise ``FusedPathError``;
    reactive baselines, ``n > FUSED_MAX_PARTITIONS`` and per-step frame
    recording (an O(T) surface the fused engine does not emit) fall
    back to the unfused scan per policy.

    ``crash`` (bool[B, T, 2N + 2], optional) is a schedule of hard
    consumer failures over the consumer ids the policies name:
    ``crash[b, t, c]`` kills consumer ``c`` of group ``b`` at the start
    of step ``t``, with no ``LeaveGroup``.  At each step, in order:

    1. expel: a consumer dead for ``orphan_steps`` steps (a config knob,
       the session timeout in steps, default 15) loses its partitions,
       which go unowned, and its id is free again; placing them again is
       no move;
    2. crash: a scheduled id that an active partition holds and that is
       alive dies for ``orphan_steps`` steps, and every partition on it is
       orphaned for as long (a crash of an unused or dead id does
       nothing);
    3. the policy's step and migration downtime, as without a schedule;
    4. orphaned partitions, those inside their window (moved or not) and
       those the policy puts on a dead id, are unread and count in
       ``unreadable``; ``.orphaned`` counts the active ones per step;
    5. the drain.

    ``consumers`` stays the policy's count: a dead pod is billed until it
    is expelled.  A schedule of the wrong shape or dtype raises a
    ``ValueError`` that names both shapes; a run with one takes the
    per-step scan (no fused path)."""
    import dataclasses as _dc

    from repro.lagsim import ControlPlaneConfig as _CPC
    from repro.lagsim import LagSimConfig

    if policies is None:
        policies = list_policies(backend="jax")
    cfg = config if config is not None else LagSimConfig()
    if control_plane is not None:
        if isinstance(control_plane, Mapping):
            control_plane = _CPC(**control_plane)
        cfg_overrides["control_plane"] = control_plane
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    cfg.resolve(traces.shape[-1] if hasattr(traces, "shape")
                else np.asarray(traces).shape[-1])  # fail fast on bad knobs
    runner = fleet if fleet is not None else default_fleet()
    res = runner.simulate(tuple(policies), traces, cfg, active=active,
                          crash=crash)
    with span("sim.summarize"):
        st = res.stacked()
        metrics = {k: np.asarray(v)
                   for k, v in res.summarize(cfg, stacked=st).items()}
    sketches = None
    if res.sketch is not None:
        with span("sim.sketches"):
            sketches = [[s for _, s in res.sketch_summaries(i)]
                        for i in range(len(res.sketch))]
    incidents = None
    if res.incidents is not None:
        with span("sim.incidents"):
            incidents = [res.scenario_incidents(i)
                         for i in range(len(res.incidents))]
    return SimulateOutcome(policies=res.policies, metrics=metrics,
                           lag_total=st["lag_total"],
                           consumers=st["consumers"],
                           migrations=st["migrations"],
                           telemetry=res.telemetry,
                           sketches=sketches, incidents=incidents,
                           orphaned=st.get("orphaned"))


@traced("api.optimize")
def optimize(speeds, prev=None, capacity: float = 1.0, *,
             lambdas: Sequence[float] = (0.0, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0),
             restarts: int = 4, steps: int = 250, seed: int = 0,
             score_heuristics: Union[bool, Sequence[str]] = True
             ) -> OptimizeOutcome:
    """Trace the bins-vs-R-score Pareto frontier of one instance with the
    batched annealer, and (optionally) place registered heuristics
    against it by domination status and hypervolume share."""
    import jax

    from repro.opt import anneal_frontier, heuristic_point

    sp = np.asarray(speeds, np.float64)
    pv = (np.full(sp.shape[0], -1, np.int32) if prev is None
          else np.asarray(prev, np.int32))
    fr = anneal_frontier(sp, pv, capacity, jax.random.key(seed),
                         lambdas=tuple(lambdas), restarts=restarts,
                         steps=steps)
    if score_heuristics is True:
        names = list_policies(family=PACKER_FAMILIES, backend="jax")
    elif score_heuristics:
        names = tuple(score_heuristics)
    else:
        names = ()
    heur = {name: fr.heuristic_metrics(heuristic_point(name, sp, pv, capacity))
            for name in names}
    return OptimizeOutcome(lambdas=fr.lambdas, per_lambda=fr.per_lambda,
                           front=fr.front, hypervolume=fr.hypervolume,
                           heuristics=heur)


@traced("api.evaluate")
def evaluate(*, algorithms: Optional[Sequence[str]] = None,
             deltas: Sequence[int] = (5, 15, 25), n_partitions: int = 30,
             n_measurements: int = 120, capacity: float = 1.0,
             seed: int = 0) -> EvaluateOutcome:
    """The paper's evaluation (Figs. 6-9): Cardinal Bin Score (Eq. 12),
    average R-score (Eq. 13) and Pareto-front membership per
    delta-stream (Eq. 11), through the batched sweep engine."""
    from repro.core.metrics import cbs_from_bins, pareto_front
    from repro.core.streams import generate_stream

    if algorithms is None:
        algorithms = list_policies(family=PACKER_FAMILIES, backend="jax")
    algorithms = tuple(a.upper() for a in algorithms)
    deltas = tuple(int(d) for d in deltas)
    batch = np.stack([
        generate_stream(n_partitions, n_measurements, d, capacity, seed=seed)
        for d in deltas
    ])
    out = sweep(batch, capacity, algorithms=algorithms)
    cbs: Dict[int, Dict[str, float]] = {}
    avg_r: Dict[int, Dict[str, float]] = {}
    pareto: Dict[int, List[str]] = {}
    for i, d in enumerate(deltas):
        cbs[d] = dict(zip(algorithms,
                          cbs_from_bins(out.bins[:, i, :]).tolist()))
        avg_r[d] = dict(zip(algorithms,
                            out.rscores[:, i, :].mean(axis=1).tolist()))
        pts = {a: (cbs[d][a], avg_r[d][a]) for a in algorithms}
        pareto[d] = sorted(pareto_front(pts))
    return EvaluateOutcome(algorithms=algorithms, deltas=deltas, cbs=cbs,
                           avg_rscore=avg_r, pareto=pareto)


@traced("api.attack")
def attack(policy: str, *, family: str = "adversarial", config=None,
           sim=None, seed: int = 0, baseline: bool = True,
           fleet=None) -> AttackOutcome:
    """Evolve the scenario genome that maximizes ``policy``'s SLO
    violation (``repro.scenarios.search``), then -- with ``baseline=True``
    -- run uniform random search at the *same* fitness-oracle eval budget
    and report whether the evolution strictly beat it.

    ``config`` is a ``SearchConfig`` (population, generations, trace
    shape, incident weight); ``sim`` a ``LagSimConfig`` for the fitness
    oracle.  Fixed ``seed`` -> bit-identical search.  The witness genome
    replays via ``SearchResult.witness_trace`` + :func:`replay`.
    """
    from repro.lagsim import LagSimConfig
    from repro.scenarios import search as _search

    cfg = config if config is not None else _search.SearchConfig()
    sim_cfg = sim if sim is not None else LagSimConfig()
    runner = fleet if fleet is not None else default_fleet()
    res = _search.attack(policy, family=family, config=cfg, sim=sim_cfg,
                         seed=seed, runner=runner)
    base = None
    if baseline:
        base = _search.random_search(policy, family=family, config=cfg,
                                     sim=sim_cfg, seed=seed, runner=runner,
                                     evals=res.evals)
    return AttackOutcome(
        policy=res.policy, family=res.family,
        best_fitness=res.best_fitness,
        best_violation_frac=res.best_violation_frac,
        best_incidents=res.best_incidents,
        witness_genome=[float(g) for g in res.best_genome],
        witness_knobs=dict(res.best_knobs),
        history=list(res.history), evals=res.evals,
        generations_run=res.generations_run, seed=int(seed),
        baseline_fitness=None if base is None else base.best_fitness,
        beats_baseline=(None if base is None
                        else res.best_fitness > base.best_fitness),
        search=res, baseline=base)


@traced("api.replay")
def replay(trace, *, policies: Optional[Sequence[str]] = None,
           config=None, iters: Optional[int] = None,
           method: str = "hold", fleet=None,
           **cfg_overrides) -> ReplayOutcome:
    """Replay an on-disk trace (a path to a ``.json``/``.npz`` written by
    ``repro.scenarios.save_trace``, or a ``Trace``) through the fleet
    path -- load, validate, optionally resample to ``iters`` steps, and
    run :func:`simulate` on the trace's rates + mask.

    The trace's recorded ``capacity`` drives the sim unless the caller
    overrides it (``config=`` or ``capacity=``).  Replay is
    padding-exact: the metrics equal a direct run of the same arrays.
    """
    from repro.scenarios import load_trace as _load
    from repro.scenarios import resample_trace as _resample

    tr = _load(trace) if isinstance(trace, str) else trace
    resampled = False
    if iters is not None and int(iters) != tr.iters:
        tr = _resample(tr, int(iters), method=method)
        resampled = True
    if config is None and "capacity" not in cfg_overrides:
        cfg_overrides["capacity"] = float(tr.capacity)
    out = simulate(tr.rates, policies=policies, config=config,
                   active=tr.active, fleet=fleet, **cfg_overrides)
    return ReplayOutcome(
        trace_name=tr.name, source=tr.source,
        shape=(tr.batch, tr.iters, tr.n), resampled=resampled,
        policies=out.policies, metrics=out.metrics, result=out)


# ---------------------------------------------------------------------------
# surface checks (CI)
# ---------------------------------------------------------------------------

def selfcheck() -> None:
    """CI smoke: the exported surface is intact, matches the documented
    surface (README "Public API", when the repo checkout is present), and
    the registry is populated for every family on its expected backends."""
    import os
    import re

    import sys

    mod = sys.modules[__name__]
    # hasattr, not a globals() lookup: the fleet re-exports resolve through
    # the module-level __getattr__ to stay lazy
    missing = [name for name in __all__ if not hasattr(mod, name)]
    assert not missing, f"__all__ exports missing objects: {missing}"
    assert __all__ == sorted(__all__, key=str.lower), (
        "__all__ must stay sorted (case-insensitive) so the documented "
        "surface is diffable")
    readme = os.path.join(os.path.dirname(__file__), "..", "..", "README.md")
    if os.path.exists(readme):            # repo checkout (not an install)
        with open(readme) as f:
            text = f.read()
        m = re.search(r"## Public API\n(.*?)(?:\n## |\Z)", text, re.S)
        assert m, "README.md must keep a '## Public API' section"
        documented = set(re.findall(r"`([A-Za-z_][A-Za-z0-9_]*)`",
                                    m.group(1)))
        undocumented = set(__all__) - documented
        assert not undocumented, (
            f"exports missing from README Public API: {sorted(undocumented)}")
    for family in FAMILIES:
        names = list_policies(family=family)
        assert names, f"no policies registered for family {family!r}"
    packers_py = list_policies(family=PACKER_FAMILIES, backend="py")
    packers_jax = list_policies(family=PACKER_FAMILIES, backend="jax")
    assert packers_py == packers_jax, (
        "every packer must be registered on both backends: "
        f"{packers_py} != {packers_jax}")
    assert len(packers_jax) == 12, packers_jax


if __name__ == "__main__":
    selfcheck()
    for fam in FAMILIES:
        print(f"{fam:<10} {', '.join(list_policies(family=fam))}")
    print("repro.api selfcheck OK "
          f"(API_VERSION={API_VERSION}, {len(__all__)} exports)")

"""Scan-safe streaming metric sketches: whole-run summaries in O(1)/step.

The flight recorder (``telemetry.record``) answers *what happened at
step t* -- but materializing T frames is exactly the O(T) the ROADMAP's
planet-scale item (10^4-10^6 partitions, week-long horizons) rules out.
This module carries constant-size **online aggregators** through the
lagsim ``lax.scan``, one slot per telemetry channel:

* Welford mean / variance (numerically stable single-pass moments);
* running min / max;
* debiased EWMA windows at configurable half-lives (the "last ~H steps"
  view an SLO dashboard plots);
* a fixed-bin histogram sketch over selected channels, giving whole-run
  quantiles (e.g. the p99 of total lag) within one bin of resolution --
  without ever holding the per-step history.

Everything is pure ``jnp`` on values the engine's step already computes:
sketches on never changes the simulated trajectories, and sketches off
emits the pre-existing program bit-for-bit.

The update takes an optional ``valid`` scalar so the fleet layer's
bucket padding stays exact: a padded timestep leaves the sketch state
untouched (``where(valid, new, old)``), so a padded run's sketch agrees
with the direct run's (``repro.lagsim.metrics.agrees``: counts exact,
floats to rounding).  Host-side, :class:`SketchSummary`
finalizes a state (debiasing EWMAs, deriving stddev and quantiles) and
**merges across buckets/scenarios** with Chan's parallel-variance
update, so a fleet of thousands of scenarios reduces to one summary
without restacking trajectories.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Static sketch knobs (hashable: rides ``TelemetryConfig`` inside
    the engine's jit key).

    ``ewma_halflives`` are in *steps*: a window's weight on a sample
    halves every ``h`` steps (``alpha = 1 - 2**(-1/h)``).
    ``hist_channels`` selects which channels get a fixed-bin histogram
    over ``[0, hist_max]`` (values clamp into the edge bins, so choose
    ``hist_max`` to cover the workload's lag range; ``None`` lets
    ``LagSimConfig.resolve`` default it to ``8 * capacity * dt * n`` --
    eight consumer-steps of drain per partition).  Quantile estimates
    are exact to one bin width ``hist_max / hist_bins``.
    """

    ewma_halflives: Tuple[float, ...] = (8.0, 64.0)
    hist_bins: int = 64
    hist_channels: Tuple[str, ...] = ("lag_total",)
    hist_max: Optional[float] = None

    def __post_init__(self) -> None:
        for h in self.ewma_halflives:
            if not float(h) > 0.0:
                raise ValueError(
                    f"ewma_halflives entries must be > 0 steps, got {h!r}")
        if int(self.hist_bins) < 2:
            raise ValueError(
                f"hist_bins={self.hist_bins!r} must be >= 2 (one bin cannot "
                f"locate a quantile)")
        if self.hist_max is not None and not float(self.hist_max) > 0.0:
            raise ValueError(
                f"hist_max={self.hist_max!r} must be > 0 (or None to derive "
                f"a default from the lagsim config)")

    @property
    def alphas(self) -> Tuple[float, ...]:
        """Per-step EWMA decay rates derived from the half-lives."""
        return tuple(1.0 - 2.0 ** (-1.0 / float(h))
                     for h in self.ewma_halflives)

    @property
    def bin_width(self) -> float:
        """Histogram bin width -- the quantile resolution bound."""
        if self.hist_max is None:
            raise ValueError(
                "hist_max is unresolved (None); run through LagSimConfig."
                "resolve or set it explicitly")
        return float(self.hist_max) / int(self.hist_bins)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchState:
    """The carried aggregator bundle (``K`` channels, ``H`` half-lives,
    ``C`` histogrammed channels x ``B`` bins).  All leaves are fixed
    shape, so the state scans, jits, vmaps, and stacks."""

    count: jax.Array      # f32[]     valid steps aggregated
    mean: jax.Array       # f32[K]    Welford running mean
    m2: jax.Array         # f32[K]    Welford sum of squared deviations
    vmin: jax.Array       # f32[K]
    vmax: jax.Array       # f32[K]
    ewma: jax.Array       # f32[H, K] biased EWMA (debias via ewma_w)
    ewma_w: jax.Array     # f32[H]    accumulated EWMA weight (debiasing)
    hist: jax.Array       # f32[C, B] per-channel fixed-bin counts
    names: Tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    hist_names: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True))


def _hist_indices(cfg: SketchConfig, names: Tuple[str, ...]) -> Tuple[int, ...]:
    idx = []
    for ch in cfg.hist_channels:
        if ch not in names:
            raise ValueError(
                f"SketchConfig.hist_channels names unknown channel {ch!r}; "
                f"this run records {names}")
        idx.append(names.index(ch))
    return tuple(idx)


def sketch_init(cfg: SketchConfig, names: Tuple[str, ...]) -> SketchState:
    """Zero state for ``names`` (the run's full channel tuple, custom
    counters included).  Raises (named) if a ``hist_channels`` entry is
    not a recorded channel."""
    _hist_indices(cfg, names)           # fail fast on unknown channels
    k = len(names)
    h = len(cfg.ewma_halflives)
    c = len(cfg.hist_channels)
    return SketchState(
        count=jnp.float32(0.0),
        mean=jnp.zeros(k, jnp.float32),
        m2=jnp.zeros(k, jnp.float32),
        vmin=jnp.full(k, jnp.inf, jnp.float32),
        vmax=jnp.full(k, -jnp.inf, jnp.float32),
        ewma=jnp.zeros((h, k), jnp.float32),
        ewma_w=jnp.zeros(h, jnp.float32),
        hist=jnp.zeros((c, int(cfg.hist_bins)), jnp.float32),
        names=tuple(names),
        hist_names=tuple(cfg.hist_channels))


def sketch_update(cfg: SketchConfig, state: SketchState, vec: jax.Array,
                  valid: Optional[jax.Array] = None) -> SketchState:
    """One O(K) update with the step's channel vector ``f32[K]``.

    ``valid`` (scalar bool, optional) gates the update: a ``False`` step
    (fleet bucket padding) leaves every aggregate untouched, so padded
    runs agree with direct runs.
    """
    c1 = state.count + 1.0
    d = vec - state.mean
    mean = state.mean + d / c1
    m2 = state.m2 + d * (vec - mean)
    vmin = jnp.minimum(state.vmin, vec)
    vmax = jnp.maximum(state.vmax, vec)
    al = jnp.asarray(cfg.alphas, jnp.float32)[:, None]        # [H, 1]
    ewma = (1.0 - al) * state.ewma + al * vec[None, :]
    ewma_w = (1.0 - al[:, 0]) * state.ewma_w + al[:, 0]
    hist = state.hist
    if state.hist_names:
        width = jnp.float32(cfg.bin_width)
        rows = jnp.arange(len(state.hist_names))
        x = vec[jnp.asarray(_hist_indices(cfg, state.names))]
        slot = jnp.clip((x / width).astype(jnp.int32), 0,
                        int(cfg.hist_bins) - 1)
        hist = hist.at[rows, slot].add(1.0)
    new = SketchState(count=c1, mean=mean, m2=m2, vmin=vmin, vmax=vmax,
                      ewma=ewma, ewma_w=ewma_w, hist=hist,
                      names=state.names, hist_names=state.hist_names)
    if valid is None:
        return new
    keep = jnp.asarray(valid, bool)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(keep, a, b), new, state)


# ---------------------------------------------------------------------------
# host-side finalization + cross-bucket merging
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SketchSummary:
    """A finalized sketch: plain numpy, one row per channel.

    ``ewma`` maps half-life -> debiased window value per channel;
    ``hist`` / ``edges`` back :meth:`quantile`.  ``m2`` is kept (not just
    the stddev) so :func:`merge_summaries` can combine summaries with
    Chan's parallel-variance update.
    """

    names: Tuple[str, ...]
    count: float
    mean: np.ndarray                    # f64[K]
    m2: np.ndarray                      # f64[K]
    vmin: np.ndarray                    # f64[K]
    vmax: np.ndarray                    # f64[K]
    ewma: Dict[float, np.ndarray]       # halflife -> f64[K] (debiased)
    hist: np.ndarray                    # f64[C, B]
    hist_names: Tuple[str, ...]
    edges: np.ndarray                   # f64[B + 1] shared bin edges

    @classmethod
    def from_state(cls, state: SketchState,
                   cfg: SketchConfig) -> "SketchSummary":
        """Finalize one stream's state (no leading batch axes -- index
        or ``tree_map`` a batched state down to one stream first)."""
        count = np.asarray(state.count, np.float64)
        if count.ndim != 0:
            raise ValueError(
                f"from_state finalizes ONE stream; this state has leading "
                f"batch shape {count.shape} -- slice it (see "
                f"summaries_from_state) or merge_summaries the slices")
        w = np.asarray(state.ewma_w, np.float64)
        raw = np.asarray(state.ewma, np.float64)
        ewma = {
            float(h): (raw[i] / w[i] if w[i] > 0 else np.zeros(raw.shape[1]))
            for i, h in enumerate(cfg.ewma_halflives)
        }
        bins = int(cfg.hist_bins)
        return cls(
            names=state.names,
            count=float(count),
            mean=np.asarray(state.mean, np.float64),
            m2=np.asarray(state.m2, np.float64),
            vmin=np.asarray(state.vmin, np.float64),
            vmax=np.asarray(state.vmax, np.float64),
            ewma=ewma,
            hist=np.asarray(state.hist, np.float64),
            hist_names=state.hist_names,
            edges=np.linspace(0.0, float(cfg.hist_max), bins + 1))

    # -- derived views ------------------------------------------------------

    def channel_index(self, name: str) -> int:
        try:
            return self.names.index(name)
        except ValueError:
            raise ValueError(
                f"unknown channel {name!r}; this sketch covers {self.names}")

    def variance(self) -> np.ndarray:
        """Population variance per channel (0 where count < 2)."""
        if self.count < 2:
            return np.zeros_like(self.mean)
        return self.m2 / self.count

    def stddev(self) -> np.ndarray:
        return np.sqrt(np.maximum(self.variance(), 0.0))

    def quantile(self, q: float, channel: Optional[str] = None) -> float:
        """Histogram quantile estimate (bin-center of the bin holding the
        q-th observation; exact to one bin width).  ``channel`` defaults
        to the single histogrammed channel."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q!r}")
        if channel is None:
            if len(self.hist_names) != 1:
                raise ValueError(
                    f"pass channel= explicitly; this sketch histograms "
                    f"{self.hist_names}")
            channel = self.hist_names[0]
        if channel not in self.hist_names:
            raise ValueError(
                f"channel {channel!r} has no histogram; sketched: "
                f"{self.hist_names} (add it to SketchConfig.hist_channels)")
        counts = self.hist[self.hist_names.index(channel)]
        total = counts.sum()
        if total <= 0:
            return 0.0
        cum = np.cumsum(counts)
        k = int(np.searchsorted(cum, q * total, side="left"))
        k = min(k, len(counts) - 1)
        return float(0.5 * (self.edges[k] + self.edges[k + 1]))

    def as_dict(self, quantiles: Sequence[float] = (0.5, 0.9, 0.99)
                ) -> Dict[str, Any]:
        """JSON-ready nested dict (the shape the BENCH ``telemetry``
        blocks embed)."""
        std = self.stddev()
        out: Dict[str, Any] = {"count": self.count, "channels": {}}
        for i, nm in enumerate(self.names):
            row = {
                "mean": float(self.mean[i]),
                "std": float(std[i]),
                "min": float(self.vmin[i]) if self.count else 0.0,
                "max": float(self.vmax[i]) if self.count else 0.0,
            }
            for h, v in sorted(self.ewma.items()):
                row[f"ewma_h{h:g}"] = float(v[i])
            out["channels"][nm] = row
        for ch in self.hist_names:
            out["channels"][ch].update({
                f"p{int(round(q * 100)):02d}": self.quantile(q, ch)
                for q in quantiles
            })
        return out


def summaries_from_state(state: SketchState, cfg: SketchConfig
                         ) -> List[Tuple[Tuple[int, ...], SketchSummary]]:
    """Finalize every stream of a batched state (any leading shape on
    ``count``) -> ``[(index, summary), ...]`` in ``np.ndindex`` order."""
    lead = np.asarray(state.count).shape
    out = []
    for index in (np.ndindex(*lead) if lead else [()]):
        one = jax.tree_util.tree_map(lambda a: np.asarray(a)[index], state)
        out.append((index, SketchSummary.from_state(one, cfg)))
    return out


def merge_summaries(summaries: Sequence[SketchSummary]) -> SketchSummary:
    """Combine per-bucket/per-scenario summaries into one, as if a single
    sketch had seen every (valid) step.

    Exact for count / mean / variance (Chan's parallel update), min /
    max, and the histogram (bin-wise sum, so merged quantiles keep the
    one-bin resolution bound).  EWMA windows are *stream-local* recency
    views with no exact cross-stream merge; the merged value is the
    count-weighted mean, flagged as such in the docs.
    """
    ss = list(summaries)
    if not ss:
        raise ValueError("merge_summaries needs at least one summary")
    first = ss[0]
    for s in ss[1:]:
        if s.names != first.names or s.hist_names != first.hist_names:
            raise ValueError(
                f"cannot merge sketches over different channel sets: "
                f"{s.names} vs {first.names}")
        if s.edges.shape != first.edges.shape or not np.allclose(
                s.edges, first.edges):
            raise ValueError(
                "cannot merge sketches with different histogram edges "
                "(hist_max/hist_bins must match across the fleet)")
    count = 0.0
    mean = np.zeros_like(first.mean)
    m2 = np.zeros_like(first.m2)
    vmin = np.full_like(first.vmin, np.inf)
    vmax = np.full_like(first.vmax, -np.inf)
    hist = np.zeros_like(first.hist)
    ew_num = {h: np.zeros_like(v) for h, v in first.ewma.items()}
    for s in ss:
        if s.count > 0:
            delta = s.mean - mean
            tot = count + s.count
            m2 = m2 + s.m2 + delta * delta * (count * s.count / tot)
            mean = mean + delta * (s.count / tot)
            count = tot
            vmin = np.minimum(vmin, s.vmin)
            vmax = np.maximum(vmax, s.vmax)
        hist = hist + s.hist
        for h, v in s.ewma.items():
            ew_num[h] = ew_num[h] + v * s.count
    ewma = {h: (num / count if count > 0 else num)
            for h, num in ew_num.items()}
    return SketchSummary(names=first.names, count=count, mean=mean, m2=m2,
                         vmin=vmin, vmax=vmax, ewma=ewma, hist=hist,
                         hist_names=first.hist_names, edges=first.edges)


__all__ = [
    "SketchConfig",
    "SketchState",
    "SketchSummary",
    "merge_summaries",
    "sketch_init",
    "sketch_update",
    "summaries_from_state",
]

"""SLO metrics over simulated lag trajectories.

The paper's claim is qualitative ("guarantees adequate consumption
rates ... at lower operational costs"); these metrics make it measurable
per (policy, scenario):

* ``peak_lag``        -- worst total backlog ever observed (bytes).
* ``mean_lag``        -- time-averaged total backlog (bytes).
* ``violation_frac``  -- fraction of steps with total lag above the SLO
                         threshold (a lag-based availability SLO).
* ``time_to_drain``   -- longest single excursion above the threshold
                         (seconds): how long a spike takes to drain.
* ``consumer_seconds``-- integral of the consumer count over time: the
                         operational cost the paper minimizes.
* ``total_migrations``-- partitions moved over the run (rebalance churn;
                         the R-score prices exactly this).

All functions are plain numpy over trailing-time arrays ``[..., T]`` so
they work on a single ``LagTrace`` and on stacked ``[P, B, T]`` sweeps
alike.

``agrees`` states when two runs of the same simulation that differ only
in program shape or backend (fleet padding, the fused engine, the Pallas
kernels, CPU vs TPU) count as the same result.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: float fields of two agreeing runs differ by at most this fraction of
#: the field's largest finite magnitude along its last axis (its scale:
#: a trajectory's peak over time, a sketch's over channels)
FLOAT_RTOL = 1e-4

SLO_METRIC_NAMES = ("peak_lag", "mean_lag", "violation_frac", "time_to_drain",
                    "consumer_seconds", "total_migrations")


def longest_excursion(mask: np.ndarray) -> np.ndarray:
    """Length (in steps) of the longest run of ``True`` along the last axis."""
    mask = np.asarray(mask, bool)
    run = np.zeros(mask.shape[:-1], np.int64)
    best = np.zeros_like(run)
    for t in range(mask.shape[-1]):
        run = np.where(mask[..., t], run + 1, 0)
        best = np.maximum(best, run)
    return best


def slo_summary(lag_total, consumers, migrations, *, slo_lag: float,
                dt: float = 1.0) -> Dict[str, np.ndarray]:
    """Reduce trajectories ``[..., T]`` to the SLO metric dict ``[...]``."""
    lag_total = np.asarray(lag_total)
    consumers = np.asarray(consumers)
    migrations = np.asarray(migrations)
    over = lag_total > slo_lag
    return {
        "peak_lag": lag_total.max(axis=-1),
        "mean_lag": lag_total.mean(axis=-1),
        "violation_frac": over.mean(axis=-1),
        "time_to_drain": longest_excursion(over) * dt,
        "consumer_seconds": consumers.sum(axis=-1) * dt,
        "total_migrations": migrations.sum(axis=-1),
    }


def summarize_sweep(result, cfg) -> Dict[str, np.ndarray]:
    """SLO summary of a ``LagSweepResult`` under ``cfg`` (arrays ``[P, B]``).

    Pass the same config the sweep ran with; an unset ``slo_lag`` uses the
    config's own default (``cfg.slo_lag_or_default``).
    """
    return slo_summary(result.lag_total, result.consumers, result.migrations,
                       slo_lag=cfg.slo_lag_or_default, dt=cfg.dt)


def agrees(got, want) -> bool:
    """The agreement contract on one field of two runs.

    Integer and bool fields -- the decisions (consumers, migrations,
    assignments) and every count -- must be equal.  Float fields may
    differ by rounding: programs of different shapes, and different
    backends, reduce in different orders, so only ``|got - want| <=
    FLOAT_RTOL * scale`` is required, where ``scale`` is ``want``'s
    largest finite magnitude along the last axis; equal infinities and
    NaN positions must match exactly.
    """
    a, b = np.asarray(got), np.asarray(want)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if not np.issubdtype(b.dtype, np.floating):
        return bool(np.array_equal(a, b))
    mag = np.where(np.isfinite(b), np.abs(b), 0.0)
    scale = np.max(mag, axis=-1, keepdims=True) if b.ndim else mag
    with np.errstate(invalid="ignore"):
        close = np.abs(a - b) <= FLOAT_RTOL * scale
    return bool(np.all((a == b) | (np.isnan(a) & np.isnan(b)) | close))

"""The fused multi-step lag engine, pinned to the scan.

Load-bearing properties:

* **Fused == unfused** -- with ``fused_steps > 0`` every heuristic
  policy's trajectory (all five ``LagTrace`` fields) agrees with the
  per-step ``lax.scan`` (decisions exact, lag within
  ``repro.lagsim.metrics.agrees``), across every scenario
  family, under partition masking (``topic_lifecycle`` / ``churn``),
  with ``T % K != 0`` remainders, and with a seeded ``initial_lag``
  (hypothesis property + deterministic fallback).
* **Observability carries over** -- sketch summaries and alert/incident
  states from the fused path equal the unfused ones leaf-for-leaf.
* **The Pallas megakernel agrees** -- ``fused_kernel=True`` routes
  through ``kernels/loop_fused.py`` and still agrees with the scan
  (interpreter mode off-TPU, like every kernel in the repo).
* **Fleet padding is preserved** -- a padded bucket run with the fused
  config equals the padded run of the unfused config byte-for-byte.
* **Refusals are named** -- optimizer policies, control-plane configs
  and control-plane-wrapped REAL scalers raise ``FusedPathError``;
  everything else the fused loop cannot express falls back to the scan
  per policy (``fused_mode`` is the documented routing table).
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - CI installs it
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro.core.scenarios import generate_masked_scenario, scenario_suite
from repro.fleet import FleetConfig, FleetRunner
from repro.lagsim import (
    FUSED_MAX_PARTITIONS,
    ControlPlaneConfig,
    FusedPathError,
    LagSimConfig,
    fused_mode,
    simulate_lag,
    sweep_lag,
)
from repro.lagsim.metrics import agrees
from repro.telemetry import (AlertConfig, SketchConfig, TelemetryConfig,
                             default_rules)

HEURISTICS = ("NF", "NFD", "FF", "FFD", "BF", "BFD", "WF", "WFD")
FIELDS = ("lag_total", "lag_max", "consumers", "migrations", "unreadable")

BASE = LagSimConfig(capacity=1.0, dt=0.7, migration_steps=3)
FUSED = dataclasses.replace(BASE, fused_steps=8)


def _fused_pair(cfg, **over):
    """(unfused, fused) configs differing only in ``fused_steps``."""
    a = dataclasses.replace(cfg, **over)
    return a, dataclasses.replace(a, fused_steps=8)


def _assert_traces_equal(a, b, msg=""):
    """Decisions (integer fields) exact, lag within the agreement contract
    (``repro.lagsim.metrics.agrees``): programs of different shapes sum
    the same lags in different orders."""
    for f in FIELDS:
        assert agrees(getattr(a, f), getattr(b, f)), (msg, f)


# ---------------------------------------------------------------------------
# fused agrees with unfused
# ---------------------------------------------------------------------------
def test_fused_equals_scan_every_scenario_family():
    suite = scenario_suite(jax.random.key(0), 2, 37, 10)
    for fam, traces in suite.items():
        a = sweep_lag(HEURISTICS, traces, BASE)
        b = sweep_lag(HEURISTICS, traces, FUSED)
        _assert_traces_equal(a, b, fam)


@pytest.mark.parametrize("family", ("churn", "topic_lifecycle"))
def test_fused_equals_scan_masked(family):
    """Partition masking (birth/death mid-stream) flows through the fused
    carry exactly: dead partitions stay unreadable-and-empty."""
    sp, act = generate_masked_scenario(family, jax.random.key(1), 2, 41, 9)
    a = sweep_lag(HEURISTICS, sp, BASE, active=act)
    b = sweep_lag(HEURISTICS, sp, FUSED, active=act)
    _assert_traces_equal(a, b, family)


@pytest.mark.parametrize("k", (1, 5, 8, 64))
def test_fused_remainder_blocks(k):
    """T % K != 0: the internal pad to a K multiple never leaks into the
    real steps (incl. K == 1 and K > T degenerate blockings)."""
    tr = jax.random.uniform(jax.random.key(2), (2, 23, 7), maxval=1.1)
    a = sweep_lag(("BFD", "WFD"), tr, BASE)
    b = sweep_lag(("BFD", "WFD"),
                  tr, dataclasses.replace(BASE, fused_steps=k))
    _assert_traces_equal(a, b, f"K={k}")


def test_fused_single_stream_initial_lag_and_assigns():
    tr = jax.random.uniform(jax.random.key(3), (29, 8), maxval=0.9)
    il = jnp.linspace(0.0, 3.0, 8)
    ra, aa = simulate_lag(tr, policy="BFD", cfg=BASE, initial_lag=il,
                          record_assign=True)
    rb, ab = simulate_lag(tr, policy="BFD", cfg=FUSED, initial_lag=il,
                          record_assign=True)
    _assert_traces_equal(ra, rb)
    np.testing.assert_array_equal(np.asarray(aa), np.asarray(ab))


def _check_fused_equals_scan(seed, policy, k):
    rng = np.random.default_rng(seed)
    tr = jnp.asarray(rng.uniform(0, 1.3, (19, 6)), jnp.float32)
    a = simulate_lag(tr, policy=policy, cfg=BASE)
    b = simulate_lag(tr, policy=policy,
                     cfg=dataclasses.replace(BASE, fused_steps=k))
    _assert_traces_equal(a, b, (policy, k))


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           policy=st.sampled_from(HEURISTICS),
           k=st.sampled_from((1, 3, 8, 32)))
    def test_fused_equals_scan_property(seed, policy, k):
        _check_fused_equals_scan(seed, policy, k)


@pytest.mark.parametrize("policy", HEURISTICS)
@pytest.mark.parametrize("seed,k", ((0, 3), (7, 8)))
def test_fused_equals_scan_fixed_instances(policy, seed, k):
    """Deterministic fallback of the hypothesis property above (always
    runs, with or without hypothesis installed)."""
    _check_fused_equals_scan(seed, policy, k)


# ---------------------------------------------------------------------------
# observability: same aggregates off the fused path
# ---------------------------------------------------------------------------
def test_fused_sketch_and_incident_states_equal():
    tele = TelemetryConfig(record_frames=False, sketch=SketchConfig(),
                           alerts=AlertConfig(rules=default_rules()))
    cfg_a, cfg_b = _fused_pair(BASE, telemetry=tele)
    sp, act = generate_masked_scenario("topic_lifecycle", jax.random.key(4),
                                       2, 33, 8)
    a = sweep_lag(("BFD", "WFD"), sp, cfg_a, active=act)
    b = sweep_lag(("BFD", "WFD"), sp, cfg_b, active=act)
    _assert_traces_equal(a, b)
    assert a.sketch is not None and a.incidents is not None
    for x, y in ((a.sketch, b.sketch), (a.incidents, b.incidents)):
        la, lb = jax.tree_util.tree_leaves(x), jax.tree_util.tree_leaves(y)
        assert len(la) == len(lb) and len(la) > 0
        for u, v in zip(la, lb):
            assert np.asarray(u).tobytes() == np.asarray(v).tobytes()
    assert a.sketch.names == b.sketch.names


def test_fused_frame_recording_falls_back():
    """O(T) per-step frame recording is an unfused-only surface."""
    tele = TelemetryConfig(record_frames=True)
    cfg = dataclasses.replace(FUSED, telemetry=tele)
    assert fused_mode("BFD", cfg, 6) == "unfused"


# ---------------------------------------------------------------------------
# the Pallas megakernel path
# ---------------------------------------------------------------------------
def test_megakernel_equals_scan():
    cfg_k = dataclasses.replace(BASE, fused_steps=7, fused_kernel=True)
    tr = jax.random.uniform(jax.random.key(5), (2, 23, 6), maxval=1.0)
    a = sweep_lag(("BFD", "NF"), tr, BASE)
    b = sweep_lag(("BFD", "NF"), tr, cfg_k)
    _assert_traces_equal(a, b)


def test_megakernel_masked_equals_scan():
    cfg_k = dataclasses.replace(BASE, fused_steps=8, fused_kernel=True)
    sp, act = generate_masked_scenario("topic_lifecycle", jax.random.key(6),
                                       1, 19, 6)
    a = sweep_lag(("FFD",), sp, BASE, active=act)
    b = sweep_lag(("FFD",), sp, cfg_k, active=act)
    _assert_traces_equal(a, b)


def test_loop_fused_batch_direct_call():
    """The kernel entry point itself: carry (lag/assign/downtime) across
    K-blocks with a seeded initial lag, vs the single-stream engine."""
    from repro.kernels.loop_fused import loop_fused_batch

    rng = np.random.default_rng(7)
    tr = jnp.asarray(rng.uniform(0, 1.2, (17, 5)), jnp.float32)
    il = jnp.asarray(rng.uniform(0, 2.0, 5), jnp.float32)
    ref, assigns = simulate_lag(tr, policy="BFD", cfg=BASE, initial_lag=il,
                                record_assign=True)
    tot, mx, cons, migs, unread, asg = loop_fused_batch(
        tr[None], strategy="best", decreasing=True, capacity=1.0, dt=0.7,
        migration_steps=3, fused_steps=4, initial_lag=il[None])
    for got, want in ((tot, ref.lag_total), (mx, ref.lag_max),
                      (cons, ref.consumers), (migs, ref.migrations),
                      (unread, ref.unreadable)):
        assert np.asarray(got[0]).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_array_equal(np.asarray(asg[0]), np.asarray(assigns))


def test_loop_fused_batch_rejects_wide_instances():
    from repro.kernels.loop_fused import loop_fused_batch

    with pytest.raises(ValueError, match="n <= 14"):
        loop_fused_batch(jnp.zeros((1, 4, 15)), strategy="best",
                         decreasing=True)


# ---------------------------------------------------------------------------
# fleet: fused config in the bucket/compile key, padding preserved
# ---------------------------------------------------------------------------
def test_fleet_padded_fused_equals_padded_scan():
    rng = np.random.default_rng(8)
    shapes = ((14, 4), (20, 8), (9, 6))
    scen = [jnp.asarray(rng.uniform(0, 1.2, s), jnp.float32)
            for s in shapes]

    def run(cfg):
        runner = FleetRunner(FleetConfig(t_buckets=(20,), n_buckets=(8,)))
        return runner.simulate(("BFD", "WFD"), scen, cfg)

    a, b = run(BASE), run(FUSED)
    for i in range(len(scen)):
        assert a.lag_total[i].tobytes() == b.lag_total[i].tobytes()
        np.testing.assert_array_equal(a.consumers[i], b.consumers[i])
        np.testing.assert_array_equal(a.migrations[i], b.migrations[i])


def test_fleet_n_bucket_above_limit_falls_back_inside_program():
    """A scenario padded into an N bucket wider than the bitmask limit
    runs unfused inside the same program -- and still matches."""
    runner = FleetRunner(FleetConfig(t_buckets=(16,),
                                     n_buckets=(FUSED_MAX_PARTITIONS + 2,)))
    tr = jax.random.uniform(jax.random.key(9), (12, 5), maxval=1.0)
    res = runner.simulate(("BFD",), [tr], FUSED)
    solo = sweep_lag(("BFD",), tr[None], BASE)
    np.testing.assert_array_equal(res.consumers[0],
                                  np.asarray(solo.consumers)[:, 0, :])
    np.testing.assert_array_equal(res.migrations[0],
                                  np.asarray(solo.migrations)[:, 0, :])
    np.testing.assert_allclose(res.lag_total[0],
                               np.asarray(solo.lag_total)[:, 0, :],
                               atol=1e-6)


# ---------------------------------------------------------------------------
# routing: named refusals and documented fallbacks
# ---------------------------------------------------------------------------
def test_fused_mode_routing_table():
    assert fused_mode("BFD", FUSED, 10) == "fused"
    assert fused_mode("BFD", FUSED, FUSED_MAX_PARTITIONS + 1) == "unfused"
    assert fused_mode("MBFP", FUSED, 10) == "unfused"      # sweep family
    assert fused_mode("KEDA_LAG", FUSED, 10) == "unfused"  # reactive (ideal)
    kern = dataclasses.replace(FUSED, use_kernel=True)
    assert fused_mode("BFD", kern, 10) == "unfused"


@pytest.mark.parametrize("policy", ("ANNEAL", "ANNEAL_STICKY"))
def test_fused_optimizer_policy_raises(policy):
    tr = jnp.ones((1, 6, 4), jnp.float32) * 0.4
    with pytest.raises(FusedPathError, match="optimizer"):
        sweep_lag((policy,), tr, FUSED)


@pytest.mark.parametrize("policy", ("KEDA_LAG_REAL", "CLOUD_RUN_CPU_LAG"))
def test_fused_real_scaler_raises(policy):
    tr = jnp.ones((6, 4), jnp.float32) * 0.4
    with pytest.raises(FusedPathError, match="control-plane-wrapped"):
        simulate_lag(tr, policy=policy, cfg=FUSED)


def test_fused_control_plane_raises():
    cfg = dataclasses.replace(FUSED, control_plane=ControlPlaneConfig())
    tr = jnp.ones((6, 4), jnp.float32) * 0.4
    with pytest.raises(FusedPathError, match="control_plane"):
        simulate_lag(tr, policy="BFD", cfg=cfg)


def test_fused_kernel_requires_fused_steps():
    with pytest.raises(ValueError, match="fused_kernel=True requires"):
        LagSimConfig(fused_kernel=True).resolve(4)
    with pytest.raises(ValueError, match="fused_steps must be >= 0"):
        LagSimConfig(fused_steps=-1).resolve(4)


def test_mixed_sweep_falls_back_per_policy():
    """One sweep mixing fused-capable and fallback policies: the fused
    group runs fused, the rest keep the scan, stacking order holds."""
    tr = jax.random.uniform(jax.random.key(10), (2, 21, 7), maxval=1.0)
    pols = ("BFD", "MBFP", "KEDA_LAG")
    a = sweep_lag(pols, tr, BASE)
    b = sweep_lag(pols, tr, FUSED)
    assert a.policies == b.policies == pols
    _assert_traces_equal(a, b)


# ---------------------------------------------------------------------------
# satellite: the rank-1 drain entry point
# ---------------------------------------------------------------------------
def test_lag_update_single_equals_batch_row():
    from repro.kernels.lag_update import (lag_update_batch,
                                          lag_update_reference,
                                          lag_update_single)

    rng = np.random.default_rng(11)
    n, m = 9, 19
    lag = jnp.asarray(rng.uniform(0, 5, n), jnp.float32)
    prod = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    assign = jnp.asarray(rng.integers(-1, m, n), jnp.int32)
    readable = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    cap = jnp.asarray(rng.uniform(0.5, 1.5, m), jnp.float32)
    active = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    for act in (None, active):
        one = lag_update_single(lag, prod, assign, readable, cap, active=act)
        batch = lag_update_batch(
            lag[None], prod[None], assign[None], readable[None], cap[None],
            active=None if act is None else act[None])
        ref = lag_update_reference(lag, prod, assign, readable, cap, m=m,
                                   active=act)
        assert np.asarray(one).tobytes() == np.asarray(batch[0]).tobytes()
        np.testing.assert_allclose(np.asarray(one), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

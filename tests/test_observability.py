"""Streaming-observability tests: sketches, alerting, export.

Load-bearing properties (ISSUE acceptance criteria):

* sketches/alerts **on** never change the simulated trajectories, and
  **off** leaves the engine's program untouched (``.sketch`` /
  ``.incidents`` stay ``None``);
* sketch moments agree with full-frame numpy on the recorded channels,
  and histogram quantiles agree with ``np.quantile(...,
  method="inverted_cdf")`` within one bin width;
* the debiased EWMA matches a reference python loop;
* fleet bucket padding changes no result: padded sketch and alert state
  agree with the direct engine's (``repro.lagsim.metrics.agrees``:
  integer fields exact, floats to rounding), and ``merge_summaries``
  over scenario parts equals a summary of the whole;
* alert rules open/close incidents with the documented step semantics,
  the bounded incident table overflows by counting (not corrupting);
* a fixed-seed run decodes to the checked-in golden incident stream
  (``tests/data/golden_incidents.json``);
* Prometheus exposition round-trips the validator, the validator rejects
  malformed exposition, and OTLP JSON is deterministic;
* the bench gate classifies incident leaves as regressions even from a
  zero baseline, and ``api.simulate`` surfaces sketches + incidents.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scenarios import generate_masked_scenario
from repro.fleet import FleetConfig, FleetProgress, FleetRunner
from repro.lagsim import LagSimConfig, simulate_lag, sweep_lag
from repro.lagsim.metrics import agrees
from repro.telemetry import (
    AlertConfig,
    AlertRule,
    SketchConfig,
    SketchSummary,
    TelemetryConfig,
    alert_init,
    alert_step,
    decode_incidents,
    default_rules,
    incident_counts,
    incident_summary,
    merge_summaries,
    otlp_metrics_json,
    prometheus_exposition,
    summaries_from_state,
    validate_exposition,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "golden_incidents.json")

CFG = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2)
TRACE_FIELDS = ("lag_total", "lag_max", "consumers", "migrations",
                "unreadable")
POLICIES = ("MBFP", "KEDA_LAG")


def _obs(cfg, *, frames=True, sketch=True, alerts=True, **sk):
    return dataclasses.replace(cfg, telemetry=TelemetryConfig(
        record_frames=frames,
        sketch=SketchConfig(**sk) if sketch else None,
        alerts=AlertConfig(rules=default_rules()) if alerts else None))


def _scenario(seed=0, batch=2, t=24, n=6):
    return generate_masked_scenario(
        "topic_lifecycle", jax.random.key(seed), batch, t, n)


# ---------------------------------------------------------------------------
# on never changes trajectories; off carries nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
def test_sketch_alerts_on_trajectories_unchanged(policy):
    speeds, active = _scenario()
    off = simulate_lag(speeds[0], policy=policy, cfg=CFG, active=active[0])
    on = simulate_lag(speeds[0], policy=policy, cfg=_obs(CFG),
                      active=active[0])
    for f in TRACE_FIELDS:
        assert np.asarray(getattr(off, f)).tobytes() == \
            np.asarray(getattr(on, f)).tobytes(), f
    assert off.sketch is None and off.incidents is None
    assert on.sketch is not None and on.incidents is not None


def test_frames_off_still_sketches():
    """``record_frames=False`` drops the O(T) frame but keeps the O(1)
    sketch + alert state -- the planet-scale configuration."""
    speeds, active = _scenario()
    res = simulate_lag(speeds[0], policy="MBFP",
                       cfg=_obs(CFG, frames=False), active=active[0])
    assert res.telemetry is None
    assert res.sketch is not None and res.incidents is not None
    assert float(res.sketch.count) == speeds.shape[1]


def test_config_validation():
    with pytest.raises(ValueError, match="ring"):
        TelemetryConfig(record_frames=False, ring=8)
    with pytest.raises(TypeError, match="SketchConfig"):
        TelemetryConfig(sketch="yes")
    with pytest.raises(TypeError, match="AlertConfig"):
        TelemetryConfig(alerts="yes")
    with pytest.raises(ValueError, match="hist_bins"):
        SketchConfig(hist_bins=1)
    with pytest.raises(ValueError, match="ewma_halflives"):
        SketchConfig(ewma_halflives=(0.0,))
    with pytest.raises(ValueError, match="at least one AlertRule"):
        AlertConfig()
    with pytest.raises(ValueError, match="unknown alert kind"):
        AlertRule(name="x", kind="nope")
    with pytest.raises(ValueError, match="unique"):
        AlertConfig(rules=(AlertRule.slo_burn(), AlertRule.slo_burn()))
    with pytest.raises(ValueError, match="unknown channel"):
        simulate_lag(_scenario()[0][0], policy="MBFP",
                     cfg=_obs(CFG, hist_channels=("nope",)))


# ---------------------------------------------------------------------------
# sketch numerics vs full-frame numpy
# ---------------------------------------------------------------------------

def _summary_and_frame(policy="MBFP", seed=0, t=48, n=6):
    speeds, active = _scenario(seed=seed, batch=1, t=t, n=n)
    cfg = _obs(CFG, alerts=False)
    res = simulate_lag(speeds[0], policy=policy, cfg=cfg, active=active[0])
    rcfg = cfg.resolve(n)
    summary = SketchSummary.from_state(res.sketch, rcfg.telemetry.sketch)
    return summary, np.asarray(res.telemetry.channels), rcfg.telemetry.sketch


def test_sketch_moments_match_numpy():
    summary, frame, _ = _summary_and_frame()
    assert summary.count == frame.shape[0]
    assert np.allclose(summary.mean, frame.mean(axis=0), atol=1e-4)
    assert np.allclose(summary.variance(), frame.var(axis=0), atol=1e-3)
    assert np.allclose(summary.vmin, frame.min(axis=0), atol=1e-6)
    assert np.allclose(summary.vmax, frame.max(axis=0), atol=1e-6)


@pytest.mark.parametrize("q", (0.5, 0.9, 0.99))
def test_sketch_quantile_within_bin_width(q):
    summary, frame, scfg = _summary_and_frame()
    lag = frame[:, summary.channel_index("lag_total")]
    exact = float(np.quantile(lag, q, method="inverted_cdf"))
    got = summary.quantile(q, "lag_total")
    assert abs(got - exact) <= scfg.bin_width + 1e-6, (got, exact)


def test_ewma_matches_reference_loop():
    summary, frame, scfg = _summary_and_frame()
    for h, got in summary.ewma.items():
        alpha = 1.0 - 2.0 ** (-1.0 / h)
        acc = np.zeros(frame.shape[1])
        w = 0.0
        for row in frame:
            acc = (1 - alpha) * acc + alpha * row
            w = (1 - alpha) * w + alpha
        assert np.allclose(got, acc / w, atol=1e-4), h


def test_sweep_stacks_sketch_and_for_policy_slices():
    speeds, active = _scenario()
    res = sweep_lag(POLICIES, speeds, cfg=_obs(CFG), active=active)
    p, b = len(POLICIES), speeds.shape[0]
    assert res.sketch.count.shape == (p, b)
    assert res.incidents.count.shape[:2] == (p, b)
    one = res.for_policy("KEDA_LAG")
    assert np.array_equal(np.asarray(one.sketch.mean),
                          np.asarray(res.sketch.mean[1]))
    cfg = _obs(CFG).resolve(speeds.shape[2])
    pairs = summaries_from_state(res.sketch, cfg.telemetry.sketch)
    assert [idx for idx, _ in pairs] == \
        [(i, j) for i in range(p) for j in range(b)]


# ---------------------------------------------------------------------------
# fleet padding exactness + merging + progress
# ---------------------------------------------------------------------------

def test_fleet_padded_sketch_and_alerts_match_direct():
    speeds, active = _scenario(t=20, n=5)
    cfg = _obs(CFG)
    fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
    res = fleet.simulate(POLICIES, speeds, cfg, active=active)
    rcfg = cfg.resolve(speeds.shape[2])
    for i in range(speeds.shape[0]):
        for pi, pol in enumerate(POLICIES):
            direct = simulate_lag(speeds[i], policy=pol, cfg=cfg,
                                  active=active[i])
            got = jax.tree_util.tree_map(lambda a: a[pi], res.sketch[i])
            # the padded program sums in another order: integer fields
            # exact, floats within the agreement contract
            for fld in ("count", "mean", "m2", "vmin", "vmax", "ewma",
                        "ewma_w", "hist"):
                assert agrees(getattr(got, fld),
                              getattr(direct.sketch, fld)), (i, pol, fld)
            inc = jax.tree_util.tree_map(lambda a: a[pi], res.incidents[i])
            for fld in ("tick", "active", "open_step", "close_step",
                        "peak", "count"):
                assert agrees(getattr(inc, fld),
                              getattr(direct.incidents, fld)), (i, pol, fld)
            # and the finalized views agree
            want = SketchSummary.from_state(direct.sketch,
                                            rcfg.telemetry.sketch)
            have = dict(res.sketch_summaries(i))[(pi,)]
            assert agrees(have.mean, want.mean)
    # decoded incidents carry the policy index
    incs = res.scenario_incidents(0)
    assert incs and all(inc.index[0] in (0, 1) for inc in incs)


def test_fleet_raises_named_errors_when_off():
    speeds, active = _scenario(t=10, n=4)
    fleet = FleetRunner(FleetConfig())
    res = fleet.simulate(("MBFP",), speeds, CFG, active=active)
    with pytest.raises(ValueError, match="no sketches"):
        res.sketch_summaries(0)
    with pytest.raises(ValueError, match="no alerting"):
        res.scenario_incidents(0)


def test_merge_summaries_equals_whole():
    """Chan's merge over per-scenario summaries == one summary whose
    counts/hist are the element-wise union."""
    speeds, active = _scenario(batch=3, t=32, n=6)
    cfg = _obs(CFG, alerts=False)
    res = sweep_lag(("MBFP",), speeds, cfg=cfg, active=active)
    scfg = cfg.resolve(speeds.shape[2]).telemetry.sketch
    parts = [s for _, s in summaries_from_state(res.sketch, scfg)]
    merged = merge_summaries(parts)
    frames = np.asarray(res.telemetry.channels)[0]     # [B, T, K]
    allsteps = frames.reshape(-1, frames.shape[-1])
    assert merged.count == allsteps.shape[0]
    assert np.allclose(merged.mean, allsteps.mean(axis=0), atol=1e-4)
    assert np.allclose(merged.variance(), allsteps.var(axis=0), atol=1e-3)
    assert np.allclose(merged.vmin, allsteps.min(axis=0))
    assert np.allclose(merged.vmax, allsteps.max(axis=0))
    assert np.allclose(merged.hist.sum(axis=1),
                       [allsteps.shape[0]] * len(merged.hist_names))
    with pytest.raises(ValueError, match="at least one summary"):
        merge_summaries([])


def test_fleet_progress_callback_streams_snapshots():
    speeds_a, active_a = _scenario(seed=0, batch=2, t=20, n=5)
    speeds_b, active_b = _scenario(seed=1, batch=1, t=40, n=5)
    scen = [(speeds_a[i], active_a[i]) for i in range(2)]
    scen.append((speeds_b[0], active_b[0]))
    fleet = FleetRunner(FleetConfig(t_buckets=(32, 64), n_buckets=(8,)))
    snaps = []
    fleet.simulate(POLICIES, scen, _obs(CFG), progress=snaps.append)
    assert len(snaps) >= 2                       # two bucket groups
    assert [s.done for s in snaps] == sorted(s.done for s in snaps)
    last = snaps[-1]
    assert isinstance(last, FleetProgress)
    assert last.done == last.total == len(scen)
    assert last.sketch is not None and last.sketch.count > 0
    assert set(last.incidents) == set(r.name for r in default_rules())


# ---------------------------------------------------------------------------
# alert semantics: open/close steps, durations, overflow
# ---------------------------------------------------------------------------

def _drive(cfg, signals):
    """Run ``alert_step`` over ``signals`` dicts; -> final state."""
    state = alert_init(cfg)
    for sig in signals:
        state = alert_step(cfg, state, slo_lag=1.0, **sig)
    return state


def _quiet(**kw):
    sig = dict(lag_total=0.0, consumers=1.0, unreadable=0.0,
               storm_parts=0.0)
    sig.update(kw)
    return sig


def test_storm_incident_open_close_steps():
    """rebalance_storm fires on the storm_steps-th consecutive blocked
    step and closes on the first unblocked one (close_step inclusive)."""
    cfg = AlertConfig(rules=(AlertRule.rebalance_storm(storm_steps=3),))
    sigs = [_quiet()] * 2 + [_quiet(unreadable=2.0)] * 5 + [_quiet()] * 2
    state = _drive(cfg, sigs)
    (inc,) = decode_incidents(state, cfg, dt=2.0)
    assert inc.kind == "rebalance_storm" and not inc.still_open
    # blocked on steps 2..6 -> consec hits 3 at step 4, unblocked at 7
    assert (inc.open_step, inc.close_step) == (4, 6)
    assert inc.duration_s == (6 - 4 + 1) * 2.0
    assert inc.peak == 5.0                       # longest consec run


def test_still_open_incident_closes_at_last_step():
    cfg = AlertConfig(rules=(AlertRule.rebalance_storm(storm_steps=2),))
    state = _drive(cfg, [_quiet(unreadable=1.0)] * 4)
    (inc,) = decode_incidents(state, cfg)
    assert inc.still_open
    assert (inc.open_step, inc.close_step) == (1, 3)
    assert inc.duration_s == 3.0


def test_incident_table_overflow_counts_without_rows():
    cfg = AlertConfig(rules=(AlertRule.rebalance_storm(storm_steps=1),),
                      max_incidents=1)
    burst = [_quiet(unreadable=1.0), _quiet()]
    state = _drive(cfg, burst * 3)
    assert incident_counts(state) == {"rebalance_storm": 3}
    decoded = decode_incidents(state, cfg)
    assert len(decoded) == 1                     # only the tabled row
    assert decoded[0].open_step == 0
    summ = incident_summary(state, cfg)["rebalance_storm"]
    assert summ["count"] == 3.0 and summ["open"] == 0.0


def test_slo_burn_needs_both_windows():
    """Once the slow window is anchored by healthy history, a short lag
    spike burns only the fast window -- multi-window burn rate
    suppresses the page; a sustained violation burns both and fires."""
    rule = AlertRule.slo_burn(slo_target=0.9, burn_threshold=3.0,
                              fast_halflife=2.0, slow_halflife=64.0)
    cfg = AlertConfig(rules=(rule,))
    healthy = [_quiet()] * 40
    spike = healthy + [_quiet(lag_total=5.0)] * 3 + [_quiet()] * 10
    assert incident_counts(_drive(cfg, spike)) == {"slo_burn": 0}
    sustained = healthy + [_quiet(lag_total=5.0)] * 30
    assert incident_counts(_drive(cfg, sustained)) == {"slo_burn": 1}


def test_valid_false_freezes_alert_state():
    cfg = AlertConfig(rules=default_rules())
    state = alert_init(cfg)
    st1 = alert_step(cfg, state, slo_lag=1.0, **_quiet(lag_total=9.0))
    frozen = alert_step(cfg, st1, slo_lag=1.0, valid=jnp.asarray(False),
                        **_quiet(lag_total=99.0))
    for fld in ("tick", "fast", "prev_lag", "count"):
        assert np.array_equal(np.asarray(getattr(frozen, fld)),
                              np.asarray(getattr(st1, fld))), fld


# ---------------------------------------------------------------------------
# golden incident stream (fixed seed, pinned)
# ---------------------------------------------------------------------------

def _golden_incidents():
    """The exact fixed-seed run the golden file pins (see the generator
    note inside the golden)."""
    speeds, active = _scenario(seed=0, batch=2, t=32, n=8)
    cfg = _obs(CFG, frames=False)
    res = simulate_lag(speeds[0], policy="KEDA_LAG", cfg=cfg,
                       active=active[0])
    return decode_incidents(res.incidents, cfg.telemetry.alerts, dt=CFG.dt)


def test_golden_incident_stream():
    with open(GOLDEN) as f:
        want = json.load(f)
    got = [inc.as_dict() for inc in _golden_incidents()]
    assert len(got) == len(want["incidents"])
    for g, w in zip(got, want["incidents"]):
        for key in ("rule", "kind", "severity", "open_step", "close_step",
                    "still_open", "index"):
            assert g[key] == w[key], (g, w, key)
        assert g["duration_s"] == pytest.approx(w["duration_s"])
        assert g["peak"] == pytest.approx(w["peak"], abs=1e-4)


# ---------------------------------------------------------------------------
# export: Prometheus + OTLP
# ---------------------------------------------------------------------------

def _export_inputs():
    speeds, active = _scenario(batch=1, t=32, n=6)
    cfg = _obs(CFG, frames=False)
    res = simulate_lag(speeds[0], policy="KEDA_LAG", cfg=cfg,
                       active=active[0])
    rcfg = cfg.resolve(6)
    summary = SketchSummary.from_state(res.sketch, rcfg.telemetry.sketch)
    incidents = decode_incidents(res.incidents, cfg.telemetry.alerts)
    return summary, incidents


def test_prometheus_exposition_lints_clean():
    summary, incidents = _export_inputs()
    text = prometheus_exposition(sketch=summary, incidents=incidents,
                                 spans={"api.simulate": {
                                     "count": 2, "total_us": 10.0,
                                     "steady_us": 4.0}},
                                 labels={"run": "test"})
    validate_exposition(text)
    assert 'repro_sketch_mean{channel="lag_total",run="test"}' in text
    assert "# TYPE repro_sketch_lag_total histogram" in text
    assert 'le="+Inf"' in text
    assert "repro_incidents_total{" in text
    assert "repro_span_calls_total{" in text
    with pytest.raises(ValueError, match="label"):
        prometheus_exposition(sketch=summary, labels={"bad-name": "x"})


def test_validator_rejects_malformed_exposition():
    with pytest.raises(ValueError, match="no preceding # TYPE"):
        validate_exposition("untyped_metric 1\n")
    with pytest.raises(ValueError, match="invalid metric name"):
        validate_exposition("# TYPE 9bad counter\n")
    with pytest.raises(ValueError, match="non-numeric"):
        validate_exposition("# TYPE m gauge\nm abc\n")
    with pytest.raises(ValueError, match="not cumulative"):
        validate_exposition(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\n'
            'h_bucket{le="+Inf"} 5\nh_count 5\n')
    with pytest.raises(ValueError, match="no '\\+Inf'"):
        validate_exposition(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_count 5\n')
    with pytest.raises(ValueError, match="!= _count"):
        validate_exposition(
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 5\nh_count 7\n')


def test_otlp_metrics_json_deterministic_and_coherent():
    summary, incidents = _export_inputs()
    a = otlp_metrics_json(sketch=summary, incidents=incidents)
    b = otlp_metrics_json(sketch=summary, incidents=incidents)
    assert a == b                                # no wall clock leaked
    metrics = a["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
    by_name = {m["name"]: m for m in metrics}
    hist = by_name["repro.sketch.hist.lag_total"]["histogram"]["dataPoints"][0]
    assert sum(int(c) for c in hist["bucketCounts"]) == int(hist["count"])
    assert len(hist["explicitBounds"]) == len(hist["bucketCounts"]) - 1
    counts = by_name["repro.incidents.count"]["sum"]["dataPoints"]
    assert sum(p["asDouble"] for p in counts) == len(incidents)
    assert json.dumps(a)                         # JSON-serializable


# ---------------------------------------------------------------------------
# bench gate + api surface
# ---------------------------------------------------------------------------

def test_bench_diff_gates_incident_leaves():
    from benchmarks.bench_diff import (DEFAULT_THRESHOLD, _direction, diff,
                                       _inject_incident_regression)

    # incident classification wins over the informational fragments
    assert _direction(("telemetry", "incidents", "count")) == "incident"
    assert _direction(("observability", "per_policy", "MBFP", "incidents",
                       "slo_burn", "count")) == "incident"
    assert _direction(("observability", "per_policy", "MBFP", "sketch",
                       "channels", "lag_total", "mean")) == "info"
    report = {"kind": "x", "observability": {"per_policy": {"MBFP": {
        "incidents": {"slo_burn": {"count": 0.0, "total_duration_s": 0.0},
                      "lag_growth": {"count": 2.0}}}}}}
    # zero baseline still gates: 0 -> 1 incident is a regression
    hurt = _inject_incident_regression(report)
    res = diff(report, hurt, DEFAULT_THRESHOLD)
    regressed = {name for name, *_ in res["regressions"]}
    assert any(name.endswith("slo_burn/count") for name in regressed)
    assert any(name.endswith("lag_growth/count") for name in regressed)
    # identity diff is clean; fewer incidents is an improvement
    assert diff(report, report, DEFAULT_THRESHOLD)["regressions"] == []
    better = json.loads(json.dumps(report))
    better["observability"]["per_policy"]["MBFP"]["incidents"][
        "lag_growth"]["count"] = 0.0
    res = diff(report, better, DEFAULT_THRESHOLD)
    assert res["regressions"] == [] and len(res["improvements"]) == 1


def test_api_simulate_surfaces_sketches_and_incidents():
    from repro import api

    speeds, active = _scenario()
    out = api.simulate(
        speeds, policies=POLICIES, config=CFG, active=active,
        telemetry=TelemetryConfig(record_frames=False,
                                  sketch=SketchConfig(),
                                  alerts=AlertConfig(rules=default_rules())))
    assert out.telemetry is None
    assert len(out.sketches) == speeds.shape[0]
    assert len(out.sketches[0]) == len(POLICIES)
    merged = merge_summaries([s for per in out.sketches for s in per])
    assert merged.count == len(POLICIES) * speeds.shape[0] * speeds.shape[1]
    incs = [i for per in out.incidents for i in per]
    assert incs and all(i.index[0] < len(POLICIES) for i in incs)
    validate_exposition(prometheus_exposition(sketch=merged, incidents=incs))
    # without the override nothing observability-shaped is carried
    plain = api.simulate(speeds[:1], policies=("MBFP",), config=CFG,
                         active=active[:1])
    assert plain.sketches is None and plain.incidents is None

"""Flight-recorder telemetry tests.

Load-bearing properties (ISSUE acceptance criteria):

* telemetry **off** (``telemetry=None`` or ``enabled=False``) is
  bit-identical to the pre-telemetry engine, on the direct path AND
  through the fleet's padded buckets (hypothesis property with a
  deterministic fixed-seed fallback);
* telemetry **on** never changes the simulated trajectories -- the
  recorder only reads values the step already computes;
* the fleet's padded-bucket frames match the direct engine's frames on
  the true steps; ring mode through the fleet raises a named error;
* a fixed-seed ``topic_lifecycle`` run decodes to the checked-in golden
  event stream (``tests/data/golden_telemetry_events.json``);
* the host-side tracer produces valid Chrome/Perfetto traces, separates
  first-call from steady-state, and stays bounded;
* the bench regression gate (``benchmarks/bench_diff.py``) passes an
  identity diff and catches an injected 50% throughput regression.
"""
import dataclasses
import json
import os

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

from repro.fleet import FleetConfig, FleetRunner
from repro.core.scenarios import generate_masked_scenario
from repro.lagsim import LagSimConfig, simulate_lag, sweep_lag
from repro.telemetry import (
    BASE_CHANNELS,
    EventStream,
    TelemetryConfig,
    Tracer,
    decode_events,
    span,
    traced,
    validate_chrome_trace,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "golden_telemetry_events.json")

CFG = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2)
TRACE_FIELDS = ("lag_total", "lag_max", "consumers", "migrations",
                "unreadable")
POLICIES = ("MBFP", "KEDA_LAG")


def _with_tele(cfg, **kw):
    return dataclasses.replace(cfg, telemetry=TelemetryConfig(**kw))


def _scenario(seed=0, batch=2, t=24, n=6):
    """A fixed topic_lifecycle batch: births/deaths, storms, migrations."""
    return generate_masked_scenario(
        "topic_lifecycle", jax.random.key(seed), batch, t, n)


# ---------------------------------------------------------------------------
# off == bit-identical (the goldens' guarantee)
# ---------------------------------------------------------------------------

def _assert_bit_identical(a, b):
    for f in TRACE_FIELDS:
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f


@pytest.mark.parametrize("policy", POLICIES)
def test_off_is_bit_identical_direct(policy):
    """telemetry=None and TelemetryConfig(enabled=False) produce the
    exact bytes of each other -- the disabled config compiles to the
    pre-telemetry jaxpr."""
    speeds, active = _scenario()
    off = simulate_lag(speeds[0], policy=policy, cfg=CFG, active=active[0])
    dis = simulate_lag(speeds[0], policy=policy,
                       cfg=_with_tele(CFG, enabled=False), active=active[0])
    _assert_bit_identical(off, dis)
    assert off.telemetry is None
    assert dis.telemetry is None


@pytest.mark.parametrize("policy", POLICIES)
def test_on_trajectories_unchanged_direct(policy):
    """The recorder only reads values the step computes: trajectories
    with telemetry on are bit-identical to off."""
    speeds, active = _scenario()
    off = simulate_lag(speeds[0], policy=policy, cfg=CFG, active=active[0])
    on = simulate_lag(speeds[0], policy=policy, cfg=_with_tele(CFG),
                      active=active[0])
    _assert_bit_identical(off, on)
    frame = on.telemetry
    assert frame is not None
    t, k = speeds.shape[1], len(frame.names)
    assert frame.names[:len(BASE_CHANNELS)] == BASE_CHANNELS
    assert frame.channels.shape == (t, k)
    assert int(frame.count) == t
    assert np.array_equal(np.asarray(frame.steps), np.arange(t))


def test_off_is_bit_identical_fleet_padded():
    """Same property through the fleet's padded buckets (T and N both
    rounded up)."""
    speeds, active = _scenario(t=20, n=5)
    fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
    off = fleet.simulate(POLICIES, speeds, CFG, active=active)
    dis = fleet.simulate(POLICIES, speeds, _with_tele(CFG, enabled=False),
                         active=active)
    for i in range(speeds.shape[0]):
        for f in TRACE_FIELDS:
            assert np.asarray(getattr(off, f)[i]).tobytes() == \
                np.asarray(getattr(dis, f)[i]).tobytes(), (i, f)
    assert off.telemetry is None
    assert dis.telemetry is None


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), t=st.integers(4, 24),
           n=st.integers(2, 8))
    def test_off_bit_identical_property(seed, t, n):
        speeds, active = _scenario(seed=seed, batch=1, t=t, n=n)
        off = simulate_lag(speeds[0], policy="MBFP", cfg=CFG,
                           active=active[0])
        dis = simulate_lag(speeds[0], policy="MBFP",
                           cfg=_with_tele(CFG, enabled=False),
                           active=active[0])
        on = simulate_lag(speeds[0], policy="MBFP", cfg=_with_tele(CFG),
                          active=active[0])
        _assert_bit_identical(off, dis)
        _assert_bit_identical(off, on)


def test_off_bit_identical_fixed_seeds():
    """Deterministic fallback of the hypothesis property above (always
    runs, with or without hypothesis installed)."""
    for seed, t, n in ((0, 4, 2), (1, 13, 5), (7, 24, 8)):
        speeds, active = _scenario(seed=seed, batch=1, t=t, n=n)
        off = simulate_lag(speeds[0], policy="MBFP", cfg=CFG,
                           active=active[0])
        dis = simulate_lag(speeds[0], policy="MBFP",
                           cfg=_with_tele(CFG, enabled=False),
                           active=active[0])
        on = simulate_lag(speeds[0], policy="MBFP", cfg=_with_tele(CFG),
                          active=active[0])
        _assert_bit_identical(off, dis)
        _assert_bit_identical(off, on)


# ---------------------------------------------------------------------------
# recorder semantics: sweep stacking, fleet padding, ring mode
# ---------------------------------------------------------------------------

def test_sweep_stacks_frames_and_for_policy_slices():
    speeds, active = _scenario()
    res = sweep_lag(POLICIES, speeds, cfg=_with_tele(CFG), active=active)
    p, b, t = len(POLICIES), speeds.shape[0], speeds.shape[1]
    k = len(res.telemetry.names)
    assert res.telemetry.channels.shape == (p, b, t, k)
    for pi, pol in enumerate(POLICIES):
        one = res.for_policy(pol)
        direct = jax.vmap(
            lambda tr, act: simulate_lag(tr, policy=pol,
                                         cfg=_with_tele(CFG), active=act)
        )(speeds, active)
        assert np.array_equal(np.asarray(one.telemetry.channels),
                              np.asarray(direct.telemetry.channels))


def test_fleet_padded_frames_match_direct():
    """Bucket padding must not leak into the recorded frames: the fleet's
    per-scenario frame equals the direct engine's on the true steps."""
    speeds, active = _scenario(t=20, n=5)
    fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
    res = fleet.simulate(POLICIES, speeds, _with_tele(CFG), active=active)
    assert res.telemetry is not None
    t = speeds.shape[1]
    for i in range(speeds.shape[0]):
        frame = res.telemetry[i]             # [P, t, K]
        assert frame.channels.shape[1] == t
        for pi, pol in enumerate(POLICIES):
            direct = simulate_lag(speeds[i], policy=pol,
                                  cfg=_with_tele(CFG), active=active[i])
            assert np.array_equal(np.asarray(frame.channels[pi]),
                                  np.asarray(direct.telemetry.channels)), \
                (i, pol)


def test_ring_mode_keeps_exact_tail():
    speeds, active = _scenario(batch=1, t=40, n=6)
    full = simulate_lag(speeds[0], policy="MBFP", cfg=_with_tele(CFG),
                        active=active[0])
    ring = simulate_lag(speeds[0], policy="MBFP",
                        cfg=_with_tele(CFG, ring=8), active=active[0])
    rf = ring.telemetry
    assert rf.channels.shape[0] == 8
    assert int(rf.count) == 40
    order = np.argsort(np.asarray(rf.steps), kind="stable")
    assert np.array_equal(np.asarray(rf.steps)[order], np.arange(32, 40))
    assert np.array_equal(np.asarray(rf.channels)[order],
                          np.asarray(full.telemetry.channels)[32:])


def test_ring_through_fleet_raises():
    """Padded bucket tails are not history: ring mode must be refused by
    the fleet before anything compiles."""
    speeds, active = _scenario(t=20, n=5)
    fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
    with pytest.raises(ValueError, match="ring"):
        fleet.simulate(POLICIES, speeds, _with_tele(CFG, ring=8),
                       active=active)


def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="lag_quantiles"):
        TelemetryConfig(lag_quantiles=(1.5,))
    with pytest.raises(ValueError, match="ring"):
        TelemetryConfig(ring=0)
    with pytest.raises(ValueError, match="telemetry"):
        LagSimConfig(capacity=1.0, telemetry="yes").resolve(4)


# ---------------------------------------------------------------------------
# event decoding: golden stream + internal consistency
# ---------------------------------------------------------------------------

def _golden_stream():
    """The exact fixed-seed run the golden file pins (see the generator
    note inside the golden)."""
    speeds, active = _scenario(seed=0, batch=2, t=32, n=8)
    res = simulate_lag(speeds[0], policy="MBFP", cfg=_with_tele(CFG),
                       active=active[0])
    return EventStream.from_frame(res.telemetry)


def test_golden_event_stream():
    with open(GOLDEN) as f:
        want = json.load(f)
    got = json.loads(_golden_stream().to_json())
    assert got["channels"] == want["channels"]
    assert got["recorded_steps"] == want["recorded_steps"]
    assert got["counts"] == want["counts"]
    assert len(got["events"]) == len(want["events"])
    for g, w in zip(got["events"], want["events"]):
        assert (g["kind"], g["step"], g["index"]) == \
            (w["kind"], w["step"], w["index"])
        assert set(g["data"]) == set(w["data"])
        for key in g["data"]:
            assert g["data"][key] == pytest.approx(w["data"][key],
                                                   abs=1e-5), (g, w, key)


def test_event_stream_consistency():
    stream = _golden_stream()
    events = stream.events
    assert events, "the lifecycle scenario must produce events"
    counts = stream.counts()
    assert sum(counts.values()) == len(events)
    assert {"scale", "migration", "lifecycle"} <= set(counts)
    # every event's step must be a recorded step
    steps = set(np.asarray(stream.frame.steps).ravel().tolist())
    for e in events:
        assert e.step in steps
    # decode_events is what from_frame used
    assert [e.as_dict() for e in decode_events(stream.frame)] == \
        [e.as_dict() for e in events]


def test_event_stream_dataframes():
    pd = pytest.importorskip("pandas")
    stream = _golden_stream()
    df = stream.to_dataframe()
    assert isinstance(df, pd.DataFrame)
    assert len(df) == int(stream.frame.count)
    for nm in stream.frame.names:
        assert nm in df.columns
    ev = stream.events_dataframe()
    assert len(ev) == len(stream.events)


def test_api_simulate_carries_frames():
    from repro import api

    speeds, active = _scenario()
    out = api.simulate(speeds, policies=POLICIES, config=CFG,
                       active=active, telemetry=TelemetryConfig())
    assert out.telemetry is not None and len(out.telemetry) == \
        speeds.shape[0]
    assert EventStream.from_frame(out.telemetry[0]).counts()


# ---------------------------------------------------------------------------
# host-side tracer: spans, first-vs-steady, Chrome trace, bounds
# ---------------------------------------------------------------------------

def test_tracer_spans_and_summary():
    tr = Tracer()
    for i in range(3):
        with tr.span("work", idx=i):
            with tr.span("inner"):
                pass
    recs = tr.records("work")
    assert len(recs) == 3
    assert [r.call_index for r in recs] == [0, 1, 2]
    assert [r.args["idx"] for r in recs] == [0, 1, 2]
    assert tr.records("work", idx=1)[0].call_index == 1
    s = tr.summary()["work"]
    assert s["count"] == 3
    assert s["first_us"] >= 0.0 and s["steady_us"] >= 0.0
    assert s["total_us"] >= s["first_us"]
    inner = tr.records("inner")
    assert len(inner) == 3 and inner[0].call_index == 0


def test_tracer_chrome_trace_valid(tmp_path):
    tr = Tracer()
    with tr.span("outer", label="x"):
        tr.instant("marker", hit=True)
    path = tmp_path / "trace.json"
    trace = tr.write(str(path))
    validate_chrome_trace(trace)
    validate_chrome_trace(json.loads(path.read_text()))
    names = [ev["name"] for ev in trace["traceEvents"]]
    assert "outer" in names and "marker" in names
    by_name = {ev["name"]: ev for ev in trace["traceEvents"]
               if ev["ph"] == "X"}
    assert by_name["outer"]["args"]["label"] == "x"
    assert by_name["marker"]["args"]["hit"] is True
    assert by_name["marker"]["dur"] >= 0.0
    assert by_name["marker"]["args"]["parent_id"] == \
        by_name["outer"]["args"]["span_id"]
    assert by_name["outer"]["args"]["root_id"] == \
        by_name["outer"]["args"]["span_id"]


def test_tracer_bounded():
    tr = Tracer(max_spans=2)
    for i in range(5):
        with tr.span("s", i=i):
            pass
    assert len(tr.records()) == 2
    assert tr.dropped == 3
    tr.reset()
    assert tr.records() == [] and tr.dropped == 0


def test_traced_decorator_and_disabled_tracer():
    tr = Tracer()

    @tr.traced("api.fake")
    def fn(x):
        return x + 1

    assert fn(1) == 2 and fn(2) == 3
    assert [r.name for r in tr.records()] == ["api.fake", "api.fake"]
    tr.enabled = False
    with tr.span("invisible") as args:
        assert args is None
    assert len(tr.records()) == 2


def test_module_level_span_hits_default_tracer():
    from repro.telemetry import default_tracer, instant

    tracer = default_tracer()
    n0 = len(tracer.records())
    with span("test.adhoc", unit=True):
        instant("test.marker")

    @traced("test.fn")
    def fn():
        return 7

    assert fn() == 7
    names = [r.name for r in tracer.records()[n0:]]
    assert names == ["test.marker", "test.adhoc", "test.fn"]


def test_validate_chrome_trace_rejects_garbage():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({})


def test_span_ids_nested_and_per_thread():
    import threading

    tr = Tracer()
    with tr.span("root"):
        with tr.span("child"):
            tr.instant("leaf")
        with tr.span("sibling"):
            pass
    with tr.span("next"):
        pass
    rec = {r.name: r for r in tr.records()}
    # appended as each span ends: children before their parent
    assert [r.name for r in tr.records()] == [
        "leaf", "child", "sibling", "root", "next"]
    root = rec["root"]
    assert root.parent_id is None and root.root_id == root.span_id
    assert rec["child"].parent_id == root.span_id
    assert rec["sibling"].parent_id == root.span_id
    assert rec["leaf"].parent_id == rec["child"].span_id
    assert {rec[n].root_id for n in ("child", "sibling", "leaf")} == {
        root.span_id}
    assert rec["next"].parent_id is None
    assert rec["next"].root_id == rec["next"].span_id != root.span_id
    assert len({r.span_id for r in tr.records()}) == 5

    # each thread keeps its own stack: a span opened on another thread
    # while "outer" is open here is a root of its own
    tr.reset()
    opened, release = threading.Event(), threading.Event()

    def worker():
        with tr.span("t.outer"):
            opened.set()
            release.wait(5)
            with tr.span("t.inner"):
                pass

    th = threading.Thread(target=worker)
    with tr.span("main.outer"):
        th.start()
        opened.wait(5)
        with tr.span("main.inner"):
            pass
        release.set()
        th.join(5)
    rec = {r.name: r for r in tr.records()}
    for side in ("main", "t"):
        outer, inner = rec[f"{side}.outer"], rec[f"{side}.inner"]
        assert outer.parent_id is None
        assert inner.parent_id == outer.span_id == inner.root_id
    assert rec["main.outer"].tid != rec["t.outer"].tid


def test_spans_on_the_profiler_timeline(tmp_path):
    """Under ``jax.profiler.trace`` every program span is a host event of
    the profile, nested as recorded."""
    import glob

    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("prof.root"):
            with tr.span("prof.child"):
                jnp.ones(8).block_until_ready()
            tr.instant("prof.mark")
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    prof = jax.profiler.ProfileData.from_file(paths[0])
    found = {}
    for plane in prof.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("prof."):
                    s = int(ev.start_ns)
                    found[ev.name] = (line.name, s, s + int(ev.duration_ns))
    assert set(found) == {"prof.root", "prof.child", "prof.mark"}
    (line, r0, r1), (cline, c0, c1), (mline, m0, _) = (
        found["prof.root"], found["prof.child"], found["prof.mark"])
    assert line == cline == mline
    assert r0 <= c0 <= c1 <= r1 and c1 <= m0 <= r1
    rec = {r.name: r for r in tr.records()}
    assert rec["prof.child"].parent_id == rec["prof.root"].span_id


def test_otlp_spans_carry_parent_links_and_trace_ids():
    from repro.telemetry import otlp_spans_json

    tr = Tracer()
    for _ in range(2):
        with tr.span("api.call"):
            with tr.span("step.a"):
                pass
            with tr.span("step.b"):
                pass
    out = otlp_spans_json(tr.records())
    spans = out["resourceSpans"][0]["scopeSpans"][0]["spans"]
    by_id = {s["spanId"]: s for s in spans}
    assert len(by_id) == 6
    roots = [s for s in spans if s["name"] == "api.call"]
    assert len(roots) == 2 and all("parentSpanId" not in s for s in roots)
    assert roots[0]["traceId"] != roots[1]["traceId"]
    for s in spans:
        assert len(s["traceId"]) == 32 and len(s["spanId"]) == 16
        if s["name"].startswith("step."):
            parent = by_id[s["parentSpanId"]]
            assert parent["name"] == "api.call"
            assert s["traceId"] == parent["traceId"]


# ---------------------------------------------------------------------------
# fleet runner: per-bucket stats, reset, AOT spans
# ---------------------------------------------------------------------------

def test_fleet_stats_per_bucket_and_reset():
    speeds, active = _scenario(t=20, n=5)
    fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
    fleet.simulate(POLICIES, speeds, CFG, active=active)
    st = fleet.stats()
    assert st["cache_misses"] >= 1
    assert st["per_bucket"], st
    (bucket, counters), = list(st["per_bucket"].items())[:1] or [(None, {})]
    assert bucket == "32x8"
    assert counters["misses"] >= 1
    fleet.reset()
    st2 = fleet.stats()
    assert st2["cache_hits"] == st2["cache_misses"] == 0
    assert st2["per_bucket"] == {}
    assert st2["cache_entries"] == st["cache_entries"]  # executables kept
    fleet.simulate(POLICIES, speeds, CFG, active=active)
    st3 = fleet.stats()
    assert st3["cache_misses"] == 0 and st3["cache_hits"] >= 1
    assert st3["per_bucket"]["32x8"]["hits"] >= 1


def test_api_pack_emits_child_spans():
    from repro import api
    from repro.telemetry import default_tracer

    tracer = default_tracer()
    n0 = len(tracer.records())
    w = np.linspace(0.05, 0.6, 12)
    first = api.pack(w, 1.0, algorithm="MBFP", backend="jax")
    prev = np.array([first.assignment[j] for j in range(12)], np.int32)
    out = api.pack(w, 1.0, algorithm="MBFP", prev=prev, backend="jax")
    assert out.rscore is not None
    recs = tracer.records()[n0:]
    roots = [r for r in recs if r.name == "api.pack"]
    assert len(roots) == 2 and all(r.parent_id is None for r in roots)
    for root in roots:
        kids = [r for r in recs if r.parent_id == root.span_id]
        assert [r.name for r in kids] == [
            "pack.put", "pack.run", "pack.read", "pack.reply"]
        assert all(r.root_id == root.span_id for r in kids)
        read = kids[2]
        assert read.args["arrays"] == 4
        # bin_of i32[12], n_bins i32, names i32[m], loads f32[m]
        assert read.args["bytes"] >= 4 * (12 + 1 + 1 + 1)
        assert sum(r.dur_us for r in kids) <= root.dur_us


def test_api_simulate_emits_read_and_post_spans():
    from repro import api
    from repro.telemetry import (AlertConfig, SketchConfig, default_rules,
                                 default_tracer)

    tracer = default_tracer()
    n0 = len(tracer.records())
    speeds, active = _scenario(t=10, n=4)
    tele = TelemetryConfig(record_frames=False, sketch=SketchConfig(),
                           alerts=AlertConfig(rules=default_rules()))
    out = api.simulate(speeds, policies=("KEDA_LAG",), config=CFG,
                       active=active, telemetry=tele,
                       fleet=FleetRunner(FleetConfig()))
    assert out.sketches is not None and out.incidents is not None
    recs = tracer.records()[n0:]
    root, = [r for r in recs if r.name == "api.simulate"]
    assert root.parent_id is None
    mine = {r.name: r for r in recs if r.root_id == root.span_id}
    for name in ("fleet.simulate", "fleet.dispatch", "fleet.read",
                 "fleet.unpack", "sim.summarize", "sim.sketches",
                 "sim.incidents"):
        assert name in mine, (name, sorted(mine))
    for name in ("sim.summarize", "sim.sketches", "sim.incidents",
                 "fleet.simulate"):
        assert mine[name].parent_id == root.span_id
    fsim = mine["fleet.simulate"].span_id
    assert mine["fleet.unpack"].parent_id == fsim
    assert mine["fleet.read"].parent_id == fsim
    # lag/consumer trajectories, sketch and alert state leaves
    assert mine["fleet.read"].args["arrays"] > 5
    assert mine["fleet.read"].args["bytes"] > 0


def test_fleet_cache_hit_is_counted_not_traced():
    from repro.telemetry import default_tracer

    tracer = default_tracer()
    speeds, active = _scenario(t=10, n=4)
    fleet = FleetRunner(FleetConfig())
    fleet.simulate(("MBFP",), speeds, CFG, active=active)
    n0 = len(tracer.records())
    fleet.simulate(("MBFP",), speeds, CFG, active=active)
    assert fleet.stats()["per_bucket"]["10x4"]["hits"] == 1
    names = [r.name for r in tracer.records()[n0:]]
    assert "fleet.cache_miss" not in names
    assert not any(n.startswith("fleet.cache_hit") for n in names)


def test_fleet_emits_aot_spans():
    from repro.telemetry import default_tracer

    tracer = default_tracer()
    n0 = len(tracer.records())
    speeds, active = _scenario(t=10, n=4)
    fleet = FleetRunner(FleetConfig())
    fleet.simulate(("MBFP",), speeds, CFG, active=active)
    names = [r.name for r in tracer.records()[n0:]]
    for required in ("fleet.simulate", "fleet.trace_lower", "fleet.compile",
                     "fleet.dispatch"):
        assert required in names, (required, names)


# ---------------------------------------------------------------------------
# bench regression gate
# ---------------------------------------------------------------------------

def test_bench_diff_gate():
    from benchmarks.bench_diff import (DEFAULT_THRESHOLD, diff,
                                       _direction,
                                       _inject_throughput_regression)

    report = {"kind": "x",
              "timing": {"scenario_steps_per_s": 100.0, "steady_us": 10.0,
                         "speedup_vs_python": 50.0, "compile_us": 1e6,
                         "steps_per_scenario": 32, "violation_frac": 0.25}}
    clean = diff(report, report, DEFAULT_THRESHOLD)
    assert clean["regressions"] == [] and clean["improvements"] == []
    hurt = _inject_throughput_regression(report, factor=0.5)
    res = diff(report, hurt, DEFAULT_THRESHOLD)
    regressed = {name for name, *_ in res["regressions"]}
    assert regressed == {"timing/scenario_steps_per_s", "timing/steady_us",
                         "timing/speedup_vs_python"}
    # compile time, bare counts and SLO metrics never gate
    assert _direction(("timing", "compile_us")) == "info"
    assert _direction(("timing", "steps_per_scenario")) == "info"
    assert _direction(("timing", "violation_frac")) == "info"
    assert _direction(("x", "consumer_seconds")) == "info"
    # an improvement is not a regression
    better = _inject_throughput_regression(report, factor=2.0)
    res = diff(report, better, DEFAULT_THRESHOLD)
    assert res["regressions"] == [] and len(res["improvements"]) == 3


# ---------------------------------------------------------------------------
# custom-counter policies end to end + optional-pandas degradation
# ---------------------------------------------------------------------------

def test_counter_state_flows_into_fleet_sketch():
    """A registered policy carrying ``CounterState`` gets its counters
    recorded as first-class channels all the way through the
    fleet-padded path: frame names, sketch aggregation, histograms."""
    from repro import registry
    from repro.telemetry import CounterState, SketchConfig

    NAME = "TEST_COUNTED"

    @registry.register(NAME, family="reactive", backend="jax",
                       summary="test-only KEDA_LAG wrapper with counters")
    def _build(n, capacity):
        inner = registry.make_policy("KEDA_LAG", n, capacity, backend="jax")

        def init(n_partitions):
            return CounterState(counters=jnp.zeros(2, jnp.float32),
                                inner=inner.init(n_partitions),
                                names=("steps_seen", "scale_ups"))

        def step(speeds, lag, prev, state, active=None):
            args = (speeds, lag, prev, state.inner)
            assign, k, nxt = inner.step(*(args if active is None
                                          else args + (active,)))
            up = (nxt[0] > state.inner[0]).astype(jnp.float32)
            counters = state.counters + jnp.stack([jnp.float32(1.0), up])
            return assign, k, CounterState(counters=counters, inner=nxt,
                                           names=state.names)

        return init, step

    try:
        speeds, active = _scenario(t=20, n=5)
        tele = TelemetryConfig(sketch=SketchConfig(
            hist_channels=("lag_total", "steps_seen")))
        cfg = dataclasses.replace(CFG, telemetry=tele)
        # through the fleet (T padded 20 -> 32, N padded 5 -> 8) ...
        fleet = FleetRunner(FleetConfig(t_buckets=(32,), n_buckets=(8,)))
        res = fleet.simulate((NAME,), speeds, cfg, active=active)
        frame_names = res.telemetry[0].names
        assert frame_names[-2:] == ("steps_seen", "scale_ups")
        ((_, counted),) = res.sketch_summaries(0)
        assert counted.names[-2:] == ("steps_seen", "scale_ups")
        # ... the padded steps stay invisible: steps_seen counts exactly
        # the T real steps, on every aggregator
        t = speeds.shape[1]
        i = counted.channel_index("steps_seen")
        assert counted.count == t
        assert float(counted.vmax[i]) == t
        assert float(counted.vmin[i]) == 1.0
        assert float(counted.mean[i]) == pytest.approx((t + 1) / 2)
        assert counted.quantile(1.0, "steps_seen") == pytest.approx(
            t, abs=counted.edges[1] - counted.edges[0])
        # and the fleet result equals the direct engine bit-for-bit
        direct = simulate_lag(speeds[0], policy=NAME, cfg=cfg,
                              active=active[0])
        got = jax.tree_util.tree_map(lambda a: a[0], res.sketch[0])
        for fld in ("count", "mean", "m2", "vmin", "vmax", "hist"):
            assert np.asarray(getattr(got, fld)).tobytes() == \
                np.asarray(getattr(direct.sketch, fld)).tobytes(), fld
        # mixing counter channel sets in one sweep fails by name, not
        # with a cryptic treedef mismatch
        mixed = dataclasses.replace(CFG, telemetry=TelemetryConfig())
        with pytest.raises(ValueError, match="identical telemetry channels"):
            sweep_lag((NAME, "KEDA_LAG"), speeds, cfg=mixed, active=active)
    finally:
        registry._REGISTRY.pop((NAME, "jax"), None)
        if NAME in registry._ORDER:
            registry._ORDER.remove(NAME)


def test_to_dataframe_degrades_without_pandas(monkeypatch):
    """pandas is optional: the dataframe exporters raise a named
    ImportError pointing at the stdlib path, everything else works."""
    import builtins

    speeds, active = _scenario(t=10, n=4)
    res = simulate_lag(speeds[0], policy="MBFP", cfg=_with_tele(CFG),
                       active=active[0])
    stream = EventStream.from_frame(res.telemetry)

    real_import = builtins.__import__

    def no_pandas(name, *a, **kw):
        if name == "pandas" or name.startswith("pandas."):
            raise ImportError(f"No module named {name!r}")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_pandas)
    with pytest.raises(ImportError, match="to_dataframe needs pandas"):
        stream.to_dataframe()
    with pytest.raises(ImportError, match="optional dependency"):
        stream.events_dataframe()
    # the stdlib escape hatches named in the error still work
    assert json.loads(stream.to_json())

"""Hard consumer failures in the lag twin (the ``crash`` schedule).

Load-bearing properties:

* **The engine follows the five-step semantics** -- expel, crash, the
  policy's step with migration downtime, orphans unread, drain -- as the
  plain NumPy twin of the benchmark's ``crash`` entry
  (``bench/entries/crash.py``) computes them: decisions, ``unreadable``
  and ``orphaned`` exactly, lag to float32 rounding, for BFD, MBFP and
  ``KEDA_LAG_REAL`` behind its control plane.
* **Edge cases** -- a crash of an id nobody holds or of a dead id changes
  nothing; a partition that is never moved is unread for exactly
  ``orphan_steps`` steps and is placed again with no migration; an
  orphan moved in its window stays unread; a partition placed on a dead
  id is unread; an expelled id is reused.
* **No schedule, no change** -- ``crash=None`` traces the jaxpr the
  engine traced before schedules existed, returns no ``orphaned`` and
  records the same telemetry channels; an all-False schedule changes no
  decision.
* **The fleet path** pads the schedule on the batch, step and consumer-id
  axes and shards it like ``active``: ragged buckets and four forced host
  devices equal ``simulate_lag`` group by group.
* **Routing and validation** -- a run with a schedule takes the per-step
  scan under ``fused_steps``; a schedule of the wrong shape or dtype,
  and ``orphan_steps < 1``, fail with named errors.
"""
import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bench.entries.crash as entry
from bench import reference as ref
from bench.traffic import families
from repro import api
from repro.fleet import FleetConfig, FleetRunner
from repro.lagsim import LagSimConfig, simulate_lag
from repro.lagsim.engine import _sweep_impl
from repro.lagsim.fused import fused_mode
from repro.lagsim.metrics import agrees
from repro.telemetry import default_rules
from repro.telemetry.alerts import incident_matrix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = ("random_walk", "diurnal", "ramp", "bursty", "churn",
            "heavy_tail", "topic_lifecycle", "adversarial")
F64 = ref.PRECISIONS["float64"]
OBS = api.TelemetryConfig(record_frames=False, sketch=api.SketchConfig(),
                          alerts=api.AlertConfig(rules=default_rules()))


def _fleet(seed, n, steps=40):
    """Eight groups, one a family, rates on a 1/1024 grid (float32 load
    sums exact, so a float64 reference packs decision for decision)."""
    _, sp, ac = families.fleet(seed, FAMILIES, 1, steps, n, 1024)
    return np.asarray(sp), np.asarray(ac)


def _losses(seed, b, steps, n, p=0.2):
    return entry.node_losses(seed, 0, b, steps, n, 8, 5, p)


PACKER_CFG = LagSimConfig(capacity=1.0, dt=3.0, migration_steps=2,
                          orphan_steps=15)
KEDA_CP = dict(polling_interval=3, observation_delay=1, actuation_delay=1,
               cooldown_period=20, min_replicas=1, max_replicas=10,
               warmup_steps=2)
KEDA_CFG = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=6,
                        lag_threshold=0.0013356521739130436,
                        scale_down_patience=3, orphan_steps=15,
                        control_plane=api.ControlPlaneConfig(**KEDA_CP))


def _reference_policy(name, cfg, n):
    if name == "KEDA_LAG_REAL":
        cp = KEDA_CP
        return ref.KedaLag(
            n=n, lag_threshold=cfg.lag_threshold,
            patience=cfg.scale_down_patience, poll=cp["polling_interval"],
            obs_delay=cp["observation_delay"],
            act_delay=cp["actuation_delay"],
            cooldown=cp["cooldown_period"], min_replicas=cp["min_replicas"],
            max_replicas=cp["max_replicas"], warmup=cp["warmup_steps"],
            ar=F64)
    return ref.PackerPolicy(name, cfg.capacity, F64)


def _twin(sp, ac, crash, name, cfg):
    return entry.twin_with_crashes(
        sp, ac, crash, _reference_policy(name, cfg, sp.shape[-1]),
        dt=cfg.dt, capacity=cfg.capacity,
        migration_steps=cfg.migration_steps, orphan_steps=cfg.orphan_steps,
        ar=F64)


def _assert_matches_twin(got, want):
    for f in ("consumers", "migrations", "unreadable", "orphaned"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
    for f in ("lag_total", "lag_max"):
        scale = np.maximum(np.max(np.abs(want[f]), axis=-1, keepdims=True),
                           1e-30)
        assert np.max(np.abs(got[f] - want[f]) / scale) <= 1e-5, f


# ---------------------------------------------------------------------------
# the engine against the NumPy twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,cfg,n", [
    ("BFD", PACKER_CFG, 24), ("MBFP", PACKER_CFG, 24),
    ("KEDA_LAG_REAL", KEDA_CFG, 16)], ids=["BFD", "MBFP", "KEDA_LAG_REAL"])
def test_engine_equals_numpy_twin(name, cfg, n):
    sp, ac = _fleet(20261019, n)
    crash = _losses(20261019, sp.shape[0], sp.shape[1], n)
    want = _twin(sp, ac, crash, name, cfg)
    assert np.all(want["crashes"].sum(axis=1) > 0)    # every group lost one
    assert want["orphaned"].sum() > 0
    got = {f: [] for f in want}
    for g in range(sp.shape[0]):
        tr = simulate_lag(sp[g], policy=name, cfg=cfg, active=ac[g],
                          crash=crash[g])
        for f in want:
            if f != "crashes":
                got[f].append(np.asarray(getattr(tr, f)))
    _assert_matches_twin({f: np.stack(v) for f, v in got.items() if v}, want)


# ---------------------------------------------------------------------------
# edge cases on hand-made groups (BFD, S = 4)
# ---------------------------------------------------------------------------

EDGE_CFG = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2,
                        orphan_steps=4)
#: two partitions of 0.6 C: BFD keeps them on ids 0 and 1 at every step
STEADY = np.tile(np.float32([0.6, 0.6]), (12, 1))
#: p2 on id 1 until the rates change at step 4; then BFD moves p1 onto
#: id 1 and p2 onto id 0
SHIFT = np.float32([[0.6, 0.3, 0.3]] * 4 + [[0.6, 0.45, 0.3]] * 8)


def _run(rates, crashes, cfg=EDGE_CFG):
    t, n = rates.shape
    crash = np.zeros((t, 2 * n + 2), bool)
    for step, c in crashes:
        crash[step, c] = True
    tr, assign = simulate_lag(rates, policy="BFD", cfg=cfg, crash=crash,
                              record_assign=True)
    got = {f: np.asarray(getattr(tr, f)) for f in (
        "lag_total", "lag_max", "consumers", "migrations", "unreadable",
        "orphaned")}
    want = _twin(rates[None], np.ones((1, t, n), bool), crash[None], "BFD",
                 cfg)
    _assert_matches_twin({f: v[None] for f, v in got.items()}, want)
    return got, np.asarray(assign)


def test_crash_of_unused_id_changes_nothing():
    base, a0 = _run(STEADY, [])
    got, a1 = _run(STEADY, [(3, 5)])           # ids 0 and 1 are in use
    for f in base:
        assert got[f].tobytes() == base[f].tobytes(), f
    np.testing.assert_array_equal(a1, a0)
    assert not got["orphaned"].any()


def test_crash_of_dead_id_changes_nothing():
    once, a0 = _run(STEADY, [(3, 1)])
    twice, a1 = _run(STEADY, [(3, 1), (5, 1)])  # dead until step 7
    for f in once:
        assert twice[f].tobytes() == once[f].tobytes(), f
    np.testing.assert_array_equal(a1, a0)


def test_unmoved_partition_is_unread_exactly_s_steps():
    got, assign = _run(STEADY, [(3, 1)])
    np.testing.assert_array_equal(
        got["orphaned"], [0, 0, 0, 1, 1, 1, 1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(got["unreadable"], got["orphaned"])
    assert not got["migrations"].any()         # placed again: no move
    assert np.all(got["consumers"] == 2)       # the dead pod is billed
    lag = got["lag_total"]
    assert np.all(np.diff(lag[3:7]) > 0)       # backlog piles up unread
    assert lag[8] < lag[6]                     # and drains once placed


def test_expelled_id_is_reused():
    _, assign = _run(STEADY, [(3, 1)])
    # expelled at step 7 (3 + S), p1 goes unowned and BFD's sticky naming
    # hands it the lowest free id, which is the expelled one
    np.testing.assert_array_equal(assign[:, 1], np.ones(12))


def test_moved_orphan_stays_unread_and_dead_id_placement_is_orphaned():
    got, assign = _run(SHIFT, [(3, 1)])
    np.testing.assert_array_equal(assign[3], [0, 0, 1])
    np.testing.assert_array_equal(assign[4], [0, 1, 0])
    # step 4: p2 moved to live id 0 inside its window, p1 moved onto dead
    # id 1; both unread until the expulsion at step 7
    np.testing.assert_array_equal(
        got["orphaned"], [0, 0, 0, 1, 2, 2, 2, 0, 0, 0, 0, 0])
    assert got["migrations"][4] == 2
    assert got["orphaned"][7:].sum() == 0


# ---------------------------------------------------------------------------
# no schedule: the engine as it was
# ---------------------------------------------------------------------------

#: sha256 of the jaxpr text of ``_sweep_impl`` without a schedule, as the
#: engine traced it before crash schedules existed (jax 0.9.0 as pinned in
#: requirements.txt; B, T, N = 4, 12, 6).  Change one only with a change
#: to the schedule-free path that is meant to change its program.
JAXPR_SHA = {
    "packers": "d7cc816cb8fc115d",
    "packers_valid": "02117c8ca86e7933",
    "frames": "11fb7c272f777184",
    "keda": "37c556ceba653d7d",
    "fused": "eed2cd71eeaacc22",
}


def _jaxpr_cases(n):
    return {
        "packers": (("BFD", "MBFP"), LagSimConfig(
            capacity=1.0, dt=3.0, migration_steps=2, telemetry=OBS),
            True, False),
        "packers_valid": (("BFD", "MBFP"), LagSimConfig(telemetry=OBS),
                          True, True),
        "frames": (("BFD",), LagSimConfig(telemetry=api.TelemetryConfig()),
                   False, False),
        "keda": (("KEDA_LAG_REAL",), LagSimConfig(
            dt=1.0, telemetry=OBS, control_plane=api.ControlPlaneConfig(
                polling_interval=3, cooldown_period=20, max_replicas=4)),
            True, False),
        "fused": (("BFD", "FFD"), LagSimConfig(fused_steps=4), True, False),
    }


@pytest.mark.parametrize("case", sorted(JAXPR_SHA))
def test_no_schedule_traces_the_same_jaxpr(case):
    b, t, n = 4, 12, 6
    pols, cfg, use_ac, use_va = _jaxpr_cases(n)[case]
    cfg = cfg.resolve(n)
    args = (jax.ShapeDtypeStruct((b, t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, t, n), bool) if use_ac else None,
            jax.ShapeDtypeStruct((b, t), bool) if use_va else None)
    text = str(jax.make_jaxpr(
        lambda x, a, v: _sweep_impl(pols, x, cfg, a, v))(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == JAXPR_SHA[case]


def test_no_schedule_no_orphaned_and_same_channels():
    sp, ac = _fleet(7, 12, steps=20)
    cfg = LagSimConfig(
        telemetry=api.TelemetryConfig(sketch=api.SketchConfig()))
    out = api.simulate(sp, policies=("BFD",), active=ac, config=cfg,
                       fleet=FleetRunner())
    assert out.orphaned is None
    names = out.telemetry[0].names
    assert names == cfg.telemetry.base_channels and "orphaned" not in names
    crash = _losses(7, sp.shape[0], 20, 12, p=0.5)
    with_crash = api.simulate(sp, policies=("BFD",), active=ac, config=cfg,
                              crash=crash, fleet=FleetRunner())
    frame = with_crash.telemetry[0]
    assert frame.names == cfg.telemetry.base_channels + ("orphaned",)
    np.testing.assert_array_equal(frame.channel("orphaned")[0],
                                  with_crash.orphaned[0, 0])
    assert with_crash.sketches[0][0].names == frame.names


@pytest.mark.parametrize("name,cfg", [("MBFP", PACKER_CFG),
                                      ("KEDA_LAG_REAL", KEDA_CFG)],
                         ids=["MBFP", "KEDA_LAG_REAL"])
def test_all_false_schedule_agrees_with_none(name, cfg):
    """A schedule that crashes nobody changes no decision and no count;
    the larger program may sum the lag in another order
    (``repro.lagsim.metrics.agrees``)."""
    sp, ac = _fleet(11, 16, steps=30)
    cfg = dataclasses.replace(cfg, telemetry=OBS)
    runner = FleetRunner()
    none = runner.simulate((name,), sp, cfg, active=ac)
    quiet = runner.simulate((name,), sp, cfg, active=ac,
                            crash=np.zeros((8, 30, 34), bool))
    for f in ("lag_total", "lag_max", "consumers", "migrations",
              "unreadable"):
        assert agrees(np.stack(getattr(quiet, f)),
                      np.stack(getattr(none, f))), f
    assert none.orphaned is None and not np.stack(quiet.orphaned).any()
    assert (incident_matrix(quiet.incidents[0]).tolist()
            == incident_matrix(none.incidents[0]).tolist())


# ---------------------------------------------------------------------------
# the fleet path
# ---------------------------------------------------------------------------

def _ragged():
    rng = np.random.default_rng(16)
    shapes = ((14, 4), (20, 8), (9, 6), (20, 5))
    scen = [np.round(rng.uniform(0, 0.9, s) * 1024).astype(np.float32) / 1024
            for s in shapes]
    crashes = [rng.random((t, 2 * n + 2)) < 0.1 for t, n in shapes]
    return scen, crashes


def _check_fleet_equals_direct(res, scen, crashes, policies, cfg):
    for i, (sp, cr) in enumerate(zip(scen, crashes)):
        for p, name in enumerate(policies):
            tr = simulate_lag(sp, policy=name, cfg=cfg, crash=cr)
            for f in ("consumers", "migrations", "unreadable", "orphaned"):
                np.testing.assert_array_equal(
                    getattr(res, f)[i][p], np.asarray(getattr(tr, f)),
                    err_msg=f"scenario {i} {name} {f}")
            np.testing.assert_allclose(getattr(res, "lag_total")[i][p],
                                       np.asarray(tr.lag_total), rtol=1e-6,
                                       atol=1e-6)


def test_fleet_pads_schedule_in_mixed_buckets():
    scen, crashes = _ragged()
    runner = FleetRunner(FleetConfig(t_buckets=(16, 20), n_buckets=(8,)))
    res = runner.simulate(("BFD", "MBFP"), scen, PACKER_CFG, crash=crashes)
    assert runner.stats()["buckets"] == {"16x8": 2, "20x8": 2}
    assert sum(int(o.sum()) for o in res.orphaned) > 0
    _check_fleet_equals_direct(res, scen, crashes, ("BFD", "MBFP"),
                               PACKER_CFG)


CHILD = """
import json
import numpy as np
from repro.fleet import FleetConfig, FleetRunner
from tests import test_lagsim_crash as t

scen, crashes = t._ragged()
runner = FleetRunner(FleetConfig(t_buckets=(16, 20), n_buckets=(8,)))
res = runner.simulate(("BFD", "MBFP"), scen, t.PACKER_CFG, crash=crashes)
t._check_fleet_equals_direct(res, scen, crashes, ("BFD", "MBFP"),
                             t.PACKER_CFG)
sp, ac = t._fleet(5, 12, steps=20)
crash = t._losses(5, 8, 20, 12, p=0.3)
full = FleetRunner().simulate(("MBFP",), sp, t.PACKER_CFG, active=ac,
                              crash=crash)
one = FleetRunner(FleetConfig(shard=False)).simulate(
    ("MBFP",), sp, t.PACKER_CFG, active=ac, crash=crash)
for f in ("consumers", "migrations", "unreadable", "orphaned"):
    np.testing.assert_array_equal(np.stack(getattr(full, f)),
                                  np.stack(getattr(one, f)))
print(json.dumps({"devices": runner.stats()["devices"],
                  "orphaned": int(np.stack(full.orphaned).sum())}))
"""


def test_fleet_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip(),
        PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src"),
                                    os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["orphaned"] > 0


# ---------------------------------------------------------------------------
# routing and validation
# ---------------------------------------------------------------------------

def test_schedule_takes_the_per_step_scan():
    cfg = LagSimConfig(fused_steps=4)
    assert fused_mode("BFD", cfg, 6) == "fused"
    assert fused_mode("BFD", cfg, 6, crash=True) == "unfused"
    sp, ac = _fleet(3, 6, steps=20)
    crash = _losses(3, 8, 20, 6, p=0.3)
    fused = FleetRunner().simulate(("BFD", "FFD"), sp, cfg, active=ac,
                                   crash=crash)
    scan = FleetRunner().simulate(("BFD", "FFD"), sp, LagSimConfig(),
                                  active=ac, crash=crash)
    for f in ("lag_total", "consumers", "migrations", "unreadable",
              "orphaned"):
        assert (np.stack(getattr(fused, f)).tobytes()
                == np.stack(getattr(scan, f)).tobytes()), f


@pytest.mark.parametrize("bad", ["shape", "dtype", "batch"])
def test_bad_schedule_is_refused_with_both_shapes(bad):
    sp = np.full((3, 10, 4), 0.5, np.float32)
    crash = {"shape": np.zeros((3, 10, 8), bool),
             "dtype": np.zeros((3, 10, 10), np.int8),
             "batch": np.zeros((2, 10, 10), bool)}[bad]
    for call in (lambda: api.simulate(sp, policies=("BFD",), crash=crash,
                                      fleet=FleetRunner()),
                 lambda: FleetRunner().simulate(("BFD",), sp, LagSimConfig(),
                                                crash=crash)):
        with pytest.raises(ValueError, match="crash schedule") as err:
            call()
        assert str(crash.shape) in str(err.value)
        assert "(3, 10, 4)" in str(err.value)


def test_orphan_steps_below_one_is_refused():
    with pytest.raises(ValueError, match="orphan_steps"):
        LagSimConfig(orphan_steps=0).resolve(4)
    with pytest.raises(ValueError, match="orphan_steps"):
        api.simulate(np.zeros((1, 4, 4), np.float32), policies=("BFD",),
                     orphan_steps=0, fleet=FleetRunner())

"""``correct`` separates sound runs from broken ones.

Each cell runs here on the CPU at a small size through the harness's own
run (``harness.run_cell``), with the look for a chip skipped:

* as it is, ``correct`` is true;
* with the reference computed in bfloat16 put in the program's place
  (the control), a number compared passes its limit;
* with a fault planted underneath the timed path, ``correct`` is false:
  a step that returns its state unchanged, half of the fleet left out
  (its groups given the other half's results), and an answer altered
  where it is produced.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.drivers import DRIVERS
from bench.reference import PRECISIONS

SMALL = {"simulate": dict(groups_per_family=1, steps=16, fleets=2,
                          check_per_family=1),
         "pack": dict(steps=40, check_decisions=80)}
SMALL_N = {"omb100-sim-packers": 24}
CELLS = [w["name"] for w in harness.spec()["workloads"]]
SIM = [c for c in CELLS if harness.cell(c)[3]["entry"] == "simulate"]
PACK = [c for c in CELLS if harness.cell(c)[3]["entry"] == "pack"]


def small(name, seed=20261017, plant=None):
    """The cell's driver at a small size; ``plant(driver)`` runs after its
    set-up, before the window."""
    _, _, config, mix = harness.cell(name)
    mix = {**mix, **SMALL[mix["entry"]]}
    if name in SMALL_N:
        config = {**config, "partitionsPerTopic": SMALL_N[name]}
    drv = DRIVERS[mix["entry"]](config, mix, seed)
    if plant is not None:
        setup = drv.setup

        def planted():
            setup()
            plant(drv)
        drv.setup = planted
    return drv


def run(name, drv):
    line, _ = harness.run_cell(name, drv.seed, 0.3, False,
                               t_start=time.perf_counter(),
                               devices=jax.devices()[:1], driver=drv)
    return line


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = run(name, small(name))
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    _, _, _, mix = harness.cell(name)
    drv = small(name)
    drv.setup()
    drv.window(0.3, harness.WindowTracer(None, 0))
    nums, _ = drv.numbers(control=PRECISIONS["bfloat16"])
    assert any(v > mix["limits"][k] for k, v in nums.items()), nums


# -- faults planted in the simulate path ---------------------------------------

def lag_unchanged(drv, monkeypatch):
    import repro.lagsim.engine as engine

    monkeypatch.setattr(engine, "lag_update_reference",
                        lambda lag, *a, **kw: lag)
    drv.runner.clear()


def half_fleet(drv, monkeypatch):
    run_sim = drv.runner._run_sim

    def half(policies, speeds, act, *a, **kw):
        h = speeds.shape[0] // 2
        speeds = jnp.concatenate([speeds[:h], speeds[:h]])
        if act is not None:
            act = jnp.concatenate([act[:h], act[:h]])
        return run_sim(policies, speeds, act, *a, **kw)
    monkeypatch.setattr(drv.runner, "_run_sim", half)


def consumer_altered(drv, monkeypatch):
    run_sim = drv.runner._run_sim

    def altered(*a, **kw):
        arrays, *rest = run_sim(*a, **kw)
        arrays["consumers"] = arrays["consumers"].copy()
        arrays["consumers"][0, :, 3] += 1
        return (arrays, *rest)
    monkeypatch.setattr(drv.runner, "_run_sim", altered)


@pytest.mark.parametrize("fault", [lag_unchanged, half_fleet,
                                   consumer_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", SIM)
def test_simulate_fault_is_caught(name, fault, monkeypatch):
    line = run(name, small(name, plant=lambda d: fault(d, monkeypatch)))
    assert not line["correct"], line["compared"]


# -- faults planted in the decision path ---------------------------------------

def assignment_unchanged(drv, monkeypatch):
    import repro.api as api

    real = api.packer_for

    def packer_for(name, backend):
        fn = real(name, backend=backend)
        return lambda sp, pv, cap: dataclasses.replace(fn(sp, pv, cap),
                                                       bin_of=pv)
    monkeypatch.setattr(api, "packer_for", packer_for)


def bin_altered(drv, monkeypatch):
    import repro.api as api

    real = api.packer_for

    def packer_for(name, backend):
        fn = real(name, backend=backend)

        def altered(sp, pv, cap):
            res = fn(sp, pv, cap)
            b = np.asarray(res.bin_of).copy()
            b[0] = b[0] + 1
            return dataclasses.replace(res, bin_of=jnp.asarray(b))
        return altered
    monkeypatch.setattr(api, "packer_for", packer_for)


@pytest.mark.parametrize("fault", [assignment_unchanged, bin_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", PACK)
def test_pack_fault_is_caught(name, fault, monkeypatch):
    line = run(name, small(name, plant=lambda d: fault(d, monkeypatch)))
    assert not line["correct"], line["compared"]

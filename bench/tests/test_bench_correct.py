"""``correct`` separates sound runs from broken ones.

Each cell runs here on the CPU at a small size (its driver's ``small``)
through the harness's own run (``harness.run_cell``), with the look for a
chip skipped:

* as it is, ``correct`` is true;
* with the reference computed in bfloat16 put in the program's place
  (the control), a number compared passes its limit;
* with a fault planted underneath the timed path, ``correct`` is false:
  a step that returns its state unchanged, half of the fleet left out
  (its groups given the other half's results), and an answer altered
  where it is produced.

Cells are picked by their driver's class, so a cell whose entry file
subclasses ``drivers.Simulate`` or ``drivers.Pack`` gets these tests with
no edit here.  A cell on four chips also runs on four host devices in a
child process, where the fleet is sharded as on the chip: there the
host's gather of the sharded result with two shards swapped is a fault
too.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import drivers, harness
from bench.reference import PRECISIONS


def cells(root=harness.ROOT, base=object, chips=None):
    """The cells of ``root``'s ``BENCHMARK.json`` whose driver is a
    ``base``, on ``chips`` chips where given."""
    out = []
    for w in harness.spec(root)["workloads"]:
        entry = harness.cell(w["name"], root)[3]["entry"]
        if (issubclass(harness.driver(entry, root), base)
                and chips in (None, w["chips"])):
            out.append(w["name"])
    return out


CELLS = cells()
SIM = cells(base=drivers.Simulate)
PACK = cells(base=drivers.Pack)
SIM_X4 = cells(base=drivers.Simulate, chips=4)


def small(name, seed=20261017, plant=None, root=harness.ROOT, devices=None):
    """The cell's driver at its small size, on ``devices`` (one by
    default); ``plant(driver)`` runs after its set-up, before the
    window."""
    _, _, config, mix = harness.cell(name, root)
    cls = harness.driver(mix["entry"], root)
    config, mix = cls.small(config, mix)
    drv = cls(config, mix, seed, devices or jax.devices()[:1])
    if plant is not None:
        setup = drv.setup

        def planted():
            setup()
            plant(drv)
        drv.setup = planted
    return drv


def run(name, drv, root=harness.ROOT, devices=None):
    line, _ = harness.run_cell(name, drv.seed, 0.3, False,
                               t_start=time.perf_counter(),
                               devices=devices or jax.devices()[:1],
                               root=root, drv=drv)
    return line


def check_sound(name, root=harness.ROOT):
    line = run(name, small(name, root=root), root)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    return line


def check_control(name, root=harness.ROOT):
    _, _, _, mix = harness.cell(name, root)
    drv = small(name, root=root)
    drv.setup()
    drv.window(0.3, harness.WindowTracer(None, 0))
    nums, _ = drv.numbers(control=PRECISIONS["bfloat16"])
    assert any(v > drv.mix["limits"][k] for k, v in nums.items()), nums


def check_fault(name, fault, monkeypatch, root=harness.ROOT):
    drv = small(name, plant=lambda d: fault(d, monkeypatch), root=root)
    line = run(name, drv, root)
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    check_sound(name)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    check_control(name)


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


def shards_swapped(drv, monkeypatch):
    """The host's gather of each result sharded over the devices puts its
    first two shards in each other's place."""
    import repro.fleet.runner as runner

    def asarray(x, *a, **kw):
        if isinstance(x, jax.Array) and len(x.addressable_shards) > 1:
            shards = sorted(x.addressable_shards,
                            key=lambda s: [sl.start or 0 for sl in s.index])
            axes = [i for i, sl in enumerate(shards[1].index)
                    if (sl.start or 0) > 0]
            if axes:
                parts = [np.asarray(s.data) for s in shards]
                parts[0], parts[1] = parts[1], parts[0]
                x = np.concatenate(parts, axis=axes[0])
        return np.asarray(x, *a, **kw)

    class Numpy:
        def __getattr__(self, name):
            return getattr(np, name)

    swapping = Numpy()
    swapping.asarray = asarray
    monkeypatch.setattr(runner, "np", swapping)


SIM_FAULTS = [lag_unchanged, half_fleet, consumer_altered]


@pytest.mark.parametrize("fault", SIM_FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", SIM)
def test_simulate_fault_is_caught(name, fault, monkeypatch):
    check_fault(name, fault, monkeypatch)


# -- a four-chip cell on four host devices -------------------------------------

X4_CASES = ("sound", "half_fleet", "consumer_altered", "shards_swapped")
CHILD = """
import json, sys
import jax, pytest
from bench import harness
from bench.tests import test_bench_correct as t

name = sys.argv[1]
devices = jax.devices()[:harness.cell(name)[1]["chips"]]
out = {}
for case in t.X4_CASES:
    with pytest.MonkeyPatch.context() as mp:
        plant = (None if case == "sound" else
                 lambda d, f=getattr(t, case): f(d, mp))
        drv = t.small(name, plant=plant, devices=devices)
        line = t.run(name, drv, devices=devices)
        out[case] = {"correct": line["correct"],
                     "compared": line["compared"],
                     "devices": len(drv.runner._devices()),
                     "count": line["device"]["count"]}
print(json.dumps(out))
"""
_X4_LINES = {}


def four_device_lines(name):
    """Each of ``X4_CASES`` of cell ``name`` in one child process that sees
    the cell's chips as host devices."""
    if name not in _X4_LINES:
        chips = harness.cell(name)[1]["chips"]
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip(),
            PYTHONPATH=os.pathsep.join(
                [harness.ROOT, os.path.join(harness.ROOT, "src"),
                 os.environ.get("PYTHONPATH", "")]))
        done = subprocess.run([sys.executable, "-c", CHILD, name],
                              cwd=harness.ROOT, env=env, capture_output=True,
                              text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-4000:]
        _X4_LINES[name] = json.loads(done.stdout.strip().splitlines()[-1])
    return _X4_LINES[name]


@pytest.mark.parametrize("case", X4_CASES)
@pytest.mark.parametrize("name", SIM_X4)
def test_four_chip_cell_on_four_devices(name, case):
    got = four_device_lines(name)[case]
    chips = harness.cell(name)[1]["chips"]
    assert got["devices"] == got["count"] == chips
    assert got["correct"] == (case == "sound"), got["compared"]


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
    check_fault(name, fault, monkeypatch)

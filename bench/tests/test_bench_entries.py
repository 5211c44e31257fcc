"""A cell with new semantics takes new files only.

A temporary root holds a copy of the benchmark's data (``BENCHMARK.json``,
configurations, traffic mixes, entries) with one more cell, whose entry
file and traffic mix are planted there.  Its ``DRIVER`` subclasses
``drivers.Simulate`` and overrides only the seams: it adds a policy that
the built-in reference lacks (FFD, with its reference in the planted
file), a program input that the built-in entry does not pass
(``migration_steps``, from the mix, given to the reference run too) and
one more number compared.  Through ``harness.run_cell`` on the CPU at its
small size, ``correct`` is true as it is and false with a consumer count
altered where it is produced or with the input left out of the program's
call; and the cell gets the tests that ``test_bench_correct`` runs over
every cell, with no edit there.
"""
import json
import os
import shutil
import time
import types

import jax
import pytest

from bench import drivers, harness
from bench.tests import test_bench_correct as correct

ENTRY = '''
"""FFD, sticky naming, over fleets whose moved partitions are down for
the mix's ``migration_steps``: the program gets it as a keyword, and the
reference run as a twin parameter."""
from bench import reference as ref
from bench.drivers import Simulate


def ffd(speeds, active, prev, cap, r=float):
    """First fit decreasing with sticky naming: ``(assignment, bins)``."""
    bins = ref._Bins(cap, r)
    assign = [ref.NEG] * len(speeds)
    for j in sorted((j for j in range(len(speeds)) if active[j]),
                    key=lambda j: (-speeds[j], j)):
        slot = next((s for s, load in enumerate(bins.loads)
                     if r(load + speeds[j]) <= cap), -1)
        if slot < 0:
            slot = bins.open(ref._fresh_name(bins.used, prev[j]))
        assign[j] = bins.add(slot, speeds[j])
    return assign, len(bins.loads)


class FFDSimulate(Simulate):
    def call_kwargs(self, k):
        return {"migration_steps": int(self.mix["migration_steps"])}

    def reference_policy(self, name, arith):
        cap = float(self.config["twin"]["capacity"])
        return ref.PackerPolicy(name, cap, arith, fn=ffd)

    def reference_run(self, k, groups, policy, arith):
        return super().reference_run(
            k, groups, policy, arith,
            migration_steps=int(self.mix["migration_steps"]))

    def extra_numbers(self, pairs):
        return {"moves_off": sum(
            abs(int(g["migrations"].sum()) - int(w["migrations"].sum()))
            for _, _, g, w in pairs)}


DRIVER = FFDSimulate
'''

CELL = "omb24-sim-ffd"
SEED = 20261018


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark's data as it is, with the planted cell added."""
    root = str(tmp_path_factory.mktemp("planted"))
    for sub in ("configs", "traffic", "entries"):
        shutil.copytree(os.path.join(harness.BENCH, sub),
                        os.path.join(root, "bench", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.spec()
    spec["workloads"].append({
        "name": CELL, "config": "omb-100p", "traffic": "fleet-ffd",
        "chips": 1, "why": "FFD over a fleet, moved partitions down for "
                           "three steps"})
    for m in spec["end_to_end"]:
        if m["name"] == "eval_rate":
            m["workloads"].append(CELL)
    _, _, _, mix = harness.cell("omb100-sim-packers")
    mix = {**mix, "entry": "ffd-sim", "policies": ["FFD"],
           "migration_steps": 3,
           "limits": {**mix["limits"], "moves_off": 0}}
    for path, data in {os.path.join(root, "BENCHMARK.json"): spec,
                       harness.traffic_path("fleet-ffd", root): mix}.items():
        with open(path, "w") as f:
            json.dump(data, f)
    with open(harness.entry_path("ffd-sim", root), "w") as f:
        f.write(ENTRY)
    return root


def test_planted_entry_is_found(root):
    cls = harness.driver("ffd-sim", root)
    assert issubclass(cls, drivers.Simulate) and cls is not drivers.Simulate
    assert harness.driver("simulate", root) is drivers.Simulate
    with pytest.raises(harness.BenchError, match="'ffd-sim'"):
        harness.driver("no-such-entry", root)


def test_planted_entry_is_correct(root):
    line = correct.check_sound(CELL, root)
    assert set(line["compared"]) == {"decisions_off", "unreadable_off",
                                     "lag_gap", "lag_max_gap", "moves_off"}
    assert {"eval_rate", "setup_s"} <= set(line["metrics"])


def test_fields_seam_keeps_what_it_names(root):
    class Fewer(harness.driver("ffd-sim", root)):
        FIELDS = ("consumers", "migrations")

    _, _, config, mix = harness.cell(CELL, root)
    config, mix = Fewer.small(config, mix)
    drv = Fewer(config, mix, SEED, jax.devices()[:1])
    drv.setup()
    drv.call(0)
    drv.call(1)
    assert drv.outputs
    for out in drv.outputs:
        assert set(out) == {"fleet", "consumers", "migrations"}


def input_left_out(drv, monkeypatch):
    simulate = drv.api.simulate

    def dropped(*a, migration_steps, **kw):
        return simulate(*a, **kw)
    monkeypatch.setattr(drv, "api", types.SimpleNamespace(simulate=dropped))


@pytest.mark.parametrize("fault", [correct.consumer_altered, input_left_out],
                         ids=lambda f: f.__name__)
def test_planted_entry_fault_is_caught(root, fault, monkeypatch):
    correct.check_fault(CELL, fault, monkeypatch, root)


def test_planted_cell_is_in_the_cell_tests(root):
    assert CELL in correct.cells(root)
    assert CELL in correct.cells(root, drivers.Simulate)
    assert CELL not in correct.cells(root, drivers.Pack)
    assert set(correct.cells()) < set(correct.cells(root))


@pytest.mark.parametrize("fault", correct.SIM_FAULTS,
                         ids=lambda f: f.__name__)
def test_planted_cell_simulate_fault_is_caught(root, fault, monkeypatch):
    correct.check_fault(CELL, fault, monkeypatch, root)


def test_planted_cell_control_fails(root):
    correct.check_control(CELL, root)

"""The node-loss cell ``omb100-sim-nodeloss`` (entry ``crash``).

Through ``harness.run_cell`` on the CPU at the cell's small size,
``correct`` is false with a fault planted in what the failures add: the
schedule's losses left out of the program's call, a session timeout one
step short, and orphans that become readable once the policy moves them.  The
small size keeps a node loss that somebody felt in every checked group,
and says so when it does not.  The reader of ``orphaned_share.sim`` reads
the program's ``orphaned`` counter, and nothing where a program has none.
"""
import dataclasses

import pytest

from bench import harness
from bench.tests import test_bench_correct as correct
from bench.tests.test_bench_layers import rec

CELL = "omb100-sim-nodeloss"


def schedule_dropped(drv, monkeypatch):
    """The program is called with a schedule that crashes nobody."""
    import jax.numpy as jnp

    monkeypatch.setattr(drv, "call_kwargs", lambda k: {
        "crash": jnp.zeros_like(drv.crashes[k])})


def window_one_step_short(drv, monkeypatch):
    drv.cfg = dataclasses.replace(drv.cfg,
                                  orphan_steps=drv.cfg.orphan_steps - 1)


def orphans_readable_once_moved(drv, monkeypatch):
    import jax.numpy as jnp

    import repro.lagsim.engine as engine

    mask = engine.orphan_mask
    monkeypatch.setattr(
        engine, "orphan_mask",
        lambda t, new, dead, until: mask(t, new, dead, jnp.zeros_like(until)))
    drv.runner.clear()


@pytest.mark.parametrize("fault", [schedule_dropped, window_one_step_short,
                                   orphans_readable_once_moved],
                         ids=lambda f: f.__name__)
def test_failure_fault_is_caught(fault, monkeypatch):
    correct.check_fault(CELL, fault, monkeypatch)


def test_sound_run_compares_orphans():
    line = correct.check_sound(CELL)
    assert line["compared"]["orphaned_off"] == {"value": 0.0, "limit": 0.0}
    assert {"eval_rate", "setup_s"} <= set(line["metrics"])


def test_small_size_requires_a_felt_loss_in_every_checked_group():
    _, _, config, mix = harness.cell(CELL)
    cls = harness.driver(mix["entry"])
    assert cls.small(config, mix)[1]["require_losses"]
    drv = correct.small(CELL)
    drv.mix = {**drv.mix, "loss_per_step": 0.0}
    drv.setup()
    drv.call(0)
    with pytest.raises(harness.BenchError, match="lost no held consumer"):
        drv.numbers()


class _Driver:
    def work_per_call(self):
        return 2 * 64 * 200 * 100


def _call(root_id, t0, **counts):
    return [rec("fleet.read", t0 + 1, 10, root_id + 1, root_id, root_id,
                arrays=5, bytes=100, **counts),
            rec("api.simulate", t0, 20, root_id)]


def test_orphaned_share_reader():
    read = harness.reader("orphaned_share.sim")
    spans = (_call(100, 0.0, orphaned=51_200)
             + _call(200, 100.0, orphaned=76_800))
    ctx = {"window_spans": spans, "driver": _Driver()}
    assert read(ctx) == pytest.approx(64_000 / 2_560_000 * 100)
    # a program whose fleet.read spans carry no orphaned counter
    assert read({**ctx, "window_spans": _call(100, 0.0)}) is None
    _, layer = harness.metrics_of(harness.spec(), CELL)
    assert "orphaned_share.sim" in {m["name"] for m in layer}

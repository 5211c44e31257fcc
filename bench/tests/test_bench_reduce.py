"""The trace reduction (``bench/reduce.py``): busy as the union of device
op intervals, idle share, top ops and labelled idle gaps, on a made-up
profile and on a small trace recorded on a TPU v5e."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile():
    """Two calls annotated on the host, ops on two devices (overlapping
    ops on TPU:0, one op running past the window's end)."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.call", 1000, 4000),
                                  ev("bench.call", 6000, 2000)]),
        NS(name="runtime", events=[ev("host.post", 3500, 2800),
                                   ev("host.wide", 0, 10000)]),
    ])
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 1000, 1000),
                                   ev("fusion.2", 1500, 1000),
                                   ev("while.3", 7000, 5000)]),
        NS(name="XLA Modules", events=[ev("jit_f", 0, 20000)]),
    ])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 1200, 500)]),
    ])
    other = NS(name="/host:metadata", lines=[])
    return NS(planes=[host, dev0, dev1, other])


def test_self_times_of_nested_ops():
    evs = [("while", 0, 100), ("body", 10, 30), ("body", 50, 70),
           ("inner", 55, 60), ("after", 120, 130)]
    assert reduce.self_times(evs, 0, 125) == {
        "while": 60, "body": 35, "inner": 5, "after": 5}
    assert reduce.op_name("%fusion.12 = f32[8]{0} fusion(%p)") == "%fusion.12"


def test_union_and_cover():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert reduce.covered_ns([(0, 4), (5, 7)], 2, 6) == 3


def test_reduce_profile_numbers():
    red = reduce.reduce_profile(profile())
    assert red.window == (1000, 8000)
    assert red.window_s == pytest.approx(7e-6)
    # TPU:0 busy [1000, 2500) and [7000, 8000) inside the window
    assert red.busy_s("/device:TPU:0") == pytest.approx(2.5e-6)
    assert red.busy_s("/device:TPU:1") == pytest.approx(0.5e-6)
    assert red.busy_s() == pytest.approx(1.5e-6)
    assert red.busiest() == "/device:TPU:0"
    assert red.busy_in(2000, 7500) == pytest.approx(1e-6)
    # self time summed by name over devices, clipped to the window:
    # fusion.1 loses the 500 ns that fusion.2 overlaps on TPU:0
    ops = dict(red.ops)
    assert ops["fusion.1"] == pytest.approx(1e-6)
    assert ops["fusion.2"] == pytest.approx(1e-6)
    assert ops["while.3"] == pytest.approx(1e-6)
    # the longest gap [2500, 7000) is labelled by the shortest host
    # event covering its midpoint (4750: host.post, not host.wide)
    assert red.gaps == [("host.post", pytest.approx(4.5e-6))]


def test_reduce_profile_without_annotation():
    p = profile()
    p.planes[0].lines[0].events = []
    assert reduce.reduce_profile(p) is None


def test_recorded_tpu_trace():
    """A v5e trace of four annotated calls of two small programs."""
    pd = reduce.load(os.path.join(DATA, "small_trace"))
    red = reduce.reduce_profile(pd)
    assert len(red.annotations) == 4
    assert list(red.busy) == ["/device:TPU:0"]
    busy = red.busy_s()
    assert 0 < busy < red.window_s
    assert red.ops and all(s > 0 for _, s in red.ops)
    assert sum(s for _, s in red.gaps) <= red.window_s - busy + 1e-9

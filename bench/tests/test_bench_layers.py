"""The per-layer readers of the program's spans (``bench/layers/*`` over
``bench/spans.py``): exact numbers from hand-built spans and a hand-built
trace reduction on a known clock offset, and nothing (``None``, never an
error) where the spans or the pairing with the trace are missing."""
from types import SimpleNamespace as NS

import pytest

from bench import harness
from bench.reduce import Reduction
from repro.telemetry.spans import SpanRecord

DECIDE = ("launch_ms.decide", "read_ms.decide", "reply_ms.decide",
          "d2h_reads.decide")
SIM = ("read_ms.sim", "post_ms.sim")


def read(metric, ctx):
    return harness.reader(metric)(ctx)


def rec(name, start_us, dur_us, sid, parent=None, root=None, **args):
    return SpanRecord(name, start_us, dur_us, 0, 1, args, span_id=sid,
                      parent_id=parent, root_id=sid if root is None else root)


def decision(root_id, t0, read_arrays=4):
    """One ``api.pack`` call starting at ``t0`` us on the span clock:
    put [t0+1, t0+2), run [t0+2, t0+4), read [t0+4, t0+14), reply
    [t0+14, t0+17); children first, as the tracer appends them."""
    kid = lambda name, off, dur, k, **a: rec(
        name, t0 + off, dur, root_id + k, root_id, root_id, **a)
    return [kid("pack.put", 1, 1, 1), kid("pack.run", 2, 2, 2),
            kid("pack.read", 4, 10, 3, arrays=read_arrays, bytes=412),
            kid("pack.reply", 14, 3, 4),
            rec("api.pack", t0, 18, root_id)]


def decide_ctx(**over):
    """Two traced decisions and a third untraced one.  Call 0 starts at
    5 us on the span clock and its annotation at 10,000 ns on the
    profile's (offset +5,000 ns); call 1 at 100 us and 40,000 ns (offset
    -60,000 ns).  The device is busy [13,000, 20,000) and
    [41,500, 45,000) ns."""
    spans = decision(10, 5.0) + decision(20, 100.0) + decision(
        30, 200.0, read_arrays=1)
    red = Reduction(window=(10_000, 60_000),
                    annotations=[(10_000, 30_000), (40_000, 60_000)],
                    busy={"/device:TPU:0": [(13_000, 20_000),
                                            (41_500, 45_000)]})
    ctx = {"trace": red, "window_spans": spans, "traced_items": 2,
           "setup_spans": [], "mix": {}, "config": {}, "driver": None}
    ctx.update(over)
    return ctx


def test_decide_readers_exact():
    ctx = decide_ctx()
    # on the profile's clock, call 0: put [11k, 12k) all idle; run
    # [12k, 14k) idle 1,000 ns; read [14k, 24k) idle 4,000.  Call 1: put
    # [41k, 42k) idle 500; run [42k, 44k) all busy; read [44k, 54k)
    # idle 9,000.
    assert read("launch_ms.decide", ctx) == pytest.approx(
        (2_000 + 500) / 2 / 1e6)
    assert read("read_ms.decide", ctx) == pytest.approx(
        (4_000 + 9_000) / 2 / 1e6)
    # over all three decisions of the window, traced or not
    assert read("reply_ms.decide", ctx) == pytest.approx(3e-3)
    assert read("d2h_reads.decide", ctx) == pytest.approx((4 + 4 + 1) / 3)


def test_sim_readers_exact():
    def call(root_id, t0, post_extra):
        kid = lambda name, off, dur, k, parent=root_id: rec(
            name, t0 + off, dur, root_id + k, parent, root_id)
        fsim = root_id + 1
        return [kid("fleet.dispatch", 1, 700_000, 2, fsim),
                kid("fleet.read", 700_001, 2_000, 3, fsim),
                kid("fleet.unpack", 702_001, 500, 4, fsim),
                kid("fleet.simulate", 1, 702_600, 1),
                kid("sim.summarize", 702_700, 1_000, 5),
                kid("sim.sketches", 703_700, 3_000 + post_extra, 6),
                kid("sim.incidents", 706_800, 4_000, 7),
                rec("api.simulate", t0, 712_000 + post_extra, root_id)]

    spans = call(100, 0.0, 0) + call(200, 1e6, 1_000)
    ctx = {"trace": None, "window_spans": spans, "traced_items": 1}
    assert read("read_ms.sim", ctx) == pytest.approx(2.0)
    assert read("post_ms.sim", ctx) == pytest.approx(
        (8_500 + 9_500) / 2 / 1e3)


@pytest.mark.parametrize("metric", DECIDE + SIM)
def test_reader_without_spans_reads_nothing(metric):
    assert read(metric, decide_ctx(window_spans=[])) is None
    # records of a program whose spans carry no ids
    bare = [NS(name=n, start_us=0.0, dur_us=1.0, call_index=0, tid=1,
               args={"arrays": 4})
            for n in ("pack.read", "pack.reply", "api.pack", "fleet.read",
                      "fleet.unpack", "api.simulate")]
    assert read(metric, decide_ctx(window_spans=bare)) is None


@pytest.mark.parametrize("metric", ("launch_ms.decide", "read_ms.decide"))
def test_idle_readers_refuse_a_failed_pairing(metric):
    assert read(metric, decide_ctx()) is not None
    assert read(metric, decide_ctx(trace=None)) is None
    # three annotations where the window traced two calls
    red = decide_ctx()["trace"]
    red.annotations.append((70_000, 90_000))
    assert read(metric, decide_ctx(trace=red)) is None
    # two annotations where the window traced one call
    assert read(metric, decide_ctx(traced_items=1)) is None
    # a call longer than its annotation: the pairing is wrong
    red = decide_ctx()["trace"]
    red.annotations[1] = (40_000, 50_000)
    assert read(metric, decide_ctx(trace=red)) is None


def test_new_readers_are_reported_in_their_cells():
    spec = harness.spec()
    for cell, metrics in (("omb100-decide-mbfp", DECIDE),
                          ("omb100-sim-packers", SIM),
                          ("keda16-sim-reactive", SIM)):
        _, layer = harness.metrics_of(spec, cell)
        assert set(metrics) <= {m["name"] for m in layer}

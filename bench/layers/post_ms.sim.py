"""post_ms.sim: mean milliseconds per ``api.simulate`` call of the window in
the program's host post-processing spans: ``fleet.unpack`` (per-scenario
slicing) and ``sim.summarize``, ``sim.sketches`` and ``sim.incidents``,
found under the call by their ``root_id``."""
from bench import spans

POST = ("fleet.unpack", "sim.summarize", "sim.sketches", "sim.incidents")


def read(ctx):
    return spans.per_call(ctx["window_spans"], "api.simulate", POST)

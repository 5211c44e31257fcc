"""read_ms.sim: mean milliseconds per ``api.simulate`` call of the window in
the program's ``fleet.read`` spans (the result's device-to-host reads,
after the ``fleet.dispatch`` that waits for the device)."""
from bench import spans


def read(ctx):
    return spans.per_call(ctx["window_spans"], "api.simulate",
                          ("fleet.read",))

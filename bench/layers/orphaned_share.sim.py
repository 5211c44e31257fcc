"""orphaned_share.sim: percent of the simulated partition-steps that a
crashed consumer left unread, the mean over the window's ``api.simulate``
calls of the ``orphaned`` counter on the program's ``fleet.read`` spans
over the call's policies x groups x T x N.  A program whose spans carry no
such counter reads nothing here."""
from bench import spans


def read(ctx):
    per_call = spans.per_call(
        [r for r in ctx["window_spans"]
         if r.name != "fleet.read" or "orphaned" in r.args],
        "api.simulate", ("fleet.read",), value=lambda r: r.args["orphaned"])
    if per_call is None:
        return None
    return per_call / ctx["driver"].work_per_call() * 100.0

"""d2h_reads.decide: device arrays read back per decision, the mean of the
``arrays`` counter on the program's ``pack.read`` spans over the window's
decisions."""
from bench import spans


def read(ctx):
    return spans.per_call(ctx["window_spans"], "api.pack", ("pack.read",),
                          value=lambda r: r.args.get("arrays", 0))

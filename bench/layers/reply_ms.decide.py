"""reply_ms.decide: mean milliseconds of the program's ``pack.reply`` span
(the reply's assignment and loads dicts and its R-score) over the
window's decisions."""
from bench import spans


def read(ctx):
    return spans.per_call(ctx["window_spans"], "api.pack", ("pack.reply",))

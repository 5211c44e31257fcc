"""read_ms.decide: milliseconds per traced decision inside the program's
``pack.read`` span (every device-to-host read of the reply) during which
the device ran nothing: the reads' own cost, net of the compute they wait
on."""
from bench import spans


def read(ctx):
    return spans.idle_ms(ctx, "api.pack", ("pack.read",))

"""launch_ms.decide: milliseconds per traced decision inside the program's
``pack.put`` and ``pack.run`` spans (the two host-to-device puts and the
packer's dispatch) during which the device ran nothing."""
from bench import spans


def read(ctx):
    return spans.idle_ms(ctx, "api.pack", ("pack.put", "pack.run"))

"""host_ms.decide: host milliseconds per traced decision, the benchmark's
span around the ``api.pack`` call minus the device busy time inside it."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.busy:
        return None
    host = [(e - s) / 1e9 - red.busy_in(s, e) for s, e in red.annotations]
    return sum(host) / len(host) * 1e3

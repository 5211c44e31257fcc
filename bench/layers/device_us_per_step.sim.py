"""device_us_per_step.sim: device busy microseconds per simulated step,
busy time of the busiest device in the traced calls over the steps they
simulated (calls x T)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.busy:
        return None
    steps = len(red.annotations) * int(ctx["mix"]["steps"])
    busy = red.busy_s(red.busiest())
    return busy / steps * 1e6 if busy > 0 else None

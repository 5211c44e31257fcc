"""idle.sim: the device's idle share of the traced window, in percent,
averaged over the cell's devices."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red.busy or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s)

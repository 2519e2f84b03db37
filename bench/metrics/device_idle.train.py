"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx["spec"]["traffic_mix"]["kind"] != "train":
        return None
    s = ctx["trace"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)

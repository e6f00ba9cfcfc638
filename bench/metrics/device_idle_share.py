"""Share of the traced window in which no operation ran on the device:
1 - (union of the device op intervals) / (window), in percent."""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])

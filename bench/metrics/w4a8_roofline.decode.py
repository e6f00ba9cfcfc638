"""Roofline share of the ``w4a8_matmul`` kernel inside the decode step
programs of the traced window (``costs.w4a8_roofline``)."""


def read(ctx):
    return ctx["costs"].w4a8_roofline(ctx["calls"], "decode", ctx["peaks"])

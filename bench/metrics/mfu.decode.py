"""Whole decode step's share of the chip's int8 peak, in percent: the
model operations of a decode step (2 x every linear's parameters including
the lm_head per decoded row, plus attention over each row's context), as
the mean over the steps that decoded in the traced window, over the mean
device time of a decode step program there (``decode_step_ms``).  Int8 is
the stated peak: the W4A8 linears run int8 x int8."""


def read(ctx):
    c, costs, cfg = ctx["counters"], ctx["costs"], ctx["cfg"]
    n, secs = ctx["programs"].get("decode", (0, 0.0))
    if n <= 0 or secs <= 0 or c["decode_steps"] <= 0:
        return None
    ops = (c["decode_rows"] * (costs.token_linear_ops(cfg)
                               + costs.head_ops(cfg))
           + costs.attention_ops(cfg, c["decode_ctx"])) / c["decode_steps"]
    return 100.0 * ops / (secs / n) / ctx["peaks"]["int8_ops"]

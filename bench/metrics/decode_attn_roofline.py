"""Roofline share of the paged ``quant_decode_attention`` kernel, in
percent: the bytes of int8 keys, fp8 values and f32 key scales and zero
points that a decode step's rows need for their contexts (plus each row's
f32 query and output), all layers, as the mean over the steps that decoded
in the traced window, over the HBM bandwidth, over the kernel's device
time per decode step program that ran wholly inside the window."""


def read(ctx):
    c, costs = ctx["counters"], ctx["costs"]
    n, _ = ctx["programs"].get("decode", (0, 0.0))
    spent = sum(secs for kernel, kind, _, secs in ctx["calls"]
                if kernel == "quant_decode_attention" and kind == "decode")
    if n <= 0 or spent <= 0 or c["decode_steps"] <= 0:
        return None
    nbytes = costs.decode_attention_bytes(ctx["cfg"], c["decode_ctx"],
                                          c["decode_rows"]) / c["decode_steps"]
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / (spent / n)

"""Whole prefill chunk's share of the chip's int8 peak, in percent: the
model operations of the prompt tokens prefilled in the traced window (2 x
every layer's linear parameters per token, attention over each completed
prompt's causal prefix, one lm_head row per completed prompt) per chunk
(``EngineStats.prefill_chunks``), over the mean device time of a prefill
chunk program that ran wholly inside the window."""


def read(ctx):
    c, costs, cfg = ctx["counters"], ctx["costs"], ctx["cfg"]
    n, secs = ctx["programs"].get("prefill", (0, 0.0))
    if n <= 0 or secs <= 0 or c["prefill_chunks"] <= 0:
        return None
    ops = (c["prefill_tokens"] * costs.token_linear_ops(cfg)
           + c["prompts_done"] * costs.head_ops(cfg)
           + costs.attention_ops(cfg, c["prefill_keys"])) / c["prefill_chunks"]
    return 100.0 * ops / (secs / n) / ctx["peaks"]["int8_ops"]

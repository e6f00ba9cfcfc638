"""Device time of prefill per thousand prompt tokens: the mean device time
of a prefill chunk program that ran wholly inside the traced window, over
the mean prompt tokens a chunk carried in the window (the growth of
``EngineStats.prefill_tokens`` over that of ``prefill_chunks``)."""


def read(ctx):
    n, secs = ctx["programs"].get("prefill", (0, 0.0))
    c = ctx["counters"]
    if n <= 0 or c["prefill_tokens"] <= 0 or c["prefill_chunks"] <= 0:
        return None
    return 1e6 * (secs / n) / (c["prefill_tokens"] / c["prefill_chunks"])

"""Device time of one decode step: the device time of the decode step
programs that ran wholly inside the traced window over their executions
(one per engine step that decodes, in a window without KV spills)."""


def read(ctx):
    n, secs = ctx["programs"].get("decode", (0, 0.0))
    if n <= 0:
        return None
    return 1e3 * secs / n

"""Device idle time per engine step, in ms, while the host is in sampling
(``engine.sample``): the traced window's idle time intersected with that
phase's spans, over the engine steps that began in the window
(``bench/engine_steps.py``)."""
from bench import engine_steps as ES


def read(ctx):
    return ES.step_idle_ms(ctx, "sample")

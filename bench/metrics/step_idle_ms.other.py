"""Device idle time per engine step, in ms, under none of the five phase
spans (the step's own time between its phases, and the time between
steps): the traced window's idle time less that under the phase spans,
over the engine steps that began in the window
(``bench/engine_steps.py``).  With the five phase metrics it adds up to
the window's idle time per step."""
from bench import engine_steps as ES


def read(ctx):
    return ES.step_idle_ms(ctx, "other")

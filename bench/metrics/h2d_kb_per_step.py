"""Host-to-device traffic of one engine step, in KB: the growth of the
engine's ``h2d_bytes`` counter (every host array a step hands to the
device) over the steps of the traced window, per step, / 1e3
(``bench/engine_steps.py``)."""
from bench import engine_steps as ES


def read(ctx):
    v = ES.per_step(ES.of(ctx), "h2d_bytes")
    return None if v is None else v / 1e3

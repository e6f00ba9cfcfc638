"""Padding of the paged KV pool on the device, in percent: 100 x (the
growth of the engine's ``kv_pool_device_bytes`` over that of
``kv_pool_logical_bytes`` - 1) over the steps of the traced window
(``bench/engine_steps.py``).  Each launch of a step program that carries
the pool adds the pool's bytes on the device, tile padding included, and
its bytes without padding.  A program without these counters gives
nothing."""
from bench import engine_steps as ES


def read(ctx):
    st = ES.of(ctx)
    if st is None or st.counters.get("kv_pool_logical_bytes", 0) <= 0:
        return None
    return 100.0 * (st.counters["kv_pool_device_bytes"]
                    / st.counters["kv_pool_logical_bytes"] - 1.0)

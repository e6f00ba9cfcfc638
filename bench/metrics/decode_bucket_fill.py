"""Share of the decode programs' rows that carried a live row, in
percent: the growth of the engine's ``decode_rows`` over that of
``decode_bucket_rows`` (rows of the decode programs launched, bucket
padding included) over the steps of the traced window
(``bench/engine_steps.py``)."""
from bench import engine_steps as ES


def read(ctx):
    st = ES.of(ctx)
    if st is None or st.counters.get("decode_bucket_rows", 0) <= 0:
        return None
    return 100.0 * st.counters["decode_rows"] / st.counters[
        "decode_bucket_rows"]

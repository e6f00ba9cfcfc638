"""Share of the paged decode attention's grid that holds keys, in
percent: the growth of the engine's ``decode_attn_pages`` (the pages one
layer's kernel computes over the rows of the decode programs launched)
over that of ``decode_attn_grid_pages`` (those rows x the page table's
width) over the steps of the traced window (``bench/engine_steps.py``).
The rest of the grid is skipped: no fetch, no compute."""
from bench import engine_steps as ES


def read(ctx):
    st = ES.of(ctx)
    if st is None or st.counters.get("decode_attn_grid_pages", 0) <= 0:
        return None
    return 100.0 * st.counters["decode_attn_pages"] / st.counters[
        "decode_attn_grid_pages"]

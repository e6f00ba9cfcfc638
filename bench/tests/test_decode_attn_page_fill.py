"""The ``decode_attn_page_fill`` reader: the growth of the engine's
``decode_attn_pages`` over that of ``decode_attn_grid_pages``, carried on
the step spans' arguments, and nothing from a program that lacks them."""
import pytest

from bench import engine_steps as ES
from bench import run as R
from bench.tests.test_engine_steps import synthetic

READ = "decode_attn_page_fill"


def _with_pages(pages, grid):
    w, engine, args = synthetic()
    for ev, p, g in zip(sorted(args, key=lambda e: e.start), pages, grid):
        args[ev].update(decode_attn_pages=p, decode_attn_grid_pages=g)
    return ES.reduce(w, engine, args)


def test_page_fill_reads_the_step_arguments():
    # 45 of the 256 grid pages launched between the two steps hold keys
    st = _with_pages([100, 145], [512, 768])
    assert st.counters["decode_attn_pages"] == 45
    assert st.counters["decode_attn_grid_pages"] == 256
    assert R.load_reader(READ)({"engine_steps": st}) == pytest.approx(
        100 * 45 / 256)


def test_no_decode_or_no_counters_reports_nothing():
    # no decode program launched in the window
    assert R.load_reader(READ)(
        {"engine_steps": _with_pages([100, 100], [512, 512])}) is None
    # a program whose step spans carry no such counters
    w, engine, args = synthetic()
    assert R.load_reader(READ)(
        {"engine_steps": ES.reduce(w, engine, args)}) is None
    assert R.load_reader(READ)({"engine_steps": None}) is None

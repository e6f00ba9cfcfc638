"""The closed batch: every request due at once, the same lengths for every
seed, prefilled in set-up, and a window in which the engine only decodes."""
import dataclasses
import re

import numpy as np

from bench import run as R
from bench import traffic
from bench.tests.conftest import TINY_BATCH, TINY_LIMIT, tiny_opts


def test_a_batch_is_due_at_once_with_the_same_lengths_for_every_seed():
    def sizes(tr):
        return (sorted(len(r.prompt) for r in tr.requests),
                sorted(r.max_new for r in tr.requests),
                sum(r.greedy for r in tr.requests))
    runs = [traffic.build(TINY_BATCH, {}, s, 10.0, 500, "b")
            for s in (1, 2, 2 ** 35 + 11)]
    n = TINY_BATCH["requests"]
    for tr in runs:
        assert tr.arrival == "batch" and tr.preroll_s == 0.0
        assert len(tr.requests) == n
        assert all(r.due_s == 0.0 for r in tr.requests)
        assert sizes(tr) == sizes(runs[0])
        assert sum(r.greedy for r in tr.requests) == round(
            TINY_BATCH["greedy_share"] * n)
    # the lengths are the stratified quantiles, in the seed's order
    q = traffic.quantiles(TINY_BATCH["prompt"], n)
    assert sorted(len(r.prompt) for r in runs[0].requests) == sorted(q)
    orders = [[len(r.prompt) for r in tr.requests] for tr in runs]
    assert orders[0] != orders[1]
    again = traffic.build(TINY_BATCH, {}, 2, 10.0, 500, "b")
    assert all((a.prompt == b.prompt).all() and a.max_new == b.max_new
               for a, b in zip(again.requests, runs[1].requests))


def test_a_batch_larger_than_the_slots_is_refused():
    big = dict(TINY_BATCH, requests=TINY_BATCH["engine"]["slots"] + 1)
    try:
        traffic.build(big, {}, 1, 10.0, 500, "b")
    except ValueError as e:
        assert "slots" in str(e)
    else:
        raise AssertionError("a batch larger than the slots was built")


def test_a_batch_window_holds_no_prefill(capsys):
    opts = dataclasses.replace(
        tiny_opts(2 ** 33 + 5), mix_spec=TINY_BATCH,
        own={"limits": {"served_logit_gap": TINY_LIMIT}},
        metrics=[{"name": "setup_s", "unit": "s"},
                 {"name": "itl_p95_ms", "unit": "ms"}])
    res = R.run(opts)
    err = capsys.readouterr().err
    assert res["correct"], res["checks"]
    assert res["attempted"] == TINY_BATCH["requests"]
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
    # nothing prefilled inside the window, and set-up holds the prefill
    assert re.search(r"prefill chunks in the window 0\b", err), err[-2000:]
    prefill_s = float(re.search(r"prefilled in ([\d.e-]+) s", err).group(1))
    assert 0 < prefill_s < res["metrics"]["setup_s"]["value"]
    assert np.isfinite(res["metrics"]["itl_p95_ms"]["value"])

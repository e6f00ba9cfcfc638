"""The serving loop's own spans and counters: every step is an
``engine.step`` profiler span tiled in order by its phase spans, the step
counters match what the prompts and tokens imply, host-to-device traffic
is counted per transfer, and each request carries arrival, admission and
first-token times."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.serving import engine as E
from repro.serving import gateway as G
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

PHASES = ("engine.admit", "engine.prefill", "engine.sample",
          "engine.commit", "engine.decode")


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    cfg = registry.reduced(registry.get("qwen2-7b"))
    return E.build_engine(cfg, max_seq=64,
                          flash_dir=str(tmp_path_factory.mktemp("flash")))


@pytest.fixture(scope="module")
def loop(engine):
    """One warmed loop for the module: its step programs compile once,
    outside any trace."""
    lp = E.EngineLoop(engine, max_slots=2, prefill_chunk=8)
    lp.warmup()
    assert lp._chunk_sizes() == (8,)
    return lp


def _requests(uid0, lens, news, seed):
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt_tokens=rng.integers(1, 500, n).tolist(),
                    max_new_tokens=m,
                    sampling=SM.SamplingParams(temperature=0.0))
            for i, (n, m) in enumerate(zip(lens, news))]


def _engine_events(log_dir):
    """(name, start_ns, end_ns, args) of every ``engine.*`` host event."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_every_step_span_holds_its_phases_in_order(engine, loop,
                                                    tmp_path):
    # one prompt longer than the per-step budget, so some steps prefill
    # and decode together, and more requests than slots, so some admit
    for r in _requests(0, [30, 7, 5], [3, 4, 2], seed=1):
        loop.submit(r)
    budget, loop.prefill_token_budget = loop.prefill_token_budget, 16
    c0 = engine.stats.step_counters()
    n0 = loop._step_no
    jax.profiler.start_trace(str(tmp_path))
    while loop.has_work():
        loop.step()
    jax.profiler.stop_trace()
    loop.prefill_token_budget = budget
    evs = _engine_events(str(tmp_path))
    assert {e[0] for e in evs} == {"engine.step", *PHASES}
    steps = [e for e in evs if e[0] == "engine.step"]
    assert len(steps) == loop._step_no - n0
    # the step number and the counters as they stood when the step began
    # are profiler arguments, not part of the name
    assert [s[3]["step_num"] for s in steps] == list(range(n0, loop._step_no))
    assert {k: steps[0][3][k] for k in c0} == c0
    for a, b in zip(steps, steps[1:]):
        assert b[3]["steps"] == a[3]["steps"] + 1
        assert all(b[3][k] >= a[3][k] for k in c0)
    phases = [e for e in evs if e[0] in PHASES]
    held = 0
    for name, lo, hi, _ in steps:
        inner = [p for p in phases if lo <= p[1] and p[2] <= hi]
        held += len(inner)
        order = [PHASES.index(p[0]) for p in inner]
        assert order == sorted(set(order)), [p[0] for p in inner]
        assert order[0] == 0                      # every step admits
        for p, q in zip(inner, inner[1:]):
            assert p[2] <= q[1]                   # no overlap
    assert held == len(phases)                    # none outside a step


def test_counters_match_the_prompts_and_tokens(engine, loop, monkeypatch):
    lens, news = [5, 8, 7, 3], [5, 3, 4, 6]
    looked_up = []
    lookup = engine.embedding.lookup

    def spy(ids):
        rows = lookup(ids)
        looked_up.append(rows.shape[:-1])
        return rows
    monkeypatch.setattr(engine.embedding, "lookup", spy)
    for r in _requests(10, lens, news, seed=2):
        loop.submit(r)
    s = engine.stats
    c0 = s.step_counters()
    b0, t0, ch0 = s.h2d_bytes, s.h2d_transfers, s.prefill_chunks
    timers = (s.prefill_s, s.decode_s)
    prefill_phases = 0
    while loop.has_work():
        ch = s.prefill_chunks
        loop.step()
        prefill_phases += s.prefill_chunks > ch
    c = {k: v - c0[k] for k, v in s.step_counters().items()}
    # the token after the k-th (k < m) is decoded at position n + k - 1
    # and attends over n + k keys; the last token ends the request
    assert c["decode_rows"] == sum(m - 1 for m in news)
    assert c["decode_ctx_keys"] == sum(n + k for n, m in zip(lens, news)
                                       for k in range(1, m))
    assert c["prompts_completed"] == len(lens)
    assert c["prefill_causal_keys"] == sum(n * (n + 1) // 2 for n in lens)
    assert c["decode_bucket_rows"] >= c["decode_rows"]
    assert 0 < c["decode_steps"] <= c["steps"]
    # every looked-up embedding row crosses as bf16: chunk tokens
    # (padded to the chunk size) and decode bucket rows (padding too)
    d = engine.cfg.d_model
    tokens = sum(int(np.prod(shape)) for shape in looked_up)
    chunks = s.prefill_chunks - ch0
    assert tokens == 8 * chunks + c["decode_bucket_rows"]
    embed_bytes = tokens * d * 2
    # the rest: a page table per decode dispatch and per prefill phase,
    # a bool mask and an int32 slot index per bucket row, three int32
    # scalars per chunk, one int32 slot per finished request
    table = loop.pool.table.nbytes
    rest = (table * (c["decode_steps"] + prefill_phases)
            + 5 * c["decode_bucket_rows"] + 12 * chunks + 4 * len(lens))
    assert c["h2d_bytes"] == s.h2d_bytes - b0 == embed_bytes + rest
    assert c["h2d_transfers"] == s.h2d_transfers - t0 == (
        4 * c["decode_steps"] + prefill_phases + 4 * chunks + len(lens))
    # the loop keeps no phase timers: those are Engine.generate's
    assert (s.prefill_s, s.decode_s) == timers


def test_attention_page_counters_match_the_launched_rows(engine):
    """``decode_attn_pages`` counts, over the rows of every decode program
    launched (bucket padding included), the pages a row of length n
    computes, ceil(n / page); ``decode_attn_grid_pages`` the rows x the
    table's width.  The lengths here are read from the device positions
    each launch was handed."""
    lp = E.EngineLoop(engine, max_slots=4, prefill_chunk=8)
    ps, ppr = lp.geom.page_size, lp.geom.pages_per_row
    assert ppr > 1
    launched = []
    decode_b = lp._decode_b

    def spy(params, embeds, cache, lora, act, slot_idx, logits):
        launched.append(np.asarray(cache["pos"])[np.asarray(slot_idx)] + 1)
        return decode_b(params, embeds, cache, lora, act, slot_idx, logits)
    spy._cache_size = decode_b._cache_size   # read by compile_events()
    lp._decode_b = spy
    for r in _requests(40, [5, 20, 33], [4, 7, 9], seed=4):
        lp.submit(r)
    c0 = engine.stats.step_counters()
    while lp.has_work():
        lp.step()
    c = {k: v - c0[k] for k, v in engine.stats.step_counters().items()}
    assert launched and any(len(n) == 4 for n in launched)  # a padded row
    assert max(int(n.max()) for n in launched) > 2 * ps
    assert c["decode_attn_pages"] == sum(
        int(np.minimum(-(-n // ps), ppr).sum()) for n in launched)
    assert c["decode_bucket_rows"] == sum(len(n) for n in launched)
    assert c["decode_attn_grid_pages"] == c["decode_bucket_rows"] * ppr
    assert 0 < c["decode_attn_pages"] < c["decode_attn_grid_pages"]


def test_requests_carry_arrival_admission_and_first_token(engine, loop):
    rng = np.random.default_rng(3)
    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=4)
    n0 = len(engine.stats.requests)
    # the service closes the module's loop: this test uses it last
    with G.EngineService(loop, warmup=False) as svc:
        # more requests than slots: the later ones wait in the queue
        streams = [svc.submit(rng.integers(1, 500, 6).tolist(), sp)
                   for _ in range(5)]
        for st in streams:
            st.collect(timeout=120.0)
        snap = svc.stats_snapshot()
    recs = {r.uid: r for r in engine.stats.requests[n0:]}
    for st in streams:
        req = st.request
        assert 0 < req.arrival_t <= req.admitted_t <= req.first_token_t \
            <= req.finish_t
        rec = recs[req.uid]
        assert rec.queue_s == pytest.approx(req.admitted_t - req.arrival_t)
        assert rec.ttft_s == pytest.approx(req.first_token_t
                                           - req.arrival_t)
    assert max(r.queue_s for r in recs.values()) > 0
    # /v1/stats: the step counters and the queue wait, no loop timers
    for k in (*E.EngineStats.STEP_COUNTERS, "queue_wait_p50_s",
              "queue_wait_p95_s"):
        assert k in snap, k
    assert "prefill_tps" not in snap and "decode_tps" not in snap
    assert snap["steps"] >= snap["decode_steps"] > 0
    assert snap["queue_wait_p95_s"] >= snap["queue_wait_p50_s"] >= 0.0

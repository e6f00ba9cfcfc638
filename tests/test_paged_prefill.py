"""Unified paged prefill: chunked prompt ingestion + refcounted prefix
sharing on the KV pool.

Acceptance for the refactor: chunked paged prefill is bitwise-equal
(greedy tokens) to the dense reference engine on mixed traces with no
dense ``max_seq`` transient at join; alloc/adopt/free sequences never
double-free a page and residency stays exact; a freed-then-reused prefix
is bitwise equal to a cold prefill; the shared-prefix path saves pages at
equal output.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import kv_cache as kvc
from repro.core import kv_pool as KP
from repro.core.precision import DEFAULT_POLICY
from repro.kernels import flash_prefill as FP
from repro.models import transformer as T
from repro.runtime import dispatch as RD
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import ContinuousScheduler, Request


# ---------------------------------------------------------------------------
# allocator: refcount invariants
# ---------------------------------------------------------------------------

def _check_invariants(mgr: KP.KVPoolManager):
    """Residency accounting must stay exact at every transition."""
    geom = mgr.geom
    free = set(mgr._free)
    assert len(free) == len(mgr._free), "free list holds a duplicate page"
    held = [p for row in mgr.row_pages for p in row]
    indexed = set(mgr._chain_of_page)
    for p in free:
        assert mgr.refcount[p] == 0, f"free page {p} still referenced"
        assert p not in indexed
    for p in range(geom.num_pages):
        refs = held.count(p) + (1 if p in indexed else 0)
        assert mgr.refcount[p] == refs, (p, mgr.refcount[p], refs)
        assert (mgr.refcount[p] == 0) == (p in free)
    assert mgr.pages_in_use + mgr.free_pages == geom.num_pages
    assert mgr.available_pages == mgr.free_pages + mgr.reclaimable_pages


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_refcount_invariants_random_walk(seed):
    """Property: random alloc/adopt/register/ensure/free sequences never
    double-free a page, never leak one, and keep residency exact."""
    rng = np.random.default_rng(seed)
    geom = KP.PoolGeometry(page_size=4, num_pages=12, pages_per_row=6)
    mgr = KP.KVPoolManager(geom, num_slots=4)
    prompts = {}                      # row -> token ids (while allocated)
    vocab = [list(rng.integers(1, 50, int(rng.integers(1, 20))))
             for _ in range(3)]       # small prompt set => real collisions
    for _ in range(120):
        op = rng.integers(0, 5)
        row = int(rng.integers(0, 4))
        if op == 0 and not mgr.row_pages[row]:            # alloc (maybe adopt)
            toks = vocab[int(rng.integers(0, len(vocab)))]
            if mgr.alloc_row(row, len(toks), token_ids=toks):
                prompts[row] = toks
                mgr.row_pos[row] = len(toks)
        elif op == 1 and mgr.row_pages[row]:              # register prefix
            mgr.register_prefix(row, prompts[row])
        elif op == 2 and 0 < len(mgr.row_pages[row]) < geom.pages_per_row:
            mgr.ensure(row, len(mgr.row_pages[row]) * geom.page_size)
            mgr.row_pos[row] = len(mgr.row_pages[row]) * geom.page_size
        elif op == 3 and mgr.row_pages[row]:              # free (refcount dec)
            mgr.free_row(row)
            prompts.pop(row, None)
        elif op == 4 and mgr.row_pages[row]:              # cold page -> Flash
            cands = mgr.cold_pages(row)
            if cands:
                mgr.spill_page(row, cands[0])
        _check_invariants(mgr)
    for row in range(4):
        if mgr.row_pages[row]:
            mgr.free_row(row)
        _check_invariants(mgr)
    # after all rows freed, only index pins may keep pages resident
    assert mgr.pages_in_use == len(mgr._chain_of_page)


def test_adoption_caps_before_last_token_and_survives_eos():
    """The index never hands out the page holding a prompt's final token
    (its logits must be computed), and indexed pages survive free_row."""
    geom = KP.PoolGeometry(page_size=4, num_pages=8, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, num_slots=2)
    toks = list(range(1, 13))                 # 12 tokens = 3 full pages
    assert mgr.alloc_row(0, len(toks), token_ids=toks)
    assert mgr.row_shared[0] == 0
    mgr.register_prefix(0, toks)
    first_pages = list(mgr.row_pages[0])
    freed = mgr.free_row(0)                   # EOS: pins keep prefix pages
    assert freed == 0 and mgr.pages_in_use == 3
    # an identical prompt adopts at most the pages covering tokens [0, 11)
    assert mgr.probe_shared_pages(toks) == 2
    assert mgr.alloc_row(1, len(toks), token_ids=toks)
    assert mgr.row_shared[1] == 8
    assert mgr.row_pages[1][:2] == first_pages[:2]
    assert mgr.row_pages[1][2] != first_pages[2]


def test_index_pins_evicted_under_pressure():
    geom = KP.PoolGeometry(page_size=4, num_pages=4, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, num_slots=2)
    toks = list(range(8))
    assert mgr.alloc_row(0, 8, token_ids=toks)
    mgr.register_prefix(0, toks)
    mgr.free_row(0)
    assert mgr.free_pages == 2 and mgr.available_pages == 4
    # a 4-page allocation must reclaim both pins
    assert mgr.alloc_row(1, 16)
    assert mgr.prefix_evictions == 2 and not mgr._chain_of_page
    _check_invariants(mgr)


def test_same_step_admissions_never_oversubscribe_adopted_pins():
    """An admission that adopts index-only pins converts them from
    reclaimable to in-use, so it must be charged their full footprint —
    otherwise a same-step co-admission could pass ``_fits`` and then die
    in ``alloc_row`` (admission promised pages the pool cannot produce).
    Invariant: every request admit() returns can actually allocate."""
    geom = KP.PoolGeometry(page_size=4, num_pages=4, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, num_slots=2)
    sched = ContinuousScheduler(2, 16, pool=mgr)
    head = list(range(1, 14))                 # 13 toks: adopts 3 full pages
    assert mgr.alloc_row(0, 13, token_ids=head)
    mgr.register_prefix(0, head)
    mgr.free_row(0)                           # 3 pinned (rc==1) + 1 free
    a = Request(uid=0, prompt_tokens=list(head), max_new_tokens=2)
    b = Request(uid=1, prompt_tokens=list(range(20, 24)), max_new_tokens=2)
    sched.submit(a)
    sched.submit(b)
    admitted = sched.admit()
    for slot, req in admitted:
        assert mgr.alloc_row(slot, req.length,
                             token_ids=req.prompt_tokens,
                             ), f"admit() oversubscribed for uid={req.uid}"
    # index-only pins are availability, not a free lunch: a (3 adopted
    # pins + 1 fresh = the whole pool) and b (2 pages) cannot both fit
    assert len(admitted) == 1
    _check_invariants(mgr)


def test_admission_discounts_pages_held_by_running_rows():
    geom = KP.PoolGeometry(page_size=4, num_pages=6, pages_per_row=6)
    mgr = KP.KVPoolManager(geom, num_slots=2)
    sched = ContinuousScheduler(2, 24, pool=mgr)
    toks = list(range(1, 17))                 # 16 tokens = 4 full pages
    assert mgr.alloc_row(0, 16, token_ids=toks)
    mgr.register_prefix(0, toks)              # row 0 still running: rc == 2
    req = Request(uid=1, prompt_tokens=toks + [99], max_new_tokens=2)
    # 18 tokens span 5 pages; 4 are resident under the running row ->
    # the admission is charged only the single fresh page
    assert sched.need_pages(req) == 1
    assert sched._fits(req)
    # once row 0 frees, the pins (rc==1) become plain availability and
    # the same request is charged in full — but still fits (5 <= 2+4)
    mgr.free_row(0)
    assert sched.need_pages(req) == 5
    assert sched._fits(req)


# ---------------------------------------------------------------------------
# paged prompt append + prefill attention primitives
# ---------------------------------------------------------------------------

def test_append_paged_prompt_bytes_match_dense():
    """A chunked prompt append through the table stores byte-identical
    quantized KV to the dense per-token append."""
    B, Hkv, D, max_seq, ps, t = 1, 2, 64, 64, 16, 37
    geom = KP.PoolGeometry(page_size=ps, num_pages=8, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, B)
    pool = KP.init_paged_layer(geom, Hkv, D, batch=B)
    dense = kvc.init_layer_cache(B, max_seq, Hkv, D, per_row=True)
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, t, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, t, Hkv, D)), jnp.float32)
    assert mgr.alloc_row(0, t)
    table = mgr.device_table()
    for i in range(t):      # dense: per-token, matching the decode path
        dense = kvc.append(dense, k[:, i:i + 1], v[:, i:i + 1],
                           jnp.asarray([i], jnp.int32))
    for s0, c in ((0, 16), (16, 16), (32, 8)):      # chunked, padded tail
        kc = jnp.zeros((1, c, Hkv, D)).at[:, :min(c, t - s0)].set(
            k[:, s0:s0 + c])
        vc = jnp.zeros((1, c, Hkv, D)).at[:, :min(c, t - s0)].set(
            v[:, s0:s0 + c])
        pool = KP.append_paged_prompt(pool, kc, vc, jnp.int32(s0),
                                      table_row=table[0])
    kq, ks, kz, vv = KP.gather_pages(pool, table)
    assert np.array_equal(np.asarray(kq[:, :t]), np.asarray(dense.k_q[:, :t]))
    assert np.array_equal(np.asarray(ks[:, :t]),
                          np.asarray(dense.k_scale[:, :t]))
    assert np.array_equal(np.asarray(vv[:, :t]).view(np.uint8),
                          np.asarray(dense.v[:, :t]).view(np.uint8))


def _chunk_pool(B=1, Hkv=2, D=64, max_seq=64, ps=16, t=37, seed=3):
    geom = KP.PoolGeometry(page_size=ps, num_pages=8, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, B)
    pool = KP.init_paged_layer(geom, Hkv, D, batch=B)
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(B, t, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, t, Hkv, D)), jnp.float32)
    assert mgr.alloc_row(0, t)
    table = mgr.device_table()
    pool = KP.append_paged_prompt(pool, k, v, jnp.int32(0),
                                  table_row=table[0])
    return pool, table, rng


def test_paged_prefill_chunking_is_bitwise_invariant():
    """Reference acceptance: the chunk partition never changes a query's
    output — one 37-token prefill == 16+16+5 chunks, bit for bit."""
    t = 37
    pool, table, rng = _chunk_pool(t=t)
    qh = jnp.asarray(rng.normal(size=(1, t, 4, 64)), jnp.float32) / 8.0
    disp = RD.Dispatcher(backend="reference")
    mono = disp.paged_prefill_attention(qh, pool, table, jnp.int32(0),
                                        DEFAULT_POLICY)
    parts = []
    for s0, c in ((0, 16), (16, 16), (32, 5)):
        parts.append(disp.paged_prefill_attention(
            qh[:, s0:s0 + c], pool, table, jnp.int32(s0), DEFAULT_POLICY))
    chunked = jnp.concatenate(parts, axis=1)
    assert np.array_equal(np.asarray(mono, np.float32),
                          np.asarray(chunked, np.float32))


def test_paged_prefill_kernel_matches_reference():
    """The scalar-prefetched Pallas kernel (interpret) tracks the
    reference gather path; the dispatcher records no fallback."""
    t = 37
    pool, table, rng = _chunk_pool(t=t)
    qh = jnp.asarray(rng.normal(size=(1, t, 4, 64)), jnp.float32) / 8.0
    ref = RD.Dispatcher(backend="reference").paged_prefill_attention(
        qh, pool, table, jnp.int32(0), DEFAULT_POLICY)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.paged_prefill_attention(qh, pool, table, jnp.int32(0),
                                       DEFAULT_POLICY)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    # mid-prompt chunk offset: the kernel's causal mask follows pos0
    got2 = FP.paged_flash_prefill_attention(
        qh[:, 16:32], pool.k_q, pool.k_scale, pool.k_zero, pool.v,
        table, jnp.asarray([16], jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(got2, np.float32),
                               np.asarray(ref[:, 16:32], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_int4_paged_prefill_falls_back_recorded():
    geom = KP.PoolGeometry(page_size=16, num_pages=8, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, 1)
    pool = KP.init_paged_layer(geom, 2, 64, batch=1, key_bits=4)
    rng = np.random.default_rng(7)
    k = jnp.asarray(rng.normal(size=(1, 20, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 20, 2, 64)), jnp.float32)
    assert mgr.alloc_row(0, 20)
    table = mgr.device_table()
    pool = KP.append_paged_prompt(pool, k, v, jnp.int32(0),
                                  table_row=table[0])
    qh = jnp.asarray(rng.normal(size=(1, 20, 4, 64)), jnp.float32) / 8.0
    disp = RD.Dispatcher(backend="interpret")
    got = disp.paged_prefill_attention(qh, pool, table, jnp.int32(0),
                                       DEFAULT_POLICY)
    ref = RD.Dispatcher(backend="reference").paged_prefill_attention(
        qh, pool, table, jnp.int32(0), DEFAULT_POLICY)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(ref, np.float32))
    assert any(op == "paged_prefill_attention" and "int4" in why
               for op, _, why in disp.fallbacks), disp.fallbacks


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    cfg = registry.reduced(registry.get("qwen2-7b"))
    return E.build_engine(cfg, max_seq=64,
                          flash_dir=str(tmp_path_factory.mktemp("flash")))


@pytest.fixture(scope="module")
def ref_engine(tmp_path_factory):
    cfg = registry.reduced(registry.get("qwen2-7b"))
    return E.build_engine(cfg, max_seq=64,
                          flash_dir=str(tmp_path_factory.mktemp("flash2")))


def _reference(ref_engine, req):
    out = ref_engine.generate(
        [Request(uid=req.uid, prompt_tokens=list(req.prompt_tokens),
                 max_new_tokens=req.max_new_tokens)],
        SM.SamplingParams(temperature=0.0,
                          max_new_tokens=req.max_new_tokens))
    return out[0].generated


def test_no_dense_transient_on_join():
    """Structural acceptance: the join path is gone — EngineLoop owns no
    whole-prompt prefill jit and the dense scatter helpers no longer
    exist.  Prompt KV can only reach the pool through pages."""
    assert not hasattr(T, "scatter_request_paged")
    assert not hasattr(T, "scatter_request")
    assert not hasattr(KP, "scatter_pages")
    assert not hasattr(E.EngineLoop, "_prefill_impl")


def test_chunk_budget_invariance(engine, ref_engine):
    """Greedy output is independent of chunk size and per-step prefill
    budget — the knob trades TTFT for decode interleaving, never
    tokens."""
    rng = np.random.default_rng(31)
    mk = lambda: [Request(uid=i, prompt_tokens=list(p), max_new_tokens=5)
                  for i, p in enumerate(
                      [list(rng.integers(1, 400, n)) for n in (23, 37, 9)])]
    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=5)
    base = mk()
    want = [_reference(ref_engine, r) for r in base]
    for chunk, budget in ((64, 64), (16, 16), (8, 24)):
        loop = E.EngineLoop(engine, max_slots=2, prefill_chunk=chunk,
                            prefill_token_budget=budget)
        out = loop.run([Request(uid=r.uid,
                                prompt_tokens=list(r.prompt_tokens),
                                max_new_tokens=5) for r in base], sp)
        loop.close()
        for r, w in zip(sorted(out, key=lambda r: r.uid), want):
            assert r.generated == w, (chunk, budget, r.uid)


@pytest.mark.slow
def test_mixed_trace_24_requests_bitwise_acceptance(engine, ref_engine):
    """Acceptance: a mixed 24-request trace through the unified step
    (staggered arrivals, shared system prompt for a third of the trace,
    slot reuse) reproduces the dense reference engine token for token."""
    rng = np.random.default_rng(4)
    sysp = list(rng.integers(1, 400, 19))
    reqs = []
    for i in range(24):
        tail = list(rng.integers(1, 400, int(rng.integers(2, 20))))
        prompt = (sysp + tail)[:40] if i % 3 == 0 else \
            list(rng.integers(1, 400, int(rng.integers(4, 40))))
        reqs.append(Request(uid=i, prompt_tokens=prompt,
                            max_new_tokens=int(rng.integers(2, 8))))
    loop = E.EngineLoop(engine, max_slots=4, prefill_chunk=16,
                        prefill_token_budget=32)
    arrivals = [int(a) for a in sorted(rng.integers(0, 30, 24))]
    out = loop.run(reqs, SM.SamplingParams(temperature=0.0,
                                           max_new_tokens=8),
                   arrivals=arrivals)
    assert loop.pool.prefix_hits > 0          # the shared head was adopted
    loop.close()
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid


def test_shared_prefix_saves_pages_at_equal_output(engine, ref_engine):
    """A common system prompt is prefilled once: later requests adopt its
    pages (>0 pages saved) and still match the unshared loop exactly."""
    rng = np.random.default_rng(12)
    sysp = list(rng.integers(1, 400, 33))
    mk = lambda: [Request(uid=i,
                          prompt_tokens=sysp + list(rng2.integers(1, 400, 4)),
                          max_new_tokens=4)
                  for i, rng2 in ((i, np.random.default_rng(100 + i))
                                  for i in range(4))]
    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=4)
    shared = E.EngineLoop(engine, max_slots=2)
    out_s = shared.run(mk(), sp)
    cold = E.EngineLoop(engine, max_slots=2, prefix_sharing=False)
    out_c = cold.run(mk(), sp)
    assert shared.pool.prefix_hits > 0
    assert cold.pool.prefix_hits == 0
    assert engine.stats.shared_prompt_tokens > 0
    for a, b in zip(out_s, out_c):
        assert a.generated == b.generated == _reference(ref_engine, a), a.uid
    shared.close()
    cold.close()


def test_freed_then_reused_prefix_bitwise_equals_cold_prefill(engine,
                                                              ref_engine):
    """A prefix registered by a finished request, freed at EOS (refcount
    decrement) and adopted by a later identical prompt yields bitwise the
    same greedy tokens as a cold engine that never shared anything."""
    rng = np.random.default_rng(40)
    prompt = list(rng.integers(1, 400, 29))
    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=6)
    loop = E.EngineLoop(engine, max_slots=2)
    first = Request(uid=0, prompt_tokens=list(prompt), max_new_tokens=6)
    second = Request(uid=1, prompt_tokens=list(prompt), max_new_tokens=6)
    # the second request arrives only after the first fully finished —
    # its prefix pages must have survived the EOS reclaim via the index
    out = loop.run([first, second], sp, arrivals=[0, 20])
    assert loop.pool.prefix_hits > 0
    assert out[1].generated == out[0].generated
    assert out[1].generated == _reference(ref_engine, out[1])
    loop.close()


def test_adapter_salts_isolate_prefix_sharing(engine):
    """Same tokens under different LoRA adapters produce different KV —
    the chain hash is salted by the adapter so they never share pages;
    the same adapter still shares."""
    rng = np.random.default_rng(2)
    cfg = engine.cfg
    hd = cfg.resolved_head_dim
    engine.load_adapter("salt-test", (
        rng.normal(size=(cfg.d_model, 4)).astype(np.float32) * 0.3,
        rng.normal(size=(4, cfg.num_heads * hd)).astype(np.float32) * 0.3), (
        rng.normal(size=(cfg.d_model, 4)).astype(np.float32) * 0.3,
        rng.normal(size=(4, cfg.num_kv_heads * hd)).astype(np.float32) * 0.3))
    try:
        prompt = list(rng.integers(1, 400, 20))
        sp = SM.SamplingParams(temperature=0.0, max_new_tokens=4)
        loop = E.EngineLoop(engine, max_slots=2)
        base = Request(uid=0, prompt_tokens=list(prompt), max_new_tokens=4)
        styled = Request(uid=1, prompt_tokens=list(prompt), max_new_tokens=4,
                         adapter="salt-test")
        loop.run([base, styled], sp, arrivals=[0, 10])
        assert loop.pool.prefix_hits == 0      # different salt: no adoption
        assert base.generated != styled.generated
        styled2 = Request(uid=2, prompt_tokens=list(prompt),
                          max_new_tokens=4, adapter="salt-test")
        loop.run([styled2], sp)
        assert loop.pool.prefix_hits > 0       # same salt: adopts
        assert styled2.generated == styled.generated
        loop.close()
    finally:
        engine.lora_q.unload("salt-test")
        engine.lora_v.unload("salt-test")


def test_page_pressure_spills_prefilling_row_and_resumes(engine,
                                                         ref_engine):
    """A row evicted mid-prefill under page pressure spills its written
    pages and resumes from the last chunk boundary on re-admission (no
    prompt work forfeited) — and the output stays bitwise-equal to the
    dense reference.  The victim selection is driven directly (organic
    pressure timing depends on the trace; the spill path itself is what
    this test pins down)."""
    loop = E.EngineLoop(engine, max_slots=2,
                        prefill_chunk=8, prefill_token_budget=8)
    rng = np.random.default_rng(13)
    sp = SM.SamplingParams(temperature=0.0)
    a = Request(uid=0, prompt_tokens=list(rng.integers(1, 400, 8)),
                max_new_tokens=26, sampling=sp)
    b = Request(uid=1, prompt_tokens=list(rng.integers(1, 400, 30)),
                max_new_tokens=4, sampling=sp)
    loop.submit(a)
    loop.submit(b)
    for _ in range(50):
        loop.step()
        st = next((s for s in loop._prefilling.values()
                   if s["req"] is b), None)
        if st is not None and st["next"] > 0:
            break
    else:
        pytest.fail("b never reached a mid-prefill chunk boundary")
    loop._spill_prefilling_row(b)
    assert b.preemptions == 1
    assert b.resume_prefill, "mid-prefill victims resume, not restart"
    for _ in range(400):
        if a.done and b.done:
            break
        loop.step()
    assert a.done and b.done
    assert not b.resume_prefill          # the flag clears on resume
    for r in (a, b):
        assert r.generated == _reference(ref_engine, r), r.uid
    loop.close()

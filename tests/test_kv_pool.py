"""Paged KV pool: geometry, allocator, bitwise decode parity, spill tier.

Acceptance for the paged refactor: the paged decode is *bitwise identical*
to the dense-cache decode on the reference backend AND on the interpret
(Pallas kernel) backend, page-accounting admission beats slot-reservation
accounting at the same DRAM budget, and preempt-under-page-pressure
resume stays bitwise-equal to uninterrupted greedy decoding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.core import hybrid_storage as HS
from repro.core import kv_cache as kvc
from repro.core import kv_pool as KP
from repro.core.precision import DEFAULT_POLICY, PrecisionPolicy
from repro.kernels import quant_attention as QA
from repro.models.attention import decode_attention_ref
from repro.runtime import dispatch as RD
from repro.runtime import plan as RP
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# geometry + allocator
# ---------------------------------------------------------------------------

def test_page_size_lane_aligned_divisor():
    for max_seq in (32, 48, 64, 128, 256, 2048):
        ps = RP.kv_page_size(max_seq)
        assert max_seq % ps == 0
        assert ps & (ps - 1) == 0            # power of two
    assert RP.kv_page_size(2048) == RP.LANE  # long contexts hit the lane cap
    assert RP.kv_page_size(64) == 16         # short ones still page


def test_plan_owns_pool_geometry():
    cfg = registry.reduced(registry.get("qwen2-7b"))
    params = {}
    plan = RP.build_plan(cfg, params)
    geom = plan.kv_pool_geometry(cfg, 64, 4)
    assert geom.max_seq == 64
    assert geom.num_pages == 4 * geom.pages_per_row   # default: full budget
    # a byte budget shrinks the pool, clamped to at least one full row
    pb = RP.kv_page_bytes(cfg, geom.page_size)
    tight = plan.kv_pool_geometry(cfg, 64, 4, dram_budget_bytes=6 * pb)
    assert tight.num_pages == 6
    tiny = plan.kv_pool_geometry(cfg, 64, 4, dram_budget_bytes=1)
    assert tiny.num_pages == tiny.pages_per_row


def test_manager_alloc_ensure_free_reclaim():
    geom = KP.PoolGeometry(page_size=16, num_pages=6, pages_per_row=4)
    mgr = KP.KVPoolManager(geom, num_slots=2)
    assert mgr.alloc_row(0, 20)              # 2 pages
    assert mgr.pages_held(0) == 2 and mgr.free_pages == 4
    assert (mgr.table[0, :2] >= 0).all() and mgr.table[0, 2] == geom.trash_page
    # allocate-on-append: same page is a no-op, boundary takes a new page
    assert mgr.ensure(0, 20) and mgr.pages_held(0) == 2
    assert mgr.ensure(0, 32) and mgr.pages_held(0) == 3
    assert mgr.alloc_row(1, 40)              # 3 pages -> pool exhausted
    assert not mgr.ensure(0, 48)
    assert mgr.alloc_failures == 1
    # copy-free reclaim: frees return page ids, table points at trash
    freed = mgr.free_row(1)
    assert freed == 3 and mgr.free_pages == 3
    assert (mgr.table[1] == geom.trash_page).all()
    assert mgr.ensure(0, 48)
    assert mgr.residency() == {"dram_pages": 4, "free_pages": 2,
                               "flash_pages": 0, "staged_pages": 0}


# ---------------------------------------------------------------------------
# residency random walk (refcounts + DRAM/FLASH/IN_FLIGHT/STAGED states)
# ---------------------------------------------------------------------------

def _check_residency_invariants(mgr: KP.KVPoolManager):
    """The full allocator contract: exact refcounts (no double-free, no
    leak), one residency state per logical page (never DRAM *and* Flash),
    staging slots conserved, and FLASH/IN_FLIGHT pages invisible to
    dispatch (table on trash)."""
    geom = mgr.geom
    free = set(mgr._free)
    assert len(free) == len(mgr._free), "free list holds a duplicate page"
    held = [p for row in mgr.row_pages for p in row if p >= 0]
    indexed = set(mgr._chain_of_page)
    for p in range(geom.num_pages):
        refs = held.count(p) + (1 if p in indexed else 0)
        assert mgr.refcount[p] == refs, (p, mgr.refcount[p], refs)
        assert (mgr.refcount[p] == 0) == (p in free)
    # staging reserve never leaks and never double-books a slot
    assert mgr.staging_free + mgr.staged_count == geom.staging_pages
    slots = set(mgr._staged.values()) | set(mgr._staging_free)
    assert len(slots) == geom.staging_pages
    assert all(geom.staging_base <= s < geom.staging_base + geom.staging_pages
               for s in slots)
    assert sorted(mgr._stage_lru) == sorted(mgr._staged)
    for row in range(mgr.num_slots):
        pages, res = mgr.row_pages[row], mgr.row_res[row]
        assert len(pages) == len(res)
        for i, (p, state) in enumerate(zip(pages, res)):
            if state == KP.RES_DRAM:
                # a DRAM page never has a second (Flash/staged) residency
                assert p >= 0 and mgr.table[row, i] == p
                assert (row, i) not in mgr._staged
            else:
                assert p == -1, "off-DRAM page still owns a pool page"
                if state == KP.RES_STAGED:
                    assert mgr.table[row, i] == mgr._staged[(row, i)]
                else:
                    # FLASH / IN_FLIGHT: never visible to dispatch
                    assert mgr.table[row, i] == geom.trash_page
                    if state == KP.RES_FLASH:
                        assert (row, i) not in mgr._staged


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_residency_invariants_random_walk(seed):
    """Property: random alloc/adopt/register/ensure/free interleaved with
    spill/stage/commit/unstage/restore/stage-evict sequences never
    double-free a page, never give a page two residencies, never spill a
    pinned/adopted page, and never leak a staging slot."""
    rng = np.random.default_rng(seed)
    geom = KP.PoolGeometry(page_size=4, num_pages=12, pages_per_row=6,
                           staging_pages=3)
    mgr = KP.KVPoolManager(geom, num_slots=4)
    prompts = {}
    vocab = [list(rng.integers(1, 50, int(rng.integers(1, 20))))
             for _ in range(3)]       # small prompt set => real collisions
    for _ in range(200):
        op = rng.integers(0, 8)
        row = int(rng.integers(0, 4))
        if op == 0 and not mgr.row_pages[row]:            # alloc (maybe adopt)
            toks = vocab[int(rng.integers(0, len(vocab)))]
            if mgr.alloc_row(row, len(toks), token_ids=toks):
                prompts[row] = toks
                mgr.row_pos[row] = len(toks)
        elif op == 1 and mgr.row_pages[row]:              # register prefix
            mgr.register_prefix(row, prompts[row])
        elif op == 2 and 0 < len(mgr.row_pages[row]) < geom.pages_per_row:
            if mgr.ensure(row, len(mgr.row_pages[row]) * geom.page_size):
                mgr.row_pos[row] = len(mgr.row_pages[row]) * geom.page_size
        elif op == 3 and mgr.row_pages[row]:              # free (refcount dec)
            mgr.free_row(row)
            prompts.pop(row, None)
        elif op == 4 and mgr.row_pages[row]:              # cold spill
            cold = mgr.cold_pages(row, hot_pages=1)
            # the selector never offers a pinned or adopted page
            for i in cold:
                p = mgr.row_pages[row][i]
                assert mgr.refcount[p] == 1
                assert p not in mgr._chain_of_page
            if cold:
                mgr.spill_page(row, cold[0])
        elif op == 5:                                     # stage (+ commit)
            flash = [i for i, s in enumerate(mgr.row_res[row])
                     if s == KP.RES_FLASH]
            if flash:
                idx = flash[0]
                sid = mgr.begin_stage(row, idx)
                if sid is None:
                    victim = mgr.stage_victim(protect=set())
                    if victim is None:
                        continue
                    mgr.unstage(*victim)
                    sid = mgr.begin_stage(row, idx)
                # in-flight window: the table must still hide the page
                assert mgr.table[row, idx] == geom.trash_page
                _check_residency_invariants(mgr)
                mgr.commit_stage(row, idx)
        elif op == 6 and mgr._staged:                     # stage-evict
            victim = mgr.stage_victim(protect=set())
            if victim is not None:
                mgr.unstage(*victim)
        elif op == 7:                                     # restore to DRAM
            off = [i for i, s in enumerate(mgr.row_res[row])
                   if s in (KP.RES_FLASH, KP.RES_STAGED)]
            if off:
                mgr.restore_page(row, off[0])
        _check_residency_invariants(mgr)
    for row in range(4):
        if mgr.row_pages[row]:
            mgr.free_row(row)
        _check_residency_invariants(mgr)
    # all rows gone: the staging reserve is whole, only index pins remain
    assert mgr.staging_free == geom.staging_pages
    assert mgr.pages_in_use == len(mgr._chain_of_page)


# ---------------------------------------------------------------------------
# bitwise decode parity (acceptance)
# ---------------------------------------------------------------------------

def _filled_pair(B=2, Hkv=2, D=64, max_seq=64, ps=16, lens=(40, 17),
                 key_bits=8):
    """A dense per-row cache and a paged pool holding identical appends."""
    geom = KP.PoolGeometry(page_size=ps, num_pages=2 * (max_seq // ps),
                           pages_per_row=max_seq // ps)
    mgr = KP.KVPoolManager(geom, B)
    pool = KP.init_paged_layer(geom, Hkv, D, batch=B, key_bits=key_bits)
    dense = kvc.init_layer_cache(B, max_seq, Hkv, D, per_row=True,
                                 key_bits=key_bits)
    rng = np.random.default_rng(0)
    for b in range(B):
        assert mgr.alloc_row(b, lens[b])
    table = mgr.device_table()
    for step in range(max(lens)):
        k = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        pos = jnp.asarray([min(step, n) for n in lens], jnp.int32)
        dense = kvc.append(dense, k, v, pos)
        pool = KP.append_paged(pool, k, v, pos, table)
    return dense, pool, table, geom


def test_paged_append_bytes_match_dense():
    dense, pool, table, _ = _filled_pair()
    kq, ks, kz, v = KP.gather_pages(pool, table)
    n = 40
    assert np.array_equal(np.asarray(kq[:, :n]), np.asarray(dense.k_q[:, :n]))
    assert np.array_equal(np.asarray(ks[:, :n]),
                          np.asarray(dense.k_scale[:, :n]))
    assert np.array_equal(np.asarray(v[:, :n]).view(np.uint8),
                          np.asarray(dense.v[:, :n]).view(np.uint8))


def test_paged_decode_bitwise_reference():
    """Acceptance: paged decode == dense decode, bit for bit, on the
    reference backend."""
    dense, pool, table, _ = _filled_pair()
    qh = jnp.asarray(np.random.default_rng(1).normal(size=(2, 1, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray([40, 17], jnp.int32)
    ref = RD.Dispatcher(backend="reference").decode_attention(
        qh, dense, pos, DEFAULT_POLICY)
    got = RD.Dispatcher(backend="reference").paged_decode_attention(
        qh, pool, table, None, pos, DEFAULT_POLICY)
    assert np.array_equal(np.asarray(ref, np.float32),
                          np.asarray(got, np.float32))


def test_paged_decode_bitwise_interpret_kernel():
    """Acceptance: the paged Pallas kernel (interpret) == the dense kernel
    at matching block size, bit for bit — the page-table gather changes
    addressing only, never the math."""
    dense, pool, table, geom = _filled_pair()
    qh = jnp.asarray(np.random.default_rng(2).normal(size=(2, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray([40, 17], jnp.int32)
    dk = QA.quant_decode_attention(qh, dense.k_q, dense.k_scale,
                                   dense.k_zero, dense.v, pos,
                                   block_s=geom.page_size, interpret=True)
    pk = QA.paged_quant_decode_attention(
        qh, pool.k_q, pool.k_scale, pool.k_zero, pool.v, table,
        jnp.zeros((2,), jnp.int32), pos, layer=pool.layer, interpret=True)
    assert np.array_equal(np.asarray(dk), np.asarray(pk))


@pytest.mark.parametrize("lens", [(47, 9), (32, 16), (1, 33), (64, 48)],
                         ids=["mid-page", "page-boundary", "one-key",
                              "full-table"])
def test_paged_decode_live_pages_bitwise_interpret_kernel(lens):
    """The paged kernel computes only each row's live pages; the dense
    kernel at block_s == page_size walks every block.  Bit for bit the
    same wherever a row's length ends: mid-page, on a page boundary, at
    one key, at the full table."""
    dense, pool, table, geom = _filled_pair(lens=lens)
    qh = jnp.asarray(np.random.default_rng(5).normal(size=(2, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray(lens, jnp.int32)
    dk = QA.quant_decode_attention(qh, dense.k_q, dense.k_scale,
                                   dense.k_zero, dense.v, pos,
                                   block_s=geom.page_size, interpret=True)
    pk = QA.paged_quant_decode_attention(
        qh, pool.k_q, pool.k_scale, pool.k_zero, pool.v, table,
        jnp.zeros((2,), jnp.int32), pos, layer=pool.layer, interpret=True)
    assert np.array_equal(np.asarray(dk), np.asarray(pk))


def test_paged_decode_never_reads_dead_pages():
    """Dead table entries point at a page of NaN scales and values: rows
    never see it — their outputs stay finite and bitwise unchanged — and a
    row of length 0 finalizes to zeros."""
    _, pool, table, geom = _filled_pair()
    qh = jnp.asarray(np.random.default_rng(6).normal(size=(2, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray([40, 17], jnp.int32)
    base = jnp.zeros((2,), jnp.int32)
    trash = geom.trash_page
    assert (np.asarray(table) == trash).any(axis=1).all()

    def run(pl, p):
        return np.asarray(QA.paged_quant_decode_attention(
            qh, pl.k_q, pl.k_scale, pl.k_zero, pl.v, table, base, p,
            layer=pl.layer, interpret=True))

    nan = float("nan")
    poisoned = pool.replace(
        k_q=pool.k_q.at[:, trash].set(127),
        k_scale=pool.k_scale.at[:, trash].set(nan),
        k_zero=pool.k_zero.at[:, trash].set(nan),
        v=pool.v.at[:, trash].set(jnp.asarray(nan, pool.v.dtype)))
    assert np.isnan(np.asarray(poisoned.v[:, trash], np.float32)).all()
    clean, got = run(pool, pos), run(poisoned, pos)
    assert np.isfinite(got).all()
    assert np.array_equal(clean, got)
    empty = run(poisoned, jnp.asarray([0, 17], jnp.int32))
    assert np.array_equal(empty[0], np.zeros_like(empty[0]))
    assert np.array_equal(empty[1], got[1])


def test_paged_dispatch_interpret_vs_reference():
    dense, pool, table, _ = _filled_pair()
    qh = jnp.asarray(np.random.default_rng(3).normal(size=(2, 1, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray([40, 17], jnp.int32)
    ref = RD.Dispatcher(backend="reference").paged_decode_attention(
        qh, pool, table, None, pos, DEFAULT_POLICY)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.paged_decode_attention(qh, pool, table, None, pos,
                                      DEFAULT_POLICY)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_paged_int4_keys_fall_back_recorded():
    """Forced-ineligible shape: int4-key pools take the reference path and
    the dispatcher records why (surfaced into the bench JSON artifact)."""
    dense, pool, table, _ = _filled_pair(key_bits=4)
    qh = jnp.asarray(np.random.default_rng(4).normal(size=(2, 1, 4, 64)),
                     jnp.float32) / 8.0
    pos = jnp.asarray([40, 17], jnp.int32)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.paged_decode_attention(qh, pool, table, None, pos,
                                      DEFAULT_POLICY)
    ref = RD.Dispatcher(backend="reference").paged_decode_attention(
        qh, pool, table, None, pos, DEFAULT_POLICY)
    assert np.array_equal(np.asarray(ref, np.float32),
                          np.asarray(got, np.float32))
    assert any(op == "paged_decode_attention" and "int4" in why
               for op, _, why in disp.fallbacks), disp.fallbacks


# ---------------------------------------------------------------------------
# sliding-window ring recycling
# ---------------------------------------------------------------------------

def test_windowed_ring_matches_dense_ring():
    B, Hkv, D, W, ps = 2, 2, 64, 10, 4
    geom = KP.PoolGeometry(page_size=ps, num_pages=8, pages_per_row=8)
    pool = KP.init_paged_layer(geom, Hkv, D, batch=B, window=W)
    dense = kvc.init_layer_cache(B, 32, Hkv, D, window=W, per_row=True)
    rng = np.random.default_rng(0)
    lens = [25, 7]                 # row 0 wraps the ring, row 1 does not
    for step in range(max(lens)):
        k = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        pos = jnp.asarray([min(step, n) for n in lens], jnp.int32)
        dense = kvc.append(dense, k, v, pos)
        pool = KP.append_paged(pool, k, v, pos, None)
    qh = jnp.asarray(rng.normal(size=(B, 1, 4, D)), jnp.float32) / 8.0
    pos = jnp.asarray(lens, jnp.int32)
    ref = decode_attention_ref(qh, dense, pos)
    table, base = KP.ring_view(pool, pos, B)
    got = KP.paged_decode_attention_ref(qh, pool, table, base, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=1e-5)
    # the ring view also runs on the kernel path (the dense ring could not)
    disp = RD.Dispatcher(backend="interpret")
    kout = disp.paged_decode_attention(qh, pool, table, base, pos,
                                       DEFAULT_POLICY)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(kout, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("window", [10, 8])
def test_windowed_ring_live_pages_match_reference(window):
    """Ring views skip the pages outside each row's window: rows early in
    the stream (negative ``base``), one that has wrapped the ring with its
    window starting a page in, and one of a single key match the
    reference path computed in float32."""
    B, Hkv, D, ps = 4, 2, 64, 4
    geom = KP.PoolGeometry(page_size=ps, num_pages=8, pages_per_row=8)
    pool = KP.init_paged_layer(geom, Hkv, D, batch=B, window=window)
    rng = np.random.default_rng(7)
    lens = [26, 7, 1, 13]
    for step in range(max(lens)):
        k = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)), jnp.float32)
        pool = KP.append_paged(
            pool, k, v, jnp.asarray([min(step, n) for n in lens], jnp.int32),
            None)
    pos = jnp.asarray(lens, jnp.int32)
    table, base = KP.ring_view(pool, pos, B)
    assert (np.asarray(base) < 0).any()
    qh = jnp.asarray(rng.normal(size=(B, 1, 4, D)), jnp.float32) / 8.0
    ref = KP.paged_decode_attention_ref(
        qh, pool, table, base, pos,
        PrecisionPolicy(compute_dtype=jnp.float32))
    got = QA.paged_quant_decode_attention(
        qh[:, 0], pool.k_q, pool.k_scale, pool.k_zero, pool.v, table, base,
        pos, layer=pool.layer, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_pages_per_window_never_recycles_live_keys():
    for W in (3, 4, 5, 8, 10):
        for ps in (2, 4, 8):
            ppw = KP.pages_per_window(W, ps)
            for pos in range(200):
                # oldest key the window mask can reach at this position
                k = max(0, pos - W + 1)
                assert pos // ps - k // ps < ppw, (W, ps, pos)


# ---------------------------------------------------------------------------
# spill tier
# ---------------------------------------------------------------------------

def test_page_spill_store_roundtrip(tmp_path):
    flash = HS.FlashStore(str(tmp_path), HS.FlashSpec(simulate=False))
    store = HS.PageSpillStore(flash)
    a = np.arange(24, dtype=np.int8).reshape(2, 3, 4)
    b = np.arange(6, dtype=np.float32).reshape(2, 3)
    store.put(7, "s0p0", {"k_q": a, "k_scale": b}, pages=3)
    store.put(7, "s0p1", {"k_q": a + 1}, pages=0)
    assert store.pages_on_flash == 3
    store.prefetch_async(7, "s0p0")
    out = store.fetch(7, "s0p0")
    np.testing.assert_array_equal(out["k_q"], a)
    np.testing.assert_array_equal(out["k_scale"], b)
    assert store.prefetch_hits == 1
    out2 = store.fetch(7, "s0p1")          # no prefetch -> miss, still exact
    np.testing.assert_array_equal(out2["k_q"], a + 1)
    assert store.prefetch_misses == 1
    store.drop(7)
    assert store.pages_on_flash == 0
    store.close()


# ---------------------------------------------------------------------------
# end-to-end: page pressure, admission accounting
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


def test_preemption_under_page_pressure_matches_reference(engine, ref_engine):
    """Satellite: when the *pool* (not the slot count) is the binding
    constraint, preempt-and-resume via the Flash spill tier stays
    bitwise-equal to uninterrupted greedy decoding.  (Proactive cold-page
    spill is pinned off: it would sidestep the full-row preemption this
    test exists to exercise — tests/test_proactive_spill.py covers the
    cold-page path.)"""
    cfg = engine.cfg
    pb = RP.kv_page_bytes(cfg, RP.kv_page_size(engine.max_seq))
    # 5 pages: two requests peak at 3 pages each -> pressure mid-decode
    loop = E.EngineLoop(engine, max_slots=2, dram_budget_bytes=5 * pb,
                        proactive_spill=False)
    assert loop.geom.num_pages == 5
    rng = np.random.default_rng(12)
    reqs = [Request(uid=i, prompt_tokens=list(rng.integers(1, 400, 8)),
                    max_new_tokens=30) for i in range(2)]
    out = loop.run(reqs, SM.SamplingParams(temperature=0.0,
                                           max_new_tokens=30))
    assert all(r.done for r in out)
    # the pool, not the slots, forced the eviction
    assert sum(r.preemptions for r in out) >= 1
    assert engine.stats.spilled_pages > 0
    assert engine.stats.restored_pages > 0
    assert loop.spill.pages_on_flash == 0          # everything came back
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid


def test_paged_admission_beats_slot_reservation(engine):
    """Acceptance: at the same DRAM budget, page-held accounting admits
    strictly more concurrent requests than max_seq reservations."""
    cfg = engine.cfg
    ps = RP.kv_page_size(engine.max_seq)
    pb = RP.kv_page_bytes(cfg, ps)
    budget_pages = 8
    rng = np.random.default_rng(5)

    def trace():
        return [Request(uid=i, prompt_tokens=list(rng.integers(1, 400, 20)),
                        max_new_tokens=20) for i in range(6)]

    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=20)
    # baseline: worst-case token reservations under the same byte budget
    reserved = E.EngineLoop(engine, max_slots=4,
                            token_budget=budget_pages * ps)
    reserved.run(trace(), sp)
    # paged: the same budget expressed as pool pages
    paged = E.EngineLoop(engine, max_slots=4,
                         dram_budget_bytes=budget_pages * pb)
    assert paged.geom.num_pages == budget_pages
    paged.run(trace(), sp)
    assert paged.peak_active > reserved.peak_active


def test_engine_loop_paged_matches_reference(engine, ref_engine):
    """The whole paged path (prefill scatter, page-table decode, EOS
    reclaim, slot reuse) reproduces the dense single-request engine."""
    rng = np.random.default_rng(21)
    reqs = [Request(uid=i, prompt_tokens=list(rng.integers(
                1, 400, size=int(rng.integers(4, 24)))),
                    max_new_tokens=6) for i in range(4)]
    loop = E.EngineLoop(engine, max_slots=2)
    out = loop.run(reqs, SM.SamplingParams(temperature=0.0, max_new_tokens=6),
                   arrivals=[0, 0, 1, 3])
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid


@pytest.mark.slow
def test_windowed_model_paged_loop_matches_reference(tmp_path):
    """gemma3-style local+global stack through the paged EngineLoop: the
    windowed layer's ring pages recycle correctly under slot reuse."""
    cfg = registry.reduced(registry.get("gemma3-27b"))
    eng = E.build_engine(cfg, max_seq=64, flash_dir=str(tmp_path / "a"))
    ref = E.build_engine(cfg, max_seq=64, flash_dir=str(tmp_path / "b"))
    rng = np.random.default_rng(9)
    reqs = [Request(uid=i, prompt_tokens=list(rng.integers(1, 400, 8)),
                    max_new_tokens=12) for i in range(3)]
    loop = E.EngineLoop(eng, max_slots=2)
    out = loop.run(reqs, SM.SamplingParams(temperature=0.0,
                                           max_new_tokens=12))
    for r in out:
        got = ref.generate(
            [Request(uid=r.uid, prompt_tokens=list(r.prompt_tokens),
                     max_new_tokens=r.max_new_tokens)],
            SM.SamplingParams(temperature=0.0,
                              max_new_tokens=r.max_new_tokens))
        assert r.generated == got[0].generated, r.uid

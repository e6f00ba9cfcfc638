"""PR 8: Flash->DRAM weight streaming — plan-owned layer-group ring.

Acceptance for the tentpole: a config whose packed weights exceed the
DRAM budget decodes through the streamed group-by-group path BITWISE
EQUAL (greedy) to the all-DRAM run, with prefetch hit rate >= 0.9 and
``recompiles_after_warmup == 0``; the ring never aliases slots or
exposes an in-flight group; warmup is idempotent; and the weight tier
composes with the KV page-spill tier over one shared FlashStore root.
"""
import numpy as np
import pytest

from repro.configs import registry
from repro.runtime import plan as RP
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

CFG = registry.get("qwen1.5-110b@tiny")


# ---------------------------------------------------------------------------
# plan-level policy
# ---------------------------------------------------------------------------

def _weight_bytes(eng):
    head = (RP._tree_nbytes(eng.params["final_norm"])
            + RP._tree_nbytes(eng.params["lm_head"]))
    stacks = sum(RP._tree_nbytes(s) for s in eng.params["stacks"]
                 if s is not None)
    return head, stacks


def test_policy_no_budget_everything_resident(tmp_path):
    eng = E.build_engine(CFG, max_seq=64, flash_dir=str(tmp_path))
    pol = eng.weight_policy
    assert not pol.active and pol.streamed == ()
    assert all(v == "dram" for v in pol.placement.values())
    head, stacks = _weight_bytes(eng)
    assert pol.resident_bytes == head + stacks
    assert eng.weight_store is None


def test_policy_tight_budget_streams_with_double_buffer():
    # the policy is pure math over leaf sizes — drive it with a flat tree
    import jax.numpy as jnp

    (patterns, count), = CFG.layer_plan()
    stack_bytes = 600 * count
    params = {"final_norm": jnp.zeros(25, jnp.int8),
              "lm_head": jnp.zeros(75, jnp.int8),
              "stacks": (jnp.zeros(stack_bytes, jnp.int8),)}
    # budget covers the head + exactly 3 group slots
    pol = RP.weight_stream_policy(CFG, params,
                                  dram_budget_bytes=100 + 3 * 600)
    assert pol.active and len(pol.streamed) == 1
    sp = pol.streamed[0]
    assert sp.stack == 0 and sp.count == count
    assert 2 <= sp.ring_groups <= count - 1
    assert sp.ring_groups == 3
    assert pol.placement["stacks/0"] == "stream"
    assert pol.resident_bytes == 100 + sp.ring_bytes
    # a budget below even the double buffer still floors the ring at 2
    pol2 = RP.weight_stream_policy(CFG, params, dram_budget_bytes=100)
    assert pol2.streamed[0].ring_groups == 2


def test_policy_short_stack_stays_resident():
    import jax.numpy as jnp
    cfg = registry.reduced(registry.get("qwen2-7b"))     # 2 layer groups
    (patterns, count), = cfg.layer_plan()
    assert count == 2
    params = {"final_norm": jnp.zeros(10, jnp.int8),
              "lm_head": jnp.zeros(10, jnp.int8),
              "stacks": (jnp.zeros(1000, jnp.int8),)}
    # a 2-group stack can't double-buffer a strict subset: resident even
    # though the budget is hopeless
    pol = RP.weight_stream_policy(cfg, params, dram_budget_bytes=50)
    assert not pol.active
    assert pol.placement["stacks/0"] == "dram"


# ---------------------------------------------------------------------------
# engine end-to-end: streamed decode under a weight budget
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ref_engine(tmp_path_factory):
    return E.build_engine(CFG, max_seq=64,
                          flash_dir=str(tmp_path_factory.mktemp("flash_ref")))


@pytest.fixture(scope="module")
def stream_engine(tmp_path_factory, ref_engine):
    head, stacks = _weight_bytes(ref_engine)
    eng = E.build_engine(
        CFG, max_seq=64,
        flash_dir=str(tmp_path_factory.mktemp("flash_stream")),
        weight_dram_budget_bytes=head + int(0.6 * stacks))
    assert eng.weight_policy.active
    return eng


def _reference(ref_engine, req):
    out = ref_engine.generate(
        [Request(uid=req.uid, prompt_tokens=list(req.prompt_tokens),
                 max_new_tokens=req.max_new_tokens)],
        SM.SamplingParams(temperature=0.0,
                          max_new_tokens=req.max_new_tokens))
    return out[0].generated


def _trace(n, seed=7, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt_tokens=list(rng.integers(
                        1, CFG.vocab_size, size=int(rng.integers(3, 24)))),
                    max_new_tokens=int(rng.integers(2, max_new + 1)),
                    sampling=SM.SamplingParams(temperature=0.0))
            for i in range(n)]


def test_streamed_stack_dropped_from_dram(stream_engine):
    """Streamed stacks live on Flash: their DRAM param entry is gone and
    the store holds every group."""
    pol = stream_engine.weight_policy
    store = stream_engine.weight_store
    for sp in pol.streamed:
        assert stream_engine.params["stacks"][sp.stack] is None
        assert store.stack_nbytes(sp.stack) > 0
        assert len([k for k in store.groups() if k[0] == sp.stack]) \
            == sp.count
    head, stacks = _weight_bytes(stream_engine)
    assert stream_engine.stats.dram_weight_bytes == pol.resident_bytes
    assert pol.resident_bytes < head + stacks + store.total_nbytes


def test_legacy_generate_refuses_streaming(stream_engine):
    with pytest.raises(AssertionError, match="EngineLoop"):
        stream_engine.generate(
            [Request(uid=0, prompt_tokens=[1, 2, 3], max_new_tokens=2)],
            SM.SamplingParams(temperature=0.0, max_new_tokens=2))


@pytest.mark.slow
def test_streamed_bitwise_equal_24_request_trace(stream_engine, ref_engine):
    """THE acceptance test: a 24-request mixed trace (staggered arrivals,
    varied prompt/output lengths) under a DRAM weight budget < total
    weight bytes is bitwise-equal to the per-request all-DRAM reference,
    at prefetch hit rate >= 0.9 with zero post-warmup recompiles."""
    reqs = _trace(24)
    loop = E.EngineLoop(stream_engine, max_slots=4, prefill_chunk=16)
    assert loop.wpolicy.active and not loop._bucketed
    loop.warmup()
    h0 = stream_engine.stats.weight_group_hits
    m0 = stream_engine.stats.weight_group_misses
    arrivals = [i // 3 for i in range(24)]     # 3 arrivals per step
    out = loop.run(reqs, arrivals=arrivals)
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid
    s = stream_engine.stats
    assert s.recompiles_after_warmup == 0
    hits = s.weight_group_hits - h0
    misses = s.weight_group_misses - m0
    assert hits / (hits + misses) >= 0.9
    assert s.weight_stream_hit_rate >= 0.9
    assert s.weight_stall_s >= 0.0
    loop.close()


def test_streamed_bitwise_equal_small_trace(stream_engine, ref_engine):
    """Fast-leg version of the acceptance test: 6 requests."""
    reqs = _trace(6, seed=11)
    loop = E.EngineLoop(stream_engine, max_slots=4, prefill_chunk=16)
    loop.warmup()
    out = loop.run(reqs)
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid
    assert stream_engine.stats.recompiles_after_warmup == 0
    assert stream_engine.stats.weight_stream_hit_rate >= 0.9
    loop.close()


# ---------------------------------------------------------------------------
# ring residency properties
# ---------------------------------------------------------------------------

class _RingSpy(E.WeightRing):
    """Asserts the residency invariants on every obtain."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.obtained = []

    def obtain(self, group):
        out = super().obtain(group)
        # the group is fully installed: never visible while in flight
        assert (self.stack, group) not in self.store._inflight
        assert self.slot_group[self.slot_of(group)] == group
        # no slot aliasing: every installed slot names a distinct group,
        # and the groups sharing a slot are ring-distance apart
        live = [g for g in self.slot_group if g >= 0]
        assert len(live) == len(set(live))
        for r, g in enumerate(self.slot_group):
            assert g < 0 or g % self.ring_groups == r
        self.obtained.append(group)
        return out


def test_ring_slot_residency_properties(tmp_path):
    eng = E.build_engine(CFG, max_seq=64,
                         flash_dir=str(tmp_path / "flash"),
                         weight_dram_budget_bytes=1_500_000)
    assert eng.weight_policy.active
    loop = E.EngineLoop(eng, max_slots=2, prefill_chunk=16)
    (sp,) = eng.weight_policy.streamed
    loop._wstreams[sp.stack] = _RingSpy(
        eng.weight_store, sp.stack, sp.count, sp.ring_groups,
        *eng._stream_skel[sp.stack], h2d=eng.h2d)
    loop.warmup()
    reqs = _trace(3, seed=3, max_new=4)
    loop.run(reqs)
    spy = loop._wstreams[sp.stack]
    # every pass obtains the groups in execution order
    n = sp.count
    assert len(spy.obtained) % n == 0 and len(spy.obtained) >= 2 * n
    for i in range(0, len(spy.obtained), n):
        assert spy.obtained[i:i + n] == list(range(n))
    # slots were genuinely recycled (streaming, not residency)
    assert spy.installs > sp.ring_groups
    loop.close()


def test_warmup_idempotent_and_ring_stable(tmp_path):
    eng = E.build_engine(CFG, max_seq=64,
                         flash_dir=str(tmp_path / "flash"),
                         weight_dram_budget_bytes=1_500_000)
    loop = E.EngineLoop(eng, max_slots=2, prefill_chunk=16)
    rep1 = loop.warmup()
    graphs = rep1["graphs"]
    assert graphs > 0 and loop.warmed
    rep2 = loop.warmup()                      # idempotent: cache hits only
    assert rep2["graphs"] == graphs
    assert loop.compile_events() == graphs
    assert eng.stats.recompiles_after_warmup == 0
    loop.close()


# ---------------------------------------------------------------------------
# page-spill + weight-stream interaction (both tiers on one Flash root)
# ---------------------------------------------------------------------------

def test_page_spill_and_weight_stream_share_flash_root(tmp_path,
                                                       ref_engine):
    """Both Flash tiers active at once: KV pages of running rows spill to
    the same FlashStore the weight groups stream from, and greedy output
    stays bitwise-equal to the unconstrained all-DRAM run."""
    head, stacks = _weight_bytes(ref_engine)
    eng = E.build_engine(CFG, max_seq=64,
                         flash_dir=str(tmp_path / "flash"),
                         weight_dram_budget_bytes=head + int(0.5 * stacks))
    assert eng.weight_policy.active
    pb = RP.kv_page_bytes(eng.cfg, RP.kv_page_size(eng.max_seq))
    loop = E.EngineLoop(eng, max_slots=4, prefill_chunk=16,
                        dram_budget_bytes=6 * pb)
    assert loop.proactive
    # one Flash root under both tiers
    assert eng.weight_store.flash is eng.flash
    assert loop.spill.flash is eng.flash
    loop.warmup()
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt_tokens=list(rng.integers(1, 400, 30)),
                    max_new_tokens=16) for i in range(4)]
    out = loop.run(reqs, SM.SamplingParams(temperature=0.0,
                                           max_new_tokens=16))
    # both tiers actually engaged
    assert eng.stats.cold_spilled_pages > 0 or eng.stats.spilled_pages > 0
    assert eng.stats.weight_group_hits > 0
    assert eng.stats.weight_stream_hit_rate >= 0.9
    assert eng.stats.recompiles_after_warmup == 0
    for r in out:
        assert r.generated == _reference(ref_engine, r), r.uid
    loop.close()

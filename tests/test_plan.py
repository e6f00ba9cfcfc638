"""ExecutionPlan: deterministic construction, exact repack round-trips,
plan-aware ParamBuilder output, and Flash placement wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import quantization as q
from repro.models import transformer as T
from repro.runtime import plan as RP

KEY = jax.random.PRNGKey(0)


def _cfg():
    return registry.reduced(registry.get("qwen2-7b"))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("shape", [(100, 72), (128, 128), (300, 130),
                                   (3, 160, 200)])   # incl. a stacked axis
def test_pack_roundtrip(bits, shape):
    w = jax.random.normal(KEY, shape)
    qt = q.quantize(w, bits)
    packed = RP.pack_linear(qt)
    back = RP.unpack_linear(packed)
    assert back.shape == qt.shape
    assert back.bits == qt.bits
    np.testing.assert_array_equal(np.asarray(back.data), np.asarray(qt.data))
    np.testing.assert_array_equal(np.asarray(back.scale), np.asarray(qt.scale))
    np.testing.assert_array_equal(np.asarray(back.zero), np.asarray(qt.zero))
    # padded output COLUMNS must dequantize to exactly zero (scale=1,
    # zero=0); padded K rows carry q=0 and are nullified by the
    # zero-padded activations, so only the columns need the guarantee
    # (int4 is lane-packed for the kernels: re-interleave to dequantize)
    data = (q.pack_int4(q.unpack_int4_lanes(packed.data)) if bits == 4
            else packed.data)
    deq = q.dequantize(q.QuantizedTensor(
        data=data, scale=packed.scale, zero=packed.zero,
        bits=packed.bits,
        shape=(*packed.data.shape[:-2], packed.kp, packed.np_pad)),
        jnp.float32)
    assert float(jnp.abs(deq[..., :, qt.shape[-1]:]).max()) == 0.0


def test_pack_alignment():
    qt = q.quantize(jax.random.normal(KEY, (100, 72)), 4)
    packed = RP.pack_linear(qt)
    assert packed.data.shape == (128, 256 // 2)
    assert packed.scale.shape == (1, 256)
    assert (packed.k, packed.n) == (100, 72)


def test_plan_deterministic():
    cfg = _cfg()
    params = T.init_params(cfg, key=jax.random.PRNGKey(1), quantized=True)
    p1 = RP.build_plan(cfg, params)
    p2 = RP.build_plan(cfg, params)
    assert p1.quant_tag == p2.quant_tag == cfg.quant.tag()
    assert p1.placement == p2.placement
    assert p1.matmuls == p2.matmuls
    for a, b in zip(jax.tree.leaves(p1.params), jax.tree.leaves(p2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_repacks_per_layer_linears():
    cfg = _cfg()
    params = T.init_params(cfg, key=jax.random.PRNGKey(1), quantized=True)
    plan = RP.build_plan(cfg, params)
    leaves = jax.tree.leaves(
        plan.params,
        is_leaf=lambda x: isinstance(x, (RP.PackedLinear, q.QuantizedTensor)))
    packed = [x for x in leaves if isinstance(x, RP.PackedLinear)]
    raw = [x for x in leaves if isinstance(x, q.QuantizedTensor)]
    assert packed, "no weights were repacked"
    assert not raw, "dense-model weights should all repack"
    # repack preserves the quantized values exactly
    orig = [x for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, q.QuantizedTensor))
        if isinstance(x, q.QuantizedTensor)]
    for o, p in zip(orig, packed):
        np.testing.assert_array_equal(np.asarray(RP.unpack_linear(p).data),
                                      np.asarray(o.data))


def test_plan_packs_expert_tables():
    """MoE expert weights ([L, E, K, N]) repack to PackedExpertLinear (the
    grouped kernel's per-expert padded layout) with an exact round-trip."""
    cfg = registry.reduced(registry.get("dbrx-132b"))
    params = T.init_params(cfg, key=jax.random.PRNGKey(1), quantized=True)
    plan = RP.build_plan(cfg, params)
    leaves = jax.tree.leaves(
        plan.params,
        is_leaf=lambda x: isinstance(x, (RP.PackedLinear, q.QuantizedTensor)))
    experts = [x for x in leaves if isinstance(x, RP.PackedExpertLinear)]
    assert experts, "expert tables should pack to PackedExpertLinear"
    assert all(x.data.ndim == 4 for x in experts)   # [L, E, Kp, Np]
    stale = [x for x in leaves
             if isinstance(x, q.QuantizedTensor) and x.data.ndim >= 4]
    assert not stale, "no expert table should stay on the raw QT layout"


def test_pack_expert_linear_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 100, 130), jnp.float32)
    qt = q.quantize(w, 4)
    pel = RP.pack_expert_linear(qt)
    assert isinstance(pel, RP.PackedExpertLinear) and pel.experts == 3
    rt = RP.unpack_expert_linear(pel)
    np.testing.assert_array_equal(np.asarray(rt.data), np.asarray(qt.data))
    np.testing.assert_array_equal(np.asarray(rt.scale), np.asarray(qt.scale))
    np.testing.assert_array_equal(np.asarray(rt.zero), np.asarray(qt.zero))


def test_matmul_plan_blocks_divide():
    mp = RP.MatmulPlan(k=300, n=130, bits=4)
    for m in (1, 8, 33, 700):
        bm, bn, bk = mp.blocks(m)
        assert mp.np_pad % bn == 0 and mp.kp % bk == 0
        assert bm % RP.M_ALIGN == 0 or bm == RP.M_ALIGN
    # bucket cache: same bucket, same tuple
    assert mp.blocks(8) is mp.blocks(5)


def test_parambuilder_pack():
    cfg = _cfg()
    params = T.init_params(cfg, key=jax.random.PRNGKey(1), quantized=True,
                           pack=True)
    w = params["stacks"][0][0]["attn"]["wq"]["w"]
    assert isinstance(w, RP.PackedLinear)
    # abstract mirror has identical shapes/dtypes
    aparams = T.abstract_params(cfg, quantized=True)
    # (abstract without pack still yields QuantizedTensor)
    aw = aparams["stacks"][0][0]["attn"]["wq"]["w"]
    assert isinstance(aw, q.QuantizedTensor)
    ap = T.init_params(cfg, mode="abstract", quantized=True, pack=True)
    apw = ap["stacks"][0][0]["attn"]["wq"]["w"]
    assert isinstance(apw, RP.PackedLinear)
    assert apw.data.shape == w.data.shape
    assert apw.scale.shape == w.scale.shape
    assert (apw.k, apw.n) == (w.k, w.n)


def test_placement_embedding_on_flash():
    cfg = _cfg()
    placement = RP.placement_for(cfg)
    assert placement["embedding"] == "flash"
    assert placement["layers"] == "dram"
    assert placement["lm_head"] == "dram"


def test_flash_embedding_resolves_through_store(tmp_path):
    """Flash-placed embeddings still resolve through EmbeddingStore."""
    from repro.serving import engine as E
    cfg = _cfg()
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(0), max_seq=32,
                         flash_dir=str(tmp_path))
    assert eng.plan.placement["embedding"] == "flash"
    ids = np.asarray([[1, 5, 9]])
    rows = eng.embed(ids)
    assert rows.shape == (1, 3, cfg.d_model)
    direct = eng.embedding.lookup(ids)
    np.testing.assert_allclose(np.asarray(rows, np.float32),
                               np.asarray(direct, np.float32), rtol=1e-2,
                               atol=1e-2)
    assert eng.flash.bytes_read > 0

"""Every Pallas kernel of the serving path compiles for a TPU v5e.

The kernels are lowered with ``interpret=False`` and compiled by the TPU
compiler for one chip of a *described* ``v5e:2x2`` topology — nothing is
attached and nothing runs, so this catches what interpret mode cannot: a
block shape off the (8, 128) tiling, a vector op Mosaic cannot lower, a
tile set larger than the scoped VMEM.  Widths are qwen2-7b's (d_model
3584, 28 query / 4 KV heads of 128, d_ff 18944, vocab 151646) and, for the
grouped expert matmul, dbrx-132b's (16 experts, d_model 6144, d_ff 10752);
the paged attention also at glm4-9b's (32 query / 2 KV heads, an 8k
table over the stacked 40-layer pool).
Tiles come from the execution plan, as on the serving path.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist only the
worker that runs this file may take it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import flash_prefill as FP
from repro.kernels import grouped_matmul as GM
from repro.kernels import quant_attention as QA
from repro.kernels import rmsnorm as RN
from repro.kernels import w4a8_matmul as WM
from repro.runtime import plan as RP

QWEN = registry.get("qwen2-7b")
GLM = registry.get("glm4-9b")
DBRX = registry.get("dbrx-132b")
D_MODEL, D_FF = QWEN.d_model, QWEN.d_ff
H, HKV, HD = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
VOCAB = QWEN.padded_vocab_size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an ahead-of-time compile for an absent chip cannot be read back from
    # the persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _matmul(one_chip, m, k, n, bits):
    mp = RP.MatmulPlan(k=k, n=n, bits=bits)
    blocks = mp.blocks(m)
    bm = min(blocks[0], m)
    wn = mp.np_pad // 2 if bits == 4 else mp.np_pad

    def fn(xq, sx, w, ws, wz):
        return WM.w4a8_matmul(xq, sx, w, ws, wz, bits=bits,
                              blocks=(bm, blocks[1], blocks[2]),
                              interpret=False)

    _compile(fn, one_chip, ((m, mp.kp), jnp.int8), ((m, 1), jnp.float32),
             ((mp.kp, wn), jnp.int8), ((mp.np_pad,), jnp.float32),
             ((mp.np_pad,), jnp.float32))


@pytest.mark.parametrize("m", [8, 64])       # decode batch, prefill chunk
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL),
                                 (D_MODEL, HKV * HD)])
def test_w4a8_matmul_compiles(one_chip, m, k, n):
    _matmul(one_chip, m, k, n, bits=4)


def test_w8a8_lm_head_compiles(one_chip):
    _matmul(one_chip, 8, D_MODEL, VOCAB, bits=QWEN.quant.lm_head_bits)


@pytest.mark.parametrize("rows", [8, 64])
def test_rmsnorm_compiles(one_chip, rows):
    _compile(lambda x, w: RN.rmsnorm(x, w, interpret=False), one_chip,
             ((rows, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32))


def _pool_shapes(pages, ps, hkv=HKV, layers=1):
    """The paged pool's leaves, head-major, stacked over ``layers`` as the
    layer scan carries them."""
    return (((layers, pages, hkv, ps, HD), jnp.int8),
            ((layers, pages, hkv, 1, ps), jnp.float32),
            ((layers, pages, hkv, 1, ps), jnp.float32),
            ((layers, pages, hkv, ps, HD), jnp.float8_e4m3fn))


@pytest.mark.parametrize("max_seq", [256, 2048])
def test_paged_decode_attention_compiles(one_chip, max_seq):
    B, ps = 4, RP.kv_page_size(max_seq)
    ppr = max_seq // ps
    fn = lambda q, kq, ks, kz, v, tbl, base, ln: \
        QA.paged_quant_decode_attention(q, kq, ks, kz, v, tbl, base, ln,
                                        layer=0, interpret=False)
    _compile(fn, one_chip, ((B, H, HD), jnp.bfloat16),
             *_pool_shapes(B * ppr + 1, ps),
             ((B, ppr), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32))


def test_paged_windowed_decode_attention_compiles(one_chip):
    """A sliding-window ring view: per-row ``base`` and a static window
    bound each row's live pages."""
    B, ps, window = 4, 128, 4096
    ppw = window // ps + 1
    fn = lambda q, kq, ks, kz, v, tbl, base, ln: \
        QA.paged_quant_decode_attention(q, kq, ks, kz, v, tbl, base, ln,
                                        layer=0, window=window,
                                        interpret=False)
    _compile(fn, one_chip, ((B, H, HD), jnp.bfloat16),
             *_pool_shapes(B * ppw, ps),
             ((B, ppw), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32))


def test_dense_decode_attention_compiles(one_chip):
    B, S = 4, 2048
    fn = lambda q, kq, ks, kz, v, ln: QA.quant_decode_attention(
        q, kq, ks, kz, v, ln, interpret=False)
    _compile(fn, one_chip, ((B, H, HD), jnp.bfloat16),
             ((B, S, HKV, HD), jnp.int8), ((B, S, HKV), jnp.float32),
             ((B, S, HKV), jnp.float32), ((B, S, HKV, HD), jnp.float8_e4m3fn),
             ((B,), jnp.int32))


@pytest.mark.parametrize("chunk", [8, 64])
def test_paged_prefill_attention_compiles(one_chip, chunk):
    max_seq = 2048
    ps = RP.kv_page_size(max_seq)
    ppr = max_seq // ps
    fn = lambda q, kq, ks, kz, v, tbl, p0: FP.paged_flash_prefill_attention(
        q, kq, ks, kz, v, tbl, p0, layer=0, interpret=False)
    _compile(fn, one_chip, ((1, chunk, H, HD), jnp.bfloat16),
             *_pool_shapes(4 * ppr + 1, ps),
             ((1, ppr), jnp.int32), ((1,), jnp.int32))


def test_paged_decode_attention_glm4_compiles(one_chip):
    """glm4-9b's long-context cell: 2 KV heads of 16 query rows each, 32
    rows over an 8k table, the whole 40-layer pool stacked (2,048 pages,
    the trash page and 62 staging pages) with the layer read in SMEM."""
    B, max_seq = 32, 8192
    ps = RP.kv_page_size(max_seq)
    ppr = max_seq // ps
    pages = B * ppr + 1 + (ppr - 2)
    fn = lambda q, kq, ks, kz, v, tbl, base, ln, ly: \
        QA.paged_quant_decode_attention(q, kq, ks, kz, v, tbl, base, ln,
                                        layer=ly, interpret=False)
    _compile(fn, one_chip, ((B, GLM.num_heads, HD), jnp.bfloat16),
             *_pool_shapes(pages, ps, GLM.num_kv_heads, GLM.num_layers),
             ((B, ppr), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32),
             ((), jnp.int32))


def test_paged_prefill_attention_glm4_compiles(one_chip):
    """glm4-9b's 512-token prompt chunk over its 8k table, stacked pool."""
    B, max_seq, chunk = 32, 8192, 512
    ps = RP.kv_page_size(max_seq)
    ppr = max_seq // ps
    pages = B * ppr + 1 + (ppr - 2)
    fn = lambda q, kq, ks, kz, v, tbl, p0, ly: \
        FP.paged_flash_prefill_attention(q, kq, ks, kz, v, tbl, p0,
                                         layer=ly, interpret=False)
    _compile(fn, one_chip, ((1, chunk, GLM.num_heads, HD), jnp.bfloat16),
             *_pool_shapes(pages, ps, GLM.num_kv_heads, GLM.num_layers),
             ((1, ppr), jnp.int32), ((1,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("t", [64, 512])
def test_flash_prefill_attention_compiles(one_chip, t):
    fn = lambda q, k, v: FP.flash_prefill_attention(q, k, v, interpret=False)
    _compile(fn, one_chip, ((1, t, H, HD), jnp.bfloat16),
             ((1, t, HKV, HD), jnp.bfloat16), ((1, t, HKV, HD), jnp.bfloat16))


@pytest.mark.parametrize("m", [8, 64])
def test_grouped_matmul_dbrx_compiles(one_chip, m):
    E, k, n = DBRX.num_experts, DBRX.d_model, DBRX.d_ff
    mp = RP.MatmulPlan(k=k, n=n, bits=DBRX.quant.weight_bits)
    bm, bn, bk = mp.blocks(m)
    fn = lambda xq, sx, w, ws, wz: GM.grouped_matmul(
        xq, sx, w, ws, wz, bits=mp.bits, blocks=(min(bm, m), bn, bk),
        interpret=False)
    _compile(fn, one_chip, ((E, m, mp.kp), jnp.int8),
             ((E, m, 1), jnp.float32), ((E, mp.kp, mp.np_pad // 2), jnp.int8),
             ((E, mp.np_pad), jnp.float32), ((E, mp.np_pad), jnp.float32))


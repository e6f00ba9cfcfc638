"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as kvc
from repro.core import quantization as q
from repro.kernels import ops, ref
from repro.kernels import w4a8_matmul as WM
from repro.kernels import quant_attention as QA
from repro.kernels import rmsnorm as RN
from repro.runtime import plan as RP

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (16, 256, 256),
                                   (32, 128, 512), (8, 512, 128)])
@pytest.mark.parametrize("bits", [4, 8])
def test_w4a8_matmul_shapes(m, k, n, bits):
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qt = q.quantize(w, bits)
    xq, sx = q.quantize_activations(x)
    wq_un = q.unpack_int4(qt.data) if bits == 4 else qt.data
    want = ref.w4a8_matmul_ref(xq, sx, wq_un, qt.scale[0], qt.zero[0])
    # the kernel consumes the plan's lane-packed, 256-column-padded layout
    pw = RP.pack_linear(qt)
    got = WM.w4a8_matmul(xq, sx, pw.data, pw.scale[0], pw.zero[0],
                         bits=bits, blocks=(8, 256 if bits == 4 else 128, 128),
                         interpret=True)[:, :n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_w4a8_solver_blocks():
    """Kernel works with solver-chosen (not hand-picked) BlockSpecs."""
    m, k, n = 16, 512, 512
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qt = q.quantize(w, 4)
    pw = RP.pack_linear(qt)
    y = ops.quant_matmul_kernel(x, pw.data, pw.scale[0], pw.zero[0], bits=4,
                                interpret=True)
    y_ref = x @ q.dequantize(qt, jnp.float32)
    rel = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
    assert rel < 0.03


@pytest.mark.parametrize("s,hkv,g,d,blk", [(256, 2, 4, 64, 128),
                                           (512, 4, 1, 128, 256),
                                           (1024, 1, 8, 64, 512)])
def test_quant_decode_attention_shapes(s, hkv, g, d, blk):
    B = 2
    H = hkv * g
    qv = jax.random.normal(KEY, (B, H, d)) / d ** 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, s, hkv, d))
    kq, ks, kz = kvc.quantize_keys(k)
    v8 = q.to_fp8(v)
    length = jnp.asarray([s * 3 // 4], jnp.int32)
    want = ref.quant_decode_attention_ref(qv, kq, ks, kz, v8, length[0])
    got = QA.quant_decode_attention(qv, kq, ks, kz, v8, length, block_s=blk,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_quant_decode_attention_value_dtypes(dtype):
    B, S, Hkv, D = 1, 256, 2, 64
    qv = jax.random.normal(KEY, (B, 4, D)) / 8.0
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D)).astype(dtype)
    kq, ks, kz = kvc.quantize_keys(k)
    want = ref.quant_decode_attention_ref(qv, kq, ks, kz, v, jnp.int32(S))
    got = QA.quant_decode_attention(qv, kq, ks, kz, v,
                                    jnp.asarray([S], jnp.int32), block_s=128,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("rows,d", [(8, 128), (100, 256), (257, 512)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_shapes(rows, d, dtype):
    x = jax.random.normal(KEY, (rows, d), dtype)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (d,))) + 0.5
    got = RN.rmsnorm(x, w, block_rows=64, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_rmsnorm_3d_input():
    x = jax.random.normal(KEY, (2, 5, 128), jnp.bfloat16)
    w = jnp.ones((128,))
    got = ops.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("t,hkv,g,d,w,causal", [
    (128, 2, 3, 64, 0, True),
    (96, 1, 4, 32, 16, True),     # sliding window
    (64, 2, 1, 64, 0, False),     # bidirectional (encoder)
    (100, 2, 2, 64, 0, True),     # ragged T (padding path)
])
def test_flash_prefill_kernel(t, hkv, g, d, w, causal):
    from repro.kernels.flash_prefill import flash_prefill_attention
    from repro.models.attention import flash_attention
    from repro.core.precision import PrecisionPolicy
    F32 = PrecisionPolicy(compute_dtype=jnp.float32)
    B = 2
    qv = jax.random.normal(KEY, (B, t, hkv * g, d)) / d ** 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, t, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, t, hkv, d))
    got = flash_prefill_attention(qv, k, v, causal=causal, window=w,
                                  bq=32, bk=32, interpret=True)
    want = flash_attention(qv, k, v, causal=causal, window=w,
                           bq=32, bk=32, policy=F32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_prefill_kernel_dtypes(dtype):
    from repro.kernels import ops
    B, T, Hkv, G, D = 1, 64, 2, 2, 64
    qv = (jax.random.normal(KEY, (B, T, Hkv * G, D)) / 8).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, D)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, D)).astype(dtype)
    out = ops.flash_prefill(qv, k, v, bq=32, bk=32, interpret=True)
    assert out.shape == (B, T, Hkv * G, D)
    assert not bool(jnp.isnan(out).any())

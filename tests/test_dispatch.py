"""Dispatch parity: the kernel backends vs the reference path.

Every op the models route through runtime/dispatch.py is compared between
``backend="reference"`` (pure JAX/XLA) and ``backend="interpret"`` (the
Pallas kernels, interpret mode — the CPU-runnable kernel path).  Shapes
include non-multiples of the (8, 128) tile grid, so the plan's padding and
the dispatcher's M padding are both exercised.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import kv_cache as kvc
from repro.core import quantization as q
from repro.core.precision import DEFAULT_POLICY
from repro.models import attention as A
from repro.models import transformer as T
from repro.runtime import dispatch as RD
from repro.runtime import plan as RP

KEY = jax.random.PRNGKey(0)
QC = q.QuantConfig()

# non-multiple-of-tile M/K/N on purpose (plus one aligned shape)
MATMUL_SHAPES = [(5, 100, 72), (8, 128, 128), (13, 160, 200), (33, 300, 130)]


@pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
@pytest.mark.parametrize("bits", [4, 8])
def test_matmul_parity(m, k, n, bits):
    x = jax.random.normal(KEY, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    qt = q.quantize(w, bits)
    ref = RD.Dispatcher(backend="reference").linear(x, qt, QC, jnp.float32)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.linear(x, RP.pack_linear(qt), QC, jnp.float32)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_matmul_parity_unpacked_weight():
    """Plan-less dispatch repacks a raw QuantizedTensor inline."""
    x = jax.random.normal(KEY, (7, 96))
    qt = q.quantize(jax.random.normal(jax.random.PRNGKey(1), (96, 72)), 4)
    ref = RD.Dispatcher(backend="reference").linear(x, qt, QC, jnp.float32)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.linear(x, qt, QC, jnp.float32)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_matmul_batched_input_flattens():
    """[B, T, d] inputs flatten to rows and reshape back."""
    x = jax.random.normal(KEY, (2, 5, 100))
    qt = q.quantize(jax.random.normal(jax.random.PRNGKey(1), (100, 72)), 4)
    ref = RD.Dispatcher(backend="reference").linear(x, qt, QC, jnp.float32)
    got = RD.Dispatcher(backend="interpret").linear(
        x, RP.pack_linear(qt), QC, jnp.float32)
    assert got.shape == (2, 5, 72)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows,d", [(7, 96), (100, 256), (257, 512), (1, 64)])
def test_rmsnorm_parity(rows, d):
    x = jax.random.normal(KEY, (rows, d), jnp.bfloat16)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (d,))) + 0.5
    ref = RD.Dispatcher(backend="reference").rmsnorm(x, w)
    got = RD.Dispatcher(backend="interpret").rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("per_row", [False, True])
def test_decode_attention_parity(per_row):
    B, S, Hkv, G, D = 3, 96, 2, 2, 64
    cache = kvc.init_layer_cache(B, S, Hkv, D, per_row=per_row)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 40, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, 40, Hkv, D))
    start = jnp.zeros((B,) if per_row else (), jnp.int32)
    cache = kvc.append(cache, k, v, start)
    qh = jax.random.normal(KEY, (B, 1, Hkv * G, D)) / D ** 0.5
    pos = jnp.asarray([40, 17, 3], jnp.int32) if per_row \
        else jnp.asarray(40, jnp.int32)
    ref = A.decode_attention_ref(qh, cache, pos, DEFAULT_POLICY)
    disp = RD.Dispatcher(backend="interpret")
    got = disp.decode_attention(qh, cache, pos, DEFAULT_POLICY)
    assert not disp.fallbacks, disp.fallbacks
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_attention_windowed_falls_back():
    """Ring-buffer caches are ineligible: dispatch must fall back to the
    reference path (and record why), not fail."""
    B, S, Hkv, D = 1, 32, 2, 64
    cache = kvc.init_layer_cache(B, S, Hkv, D, window=32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 16, Hkv, D))
    cache = kvc.append(cache, k, k, jnp.zeros((), jnp.int32))
    qh = jax.random.normal(KEY, (B, 1, Hkv, D)) / D ** 0.5
    disp = RD.Dispatcher(backend="interpret")
    got = disp.decode_attention(qh, cache, jnp.asarray(16, jnp.int32),
                                DEFAULT_POLICY)
    ref = A.decode_attention_ref(qh, cache, jnp.asarray(16, jnp.int32),
                                 DEFAULT_POLICY)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=1e-5)
    assert any(op == "decode_attention" for op, _, _ in disp.fallbacks)


def test_prefill_attention_parity():
    B, Tn, Hkv, G, D = 2, 24, 2, 2, 64
    qh = jax.random.normal(KEY, (B, Tn, Hkv * G, D)) / D ** 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Tn, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Tn, Hkv, D))
    ref = RD.Dispatcher(backend="reference").prefill_attention(
        qh, k, v, causal=True, window=0, policy=DEFAULT_POLICY)
    got = RD.Dispatcher(backend="interpret").prefill_attention(
        qh, k, v, causal=True, window=0, policy=DEFAULT_POLICY)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_forced_ineligible_fallback_surfaces_in_bench_rows():
    """Satellite: a forced-ineligible shape (windowed dense cache on the
    kernel backend) is recorded on the dispatcher AND surfaced by the
    benchmark harness into the --json artifact via record_fallbacks — a
    silent reference fallback can no longer hide in BENCH numbers."""
    from benchmarks import common
    B, S, Hkv, D = 1, 32, 2, 64
    cache = kvc.init_layer_cache(B, S, Hkv, D, window=32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 16, Hkv, D))
    cache = kvc.append(cache, k, k, jnp.zeros((), jnp.int32))
    qh = jax.random.normal(KEY, (B, 1, Hkv, D)) / D ** 0.5
    disp = RD.Dispatcher(backend="interpret")
    disp.decode_attention(qh, cache, jnp.asarray(16, jnp.int32),
                          DEFAULT_POLICY)
    assert disp.fallbacks
    n0 = len(common.FALLBACKS)
    common.record_fallbacks("unit", disp)
    recorded = common.FALLBACKS[n0:]
    try:
        assert any(r["op"] == "decode_attention" and r["bench"] == "unit"
                   and r["backend"] == "interpret" for r in recorded)
        # run.py dumps exactly this list into the JSON artifact
        assert all({"bench", "op", "backend", "reason"} <= set(r)
                   for r in recorded)
    finally:
        del common.FALLBACKS[n0:]


def test_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    assert RD.Dispatcher().backend == "interpret"
    # env wins over the explicit argument (operator override)
    assert RD.Dispatcher(backend="reference").backend == "interpret"
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        RD.Dispatcher()
    monkeypatch.delenv("REPRO_BACKEND")
    assert RD.Dispatcher().backend == "reference"


def _decode_logits(cfg, backend):
    """Prefill 6 tokens then one decode step, all through one backend."""
    params = T.init_params(cfg, key=jax.random.PRNGKey(3), quantized=True,
                           pack=True)
    plan = RP.build_plan(cfg, params)
    ctx = T.StepCtx(cfg, dispatch=RD.Dispatcher(plan=plan, backend=backend))
    emb = jax.random.normal(jax.random.PRNGKey(4), (1, 6, cfg.d_model),
                            jnp.bfloat16)
    _, cache = T.prefill(plan.params, cfg, emb, max_seq=32, ctx=ctx)
    demb = jax.random.normal(jax.random.PRNGKey(5), (1, 1, cfg.d_model),
                             jnp.bfloat16)
    logits, cache = T.decode_step(plan.params, cfg, demb, cache, ctx=ctx)
    return logits


@pytest.mark.slow
def test_full_decode_step_parity():
    """Acceptance: dispatched interpret-mode outputs match the reference
    path within 1e-2 on a full decode_step."""
    cfg = registry.reduced(registry.get("qwen2-7b"))
    ref = _decode_logits(cfg, "reference")
    got = _decode_logits(cfg, "interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.slow
def test_full_decode_step_parity_w8a8():
    cfg = registry.reduced(registry.get("qwen2-7b"))
    cfg = dataclasses.replace(cfg, quant=q.QuantConfig(weight_bits=8,
                                                       act_bits=8))
    ref = _decode_logits(cfg, "reference")
    got = _decode_logits(cfg, "interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-2, atol=1e-2)


# --- backend resolution, loud kernel failures, launcher + cache knobs ------

def test_default_backend_follows_platform(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert RD.default_backend() == "reference"      # tests run on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert RD.default_backend() == "tpu"
    assert RD.Dispatcher().backend == "tpu"
    # an explicit argument still wins over the platform default
    assert RD.Dispatcher(backend="reference").backend == "reference"


def test_tpu_backend_without_tpu_raises(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        RD.Dispatcher(backend="tpu")
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        RD.Dispatcher()


def test_kernel_error_propagates(monkeypatch):
    """Only ``Ineligible`` falls back: any other exception from a kernel
    entry (a lowering refusal, a shape bug) reaches the caller."""
    def broken(disp, x, weight, eps):
        raise ValueError("kernel refused")
    monkeypatch.setitem(RD._REGISTRY, ("rmsnorm", "interpret", "*"), broken)
    disp = RD.Dispatcher(backend="interpret")
    x = jnp.ones((8, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="kernel refused"):
        disp.rmsnorm(x, jnp.ones((128,)))
    assert not disp.fallbacks


def test_serve_no_reduced_is_full_width():
    from repro.launch import serve
    ap = serve.build_parser()
    full = serve.model_config(ap.parse_args(["--arch", "qwen2-7b",
                                             "--no-reduced"]))
    assert full == registry.get("qwen2-7b")
    assert (full.num_layers, full.d_model, full.d_ff) == (28, 3584, 18944)
    small = serve.model_config(ap.parse_args(["--arch", "qwen2-7b"]))
    assert small == registry.reduced(registry.get("qwen2-7b"))


def test_compile_cache_location(monkeypatch, tmp_path):
    import pathlib
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.runtime import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    try:
        # an operator-set directory is left to JAX: nothing is overridden
        monkeypatch.setenv(CC.ENV, str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # otherwise: one fixed directory inside the checkout, every time
        monkeypatch.delenv(CC.ENV)
        first, second = CC.enable_compile_cache(), CC.enable_compile_cache()
        assert first == second == jax.config.jax_compilation_cache_dir
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(first) == root / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()

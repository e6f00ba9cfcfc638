"""GLM-4-9B at a reduced size: its partial, interleaved rotary, the
head-major (lane-dense) KV pool, and the step programs that update the
pool in place.

The engine's prefill-then-decode logits are compared with the plain
float32 reference ``bench/reference/glm4.py``, which is written from the
published description and imports nothing of the program."""
import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.arch import dense_gqa  # noqa: E402
from bench.reference import glm4 as REF  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.core import kv_pool as KP  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.serving import engine as E  # noqa: E402
from repro.serving import sampling as SM  # noqa: E402
from repro.serving.scheduler import Request  # noqa: E402

CFG = registry.reduced(registry.get("glm4-9b"))
# the engine's logits lie within this many standard deviations of the
# reference's logits at every served position: the program quantizes
# each linear's input to int8, keeps keys in int8 and values in fp8 and
# its activations in bf16, which reads 0.10-0.16 here; a wrong rotary
# reads above 1 at every position
LOGIT_TOL = 0.5
# the query and key projections scaled up so that attention is sharp:
# with weights at 1/sqrt(fan_in) it is nearly uniform over the keys and
# positions hardly move the logits, so no rotary could be told apart
SHARPEN = 3.0
PROMPT, NEW = 40, 24


def test_glm4_config_is_the_published_shape():
    c = registry.get("glm4-9b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.resolved_head_dim, c.d_ff, c.vocab_size) == \
        (40, 4096, 32, 2, 128, 13696, 151552)
    assert (c.rope_fraction, c.rope_interleaved, c.rope_theta) == \
        (0.5, True, 10000.0)
    assert c.qkv_bias and c.rms_eps == 1.5625e-7
    assert (CFG.num_kv_heads, CFG.rope_fraction, CFG.rope_interleaved) == \
        (2, 0.5, True)


def _published_rotary(x, pos, theta, fraction):
    """NumPy transcription of GLM-4's rotary: the first ``fraction`` of
    each head's dims as pairs (2i, 2i+1), inverse frequencies
    theta^(-2i/r) over the r rotated dims, the rest passed through."""
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    r = int(d * fraction)
    out = x.copy()
    for i in range(r // 2):
        ang = np.asarray(pos, np.float64)[..., None] * theta ** (-2 * i / r)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        out[..., 2 * i] = a * np.cos(ang) - b * np.sin(ang)
        out[..., 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    return out


def _rotate_half(x, positions, theta):
    """The full rotate-half rotary as the program had it before partial
    rotary existed."""
    d = x.shape[-1]
    freqs = jnp.asarray(L.rope_freqs(d, theta), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def test_partial_interleaved_rotary_matches_the_published_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 128), jnp.float32)
    pos = jnp.asarray(np.arange(18).reshape(2, 9) * 37, jnp.int32)
    got = L.apply_rope(x, pos, 10000.0, 0.5, True)
    want = _published_rotary(x, pos, 10000.0, 0.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    # the unrotated half passes bit for bit
    assert np.array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    # and the reference's own rotary is the same formula
    ref = REF.rotary(x, pos, 10000.0, 0.5)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_full_rotary_is_bit_equal_to_rotate_half(dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4, 64)).astype(dtype)
    pos = jnp.asarray(np.arange(10).reshape(2, 5) * 11, jnp.int32)
    got = L.apply_rope(x, pos, 1e6)
    assert np.array_equal(np.asarray(got), np.asarray(_rotate_half(x, pos,
                                                                   1e6)))


def _spec(cfg):
    return {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": 10000.0, "rms_norm_eps": 1.5625e-7,
            "partial_rotary_factor": 0.5,
            "quantization": {"weight_bits": 4, "act_bits": 8,
                             "lm_head_bits": 8, "kv_key_bits": 8,
                             "kv_value_fp8": True}}


class _Logits:
    """A loop's step program, keeping the logits of one slot each call."""

    def __init__(self, fn, out, pick):
        self.fn, self.out, self.pick = fn, out, pick

    def __call__(self, *a):
        lg, cache = self.fn(*a)
        self.out.append(self.pick(lg, a))
        return lg, cache

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _serve(cfg, prompt, new, tmp, donate=True):
    """Greedy tokens and the logits behind each (the prompt's last chunk,
    then every decode step) through EngineLoop, chunked prefill and the
    bucketed decode on the paged pool, with sharpened attention."""
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(7), max_seq=256,
                         flash_dir=str(tmp))
    (layer,) = eng.params["stacks"][0]
    for n in ("wq", "wk"):
        w = layer["attn"][n]["w"]
        layer["attn"][n]["w"] = dataclasses.replace(w,
                                                    scale=w.scale * SHARPEN)
    loop = E.EngineLoop(eng, max_slots=2, prefill_chunk=32)
    if not donate:
        loop._chunk = jax.jit(functools.partial(
            E.EngineLoop._chunk_impl, cfg, eng._ctx))
        loop._decode_b = jax.jit(functools.partial(
            E.EngineLoop._decode_bucket_impl, cfg, eng._ctx))
    out = []
    loop._chunk = _Logits(loop._chunk, out, lambda lg, a: np.asarray(lg[0]))
    loop._decode_b = _Logits(
        loop._decode_b, out,
        lambda lg, a: np.asarray(lg[int(np.asarray(a[5])[
            np.asarray(a[4])][0])]))
    req = Request(uid=0, prompt_tokens=list(prompt), max_new_tokens=new,
                  sampling=SM.SamplingParams(temperature=0.0,
                                             max_new_tokens=new))
    loop.submit(req)
    first = loop.cache["stacks"][0][0].k_q
    loop.step()
    donated = first.is_deleted()
    while loop.has_work():
        loop.step()
    return loop, req.generated, np.stack(out[-new:]), donated


def _reference(loop, prompt, toks):
    """The reference's logits at the served positions, on the served
    tokens and the engine's own weights and embedding rows."""
    eng = loop.eng
    rows = lambda ids: np.asarray(  # noqa: E731
        eng.embedding.lookup(np.asarray(ids)), np.float32)
    return REF.logits(dense_gqa.logical_layers(eng.cfg, eng.params),
                      _spec(eng.cfg), rows, [list(prompt) + toks[:-1]],
                      [len(prompt) - 1])[0]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    prompt = np.random.default_rng(3).integers(1, CFG.vocab_size, PROMPT)
    loop, toks, got, donated = _serve(CFG, prompt, NEW,
                                      tmp_path_factory.mktemp("a"))
    return dict(prompt=prompt, toks=toks, got=got, loop=loop,
                ref=_reference(loop, prompt, toks), donated=donated)


def test_pool_leaves_are_head_major_and_lane_dense(served):
    """No leaf of the paged pool has the KV head count as its minor or
    second-minor dim (the two the TPU tiles): every leaf's bytes on the
    device equal its logical bytes."""
    loop = served["loop"]
    hkv = CFG.num_kv_heads
    pools = [p for row in loop.cache["stacks"] for p in row]
    assert pools and all(isinstance(p, KP.PagedLayerKV) for p in pools)
    for p in pools:
        for name, x in p.fields().items():
            assert x.shape[-3] == hkv and hkv not in x.shape[-2:], \
                (name, x.shape)
    dev, logical = loop.pool_bytes
    assert dev == logical == sum(KP.logical_bytes(p) for p in pools)
    per_token = hkv * (2 * CFG.resolved_head_dim + 8)
    assert logical == (loop.geom.total_device_pages * loop.geom.page_size
                       * CFG.num_layers * per_token)
    # every step program that carried the pool counted its bytes
    s = loop.eng.stats
    launches = s.kv_pool_logical_bytes // logical
    assert launches == s.prefill_chunks + s.decode_steps > 0
    assert s.kv_pool_device_bytes == launches * dev


def _gap(got, ref):
    """Widest logit error at each position, in units of that position's
    reference logit std."""
    got = got[:, :ref.shape[1]]
    return np.abs(got - ref).max(axis=1) / ref.std(axis=1)


def test_prefill_then_decode_logits_match_the_reference(served):
    toks, got, ref = served["toks"], served["got"], served["ref"]
    assert len(toks) == NEW and got.shape[0] == NEW
    gap = _gap(got, ref)
    assert gap.max() < LOGIT_TOL, gap


def test_full_rotary_in_the_programs_place_fails_the_tolerance(
        served, tmp_path):
    """The program with GLM-4's rotary swapped for a full rotate-half one,
    on the same weights and prompt, is refused at every position."""
    full = dataclasses.replace(CFG, rope_fraction=1.0,
                               rope_interleaved=False)
    loop, toks, got, _ = _serve(full, served["prompt"], NEW, tmp_path)
    ref = _reference(loop, served["prompt"], toks)
    assert _gap(got, ref).min() > LOGIT_TOL


def test_a_donated_step_leaves_the_logits_of_an_undonated_run(
        served, tmp_path):
    """The step programs donate the pool (the first step consumed the
    pool it was handed: it was written in place), and the logits are
    those of the same programs run without donation."""
    assert served["donated"]
    _, toks_u, got_u, donated_u = _serve(CFG, served["prompt"], NEW,
                                         tmp_path, donate=False)
    assert not donated_u
    assert toks_u == served["toks"]
    assert np.array_equal(got_u, served["got"])

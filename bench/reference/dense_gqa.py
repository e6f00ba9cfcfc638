"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the published description of Qwen2 and GLM-4 (pre-norm RMSNorm
blocks, rotary positions, grouped-query attention with optional q/k/v bias,
SwiGLU feed-forward, final RMSNorm and an untied output head), in plain
``jax.numpy`` with ``jax.default_matmul_precision("highest")``.  It imports
nothing of the program under test.  Its weights are the benchmark's own
seeded draw, handed over as integer codes with f32 scales and zero points
(``w = scale * (code - zero)``); the int4 codes arrive two to a byte in the
kernels' lane layout and are unpacked here.

Departures from the published models, shared with the program: rotary
embedding on every dimension of each head, rotate-half form.  GLM-4 rotates
half of the dimensions.

``act_bits`` set to 4 quantizes the input of every linear to 4 bits per row
(symmetric, 7 levels a side): the control that a comparison has to reject.
The whole sequence runs layer by layer, one layer's weights dequantized at a
time, with attention in blocks of queries, so a long context fits next to
nothing else on the chip.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128
Q_BLOCK = 512


class Dims(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    d_model: int
    d_ff: int
    vocab: int
    theta: float
    eps: float
    weight_bits: int
    head_bits: int

    def shape(self, name: str):
        """(fan_in, fan_out, bits) of a linear, from the configuration."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        d, f, b = self.d_model, self.d_ff, self.weight_bits
        return {"wq": (d, q, b), "wk": (d, kv, b), "wv": (d, kv, b),
                "wo": (q, d, b), "w_gate": (d, f, b), "w_up": (d, f, b),
                "w_down": (f, d, b),
                "lm_head": (d, self.vocab, self.head_bits)}[name]


def dims_of(spec: dict) -> Dims:
    qz = spec["quantization"]
    return Dims(spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"], spec["hidden_size"],
                spec["intermediate_size"], spec["vocab_size"],
                float(spec["rope_theta"]), float(spec["rms_norm_eps"]),
                qz["weight_bits"], qz["lm_head_bits"])


def _codes(lin: dict, k: int, n: int, bits: int, idx=None) -> jax.Array:
    data = lin["data"] if idx is None else lin["data"][idx]
    if bits == 4:
        p = jax.lax.bitcast_convert_type(data, jnp.uint8)
        kp, cols = p.shape
        lo = (p & 0x0F).reshape(kp, cols // LANE, 1, LANE)
        hi = (p >> 4).reshape(kp, cols // LANE, 1, LANE)
        q = jnp.concatenate([lo, hi], axis=2).reshape(kp, 2 * cols)
    else:
        q = data
    return q[:k, :n].astype(jnp.float32)


def dequant(lin: dict, k: int, n: int, bits: int, idx=None) -> jax.Array:
    scale = lin["scale"] if idx is None else lin["scale"][idx]
    zero = lin["zero"] if idx is None else lin["zero"][idx]
    return scale[None, :n] * (_codes(lin, k, n, bits, idx) - zero[None, :n])


def _fake_quant(x: jax.Array, bits: Optional[int]) -> jax.Array:
    if bits is None:
        return x
    top = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax == 0, 1.0, amax / top)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _linear(x, w, name, dims, idx, bits):
    return _fake_quant(x, bits) @ dequant(w[name], *dims.shape(name), idx)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * inv       # [B, T, d/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention, q [B, T, H, D] already scaled, k/v [B, T, Hkv, D];
    queries in blocks of Q_BLOCK against every key."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    kk = jnp.repeat(k, G, axis=2)
    vv = jnp.repeat(v, G, axis=2)
    nb = T // Q_BLOCK if T % Q_BLOCK == 0 else 1
    bq = T // nb
    qb = q.reshape(B, nb, bq, H, D).transpose(1, 0, 2, 3, 4)
    keys = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("bthd,bshd->bhts", qi, kk)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(keys[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vv)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def _layer(x, w, idx, pos, *, dims, bits):
    H, Hkv, D, theta, eps = (dims.heads, dims.kv_heads, dims.head_dim,
                             dims.theta, dims.eps)
    B, T, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["ln1"][idx], eps)
        q = _linear(h, w, "wq", dims, idx, bits)
        k = _linear(h, w, "wk", dims, idx, bits)
        v = _linear(h, w, "wv", dims, idx, bits)
        if "bq" in w:
            q = q + w["bq"][idx].astype(jnp.float32)
            k = k + w["bk"][idx].astype(jnp.float32)
            v = v + w["bv"][idx].astype(jnp.float32)
        q = _rope(q.reshape(B, T, H, D), pos, theta) / np.sqrt(D)
        k = _rope(k.reshape(B, T, Hkv, D), pos, theta)
        o = _attention(q, k, v.reshape(B, T, Hkv, D)).reshape(B, T, H * D)
        x = x + _linear(o, w, "wo", dims, idx, bits)
        h = _rms(x, w["ln2"][idx], eps)
        g = _linear(h, w, "w_gate", dims, idx, bits)
        u = _linear(h, w, "w_up", dims, idx, bits)
        return x + _linear(jax.nn.silu(g) * u, w, "w_down", dims,
                           idx, bits)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def _head(hs, w, *, dims, bits):
    with jax.default_matmul_precision("highest"):
        h = _rms(hs, w["final_norm"], dims.eps)
        return _linear(h, w, "lm_head", dims, None, bits)


def _pad_len(t: int) -> int:
    return -(-t // Q_BLOCK) * Q_BLOCK if t > Q_BLOCK else -(-t // 64) * 64


def logits(w: dict, spec: dict, embed_rows, seqs: Sequence[Sequence[int]],
           starts: Sequence[int], act_bits: Optional[int] = None
           ) -> List[np.ndarray]:
    """Logits [len(seq) - start, vocab] of every sequence at positions
    ``start .. len(seq) - 1`` (each predicts the token after it).
    ``w``: plain arrays ({"data", "scale", "zero"} per linear), stacked
    layers on a leading axis; ``embed_rows``:
    token ids -> f32 embedding rows (host); ``spec``: the configuration."""
    L = spec["num_hidden_layers"]
    dims = dims_of(spec)
    T = _pad_len(max(len(s) for s in seqs))
    ids = np.zeros((len(seqs), T), np.int64)
    for b, s in enumerate(seqs):
        ids[b, :len(s)] = s
    x = jnp.asarray(embed_rows(ids), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (len(seqs), T))
    for layer in range(L):
        x = _layer(x, w, jnp.int32(layer), pos, dims=dims, bits=act_bits)
    rows = [(b, p) for b, (s, st) in enumerate(zip(seqs, starts))
            for p in range(st, len(s))]
    hs = x[np.asarray([r[0] for r in rows]), np.asarray([r[1] for r in rows])]
    out = np.asarray(_head(hs, w, dims=dims, bits=act_bits))
    res, i = [], 0
    for s, st in zip(seqs, starts):
        res.append(out[i:i + len(s) - st])
        i += len(s) - st
    return res

"""Plain float32 reference of GLM-4-9B.

Written from the published description of GLM-4 (hf:THUDM/glm-4-9b):
pre-norm RMSNorm blocks; grouped-query attention with q/k/v bias and no
output bias; rotary positions on the first ``partial_rotary_factor`` of
each head's dimensions, turned as interleaved pairs (2i, 2i + 1) with
inverse frequencies ``theta ** (-2i / r)`` over the r rotated dimensions,
the rest passed through; a SwiGLU feed-forward; a final RMSNorm and an
untied output head.  Plain ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program under test, and of ``dense_gqa`` only the weight unpacking, the
4-bit control's input quantization and the RMSNorm.

Each layer runs its linears and feed-forward over blocks of rows and its
attention over blocks of queries (each block against every key, grouped by
KV head), so a check's sample of up to 8 sequences of 8,192 positions fits
on the chip beside the weights.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import (Dims, _fake_quant, _pad_len, _rms,
                                       dequant, dims_of)

ROW_BLOCK = 2048          # rows of a linear or feed-forward block
QUERY_BLOCK = 64          # queries of an attention block


def rotary(x, pos, theta: float, fraction: float):
    """x [B, T, H, D]: the first ``fraction`` x D dims turn as pairs
    (2i, 2i + 1) by ``pos x theta ** (-2i / r)``; the rest pass."""
    d = x.shape[-1]
    r = int(round(d * fraction))
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = pos[..., None].astype(jnp.float32) * inv        # [B, T, r/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    even, odd = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1).reshape(*x.shape[:-1], r)
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


def _row_block(n: int) -> int:
    b = ROW_BLOCK
    while n % b:
        b //= 2
    return b


def _by_rows(fn, *xs):
    """``fn`` over blocks of rows of the arrays xs [N, ...]."""
    n = xs[0].shape[0]
    rb = _row_block(n)
    out = jax.lax.map(lambda a: fn(*a),
                      tuple(x.reshape(n // rb, rb, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape(n, *y.shape[2:]), out)


def _attention(q, k, v):
    """Causal attention; q [B, T, H, D] already scaled, k/v [B, T, Hkv, D];
    each block of queries against every key, query heads grouped by the
    KV head they share."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    bq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    nb = T // bq
    qb = q.reshape(B, nb, bq, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    keys = jnp.arange(T)

    def block(args):
        i, qi = args                                      # [B, bq, Hkv, G, D]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(keys[None, None, None, None, :]
                      <= qpos[None, None, None, :, None], s, -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (jnp.arange(nb), qb))        # [nb, B, bq, ...]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("dims", "fraction", "bits"))
def _layer(x, w, idx, pos, *, dims: Dims, fraction: float, bits):
    H, Hkv, D, eps = dims.heads, dims.kv_heads, dims.head_dim, dims.eps
    B, T, d = x.shape

    def lin(h, name):
        return _fake_quant(h, bits) @ dequant(w[name], *dims.shape(name),
                                              idx)

    with jax.default_matmul_precision("highest"):
        def qkv(rows):
            h = _rms(rows, w["ln1"][idx], eps)
            return tuple(lin(h, n) + w[b][idx].astype(jnp.float32)
                         for n, b in (("wq", "bq"), ("wk", "bk"),
                                      ("wv", "bv")))
        q, k, v = _by_rows(qkv, x.reshape(B * T, d))
        q = rotary(q.reshape(B, T, H, D), pos, dims.theta, fraction)
        k = rotary(k.reshape(B, T, Hkv, D), pos, dims.theta, fraction)
        o = _attention(q / np.sqrt(D), k, v.reshape(B, T, Hkv, D))

        def out_ffn(rows, att):
            rows = rows + lin(att, "wo")
            h = _rms(rows, w["ln2"][idx], eps)
            return rows + lin(jax.nn.silu(lin(h, "w_gate")) * lin(h, "w_up"),
                              "w_down")
        x = _by_rows(out_ffn, x.reshape(B * T, d), o.reshape(B * T, H * D))
        return x.reshape(B, T, d)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def _head(hs, w, *, dims: Dims, bits):
    with jax.default_matmul_precision("highest"):
        h = _rms(hs, w["final_norm"], dims.eps)
        return _fake_quant(h, bits) @ dequant(w["lm_head"],
                                              *dims.shape("lm_head"))


def logits(w: dict, spec: dict, embed_rows, seqs: Sequence[Sequence[int]],
           starts: Sequence[int], act_bits: Optional[int] = None
           ) -> List[np.ndarray]:
    """Logits [len(seq) - start, vocab] of every sequence at positions
    ``start .. len(seq) - 1`` (each predicts the token after it).
    ``w``: plain arrays ({"data", "scale", "zero"} per linear), stacked
    layers on a leading axis; ``embed_rows``: token ids -> f32 embedding
    rows (host); ``spec``: the configuration."""
    dims = dims_of(spec)
    fraction = float(spec["partial_rotary_factor"])
    T = _pad_len(max(len(s) for s in seqs))
    ids = np.zeros((len(seqs), T), np.int64)
    for b, s in enumerate(seqs):
        ids[b, :len(s)] = s
    x = jnp.asarray(embed_rows(ids), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (len(seqs), T))
    for layer in range(spec["num_hidden_layers"]):
        x = _layer(x, w, jnp.int32(layer), pos, dims=dims, fraction=fraction,
                   bits=act_bits)
    rows = [(b, p) for b, (s, st) in enumerate(zip(seqs, starts))
            for p in range(st, len(s))]
    hs = x[np.asarray([r[0] for r in rows]), np.asarray([r[1] for r in rows])]
    out = np.asarray(_head(hs, w, dims=dims, bits=act_bits))
    res, i = [], 0
    for s, st in zip(seqs, starts):
        res.append(out[i:i + len(s) - st])
        i += len(s) - st
    return res

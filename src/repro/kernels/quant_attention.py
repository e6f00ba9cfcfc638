"""Pallas decode attention with fused KV dequantization (paper C1 + C5).

One query token per sequence attends over the quantized cache:
int8 keys (per-token/head asymmetric scales) and fp8 values are dequantized
*inside* the kernel — HBM traffic is the quantized bytes, which is the whole
point of the paper's KV quantization in the memory-bound decode phase.

Mixed precision per the paper: the query arrives pre-scaled by 1/sqrt(D);
softmax runs in fp32 (online, flash-decoding style over S blocks).

Grid (B, nS) with S innermost.  Each step DMAs one ``[bs, Hkv, D]`` KV
block holding every KV head — the cache's native ``[.., S, Hkv, D]``
layout, so the block's last two dims are the full (Hkv, D) extent the TPU
tiling accepts — and loops the heads in the kernel; per-head online-softmax
state (m, l, acc) lives in VMEM scratch across the S steps.  Valid-prefix
lengths ride in SMEM as a [B] vector (continuous batching: every slot
decodes at its own offset); a scalar/[1] length broadcasts to all rows.

``paged_quant_decode_attention`` is the page-table variant for the paged
KV pool (core/kv_pool.py): the grid walks *logical* pages and a
scalar-prefetched per-row page table translates each one to its physical
pool page in the BlockSpec index map — the kernel body is the same
``attend_block`` as the dense kernel at block_s == page_size, so the two
are bitwise equal.  A per-row ``base`` page offset + static ``window``
serve the sliding-window ring views.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def dequant_keys_block(kq, ks, kz):
    """Fused in-kernel key dequantization: int8 block [bs, D] + per-token
    asymmetric (scale, zero) columns [bs, 1] -> f32 keys.  Shared by the
    decode kernels here and the prefill kernels (flash_prefill) so every
    kernel reads the quantized bytes identically."""
    return (kq.astype(jnp.float32) - kz) * ks


def init_softmax_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_softmax_update(h, s, v, m_ref, l_ref, acc_ref):
    """Fold one block of scores ``s`` [rows, bs] and values ``v`` [bs, D]
    into head ``h``'s running (max, denominator, numerator)."""
    m_prev = m_ref[h]                               # [rows, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
    m_ref[h] = m_new
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def finalize_softmax(o_ref, l_ref, acc_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def attend_block(q_ref, kq_ref, ks_ref, kz_ref, v_ref, m_ref, l_ref,
                 acc_ref, valid):
    """One KV block for every head: q_ref [1, Hkv, G, D] pre-scaled,
    kq/v refs [1, bs, Hkv, D], scale refs [1, bs, Hkv]; ``valid`` [1, bs]
    masks key positions (shared by all heads)."""
    ks_all, kz_all = ks_ref[0], kz_ref[0]           # [bs, Hkv]
    for h in range(kq_ref.shape[2]):
        k = dequant_keys_block(kq_ref[0, :, h, :], ks_all[:, h:h + 1],
                               kz_all[:, h:h + 1])  # [bs, D]
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_ref[0, h].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [G, bs]
        online_softmax_update(h, jnp.where(valid, s, NEG_INF), v,
                              m_ref, l_ref, acc_ref)


def _scratch(Hkv: int, rows: int, D: int):
    return [pltpu.VMEM((Hkv, rows, 1), jnp.float32),   # running max
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hkv, rows, D), jnp.float32)]   # running numerator


def _kernel(len_ref, q_ref, kq_ref, ks_ref, kz_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, bs: int):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        init_softmax_state(m_ref, l_ref, acc_ref)

    pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    attend_block(q_ref, kq_ref, ks_ref, kz_ref, v_ref, m_ref, l_ref, acc_ref,
                 pos < len_ref[b_idx])

    @pl.when(s_idx == n_s - 1)
    def _done():
        finalize_softmax(o_ref, l_ref, acc_ref)


def quant_decode_attention(q: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                           k_zero: jax.Array, v: jax.Array,
                           length: jax.Array, *, block_s: int = 512,
                           interpret: bool) -> jax.Array:
    """q: f32 [B, H, D] pre-scaled; k_q int8 [B, S, Hkv, D];
    k_scale/k_zero f32 [B, S, Hkv]; v fp8/bf16 [B, S, Hkv, D];
    length: int32 valid prefix — scalar/[1] (all rows aligned) or [B]
    per-row offsets (continuous batching).  Returns f32 [B, H, D]."""
    B, H, D = q.shape
    S, Hkv = k_q.shape[1], k_q.shape[2]
    G = H // Hkv
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    n_s = S // bs
    qg = q.reshape(B, Hkv, G, D)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_kernel, n_s=n_s, bs=bs)
    kv_spec = pl.BlockSpec((1, bs, Hkv, D), lambda b, s: (b, s, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, Hkv), lambda b, s: (b, s, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # length scalar
            pl.BlockSpec((1, Hkv, G, D), lambda b, s: (b, 0, 0, 0)),
            kv_spec, sc_spec, sc_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, s: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        scratch_shapes=_scratch(Hkv, G, D),
        interpret=interpret,
    )(length, qg, k_q, k_scale, k_zero, v)
    return out.reshape(B, H, D)


def _paged_kernel(table_ref, base_ref, len_ref, q_ref, kq_ref, ks_ref,
                  kz_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, n_p: int, ps: int, window: int):
    b_idx = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        init_softmax_state(m_ref, l_ref, acc_ref)

    # logical position of each key in this page (the index map already
    # translated logical page base_ref[b] + p_idx to its physical page)
    pos = ((base_ref[b_idx] + p_idx) * ps
           + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1))
    length = len_ref[b_idx]
    valid = (pos >= 0) & (pos < length)
    if window:
        valid = valid & (pos >= length - window)
    attend_block(q_ref, kq_ref, ks_ref, kz_ref, v_ref, m_ref, l_ref, acc_ref,
                 valid)

    @pl.when(p_idx == n_p - 1)
    def _done():
        finalize_softmax(o_ref, l_ref, acc_ref)


def paged_quant_decode_attention(q: jax.Array, k_q: jax.Array,
                                 k_scale: jax.Array, k_zero: jax.Array,
                                 v: jax.Array, table: jax.Array,
                                 base: jax.Array, length: jax.Array, *,
                                 window: int = 0,
                                 interpret: bool) -> jax.Array:
    """Decode attention over the paged pool via a per-row page table.

    q: f32 [B, H, D] pre-scaled; pool arrays [P, page, Hkv, D(k)];
    table: int32 [B, n_pages] physical page per logical page (unallocated
    entries point at the trash page — masked by ``length``); base: int32
    [B] logical page index of table column 0 (ring views start mid-stream,
    possibly negative); length: int32 [B] valid prefix.  The table rides
    in scalar-prefetch SMEM so each grid step's K/V DMA is page-gathered.
    """
    B, H, D = q.shape
    ps, Hkv = k_q.shape[1], k_q.shape[2]
    G = H // Hkv
    n_p = table.shape[1]
    qg = q.reshape(B, Hkv, G, D)
    table = jnp.asarray(table, jnp.int32)
    base = jnp.broadcast_to(jnp.asarray(base, jnp.int32).reshape(-1), (B,))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_paged_kernel, n_p=n_p, ps=ps, window=window)
    kv_spec = pl.BlockSpec((1, ps, Hkv, D),
                           lambda b, p, tbl, bs, ln: (tbl[b, p], 0, 0, 0))
    sc_spec = pl.BlockSpec((1, ps, Hkv),
                           lambda b, p, tbl, bs, ln: (tbl[b, p], 0, 0))
    q_spec = pl.BlockSpec((1, Hkv, G, D),
                          lambda b, p, tbl, bs, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_p),
        in_specs=[q_spec, kv_spec, sc_spec, sc_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=_scratch(Hkv, G, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=interpret,
    )(table, base, length, qg, k_q, k_scale, k_zero, v)
    return out.reshape(B, H, D)

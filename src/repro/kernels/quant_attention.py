"""Pallas decode attention with fused KV dequantization (paper C1 + C5).

One query token per sequence attends over the quantized cache:
int8 keys (per-token/head asymmetric scales) and fp8 values are dequantized
*inside* the kernel — HBM traffic is the quantized bytes, which is the whole
point of the paper's KV quantization in the memory-bound decode phase.

Mixed precision per the paper: the query arrives pre-scaled by 1/sqrt(D);
softmax runs in fp32 (online, flash-decoding style over S blocks).

Grid (B, nS) with S innermost.  Each step DMAs one ``[bs, Hkv, D]`` KV
block holding every KV head — the dense cache's native ``[.., S, Hkv, D]``
layout, so the block's last two dims are the full (Hkv, D) extent the TPU
tiling accepts — and loops the heads in the kernel; per-head online-softmax
state (m, l, acc) lives in VMEM scratch across the S steps.  Valid-prefix
lengths ride in SMEM as a [B] vector (continuous batching: every slot
decodes at its own offset); a scalar/[1] length broadcasts to all rows.

``paged_quant_decode_attention`` is the page-table variant for the paged
KV pool (core/kv_pool.py): the grid walks the table's *logical* pages and
a scalar-prefetched per-row page table translates each one to its physical
pool page in the BlockSpec index map.  The pool is head-major
(``[L, P, Hkv, page, D]``, key scales as lane-dense rows
``[L, P, Hkv, 1, page]``) and the kernel takes the whole layer stack, the
layer to read riding in SMEM beside the table, so nothing is sliced or
re-laid out before the call.  A per-row ``base`` page offset +
static ``window`` serve the sliding-window ring views.  Only a row's live
pages — those holding a key its length and window let it see — are
fetched and computed: the index maps clamp every other step onto the
nearest live page (consecutive steps naming one block issue no DMA) and
the body skips it.  Skipping keeps the result bitwise: a wholly masked
block after a live one folds in ``p = exp(-1e30 - m) = 0`` under
``corr = 1``, and one before the first live block is wiped by that
block's ``corr = exp(-1e30 - m) = 0``, so the live blocks alone give the
same bits.  The body is the same ``attend_block`` as the dense kernel at
block_s == page_size, so the two are bitwise equal on every row with a
key; a row with none finalizes to zeros.
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


def attend_block(q_ref, k_of, v_of, ks_col, kz_col, m_ref, l_ref,
                 acc_ref, valid):
    """One KV block for every head: q_ref [1, Hkv, G, D] pre-scaled;
    ``k_of(h)`` / ``v_of(h)`` give head h's int8 keys / values [bs, D],
    ``ks_col(h)`` / ``kz_col(h)`` its key scale / zero columns [bs, 1];
    ``valid`` [1, bs] masks key positions (shared by all heads)."""
    for h in range(q_ref.shape[1]):
        k = dequant_keys_block(k_of(h), ks_col(h), kz_col(h))   # [bs, D]
        v = v_of(h).astype(jnp.float32)
        s = jax.lax.dot_general(
            q_ref[0, h].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [G, bs]
        online_softmax_update(h, jnp.where(valid, s, NEG_INF), v,
                              m_ref, l_ref, acc_ref)


def page_scale_columns(ks_ref, kz_ref):
    """A pool page's key scales and zero points, stored as rows
    ``[1, Hkv, 1, page]`` (lane-dense), as the per-key columns the
    dequantization takes: head h's scale column is ``cols[:, h:h + 1]``,
    its zero column ``cols[:, Hkv + h:Hkv + h + 1]``.  The 2 Hkv rows
    are set into an 8-row-aligned slab and turned by one transpose."""
    hkv, ps = ks_ref.shape[1], ks_ref.shape[-1]
    rows = [ks_ref[0, h] for h in range(hkv)] + \
        [kz_ref[0, h] for h in range(hkv)]               # [1, ps] each
    n = -(-len(rows) // 8) * 8
    at = jax.lax.broadcasted_iota(jnp.int32, (n, ps), 0)
    slab = jnp.zeros((n, ps), jnp.float32)
    for i, r in enumerate(rows):
        slab = jnp.where(at == i, r, slab)
    return slab.T                                         # [ps, n]


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
    ks_all, kz_all = ks_ref[0], kz_ref[0]           # [bs, Hkv]
    attend_block(q_ref, lambda h: kq_ref[0, :, h, :],
                 lambda h: v_ref[0, :, h, :],
                 lambda h: ks_all[:, h:h + 1], lambda h: kz_all[:, h:h + 1],
                 m_ref, l_ref, acc_ref, pos < len_ref[b_idx])

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
        name="quant_decode_attention",
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


def _live_pages(b, base_ref, len_ref, *, ps: int, window: int):
    """Row ``b``'s live table columns [first, last]: the pages whose keys
    [(base + p) ps, (base + p + 1) ps) meet the visible span
    [max(0, length - window) (0 without a window), length).  Empty
    (last < first) for a row of length 0."""
    length, base = len_ref[b], base_ref[b]
    lo = jnp.maximum(length - window, 0) if window else jnp.int32(0)
    first = jax.lax.div(lo, ps) - base
    last = jax.lax.div(jnp.maximum(length, 1) - 1, ps) - base
    return first, jnp.where(length > lo, last, first - 1)


def _live_page(b, p, tbl, base_ref, len_ref, *, n_p: int, ps: int,
               window: int):
    """Physical page the grid step (b, p) fetches: its own while live,
    else the nearest live one, so the dead steps around a row's live
    range name the block already in VMEM and the pipeline skips them."""
    first, last = _live_pages(b, base_ref, len_ref, ps=ps, window=window)
    col = jnp.minimum(jnp.maximum(p, first), last)
    return tbl[b, jnp.minimum(jnp.maximum(col, 0), n_p - 1)]


def _paged_kernel(layer_ref, table_ref, base_ref, len_ref, q_ref, kq_ref,
                  ks_ref, kz_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, n_p: int, ps: int, window: int):
    b_idx = pl.program_id(0)
    p_idx = pl.program_id(1)
    hkv = kq_ref.shape[1]

    @pl.when(p_idx == 0)
    def _init():
        init_softmax_state(m_ref, l_ref, acc_ref)

    first, last = _live_pages(b_idx, base_ref, len_ref, ps=ps, window=window)

    @pl.when((p_idx >= first) & (p_idx <= last))
    def _attend():
        # logical position of each key in this page (the index map
        # already translated logical page base_ref[b] + p_idx to its
        # physical page)
        pos = ((base_ref[b_idx] + p_idx) * ps
               + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1))
        length = len_ref[b_idx]
        valid = (pos >= 0) & (pos < length)
        if window:
            valid = valid & (pos >= length - window)
        cols = page_scale_columns(ks_ref, kz_ref)
        attend_block(q_ref, lambda h: kq_ref[0, h], lambda h: v_ref[0, h],
                     lambda h: cols[:, h:h + 1],
                     lambda h: cols[:, hkv + h:hkv + h + 1],
                     m_ref, l_ref, acc_ref, valid)

    @pl.when(p_idx == n_p - 1)
    def _done():
        finalize_softmax(o_ref, l_ref, acc_ref)


def paged_quant_decode_attention(q: jax.Array, k_q: jax.Array,
                                 k_scale: jax.Array, k_zero: jax.Array,
                                 v: jax.Array, table: jax.Array,
                                 base: jax.Array, length: jax.Array, *,
                                 layer, window: int = 0,
                                 interpret: bool) -> jax.Array:
    """Decode attention over the paged pool via a per-row page table.

    q: f32 [B, H, D] pre-scaled; pool arrays stacked over layers, k_q / v
    [L, P, Hkv, page, D] and k_scale / k_zero [L, P, Hkv, 1, page], with
    ``layer`` the one to read (no layer is sliced out: the index maps
    pick it); table: int32 [B, n_pages] physical page per logical page
    (unallocated entries point at the trash page; only pages holding a
    visible key are fetched or computed); base: int32 [B] logical page
    index of table column 0 (ring views start mid-stream, possibly
    negative); length: int32 [B] valid prefix.  The layer and the table
    ride in scalar-prefetch SMEM so each grid step's K/V DMA is
    page-gathered: one dense [Hkv, page, D] block per leaf.
    """
    B, H, D = q.shape
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    Hkv, ps = k_q.shape[2], k_q.shape[3]
    G = H // Hkv
    n_p = table.shape[1]
    qg = q.reshape(B, Hkv, G, D)
    table = jnp.asarray(table, jnp.int32)
    base = jnp.broadcast_to(jnp.asarray(base, jnp.int32).reshape(-1), (B,))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_paged_kernel, n_p=n_p, ps=ps, window=window)
    page = functools.partial(_live_page, n_p=n_p, ps=ps, window=window)
    kv_spec = pl.BlockSpec((None, 1, Hkv, ps, D),
                           lambda b, p, ly, tbl, bs, ln:
                               (ly[0], page(b, p, tbl, bs, ln), 0, 0, 0))
    sc_spec = pl.BlockSpec((None, 1, Hkv, 1, ps),
                           lambda b, p, ly, tbl, bs, ln:
                               (ly[0], page(b, p, tbl, bs, ln), 0, 0, 0))
    q_spec = pl.BlockSpec((1, Hkv, G, D),
                          lambda b, p, ly, tbl, bs, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_p),
        in_specs=[q_spec, kv_spec, sc_spec, sc_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=_scratch(Hkv, G, D),
    )
    out = pl.pallas_call(
        kernel,
        name="quant_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        interpret=interpret,
    )(lyr, table, base, length, qg, k_q, k_scale, k_zero, v)
    return out.reshape(B, H, D)

"""Pallas prefill flash attention (causal / sliding-window, GQA).

The prefill-phase hot loop (paper §2.1: prefill is compute-bound): blockwise
Q.K^T with online softmax entirely in VMEM — the [T, S] score matrix never
touches HBM.  Mixed precision per C5: the query arrives pre-scaled, the
softmax state (m, l, acc) is fp32 scratch.

Grid (B, nQ, nK), K innermost.  Queries are laid out ``[B, Hkv, T*G, D]``
(row ``t*G + g`` is query ``t`` of group head ``g``), so a query tile is a
plain 2-D ``[bq*G, D]`` slab per KV head; each K/V block holds every KV
head (``[bk, Hkv, D]``, the cache's native layout) and the kernel loops the
heads.  The causal mask lets fully-masked K blocks short-circuit (pl.when)
— the TPU analogue of skipping upper triangle tiles.

``paged_flash_prefill_attention`` is the unified-prefill variant: a
prompt *chunk*'s queries (absolute positions pos0 + arange) attend over
the row's quantized KV pool pages through a scalar-prefetched page table
— the same BlockSpec gather scheme as quant_attention's paged decode
kernel, with the decode kernels' fused int8-key dequant and online-softmax
update.  Causally-dead pages (page start beyond the chunk's last query)
short-circuit, so a chunk early in a long prompt touches only the pages it
can see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_attention import (NEG_INF, dequant_keys_block,
                                           finalize_softmax,
                                           init_softmax_state,
                                           online_softmax_update,
                                           page_scale_columns)

# rows of a paged prefill query tile (bq queries x G query heads a KV head):
# at glm4-9b's G = 16 a 128-query tile's softmax state and output blocks
# would pass the 16 MB of scoped VMEM
MAX_QUERY_ROWS = 1024


def _query_mask(q_start, k_start, rows: int, bk: int, G: int, *,
                seq_len: int = 0, window: int = 0, causal: bool = True):
    """Visibility of keys ``k_start + j`` to query rows ``r`` of a tile
    whose first query sits at ``q_start`` (row r is query r // G).  The
    causal / window tests are rewritten with the row index times G so the
    kernel needs no vector integer division:
    ``kpos <= qpos  <=>  G * (kpos - q_start) <= r`` and
    ``qpos - kpos < window  <=>  r < G * (window + kpos - q_start)``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
    rel = k_start - q_start + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
    mask = jnp.ones((rows, bk), jnp.bool_)
    if seq_len:
        mask &= rel + q_start < seq_len
    if causal:
        mask &= G * rel <= r
    if window:
        mask &= r < G * (window + rel)
    return mask


def _attend_heads(q_ref, k_block, v_block, m_ref, l_ref, acc_ref, mask):
    """Every KV head of one block: ``k_block(h)`` / ``v_block(h)`` give the
    f32 [bk, D] keys / values of head h; q_ref [1, Hkv, rows, D]."""
    for h in range(q_ref.shape[1]):
        s = jax.lax.dot_general(
            q_ref[0, h].astype(jnp.float32), k_block(h),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [rows, bk]
        online_softmax_update(h, jnp.where(mask, s, NEG_INF), v_block(h),
                              m_ref, l_ref, acc_ref)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, n_k: int, bq: int, bk: int, G: int, seq_len: int,
            window: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        init_softmax_state(m_ref, l_ref, acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # visible iff any (qpos >= kpos) in the tile
    needed = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(needed)
    def _block():
        mask = _query_mask(q_start, k_start, bq * G, bk, G, seq_len=seq_len,
                           window=window, causal=causal)
        _attend_heads(q_ref,
                      lambda h: k_ref[0, :, h, :].astype(jnp.float32),
                      lambda h: v_ref[0, :, h, :].astype(jnp.float32),
                      m_ref, l_ref, acc_ref, mask)

    @pl.when(ki == n_k - 1)
    def _done():
        finalize_softmax(o_ref, l_ref, acc_ref)


def _group_queries(q: jax.Array, Hkv: int) -> jax.Array:
    """[B, T, H, D] -> [B, Hkv, T*G, D] (row t*G + g)."""
    B, T, H, D = q.shape
    G = H // Hkv
    return q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, T * G, D)


def _ungroup_queries(out: jax.Array, T: int, G: int) -> jax.Array:
    """Inverse of ``_group_queries``, keeping the first T queries."""
    B, Hkv, TG, D = out.shape
    out = out.reshape(B, Hkv, TG // G, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, TG // G, Hkv * G, D)[:, :T]


def flash_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool = True, window: int = 0,
                            bq: int = 128, bk: int = 256,
                            interpret: bool) -> jax.Array:
    """q: [B, T, H, D] PRE-SCALED (C5); k/v: [B, S, Hkv, D].
    Returns [B, T, H, D] fp32."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bq = min(bq, T)
    bk = min(bk, S)
    padq = (-T) % bq
    padk = (-S) % bk
    if padq:
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
    if padk:
        k = jnp.pad(k, ((0, 0), (0, padk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padk), (0, 0), (0, 0)))
    Tp, Sp = q.shape[1], k.shape[1]
    nq, nk = Tp // bq, Sp // bk

    kernel = functools.partial(_kernel, n_k=nk, bq=bq, bk=bk, G=G,
                               seq_len=S, window=window, causal=causal)
    q_spec = pl.BlockSpec((1, Hkv, bq * G, D), lambda b, i, j: (b, 0, i, 0))
    kv_spec = pl.BlockSpec((1, bk, Hkv, D), lambda b, i, j: (b, j, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="flash_prefill_attention",
        grid=(B, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Tp * G, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),
            pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),
            pltpu.VMEM((Hkv, bq * G, D), jnp.float32),
        ],
        interpret=interpret,
    )(_group_queries(q, Hkv), k, v)
    return _ungroup_queries(out, T, G)


def _paged_prefill_kernel(layer_ref, table_ref, pos0_ref, q_ref, kq_ref,
                          ks_ref, kz_ref, v_ref, o_ref, m_ref, l_ref,
                          acc_ref, *, n_p: int, bq: int, ps: int, G: int):
    b_idx = pl.program_id(0)
    qi = pl.program_id(1)
    pi = pl.program_id(2)
    hkv = kq_ref.shape[1]

    @pl.when(pi == 0)
    def _init():
        init_softmax_state(m_ref, l_ref, acc_ref)

    q_start = pos0_ref[b_idx] + qi * bq       # absolute chunk positions
    k_start = pi * ps                          # logical page positions
    # causally dead iff the page starts beyond the chunk's last query
    needed = k_start <= q_start + bq - 1

    @pl.when(needed)
    def _block():
        mask = _query_mask(q_start, k_start, bq * G, ps, G)
        cols = page_scale_columns(ks_ref, kz_ref)
        _attend_heads(
            q_ref,
            lambda h: dequant_keys_block(kq_ref[0, h], cols[:, h:h + 1],
                                         cols[:, hkv + h:hkv + h + 1]),
            lambda h: v_ref[0, h].astype(jnp.float32),
            m_ref, l_ref, acc_ref, mask)

    @pl.when(pi == n_p - 1)
    def _done():
        finalize_softmax(o_ref, l_ref, acc_ref)


def paged_flash_prefill_attention(q: jax.Array, k_q: jax.Array,
                                  k_scale: jax.Array, k_zero: jax.Array,
                                  v: jax.Array, table: jax.Array,
                                  pos0: jax.Array, *, layer, bq: int = 128,
                                  interpret: bool) -> jax.Array:
    """Prompt-chunk attention over the paged quantized KV pool.

    q: [B, C, H, D] PRE-SCALED queries at absolute positions
    pos0[b] + arange(C) — the chunk's K/V must already be appended to the
    pool.  Pool arrays, stacked over layers: k_q int8 / v fp8 or bf16
    [L, P, Hkv, page, D], k_scale/k_zero f32 [L, P, Hkv, 1, page], with
    ``layer`` the one to read; table: int32 [B, pages_per_row]
    (unallocated entries point at the trash page — they are causally
    masked).  The layer and the table ride in scalar-prefetch SMEM so
    each grid step's K/V DMA is page-gathered, exactly like the paged
    decode kernel.  Returns [B, C, H, D] f32.
    """
    B, C, H, D = q.shape
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    Hkv, ps = k_q.shape[2], k_q.shape[3]
    G = H // Hkv
    n_p = table.shape[1]
    bq = min(bq, C)
    while bq * G > MAX_QUERY_ROWS and bq > 8:
        bq //= 2          # a query tile's rows (and its f32 state) fit VMEM
    padq = (-C) % bq
    if padq:            # padded queries attend real keys; outputs sliced off
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
    Cp = q.shape[1]
    nq = Cp // bq
    table = jnp.asarray(table, jnp.int32)
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_paged_prefill_kernel, n_p=n_p, bq=bq, ps=ps,
                               G=G)
    q_spec = pl.BlockSpec((1, Hkv, bq * G, D),
                          lambda b, i, j, ly, tbl, p0: (b, 0, i, 0))
    kv_spec = pl.BlockSpec((None, 1, Hkv, ps, D),
                           lambda b, i, j, ly, tbl, p0:
                               (ly[0], tbl[b, j], 0, 0, 0))
    sc_spec = pl.BlockSpec((None, 1, Hkv, 1, ps),
                           lambda b, i, j, ly, tbl, p0:
                               (ly[0], tbl[b, j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nq, n_p),
        in_specs=[q_spec, kv_spec, sc_spec, sc_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),   # running max
            pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hkv, bq * G, D), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Cp * G, D), jnp.float32),
        interpret=interpret,
    )(lyr, table, pos0, _group_queries(q, Hkv), k_q, k_scale, k_zero, v)
    return _ungroup_queries(out, C, G)

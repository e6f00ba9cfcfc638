"""Block-paged quantized KV pool (vLLM's PagedAttention move, on the
paper's quantized-KV substrate).

The per-slot caches the continuous-batching engine inherited from PR 1
reserve ``max_seq`` contiguous tokens per slot, so admission pays the
worst case up front.  This module stores KV in fixed-size *pages* instead:

* ``PagedLayerKV`` — one layer's page pool.  Pages keep the existing
  quant scheme (asymmetric int8/int4 keys per (token, head), fp8 values,
  paper Fig. 3), cut into ``page_size``-token pages and laid out head
  before token (``k_q [P, H_kv, page, D]``, key scales ``[P, H_kv, 1,
  page]``) so no leaf pads a few KV heads out to a tile.  The last page
  of a full-attention pool is a *trash page*: page-table entries of
  unallocated logical pages point at it, so appends from empty slots and
  prefill scatters of short prompts need no masking — the bytes land in
  the trash and reads never reference it (validity comes from ``pos``).
* page table — ``[B, pages_per_row]`` int32 physical page ids per decode
  row, shared by every full-attention layer (all layers append the same
  token positions).  The table is an ordinary array input to the jitted
  steps: allocation changes never re-trace.
* ``KVPoolManager`` — the host-side allocator: free-list allocation,
  allocate-on-append at page boundaries, copy-free reclaim (freeing a row
  returns its page ids; no bytes move), and DRAM/Flash residency
  accounting for the spill tier (serving/engine.py spills preempted rows'
  pages through ``hybrid_storage.PageSpillStore``).  Pages carry a
  *refcount*: full prompt-prefix pages are registered in a token-hash
  chain index after prefill, and later requests with the same prompt
  prefix adopt those pages copy-free (``alloc_row`` with ``token_ids``).
  The index holds one pin per registered page, so prefix pages survive
  EOS (``free_row`` is a refcount decrement) and are evicted lazily when
  the free list runs short.

Prompt KV is written straight into pages (``append_paged_prompt``) — there
is no dense ``max_seq`` transient at join time — and chunk prefill
attention reads the pages back through the table
(``paged_prefill_attention_ref``), which is what makes chunked prefill
bitwise identical to a monolithic prefill.

Sliding-window layers need no table at all: their pages are a fixed
per-row ring — position ``p`` lives in ring page ``(p // page) % ppw`` —
so "dropping pages older than window" is just the modular index
recycling the oldest page.  This replaces the dense ring-slot special
case for the paged decode path.

``paged_decode_attention_ref`` mirrors ``attention.decode_attention_ref``
op for op, so a paged full-attention decode is *bitwise identical* to the
dense-cache decode on the reference backend (the parity tests assert
exactly that).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kv_cache as kvc
from repro.core import quantization as q
from repro.core.precision import DEFAULT_POLICY, PrecisionPolicy

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PoolGeometry:
    """Pool shape decided once by the ExecutionPlan (runtime/plan.py):
    ``page_size`` tokens per page, ``num_pages`` allocatable device pages
    (the trash page is extra), ``pages_per_row`` table width
    (= max_seq / page_size).  ``staging_pages`` is the DRAM staging
    reserve for the proactive Flash spill tier: extra device pages —
    beyond the trash page — that Flash-resident cold pages are gathered
    into before the paged kernels run, so the kernels themselves never
    know a page was ever cold."""
    page_size: int
    num_pages: int
    pages_per_row: int
    staging_pages: int = 0

    @property
    def trash_page(self) -> int:
        return self.num_pages

    @property
    def staging_base(self) -> int:
        """First staging physical page id (staging sits past the trash
        page: [staging_base, staging_base + staging_pages))."""
        return self.num_pages + 1

    @property
    def total_device_pages(self) -> int:
        return self.num_pages + 1 + self.staging_pages

    @property
    def max_seq(self) -> int:
        return self.page_size * self.pages_per_row

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)


@dataclasses.dataclass(frozen=True)
class SpillPolicy:
    """Proactive-spill knobs the ExecutionPlan owns (runtime/plan.py
    ``kv_spill_policy``), next to the pool geometry:

    * ``staging_pages``     — DRAM staging reserve size (mirrors the
      geometry; the per-row Flash residency cap, since a row must be able
      to stage every cold page for one decode wave).
    * ``hot_pages``         — trailing full pages per row that never
      spill (the paper's "window" of hot context near the tail).
    * ``low_watermark``     — free-page level below which the engine
      proactively spills cold pages of running rows.
    * ``high_watermark``    — free-page target the proactive spill
      refills to.
    * ``flash_budget_pages``— cap on total pages resident on Flash
      (admission may oversubscribe DRAM up to this).
    """
    staging_pages: int
    hot_pages: int
    low_watermark: int
    high_watermark: int
    flash_budget_pages: int


# Per-(row, logical page) residency states for the proactive spill tier.
RES_DRAM, RES_FLASH, RES_INFLIGHT, RES_STAGED = range(4)


def pages_per_window(window: int, page_size: int) -> int:
    """Ring length (in pages) for a sliding-window layer.  One extra page
    beyond ceil(window/page) guarantees a key is never recycled while the
    window mask can still reach it (the newest page is partially filled)."""
    if window % page_size == 0:
        return window // page_size + 1
    return window // page_size + 2


# ---------------------------------------------------------------------------
# The paged layer pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedLayerKV:
    """A stack of layers' paged quantized KV pools ([L, ...] along the
    scan axis), addressed at one layer.

    Head-major pages, so that no leaf has the KV head count as its minor
    or second-minor dimension (the TPU tiles those two; a dimension of 2
    there pads to a full tile):

    k_q:    int8 [L, P, H_kv, page, D]     (key_bits=8)
            int8 [L, P, H_kv, page, D//2]  (key_bits=4, nibble pairs)
    k_scale:fp32 [L, P, H_kv, 1, page]
    k_zero: fp32 [L, P, H_kv, 1, page]
    v:      fp8  [L, P, H_kv, page, D]
    layer:  int32 scalar (traced in the layer scan): every read and
            write goes to that layer in place, so the scan carries the
            whole stacked pool
    window: static; 0 => table-addressed full-attention pool,
            else per-row ring of ``ppw`` pages
    """
    k_q: Array
    k_scale: Array
    k_zero: Array
    v: Array
    layer: Array
    window: int = 0
    key_bits: int = 8
    ppw: int = 0                      # pages per window ring (window > 0)
    staging: int = 0                  # staging pages past the trash page

    def tree_flatten(self):
        return ((self.k_q, self.k_scale, self.k_zero, self.v, self.layer),
                (self.window, self.key_bits, self.ppw, self.staging))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, window=aux[0], key_bits=aux[1], ppw=aux[2],
                   staging=aux[3])

    @property
    def page_size(self) -> int:
        return self.k_q.shape[-2]

    @property
    def kv_heads(self) -> int:
        return self.k_q.shape[-3]

    @property
    def num_pages(self) -> int:
        return self.k_q.shape[-4]

    def fields(self) -> Dict[str, Array]:
        return {f: getattr(self, f) for f in KV_FIELDS}

    def replace(self, **fields) -> "PagedLayerKV":
        return dataclasses.replace(self, **fields)


KV_FIELDS = ("k_q", "k_scale", "k_zero", "v")


def at_layer(pool: PagedLayerKV, layer) -> PagedLayerKV:
    """The stacked pool addressed at ``layer`` (reads and writes in place)."""
    return pool.replace(layer=jnp.asarray(layer, jnp.int32))


def layer_arrays(pool: PagedLayerKV) -> Tuple[Array, Array, Array, Array]:
    """(k_q, k_scale, k_zero, v) of the addressed layer, [P, ...] each."""
    return tuple(jax.lax.dynamic_index_in_dim(x, pool.layer, 0, False)
                 for x in (pool.k_q, pool.k_scale, pool.k_zero, pool.v))


def logical_bytes(pool: PagedLayerKV) -> int:
    """Bytes the pool's leaves hold, with no padding."""
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in pool.fields().values())


def init_paged_layer(geom: PoolGeometry, kv_heads: int, head_dim: int, *,
                     layers: int = 1, batch: int = 0, window: int = 0,
                     key_bits: int = 8, value_fp8: bool = True
                     ) -> PagedLayerKV:
    """Zero-initialized pool.  Full-attention pools hold
    ``geom.num_pages + 1 + geom.staging_pages`` pages (the +1 is the
    trash page; staging pages sit past it and receive cold pages gathered
    back from Flash); windowed pools hold a fixed ``batch * ppw`` ring.
    ``layers`` stack on a leading scan axis, addressed at layer 0."""
    ps = geom.page_size
    ppw = pages_per_window(window, ps) if window else 0
    pages = batch * ppw if window else geom.total_device_pages
    vdt = q.FP8_DTYPE if value_fp8 else jnp.bfloat16
    kd = head_dim // 2 if key_bits == 4 else head_dim
    return PagedLayerKV(
        k_q=jnp.zeros((layers, pages, kv_heads, ps, kd), jnp.int8),
        k_scale=jnp.ones((layers, pages, kv_heads, 1, ps), jnp.float32),
        k_zero=jnp.zeros((layers, pages, kv_heads, 1, ps), jnp.float32),
        v=jnp.zeros((layers, pages, kv_heads, ps, head_dim), vdt),
        layer=jnp.zeros((), jnp.int32), window=window, key_bits=key_bits, ppw=ppw,
        staging=0 if window else geom.staging_pages)


def _page_major(kq, ks, kz, v):
    """Token-major [..., H, D] / [..., H] pieces of one row's tokens
    [n, H, ...] -> the pool's head-major page pieces [H, n, D] and
    [H, 1, n]."""
    return (jnp.swapaxes(kq, 0, 1), jnp.swapaxes(ks, 0, 1)[:, None],
            jnp.swapaxes(kz, 0, 1)[:, None], jnp.swapaxes(v, 0, 1))


def _write(pool: PagedLayerKV, page, off, pieces) -> PagedLayerKV:
    """Write head-major ``pieces`` (k_q/v [H, n, D], scales [H, 1, n])
    into page ``page`` from token offset ``off``, one dynamic update
    slice per leaf, in place at the addressed layer."""
    out = {}
    for f, x in zip(KV_FIELDS, pieces):
        big = getattr(pool, f)
        scale = f in ("k_scale", "k_zero")
        idx = (pool.layer, page, 0, 0, off) if scale \
            else (pool.layer, page, 0, off, 0)
        x = x.astype(big.dtype).reshape((1, 1) + x.shape)
        out[f] = jax.lax.dynamic_update_slice(
            big, x, tuple(jnp.asarray(i, jnp.int32) for i in idx))
    return pool.replace(**out)


def _read_page(pool: PagedLayerKV, page) -> Tuple[Array, ...]:
    """One physical page of the addressed layer: (k_q, k_scale, k_zero,
    v), head-major."""
    out = []
    for x in layer_arrays(pool):
        out.append(jax.lax.dynamic_index_in_dim(
            x, jnp.asarray(page, jnp.int32), 0, False))
    return tuple(out)


def append_paged(pool: PagedLayerKV, k_new: Array, v_new: Array, pos: Array,
                 table: Optional[Array]) -> PagedLayerKV:
    """Append one decode token per row at per-row positions ``pos`` [B].

    Full-attention pools route through ``table`` [B, pages_per_row]
    (unallocated rows point at the trash page); windowed pools compute
    their ring page from the position — trivial page recycling.
    Quantization is identical to the dense ``kv_cache.append``, so the
    stored bytes match the dense path bit for bit.  Each row's token is
    one dynamic update slice per leaf, so a donated pool is written in
    place (a scatter would make XLA re-lay the whole pool out for it).
    """
    b, t, h, d = k_new.shape
    assert t == 1, "paged append is the decode hot path (one token per row)"
    ps = pool.page_size
    kq, ks, kz = kvc.quantize_keys(k_new, bits=pool.key_bits)
    v_cast = kvc.cast_values(v_new, pool.v.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    rows = jnp.arange(b)
    if pool.window:
        page = rows * pool.ppw + jnp.mod(pos // ps, pool.ppw)
    else:
        page = table[rows, pos // ps]
    off = jnp.mod(pos, ps)
    for r in range(b):
        pool = _write(pool, page[r], off[r],
                      _page_major(kq[r], ks[r], kz[r], v_cast[r]))
    return pool


def _to_token_major(kq, ks, kz, v):
    """Gathered pages [B, n, H, page, ...] -> the dense layout
    [B, n * page, H, ...] the reference attention reads."""
    def kv(x):
        x = jnp.swapaxes(x, 2, 3)
        return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])

    def sc(x):
        x = jnp.swapaxes(x[:, :, :, 0], 2, 3)
        return x.reshape(x.shape[0], x.shape[1] * x.shape[2], x.shape[3])

    return kv(kq), sc(ks), sc(kz), kv(v)


def gather_pages(pool: PagedLayerKV, table: Array
                 ) -> Tuple[Array, Array, Array, Array]:
    """Page-table-indexed dense read view of the addressed layer: gather
    each row's pages in logical order -> [B, n_pages*page, H, ...] (the
    dense layout, so the reference attention math is unchanged)."""
    return _to_token_major(*(x[table] for x in layer_arrays(pool)))


def ring_view(pool: PagedLayerKV, pos: Array, batch: int
              ) -> Tuple[Array, Array]:
    """Windowed layers: the per-row ring as a (table, base) pair in
    *logical page order*.  ``table`` [B, ppw] holds physical page ids,
    ``base`` [B] the logical page index of table column 0 (may be
    negative early on; those positions are masked)."""
    ppw, ps = pool.ppw, pool.page_size
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    cur = jnp.maximum(pos - 1, 0) // ps
    base = cur - (ppw - 1)
    rows = jnp.arange(batch)[:, None]
    table = rows * ppw + jnp.mod(base[:, None] + jnp.arange(ppw)[None], ppw)
    return table, base


def _merge_page(pool: PagedLayerKV, page, first, valid, padded
                ) -> PagedLayerKV:
    """Read-modify-write one physical page: token offset ``o`` takes chunk
    token ``first + o`` where ``valid[o]``, else keeps its bytes.
    ``padded``: the chunk's token-major pieces with ``page_size`` zero
    tokens on either side (so the slice never clamps where a token is
    valid)."""
    ps = pool.page_size
    new = _page_major(*(jax.lax.dynamic_slice_in_dim(x, first + ps, ps, 0)
                        for x in padded))
    merged = []
    for f, n, o in zip(KV_FIELDS, new, _read_page(pool, page)):
        m = valid[None, None, :] if f in ("k_scale", "k_zero") \
            else valid[None, :, None]
        merged.append(jnp.where(m, n.astype(o.dtype), o))
    return _write(pool, page, 0, merged)


def append_paged_prompt(pool: PagedLayerKV, k_new: Array, v_new: Array,
                        pos0: Array, table_row: Optional[Array] = None,
                        slot: Optional[Array] = None,
                        valid_len: Optional[Array] = None) -> PagedLayerKV:
    """Append a C-token prompt chunk for ONE row at positions
    [pos0, pos0 + C) — prompt KV goes straight into pages, no dense
    transient.  k_new/v_new: [1, C, H, D].  ``valid_len``: real tokens in
    a padded final chunk — windowed rings MUST clamp to it (a padded
    position wraps onto the ring page holding a real earlier key; the
    full-attention path needs no clamp because padded positions land in
    the trash page or causally-dead offsets).

    Every page the chunk can touch is read, merged with the chunk's
    tokens and written back whole, one dynamic update slice per leaf, so
    a donated pool is written in place.  Full-attention pools map the
    chunk's logical pages through ``table_row`` [pages_per_row]
    (positions past the table land in the trash page, whose bytes are
    never read, so colliding trash writes don't matter); windowed pools
    write row ``slot``'s ring pages with explicit winner selection: when
    the chunk wraps the ring, each ring page receives the *newest*
    logical page that lands on it.  Quantization matches the dense append
    bit for bit.
    """
    b, C, h, d = k_new.shape
    assert b == 1, "prompt chunks are per-row (B=1)"
    ps = pool.page_size
    kq, ks, kz = kvc.quantize_keys(k_new, bits=pool.key_bits)
    v_cast = kvc.cast_values(v_new, pool.v.dtype)
    pos0 = jnp.asarray(pos0, jnp.int32)
    padded = [jnp.pad(x[0], [(ps, ps)] + [(0, 0)] * (x.ndim - 2))
              for x in (kq, ks, kz, v_cast)]
    offs = jnp.arange(ps, dtype=jnp.int32)
    if pool.window:
        ppw = pool.ppw
        vl = C if valid_len is None else jnp.asarray(valid_len, jnp.int32)
        cur = jnp.maximum(pos0 + vl - 1, 0) // ps
        for r in range(ppw):
            # newest logical page <= cur on ring slot r; chunk tokens
            # outside that page keep the slot's existing bytes (they
            # are masked by the ring view's logical-page bounds)
            g = cur - jnp.mod(cur - r, ppw)
            qpos = g * ps + offs
            valid = (qpos >= pos0) & (qpos < pos0 + vl)
            page = jnp.asarray(slot, jnp.int32) * ppw + r
            pool = _merge_page(pool, page, g * ps - pos0, valid, padded)
        return pool
    n_p = table_row.shape[0]
    # pool arrays hold num_pages + 1 + staging pages; trash sits right
    # before the staging reserve
    trash = pool.num_pages - 1 - pool.staging
    first = pos0 // ps
    for j in range(-(-C // ps) + 1):
        logical = first + j
        page = jnp.where(logical < n_p,
                         table_row[jnp.clip(logical, 0, n_p - 1)], trash)
        tok = logical * ps - pos0 + offs
        pool = _merge_page(pool, page, logical * ps - pos0,
                           (tok >= 0) & (tok < C), padded)
    return pool


@functools.partial(jax.jit, donate_argnums=0)
def write_pages(big: Array, idx: Array, vals: Array) -> Array:
    """``big[:, idx[i]] = vals[:, i]`` for every i, in place: one stacked
    pool leaf [L, P, ...] (donated) takes whole pages back from the spill
    tier."""
    def one(i, acc):
        val = jax.lax.dynamic_slice_in_dim(vals, i, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(acc, val.astype(acc.dtype),
                                                   idx[i], axis=1)
    return jax.lax.fori_loop(0, idx.shape[0], one, big)


def paged_prefill_attention_ref(qh: Array, pool: PagedLayerKV, table: Array,
                                pos0: Array,
                                policy: PrecisionPolicy = DEFAULT_POLICY
                                ) -> Array:
    """Chunk prefill attention through the page table (pure-JAX reference;
    kernels/flash_prefill.paged_flash_prefill_attention is the fused TPU
    path).  qh: [1, C, H, D] pre-scaled queries at absolute positions
    [pos0, pos0 + C); table: [1, pages_per_row].

    Gathers the row's pages into the dense logical layout and runs the
    SAME blockwise ``flash_attention`` the dense prefill path uses, with
    the chunk's query offset.  Because per-query online softmax is
    independent of the query blocking and the gathered view always spans
    the full table (causally-dead pages mask to exact zeros), a chunked
    prefill is bitwise identical to a monolithic one.
    """
    from repro.models.attention import flash_attention   # lazy: they import us
    kq, ks, kz, v = gather_pages(pool, table)
    k = kvc.dequantize_keys(kq, ks, kz, policy.compute_dtype,
                            bits=pool.key_bits)
    return flash_attention(qh, k, v.astype(policy.compute_dtype),
                           causal=True, q_offset=jnp.asarray(pos0, jnp.int32),
                           policy=policy)


def paged_prefill_window_ref(qh: Array, pool: PagedLayerKV, slot: Array,
                             pos0: Array, valid_len: Array, window: int,
                             n_pages: int,
                             policy: PrecisionPolicy = DEFAULT_POLICY
                             ) -> Array:
    """Chunk prefill attention over a windowed per-row ring (pure-JAX
    reference) — the chunked counterpart of the roundtripped whole-prompt
    path.  qh: [1, C, H, D] pre-scaled queries at absolute positions
    [pos0, pos0 + C); ``valid_len``: real tokens in the (possibly padded)
    chunk; the chunk's K/V must already be appended to the ring;
    ``n_pages``: the row's logical page capacity (sizes the static
    position-ordered view).

    Scatters each live ring slot back to its *logical* page offset —
    position p lands at view index p, exactly the dense layout — and runs
    the SAME blockwise ``flash_attention`` the dense prefill path uses,
    with the chunk's query offset.  Never-written and recycled logical
    pages stay zero; every position a chunk query can reach is still in
    the ring PROVIDED every chunk is at most one page (the ring
    guarantees M >= window + page_size, so a <=page_size chunk never
    recycles an in-window key; runtime/plan.prefill_chunk_schedule
    enforces the cap), and all other view positions are causally dead or
    out of window — exact no-ops to the online softmax.  Because the
    ring quantizes per (position, head), the dequantized view holds the
    same bytes however the prompt was partitioned, so any chunk
    partition is bitwise-identical to the whole-prompt pass AND to the
    dense reference's roundtripped-KV attention."""
    from repro.models.attention import flash_attention   # lazy: they import us
    ppw, ps = pool.ppw, pool.page_size
    table = (jnp.asarray(slot, jnp.int32) * ppw + jnp.arange(ppw))[None]
    kq, ks, kz, v = gather_pages(pool, table)            # [1, M, Hkv, ...]
    pos0 = jnp.asarray(pos0, jnp.int32)
    end = pos0 + jnp.asarray(valid_len, jnp.int32)
    cur = jnp.maximum(end - 1, 0) // ps                  # newest logical page

    def to_logical(ring):
        """[1, M, ...] ring-lane order -> [1, n_pages*ps, ...] absolute
        position order (zeros where no live page maps)."""
        out = jnp.zeros((n_pages * ps,) + ring.shape[2:], ring.dtype)
        for r in range(ppw):
            g = cur - jnp.mod(cur - r, ppw)              # slot r's group
            start = jnp.maximum(g, 0) * ps
            prev = jax.lax.dynamic_slice_in_dim(out, start, ps, axis=0)
            vals = jnp.where(g >= 0, ring[0, r * ps:(r + 1) * ps], prev)
            out = jax.lax.dynamic_update_slice_in_dim(out, vals, start,
                                                      axis=0)
        return out[None]

    k = kvc.dequantize_keys(to_logical(kq), to_logical(ks), to_logical(kz),
                            policy.compute_dtype, bits=pool.key_bits)
    return flash_attention(qh, k,
                           to_logical(v).astype(policy.compute_dtype),
                           causal=True, window=window, q_offset=pos0,
                           policy=policy)


def paged_decode_attention_ref(qh: Array, pool: PagedLayerKV, table: Array,
                               base: Optional[Array], pos: Array,
                               policy: PrecisionPolicy = DEFAULT_POLICY
                               ) -> Array:
    """One-token attention over the paged pool (pure-JAX reference).

    Mirrors ``attention.decode_attention_ref`` op for op: gather the pages
    into the dense layout, then the identical einsum/softmax sequence —
    full-attention outputs are bitwise equal to the dense path.  ``base``
    is the logical page offset of table column 0 (ring views; None => 0).
    """
    B, T, H, D = qh.shape
    Hkv = pool.kv_heads
    G = H // Hkv
    kq, ks, kz, v = gather_pages(pool, table)
    k = kvc.dequantize_keys(kq, ks, kz, policy.compute_dtype,
                            bits=pool.key_bits)              # [B,S,Hkv,D]
    v = v.astype(policy.compute_dtype)
    s = jnp.einsum("btkgd,bskd->bkgts",
                   qh.reshape(B, T, Hkv, G, D).astype(policy.compute_dtype), k,
                   preferred_element_type=jnp.float32)       # [B,Hkv,G,1,S]
    S = k.shape[1]
    ps = pool.page_size
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    if base is None:
        kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    else:
        kpos = base[:, None] * ps + jnp.arange(S)[None]
    mask = (kpos >= 0) & (kpos < pos[:, None])
    if pool.window:
        mask = mask & (kpos >= pos[:, None] - pool.window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(policy.softmax_dtype), axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(policy.compute_dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(policy.compute_dtype)


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------

class KVPoolManager:
    """Free-list page allocator + page-table bookkeeping (host side).

    The device never sees this class — it sees the [B, pages_per_row]
    int32 table the manager maintains (``device_table``).  Reclaim is
    copy-free: freeing a row returns its page ids to the free list; the
    bytes stay where they are until a new allocation overwrites them.
    ``spilled_pages`` counts pages currently resident on Flash (the
    engine moves preempted rows' pages there via PageSpillStore).

    Prefix sharing: every page has a refcount.  After a prompt prefill
    completes, its *full* pages are registered under a token-hash chain
    (``register_prefix``) — the index holds one pin (+1) per page, so the
    pages outlive the request.  A later ``alloc_row`` with ``token_ids``
    walks the chain and adopts the longest indexed prefix copy-free
    (+1 per adopted page); adoption is capped at the prompt's second-last
    page so a request always computes at least its final token.  Rows
    never write into a page they adopted (chunks start past the shared
    prefix), so no copy-on-write is ever needed.  Index pins are evicted
    lazily — newest chains first — when the free list runs short.

    Proactive spill (running rows): every (row, logical page) carries a
    residency state — RES_DRAM (owns a pool page; ``row_pages`` holds its
    id), RES_FLASH (bytes live only on Flash; ``row_pages`` holds -1 and
    the table entry points at the trash page so dispatch never sees it),
    RES_INFLIGHT (a staging fetch is in flight; still invisible to
    dispatch) or RES_STAGED (bytes gathered into one of the
    ``geom.staging_pages`` staging device pages; the table entry points
    there, so the kernels read it like any other page).  Cold candidates
    (``cold_pages``) are oldest-first: only *full*, single-owner pages
    outside the trailing hot window — a page adopted by another row or
    pinned by the prefix index is never spilled.  Cold pages are
    immutable (decode only appends at the tail), so the Flash copy is
    authoritative: staging is a cache and eviction from it (``unstage``)
    needs no writeback.
    """

    def __init__(self, geom: PoolGeometry, num_slots: int,
                 prefix_sharing: bool = True):
        self.geom = geom
        self.num_slots = num_slots
        self.prefix_sharing = prefix_sharing
        # pop() hands out low page ids first — deterministic allocation
        self._free: List[int] = list(range(geom.num_pages - 1, -1, -1))
        self.table = np.full((num_slots, geom.pages_per_row),
                             geom.trash_page, np.int32)
        self.row_pages: List[List[int]] = [[] for _ in range(num_slots)]
        self.row_pos = np.zeros(num_slots, np.int64)
        self.refcount = np.zeros(geom.num_pages, np.int64)
        # prefix index: chain-digest <-> page, pages in registration order
        self._page_of_chain: Dict[bytes, int] = {}
        self._chain_of_page: Dict[int, bytes] = {}
        self._index_order: List[int] = []
        self.row_shared = np.zeros(num_slots, np.int64)   # adopted tokens
        self.spilled_pages = 0
        self.alloc_failures = 0
        self.prefix_hits = 0          # pages adopted copy-free (pages saved)
        self.prefix_misses = 0        # fresh prompt pages that found no match
        self.prefix_evictions = 0     # index pins dropped under pressure
        # proactive spill: per-(row, logical page) residency + the staging
        # reserve (LIFO free list of staging device pages; LRU over staged)
        self.row_res: List[List[int]] = [[] for _ in range(num_slots)]
        self._staging_free: List[int] = list(
            range(geom.staging_base + geom.staging_pages - 1,
                  geom.staging_base - 1, -1))
        self._staged: Dict[Tuple[int, int], int] = {}   # (row, idx) -> page
        self._stage_lru: List[Tuple[int, int]] = []     # oldest first
        self.cold_spills = 0          # pages of running rows moved to Flash

    # --- accounting --------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def reclaimable_pages(self) -> int:
        """Indexed pages held only by their index pin — evictable on
        demand to replenish the free list."""
        return sum(1 for p in self._chain_of_page if self.refcount[p] == 1)

    @property
    def available_pages(self) -> int:
        """Pages an allocation could obtain right now: free list plus
        index-only pages (admission accounts against this, so cached
        prefixes never block new work)."""
        return len(self._free) + self.reclaimable_pages

    @property
    def pages_in_use(self) -> int:
        return self.geom.num_pages - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return self.geom.pages_for(tokens)

    def pages_held(self, row: int) -> int:
        """Logical pages the row holds (DRAM + Flash-resident)."""
        return len(self.row_pages[row])

    def dram_pages_held(self, row: int) -> int:
        return sum(1 for p in self.row_pages[row] if p >= 0)

    def flash_idxs(self, row: int) -> List[int]:
        """Logical page indices of the row living off-DRAM (FLASH,
        IN_FLIGHT or STAGED) — the pages a decode step must stage."""
        return [i for i, s in enumerate(self.row_res[row])
                if s != RES_DRAM]

    def flash_pages_of(self, row: int) -> int:
        return len(self.flash_idxs(row))

    @property
    def flash_page_count(self) -> int:
        """Cold pages of *running* rows currently off-DRAM (preempted
        rows' pages are tracked by the spill store, not here)."""
        return sum(self.flash_pages_of(r) for r in range(self.num_slots))

    @property
    def staged_count(self) -> int:
        return len(self._staged)

    @property
    def staging_free(self) -> int:
        return len(self._staging_free)

    def residency(self) -> Dict[str, int]:
        return {"dram_pages": self.pages_in_use,
                "free_pages": self.free_pages,
                "flash_pages": self.spilled_pages + self.flash_page_count,
                "staged_pages": self.staged_count}

    # --- prefix index ------------------------------------------------------
    def _chain_keys(self, token_ids, salt: str) -> List[bytes]:
        """One index key per full page of the prompt: a chained SHA-256
        digest of (salt, every token through that page).  The digest
        commits to the page's entire history at O(page) work and O(1)
        memory per link, and a collision between different prefixes is
        cryptographically infeasible — so equal keys imply equal tokens
        and one prompt's KV pages are never served to another."""
        ps = self.geom.page_size
        h = hashlib.sha256(("kv-prefix:" + salt).encode()).digest()
        out = []
        for i in range(len(token_ids) // ps):
            page = np.asarray(token_ids[i * ps:(i + 1) * ps], np.int64)
            h = hashlib.sha256(h + page.tobytes()).digest()
            out.append(h)
        return out

    def _shareable_pages(self, n_tokens: int) -> int:
        """Adoption cap: full pages covering at most tokens [0, T-1) —
        the final prompt token is always computed so its logits exist."""
        return max(0, (int(n_tokens) - 1) // self.geom.page_size)

    def _lookup_chain(self, token_ids, salt: str) -> List[int]:
        if not self.prefix_sharing:
            return []
        pages = []
        cap = self._shareable_pages(len(token_ids))
        for key in self._chain_keys(token_ids, salt)[:cap]:
            p = self._page_of_chain.get(key)
            if p is None:
                break
            pages.append(p)
        return pages

    def probe_shared_pages(self, token_ids, salt: str = "") -> int:
        """Pages a fresh prompt would adopt from the index right now."""
        return len(self._lookup_chain(token_ids, salt))

    def probe_admission_discount(self, token_ids, salt: str = "") -> int:
        """Adoptable pages that cost the admission nothing: chain pages
        some *other row still holds* (refcount >= 2).  Index-only pins
        (refcount == 1) are NOT discounted — they are counted inside
        ``available_pages`` and adopting one converts it from reclaimable
        to pinned-in-use, so it must stay charged or two same-step
        admissions could oversubscribe the pool."""
        return sum(1 for p in self._lookup_chain(token_ids, salt)
                   if self.refcount[p] >= 2)

    def retract_prompt_stats(self, row: int, tokens: int) -> None:
        """Undo a row's adoption-statistics contribution when its prefill
        is restarted (freed and requeued under page pressure) — the
        re-admission will count the same prompt again, and the BENCH
        prefix numbers must not inflate per restart."""
        if not self.prefix_sharing:
            return
        adopted = int(self.row_shared[row]) // self.geom.page_size
        self.prefix_hits -= adopted
        self.prefix_misses -= max(0, self._shareable_pages(tokens) - adopted)

    def register_prefix(self, row: int, token_ids, salt: str = "") -> int:
        """Index the row's full prompt pages (call once its prefill has
        written them).  Already-indexed chain links — including pages this
        row itself adopted — are skipped.  Returns pages newly pinned."""
        if not self.prefix_sharing:
            return 0
        pages = self.row_pages[row]
        pinned = 0
        for i, key in enumerate(self._chain_keys(token_ids, salt)):
            if key in self._page_of_chain or i >= len(pages):
                continue
            p = pages[i]
            if p < 0 or p in self._chain_of_page:
                continue          # Flash-resident pages are never indexed
            self._page_of_chain[key] = p
            self._chain_of_page[p] = key
            self.refcount[p] += 1
            self._index_order.append(p)
            pinned += 1
        return pinned

    def _unpin(self, page: int) -> None:
        key = self._chain_of_page.pop(page)
        del self._page_of_chain[key]
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self._free.append(page)
        self.prefix_evictions += 1

    def _reserve(self, need: int) -> bool:
        """Make ``need`` pages available on the free list, evicting index
        pins (newest chains first — short prefixes survive longest)."""
        while len(self._free) < need:
            victim = next((p for p in reversed(self._index_order)
                           if p in self._chain_of_page
                           and self.refcount[p] == 1), None)
            if victim is None:
                return False
            self._index_order.remove(victim)
            self._unpin(victim)
        return True

    # --- transitions -------------------------------------------------------
    def alloc_row(self, row: int, tokens: int, token_ids=None,
                  salt: str = "", flash_idxs=()) -> bool:
        """Allocate the pages holding ``tokens`` for a fresh/restored row.
        All-or-nothing; fills the row's table prefix.  With ``token_ids``
        the longest indexed prompt prefix is adopted copy-free
        (refcount +1, no bytes move); ``row_shared[row]`` records the
        adopted token count so the engine starts prefill past it.
        ``flash_idxs``: logical pages that stay Flash-resident (a
        preempted row resuming with its cold pages left in Flash) — no
        DRAM page is allocated for them and their table entries stay on
        the trash page until staged."""
        assert not self.row_pages[row], f"row {row} still holds pages"
        total = self.pages_for(tokens)
        flash = set(int(i) for i in flash_idxs)
        assert all(0 <= i < total for i in flash), (flash, total)
        shared = self._lookup_chain(token_ids, salt) \
            if token_ids is not None else []
        assert not (shared and flash), "adoption and Flash restore never mix"
        need = total - len(flash)
        # take the adoption references BEFORE reserving: _reserve may evict
        # index pins, and an adopted page must never reach the free list
        for p in shared:
            self.refcount[p] += 1
        if not self._reserve(need - len(shared)):
            for p in shared:                  # roll back the adoption refs
                self.refcount[p] -= 1
                # an adopted page always keeps its index pin (_reserve only
                # evicts refcount==1 victims, and ours were >= 2)
                assert self.refcount[p] >= 1, f"page {p} lost its pin"
            self.alloc_failures += 1
            return False
        fresh = [self._free.pop() for _ in range(need - len(shared))]
        for p in fresh:
            assert self.refcount[p] == 0, f"page {p} on free list with refs"
            self.refcount[p] = 1
        it = iter(shared + fresh)
        pages, res = [], []
        for i in range(total):
            if i in flash:
                pages.append(-1)
                res.append(RES_FLASH)
                self.table[row, i] = self.geom.trash_page
            else:
                p = next(it)
                pages.append(p)
                res.append(RES_DRAM)
                self.table[row, i] = p
        self.row_pages[row] = pages
        self.row_res[row] = res
        self.row_shared[row] = len(shared) * self.geom.page_size
        self.prefix_hits += len(shared)
        if token_ids is not None:
            self.prefix_misses += self._shareable_pages(tokens) - len(shared)
        return True

    def ensure(self, row: int, pos: int) -> bool:
        """Allocate-on-append: make sure the page for an append at
        position ``pos`` exists.  False <=> the pool is out of pages (the
        engine spills cold pages / preempts a victim and retries)."""
        idx = int(pos) // self.geom.page_size
        held = self.row_pages[row]
        if idx < len(held):
            return True
        assert idx == len(held), (row, pos, len(held))
        if not self._reserve(1):
            self.alloc_failures += 1
            return False
        page = self._free.pop()
        self.refcount[page] = 1
        held.append(page)
        self.row_res[row].append(RES_DRAM)
        self.table[row, idx] = page
        return True

    def free_row(self, row: int) -> int:
        """Refcount-decrement reclaim: each of the row's DRAM pages loses
        one reference; pages reaching zero return to the free list
        (indexed prefix pages hold a pin, so they survive EOS and stay
        adoptable).  Staged/in-flight pages release their staging slot;
        Flash-resident pages are simply forgotten here — the engine drops
        their blobs from the spill store by uid.  Copy-free either way —
        no bytes move.  Returns pages actually freed."""
        pages = self.row_pages[row]
        freed = 0
        for i in reversed(range(len(pages))):
            p = pages[i]
            if p < 0:
                if self.row_res[row][i] in (RES_STAGED, RES_INFLIGHT):
                    key = (row, i)
                    self._staging_free.append(self._staged.pop(key))
                    self._stage_lru.remove(key)
                continue
            self.refcount[p] -= 1
            assert self.refcount[p] >= 0, f"double free of page {p}"
            if self.refcount[p] == 0:
                self._free.append(p)
                freed += 1
        self.row_pages[row] = []
        self.row_res[row] = []
        self.table[row, :] = self.geom.trash_page
        self.row_pos[row] = 0
        self.row_shared[row] = 0
        return freed

    # --- proactive spill: residency transitions ----------------------------
    def cold_pages(self, row: int, hot_pages: int = 1) -> List[int]:
        """Spill candidates for one row, oldest first: *full* pages (the
        partially-written tail never spills) outside the trailing
        ``hot_pages`` window, owned by exactly this row (refcount 1 — a
        page adopted by another row or pinned by the prefix index is
        never spilled), currently DRAM-resident."""
        ps = self.geom.page_size
        full = int(self.row_pos[row]) // ps
        out = []
        for i in range(min(full - hot_pages, len(self.row_pages[row]))):
            if self.row_res[row][i] != RES_DRAM:
                continue
            p = self.row_pages[row][i]
            if self.refcount[p] != 1 or p in self._chain_of_page:
                continue
            out.append(i)
        return out

    def spill_page(self, row: int, idx: int) -> int:
        """DRAM -> FLASH for one cold page.  The caller must have written
        the page's bytes to the spill store already (the DRAM page is
        reusable the moment this returns).  The table entry flips to the
        trash page — a Flash-resident page is never visible to dispatch.
        Returns the freed physical page id."""
        assert self.row_res[row][idx] == RES_DRAM, (row, idx)
        p = self.row_pages[row][idx]
        assert self.refcount[p] == 1 and p not in self._chain_of_page, \
            f"page {p} is shared/pinned — never spilled while adopted"
        self.refcount[p] = 0
        self._free.append(p)
        self.row_pages[row][idx] = -1
        self.row_res[row][idx] = RES_FLASH
        self.table[row, idx] = self.geom.trash_page
        self.cold_spills += 1
        return p

    def begin_stage(self, row: int, idx: int) -> Optional[int]:
        """FLASH -> IN_FLIGHT: claim a staging device page for a cold
        page (None <=> staging reserve exhausted — evict via
        ``stage_victim``/``unstage`` first).  The table entry stays on the
        trash page until ``commit_stage``: an in-flight page is never
        visible to dispatch.  Re-staging an already-STAGED page is an LRU
        touch and returns its staging page."""
        key = (row, idx)
        if self.row_res[row][idx] == RES_STAGED:
            self._stage_lru.remove(key)
            self._stage_lru.append(key)
            return self._staged[key]
        assert self.row_res[row][idx] == RES_FLASH, (row, idx)
        if not self._staging_free:
            return None
        sid = self._staging_free.pop()
        self._staged[key] = sid
        self._stage_lru.append(key)
        self.row_res[row][idx] = RES_INFLIGHT
        return sid

    def commit_stage(self, row: int, idx: int) -> None:
        """IN_FLIGHT -> STAGED: the bytes landed in the staging page —
        only now does the table entry point at it."""
        assert self.row_res[row][idx] == RES_INFLIGHT, (row, idx)
        self.row_res[row][idx] = RES_STAGED
        self.table[row, idx] = self._staged[(row, idx)]

    def unstage(self, row: int, idx: int) -> None:
        """STAGED -> FLASH: evict a page from the staging cache.  No
        writeback — cold pages are immutable, the Flash copy is the
        authority."""
        key = (row, idx)
        assert self.row_res[row][idx] == RES_STAGED, \
            f"cannot evict in-flight page {key}"
        self._staging_free.append(self._staged.pop(key))
        self._stage_lru.remove(key)
        self.row_res[row][idx] = RES_FLASH
        self.table[row, idx] = self.geom.trash_page

    def stage_victim(self, protect) -> Optional[Tuple[int, int]]:
        """LRU-oldest staged page not in ``protect`` (the set of pages
        the current decode wave needs resident)."""
        for key in self._stage_lru:
            if key not in protect \
                    and self.row_res[key[0]][key[1]] == RES_STAGED:
                return key
        return None

    def restore_page(self, row: int, idx: int) -> int:
        """FLASH/STAGED -> DRAM: give the page a pool page again (the
        caller writes the bytes back after).  -1 <=> no DRAM page could
        be reserved."""
        st = self.row_res[row][idx]
        assert st in (RES_FLASH, RES_STAGED), (row, idx, st)
        if st == RES_STAGED:
            self.unstage(row, idx)
        if not self._reserve(1):
            self.alloc_failures += 1
            return -1
        p = self._free.pop()
        self.refcount[p] = 1
        self.row_pages[row][idx] = p
        self.row_res[row][idx] = RES_DRAM
        self.table[row, idx] = p
        return p

    def device_table(self) -> Array:
        return jnp.asarray(self.table)

"""ExecutionPlan: load-time weight re-layout + tile selection + placement.

The paper's backend abstraction (§5.1) rearranges weights ONCE at load time
into the layout its kernels consume and picks tile sizes per matmul shape
with the Eq. 2-4 optimizer; at run time every hot op just dispatches.  The
TPU analogue built here:

* ``PackedLinear``   — a quantized linear weight in the kernel-native layout:
  int8 carrier with the reduction dim padded to the 128-lane grid and output
  channels padded to a 256 multiple (int4 packs two 128-lane halves of
  each 256-column group into one byte lane, and any solver tile divides
  the array).  Padding is zeros with scale=1/zero=0, so padded columns
  dequantize to exactly 0 and the asymmetric correction term is
  unaffected.
* ``MatmulPlan``     — per logical (K, N, bits) shape: the padded dims plus a
  lazily-filled cache of ``solve_tpu_blocks`` tilings per M bucket.
* ``ExecutionPlan``  — built once per model (``build_plan``): repacks every
  per-layer QuantizedTensor in the parameter tree, records the matmul plans,
  and records DRAM-vs-Flash placement via ``core/hybrid_storage`` (the
  embedding's 1/vocab per-step utilization sends it to Flash first — C2).

MoE expert tables ([L, E, K, N] leaves) repack into ``PackedExpertLinear``
— the same padded kernel-native layout with a leading expert axis, consumed
by the grouped Pallas kernel (``kernels/grouped_matmul.py``) via the
``"grouped_matmul"`` dispatch op; the expert axis stays directly indexable
for the selected-expert decode gathers and per-expert weight streaming.

Cost of packing on the reference backend: the reference matmul converts
the weight back to the interleaved ``QuantizedTensor`` layout and slices
the padding off (``unpack_linear``) inside every step — a per-step
repack the reference path accepts in exchange for sharing one parameter
tree with the kernels.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import hybrid_storage as HS
from repro.core import kv_pool
from repro.core import quantization as q
from repro.core import tiling

Array = jax.Array

LANE = 128            # minor-dim tiling the MXU wants (K alignment)
N_ALIGN = 2 * LANE    # output channels: nibble pairs stay lane-aligned
M_ALIGN = 8           # sublane alignment for the activation rows
M_BUCKET_CAP = 512    # largest M the tile solver is asked about


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedLinear:
    """A quantized linear weight in the kernel-native padded layout.

    data:  int8 [..., Kp, Np//2] (bits=4, 128-lane nibble halves along N —
           ``quantization.pack_int4_lanes``) or int8 [..., Kp, Np] (bits=8)
    scale: fp32 [..., g, Np]; zero: fp32 [..., g, Np]
    k, n:  the LOGICAL (unpadded) reduction / output dims — static aux, so
           scan/vmap slices of stacked PackedLinears keep them.
    """
    data: Array
    scale: Array
    zero: Array
    bits: int
    k: int
    n: int

    def tree_flatten(self):
        return (self.data, self.scale, self.zero), (self.bits, self.k, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, zero = children
        bits, k, n = aux
        return cls(data=data, scale=scale, zero=zero, bits=bits, k=k, n=n)

    @property
    def kp(self) -> int:
        return _ceil_to(self.k, LANE)

    @property
    def np_pad(self) -> int:
        return _ceil_to(self.n, N_ALIGN)


def pack_linear(qt: q.QuantizedTensor) -> PackedLinear:
    """Repack a QuantizedTensor into the kernel-native padded layout.

    Padding is exact: padded output columns get scale=1/zero=0 with q=0
    bytes, so they dequantize to 0; padded K rows hold q=0 and only ever
    multiply the zero-padded activation columns the dispatcher feeds in,
    contributing nothing to the accumulator or the activation row sum.
    """
    k, n = int(qt.shape[-2]), int(qt.shape[-1])
    kp, np_ = _ceil_to(k, LANE), _ceil_to(n, N_ALIGN)
    pad = [(0, 0)] * (qt.data.ndim - 2) + [(0, kp - k), (0, np_ - n)]
    if qt.bits == 4:
        # interleaved nibble pairs -> the kernels' 128-lane-half packing
        data = q.pack_int4_lanes(jnp.pad(q.unpack_int4(qt.data), pad))
    else:
        data = jnp.pad(qt.data, pad)
    sz_pad = [(0, 0)] * (qt.scale.ndim - 1) + [(0, np_ - n)]
    scale = jnp.pad(qt.scale, sz_pad, constant_values=1.0)
    zero = jnp.pad(qt.zero, sz_pad, constant_values=0.0)
    return PackedLinear(data=data, scale=scale, zero=zero, bits=qt.bits,
                        k=k, n=n)


def unpack_linear(pl: PackedLinear) -> q.QuantizedTensor:
    """Slice the padding back off -> the original QuantizedTensor values
    (the reference matmul path and round-trip tests consume this)."""
    if pl.bits == 4:
        data = q.pack_int4(q.unpack_int4_lanes(pl.data)[..., :pl.k, :pl.n])
    else:
        data = pl.data[..., :pl.k, :pl.n]
    scale = pl.scale[..., :pl.n]
    zero = pl.zero[..., :pl.n]
    shape = (*data.shape[:-2], pl.k, pl.n)
    return q.QuantizedTensor(data=data, scale=scale, zero=zero, bits=pl.bits,
                             shape=shape)


def abstract_packed(shape, bits: int, group_size: int = 0) -> PackedLinear:
    """ShapeDtypeStruct mirror of ``pack_linear`` (dry-runs, no alloc)."""
    *lead, k, n = shape
    kp, np_ = _ceil_to(k, LANE), _ceil_to(n, N_ALIGN)
    pcols = np_ // 2 if bits == 4 else np_
    g = (k // group_size) if (group_size and group_size < k) else 1
    sds = jax.ShapeDtypeStruct
    return PackedLinear(
        data=sds((*lead, kp, pcols), jnp.int8),
        scale=sds((*lead, g, np_), jnp.float32),
        zero=sds((*lead, g, np_), jnp.float32),
        bits=bits, k=k, n=n)


def spec_packed(data_spec, sz_spec, bits: int, shape) -> PackedLinear:
    """PartitionSpec mirror (padding never changes the sharding layout)."""
    *_, k, n = shape
    return PackedLinear(data=P(*data_spec), scale=P(*sz_spec),
                        zero=P(*sz_spec), bits=bits, k=k, n=n)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedExpertLinear(PackedLinear):
    """A stacked per-expert quantized linear in the kernel-native layout.

    Same padded carrier as ``PackedLinear`` with a leading expert axis
    (plus an optional scan/layer axis ahead of it):

      data:  int8 [..., E, Kp, Np//2] (bits=4) or [..., E, Kp, Np]
      scale: fp32 [..., E, g, Np]; zero likewise

    The grouped kernel indexes the expert axis with its leading grid
    dimension; the selected-expert decode path and per-expert weight
    streaming gather/slice the same axis directly.
    """

    @property
    def experts(self) -> int:
        return int(self.data.shape[-3])


def pack_expert_linear(qt: q.QuantizedTensor) -> PackedExpertLinear:
    """Repack a stacked expert table ([..., E, K, N]) into the grouped
    kernel's padded layout — same exact-padding guarantees as
    ``pack_linear`` (the K/N pads are shared across experts)."""
    assert qt.data.ndim >= 3, qt.data.shape
    pl_ = pack_linear(qt)
    return PackedExpertLinear(data=pl_.data, scale=pl_.scale, zero=pl_.zero,
                              bits=pl_.bits, k=pl_.k, n=pl_.n)


def unpack_expert_linear(pel: PackedExpertLinear) -> q.QuantizedTensor:
    """Slice the padding back off every expert slab (reference grouped
    matmul + round-trip tests)."""
    return unpack_linear(pel)


def abstract_packed_expert(shape, bits: int,
                           group_size: int = 0) -> PackedExpertLinear:
    """ShapeDtypeStruct mirror of ``pack_expert_linear``."""
    pl_ = abstract_packed(shape, bits, group_size)
    return PackedExpertLinear(data=pl_.data, scale=pl_.scale, zero=pl_.zero,
                              bits=bits, k=pl_.k, n=pl_.n)


def spec_packed_expert(data_spec, sz_spec, bits: int,
                       shape) -> PackedExpertLinear:
    """PartitionSpec mirror of ``pack_expert_linear``."""
    *_, k, n = shape
    return PackedExpertLinear(data=P(*data_spec), scale=P(*sz_spec),
                              zero=P(*sz_spec), bits=bits, k=k, n=n)


def take_experts(pel: PackedExpertLinear, ids) -> PackedExpertLinear:
    """Gather expert slabs along the expert axis (axis -3 of the carrier):
    the selected-expert decode path's per-token weight gather."""
    return PackedExpertLinear(
        data=jnp.take(pel.data, ids, axis=-3),
        scale=jnp.take(pel.scale, ids, axis=-3),
        zero=jnp.take(pel.zero, ids, axis=-3),
        bits=pel.bits, k=pel.k, n=pel.n)


# ---------------------------------------------------------------------------
# Per-shape tile plans
# ---------------------------------------------------------------------------

def _fit_block(dim: int, b: int, align: int) -> int:
    """Shrink a solver block until it divides ``dim`` (dim % align == 0)."""
    b = min(b, dim)
    while dim % b:
        b -= align
    return b


def _m_bucket(m: int) -> int:
    b = M_ALIGN
    while b < min(m, M_BUCKET_CAP):
        b *= 2
    return b


@dataclasses.dataclass
class MatmulPlan:
    """Tiles for one logical matmul shape; ``blocks(m)`` is cached per M
    bucket (decode M=batch and prefill M=tokens hit different buckets)."""
    k: int
    n: int
    bits: int
    _blocks: Dict[int, Tuple[int, int, int]] = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    @property
    def kp(self) -> int:
        return _ceil_to(self.k, LANE)

    @property
    def np_pad(self) -> int:
        return _ceil_to(self.n, N_ALIGN)

    def blocks(self, m: int) -> Tuple[int, int, int]:
        bucket = _m_bucket(m)
        if bucket not in self._blocks:
            bm, bn, bk = tiling.solve_tpu_blocks(bucket, self.np_pad, self.kp,
                                                 in_bytes=1.0)
            # solver candidates are powers-of-two off the lane grid; shrink
            # to divisors of the padded dims so kernel asserts always hold
            bm = _fit_block(bucket, bm, M_ALIGN)
            if self.bits == 4:   # whole 256-column nibble lane groups
                bn = _fit_block(self.np_pad, max(bn, N_ALIGN), N_ALIGN)
            else:
                bn = _fit_block(self.np_pad, bn, LANE)
            bk = _fit_block(self.kp, bk, LANE)
            self._blocks[bucket] = (bm, bn, bk)
        return self._blocks[bucket]


# module-level cache for plan-less dispatch (tests / ad-hoc callers)
_ADHOC_PLANS: Dict[Tuple[int, int, int], MatmulPlan] = {}


def matmul_plan(k: int, n: int, bits: int) -> MatmulPlan:
    key = (k, n, bits)
    if key not in _ADHOC_PLANS:
        _ADHOC_PLANS[key] = MatmulPlan(k=k, n=n, bits=bits)
    return _ADHOC_PLANS[key]


# ---------------------------------------------------------------------------
# Weight streaming (paper §4.1 extended from KV to weights)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamedStackPlan:
    """One stack whose layer groups stream through the DRAM ring."""
    stack: int                 # index into cfg.layer_plan()
    count: int                 # layer groups in the stack (the scan length)
    group_bytes: int           # bytes of one group's leaf slices
    ring_groups: int           # DRAM ring slots (>= 2: double-buffered)
    experts: int = 0           # > 0: expert-granular streaming (MoE stack)
    expert_bytes: int = 0      # bytes of ONE expert's slice of one group
    shared_bytes: int = 0      # bytes of a group's non-expert (shared) leaves

    @property
    def ring_bytes(self) -> int:
        return self.ring_groups * self.group_bytes


@dataclasses.dataclass(frozen=True)
class WeightStreamPolicy:
    """DRAM/Flash placement for the *weights* under a byte budget —
    utilization-ordered like ``plan_embedding_placement`` (§4.1), extended
    to per-stack layer groups.  lm_head + final_norm are read fully every
    step (full utilization) and always stay resident; stacks stay resident
    in layer order while they fit, and each overflowing stack streams
    group-by-group through a double-buffered DRAM ring whose slot count is
    sized from the leftover budget.  ``placement`` mirrors the per-entry
    decision ("dram" | "stream")."""
    dram_budget_bytes: Optional[int]
    head_bytes: int                     # lm_head + final_norm (resident)
    resident_bytes: int                 # head + resident stacks + rings
    streamed: Tuple[StreamedStackPlan, ...]
    placement: Dict[str, str]

    @property
    def active(self) -> bool:
        return bool(self.streamed)

    @property
    def ring_bytes(self) -> int:
        return sum(s.ring_bytes for s in self.streamed)

    def streamed_stack(self, stack: int) -> Optional[StreamedStackPlan]:
        for s in self.streamed:
            if s.stack == stack:
                return s
        return None


def _tree_nbytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


# parameter-tree keys whose leaves carry a per-expert axis (MoE tables);
# everything else in a MoE stack (router, norms, attention) is shared
EXPERT_PARAM_KEYS = ("w_gate", "w_up", "w_down")


def is_expert_path(path) -> bool:
    """True when a tree_flatten_with_path key path names an expert table."""
    return any(getattr(p, "key", None) in EXPERT_PARAM_KEYS for p in path)


def _expert_group_bytes(stack_tree, count: int, experts: int
                       ) -> Tuple[int, int]:
    """(per-expert bytes, shared bytes) of ONE layer group of a stack.

    Walks the stack's leaves by key path: ``w_gate``/``w_up``/``w_down``
    leaves split per expert, every other leaf (router, norms, attention)
    is shared.  Returns (0, group bytes) for stacks with no expert leaves
    — including policy dry-runs driven by flat arrays."""
    leaves = jax.tree_util.tree_flatten_with_path(stack_tree)[0]
    expert_total = sum(leaf.nbytes for path, leaf in leaves
                       if is_expert_path(path))
    shared_total = sum(leaf.nbytes for path, leaf in leaves
                       if not is_expert_path(path))
    if not expert_total or not experts:
        return 0, -(-(expert_total + shared_total) // count)
    return (-(-expert_total // (count * experts)),
            -(-shared_total // count))


def weight_stream_policy(cfg, params, dram_budget_bytes: Optional[int] = None,
                         ring_groups: int = 2,
                         expert_granular: bool = True) -> WeightStreamPolicy:
    """Compute the weight placement for ``params`` under
    ``dram_budget_bytes`` (the WEIGHT budget — the caller carves it out of
    total DRAM after the KV-pool reservation).  ``None`` = everything
    resident.  A stack streams only when even its ring would be smaller
    than the full stack (``ring < count``); the ring grows into leftover
    budget up to ``count - 1`` slots, floored at 2 (double buffer — group
    g computes while g+1 installs, never aliasing)."""
    plan_stacks = cfg.layer_plan()
    head_bytes = (_tree_nbytes(params["final_norm"])
                  + _tree_nbytes(params["lm_head"]))
    placement: Dict[str, str] = {"final_norm": "dram", "lm_head": "dram"}
    if dram_budget_bytes is None:
        for si in range(len(plan_stacks)):
            placement[f"stacks/{si}"] = "dram"
        resident = head_bytes + sum(_tree_nbytes(s)
                                    for s in params["stacks"])
        return WeightStreamPolicy(
            dram_budget_bytes=None, head_bytes=head_bytes,
            resident_bytes=resident, streamed=(), placement=placement)
    left = int(dram_budget_bytes) - head_bytes
    resident = head_bytes
    streamed = []
    for si, (_patterns, count) in enumerate(plan_stacks):
        stack_bytes = _tree_nbytes(params["stacks"][si])
        group_bytes = -(-stack_bytes // count)
        if stack_bytes <= left:
            placement[f"stacks/{si}"] = "dram"
            resident += stack_bytes
            left -= stack_bytes
            continue
        # ring sized from the leftover budget: as many slots as fit,
        # clamped to [2 (double buffer), count - 1 (else it would be
        # resident)].  A 2-group stack can't double-buffer a strict
        # subset — it stays resident.
        ring = max(ring_groups, min(count - 1,
                                    left // group_bytes if group_bytes
                                    else ring_groups))
        if ring >= count or count < 3:
            placement[f"stacks/{si}"] = "dram"
            resident += stack_bytes
            left -= stack_bytes
            continue
        placement[f"stacks/{si}"] = "stream"
        experts = expert_bytes = shared_bytes = 0
        if expert_granular and getattr(cfg, "num_experts", 0):
            eb, sb = _expert_group_bytes(params["stacks"][si], count,
                                         cfg.num_experts)
            if eb:
                experts, expert_bytes, shared_bytes = cfg.num_experts, eb, sb
        streamed.append(StreamedStackPlan(
            stack=si, count=count, group_bytes=group_bytes,
            ring_groups=int(ring), experts=experts,
            expert_bytes=expert_bytes, shared_bytes=shared_bytes))
        resident += ring * group_bytes
        left -= ring * group_bytes
    return WeightStreamPolicy(
        dram_budget_bytes=int(dram_budget_bytes), head_bytes=head_bytes,
        resident_bytes=resident, streamed=tuple(streamed),
        placement=placement)


# ---------------------------------------------------------------------------
# The per-model plan
# ---------------------------------------------------------------------------

def _packable(leaf) -> bool:
    """Per-layer 2-D linears (optionally stacked on one scan axis) pack
    into ``PackedLinear``; MoE expert tables ([L, E, K, N] => ndim 4) pack
    into ``PackedExpertLinear`` via ``_expert_packable``."""
    return isinstance(leaf, q.QuantizedTensor) and leaf.data.ndim <= 3


def _expert_packable(leaf) -> bool:
    """Stacked expert tables: [L, E, K, N] QuantizedTensor leaves."""
    return isinstance(leaf, q.QuantizedTensor) and leaf.data.ndim == 4


def decode_buckets(max_slots: int, uniform: bool = True) -> Tuple[int, ...]:
    """Batch-size bucket ladder for the pre-compiled decode step graphs:
    1/2/4/... powers of two up to ``max_slots``, always topped by
    ``max_slots`` itself (a non-pow2 slot count gets its own full-batch
    bucket, so the ladder's top graph is exactly the old full-batch step).

    Geometry-aware gating: bucketed dispatch gathers the active rows
    through the shared page table, which only full-attention window-0
    stacks support — windowed rings and SSM states address KV by the
    *physical batch row* (``ring_view``'s ``rows * ppw`` pages), so a
    gathered row order would read the wrong ring.  Those stacks
    (``uniform=False``) keep the single full-batch graph."""
    if not uniform or max_slots <= 1:
        return (max(1, int(max_slots)),)
    ladder = []
    b = 1
    while b < max_slots:
        ladder.append(b)
        b *= 2
    ladder.append(max_slots)
    return tuple(ladder)


def kv_page_size(max_seq: int) -> int:
    """KV pool page size: the largest power-of-two divisor of ``max_seq``
    on the solver's lane grid — capped at LANE (the S-block alignment
    ``solve_tpu_blocks`` tilings want for the decode-attention gather) and
    at max_seq//4 (so even short serving contexts exercise multi-page
    tables), floored at the M_ALIGN sublane grid when it divides."""
    cap = max(M_ALIGN, min(LANE, max_seq // 4))
    ps = 1
    while ps * 2 <= cap and max_seq % (ps * 2) == 0:
        ps *= 2
    return ps


def prefill_chunk_schedule(cfg, prefill_chunk: int, page_size: int) -> int:
    """Resolve the engine's prefill chunk cap for this stack geometry.

    State-passing chunked prefill is bitwise partition-invariant only when
    chunk boundaries respect two alignments:

    * recurrent (SSM) scans block their associative scan in fixed
      ``ssm.SCAN_BLOCK``-token sub-blocks, so the cap is floored to a
      multiple of 8 (kept equal to ``models.ssm.SCAN_BLOCK`` — asserted
      in tests rather than imported, to keep runtime/ model-free);
    * windowed-attention rings recycle pages, so a chunk may not exceed
      one page (``kv_pool.paged_prefill_window_ref`` relies on
      M >= window + page_size) — those stacks round the cap down to the
      largest power of two <= min(cap, page_size).

    Every geometry — full-attention, windowed, recurrent, hybrid — chunks
    through this one schedule; there is no whole-prompt special case."""
    cap = max(8, (int(prefill_chunk) // 8) * 8)
    windowed = any(pat.kind == "attn" and pat.window > 0
                   for pats, _count in cfg.layer_plan() for pat in pats)
    if windowed:
        assert page_size >= 8, "windowed chunking needs >= one 8-aligned page"
        b = 8
        while b * 2 <= min(cap, page_size):
            b *= 2
        cap = b
    return cap


def kv_page_bytes(cfg, page_size: int) -> int:
    """DRAM bytes one pool page costs across every full-attention layer
    (int8/int4 keys + two fp32 scale planes + fp8/bf16 values).  Windowed
    layers are excluded: their ring pages are a fixed per-slot cost, not
    pool inventory."""
    H, D = cfg.num_kv_heads, cfg.resolved_head_dim
    kd = D // 2 if cfg.quant.kv_key_bits == 4 else D
    vb = 1 if cfg.quant.kv_value_fp8 else 2
    per_tok = H * kd + 2 * 4 * H + H * D * vb
    n_full = sum(count for pats, count in cfg.layer_plan()
                 for pat in pats if pat.kind == "attn" and pat.window == 0)
    return page_size * per_tok * n_full


@dataclasses.dataclass
class ExecutionPlan:
    """Everything decided once at load time (paper §5.1): kernel-native
    packed params, per-shape tile plans, and DRAM/Flash placement."""
    quant_tag: str
    matmuls: Dict[Tuple[int, int, int], MatmulPlan]
    placement: Dict[str, str]
    params: Any

    def matmul_plan(self, k: int, n: int, bits: int) -> MatmulPlan:
        key = (k, n, bits)
        if key not in self.matmuls:          # shape unseen at build time
            self.matmuls[key] = MatmulPlan(k=k, n=n, bits=bits)
        return self.matmuls[key]

    def decode_buckets(self, max_slots: int,
                       uniform: bool = True) -> Tuple[int, ...]:
        """The serving loop's batch-size bucket ladder (plan-owned, like
        tile shapes and pool geometry) — see module-level
        ``decode_buckets``.  ``EngineLoop.warmup()`` pre-traces one jitted
        decode step per bucket and pre-solves each bucket's matmul tiles,
        so the hot loop never compiles or solves."""
        return decode_buckets(max_slots, uniform=uniform)

    def presolve_tiles(self, m: int) -> None:
        """Fill every recorded matmul plan's tile cache for M-bucket ``m``
        (decode M = batch bucket): ``solve_tpu_blocks`` runs here, at
        warmup, never inside a trace."""
        for plan in self.matmuls.values():
            plan.blocks(m)

    def kv_pool_geometry(self, cfg, max_seq: int, max_slots: int,
                         dram_budget_bytes: Optional[int] = None,
                         staging_pages: Optional[int] = None
                         ) -> kv_pool.PoolGeometry:
        """Paged-KV pool geometry (the plan owns it, like tile shapes):
        page size from the lane grid, page inventory from the DRAM budget
        — clamped to [one full row, full per-slot reservation].  Pages
        beyond the budget live on Flash via the engine's spill tier.

        ``staging_pages`` (None => plan default) sizes the DRAM staging
        reserve for the proactive spill tier: big enough that any single
        row can stage all its spillable cold pages for one decode wave
        (``pages_per_row - 2``: the tail page and one hot page never
        spill), floored at 2 so even tiny tables stage with overlap.
        Pass 0 to disable the reserve (no proactive spill)."""
        ps = kv_page_size(max_seq)
        ppr = -(-max_seq // ps)
        if dram_budget_bytes is None:
            num = max_slots * ppr
        else:
            pb = kv_page_bytes(cfg, ps)
            num = dram_budget_bytes // pb if pb else max_slots * ppr
        num = max(min(int(num), max_slots * ppr), ppr)
        if staging_pages is None:
            staging_pages = max(2, ppr - 2)
        return kv_pool.PoolGeometry(page_size=ps, num_pages=num,
                                    pages_per_row=ppr,
                                    staging_pages=int(staging_pages))

    def kv_spill_policy(self, cfg, geom: kv_pool.PoolGeometry,
                        max_slots: int,
                        flash_budget_bytes: Optional[int] = None
                        ) -> kv_pool.SpillPolicy:
        """Proactive-spill watermarks + budgets, owned by the plan next to
        the pool geometry.  The engine spills cold pages of running rows
        when the free list drops below ``low_watermark`` (refilling to
        ``high_watermark``), keeps the last ``hot_pages`` full pages of
        every row in DRAM, and never puts more than
        ``flash_budget_pages`` on Flash (default: the full per-slot
        reservation — Flash is the cheap tier)."""
        if flash_budget_bytes is None:
            budget = max_slots * geom.pages_per_row
        else:
            pb = kv_page_bytes(cfg, geom.page_size)
            budget = flash_budget_bytes // pb if pb else 0
        low = max(1, geom.num_pages // 8)
        high = max(low, geom.num_pages // 4)
        return kv_pool.SpillPolicy(
            staging_pages=geom.staging_pages, hot_pages=1,
            low_watermark=low, high_watermark=high,
            flash_budget_pages=int(budget))

    def weight_placement(self, cfg,
                         dram_budget_bytes: Optional[int] = None,
                         ring_groups: int = 2,
                         expert_granular: bool = True) -> WeightStreamPolicy:
        """DRAM/Flash weight placement under a byte budget (plan-owned,
        like tile shapes and pool geometry) — see ``weight_stream_policy``.
        Stacks that overflow the budget stream per layer group through a
        double-buffered DRAM ring (MoE stacks additionally split each
        group's expert tables per expert when ``expert_granular``); the
        per-entry decisions merge into ``self.placement`` so observability
        sees one placement map."""
        policy = weight_stream_policy(cfg, self.params,
                                      dram_budget_bytes=dram_budget_bytes,
                                      ring_groups=ring_groups,
                                      expert_granular=expert_granular)
        self.placement.update(policy.placement)
        return policy


def placement_for(cfg, dram_budget_bytes: Optional[int] = None
                  ) -> Dict[str, str]:
    """Utilization-ordered DRAM/Flash placement (paper §4.1, C2).  The
    default budget fits exactly the full-utilization groups (layers +
    lm_head), so the embedding — utilization 1/vocab per step — spills to
    Flash, reproducing the paper's policy."""
    pc = cfg.param_count()
    sizes = {
        "embedding": pc["embedding"] * 2,                          # bf16
        "layers": pc["layers"] * cfg.quant.weight_bits // 8,
        "lm_head": pc["lm_head"] * max(cfg.quant.lm_head_bits, 8) // 8,
    }
    if dram_budget_bytes is None:
        dram_budget_bytes = sizes["layers"] + sizes["lm_head"]
    return HS.plan_embedding_placement(sizes, dram_budget_bytes)


def build_plan(cfg, params, *,
               dram_budget_bytes: Optional[int] = None) -> ExecutionPlan:
    """Build the ExecutionPlan for one model: walk the parameter tree,
    repack every per-layer QuantizedTensor into the kernel-native layout
    (already-packed leaves pass through), solve tiles per matmul shape, and
    record storage placement.  Pure function of (config, param shapes) —
    construction is deterministic."""
    matmuls: Dict[Tuple[int, int, int], MatmulPlan] = {}

    def note(k: int, n: int, bits: int) -> None:
        key = (k, n, bits)
        if key not in matmuls:
            matmuls[key] = MatmulPlan(k=k, n=n, bits=bits)
            # pre-solve the decode bucket (M ~ batch) so serving never
            # solves inside a trace; prefill buckets fill lazily
            matmuls[key].blocks(M_ALIGN)

    def repack(leaf):
        if isinstance(leaf, PackedLinear):      # incl. PackedExpertLinear
            note(leaf.k, leaf.n, leaf.bits)
            return leaf
        if _packable(leaf):
            packed = pack_linear(leaf)
            note(packed.k, packed.n, packed.bits)
            return packed
        if _expert_packable(leaf):
            packed = pack_expert_linear(leaf)
            note(packed.k, packed.n, packed.bits)
            return packed
        return leaf

    packed_params = jax.tree.map(
        repack, params,
        is_leaf=lambda x: isinstance(x, (q.QuantizedTensor, PackedLinear)))
    return ExecutionPlan(quant_tag=cfg.quant.tag(), matmuls=matmuls,
                         placement=placement_for(cfg, dram_budget_bytes),
                         params=packed_params)

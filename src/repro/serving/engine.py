"""Serving engine: the paper's runtime, end to end.

  * Embedding table lives on Flash (C2): every prefill/decode step gathers
    token rows from a disk memmap — ``serve_step`` takes embeddings, never
    token ids.
  * Weights are combined-quantized (C1): int4/int8 layers, int8 lm_head —
    repacked once at load time into the kernel-native layout by the
    ExecutionPlan (runtime/plan.py); every matmul/rmsnorm/attention in the
    jitted steps routes through the kernel dispatcher (runtime/dispatch.py,
    C3; backend via ``build_engine(backend=...)``, ``REPRO_BACKEND``
    overrides, else the platform's: compiled kernels on a TPU).
  * KV cache quantized int8-K/fp8-V (C1) inside the jitted steps.
  * Mixed precision (C5) inside the model; fp32 softmax, pre-scaled query.
  * Multi-LoRA (C7): online-loaded adapters, batched per-request selection,
    A.(B.x) ordering.
  * Request scheduling (C4): length-aware balanced batching.

Generation pattern: per-request prefill, then slot-synchronous batched
decode (requests join a decode batch after their prefill — continuous
batching at decode granularity).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import tempfile
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hybrid_storage as HS
from repro.core import kv_pool as KP
from repro.core import lora as LR
from repro.models import transformer as T
from repro.runtime import dispatch as RD
from repro.runtime import plan as RP
from repro.serving import sampling as SM
from repro.serving.scheduler import (AdmissionError, ContinuousScheduler,
                                     QueueFullError, Request)

__all__ = ["AdmissionError", "QueueFullError", "Engine", "EngineLoop",
           "EngineStats", "RequestStats", "Request", "TokenEvent",
           "bucket_cover", "build_engine", "percentile"]

# a host span on the profiler's clock around one phase of the serving
# step; one TraceMe when the profiler is off
_span = jax.profiler.TraceAnnotation


def bucket_cover(buckets: Sequence[int], wave: Sequence[int],
                 max_slots: int):
    """Gather plan for one decode wave: pick the smallest ladder bucket
    covering the wave's slots and pad to bucket size with DISTINCT idle
    slots (distinct => the logits/pos scatters have no duplicate indices,
    so their results are deterministic; the pad rows are masked inactive
    and their table rows upload as all-trash, so they write nothing).

    Returns (slot_idx int32 [bucket], active bool [bucket]) with the wave
    slots sorted first — the bucket row order is a pure function of the
    wave set, so repeated coverage of the same slots hits the same trace.
    """
    n = len(wave)
    bucket = next(b for b in buckets if b >= n)
    idx = sorted(int(s) for s in wave)
    taken = set(idx)
    for s in range(max_slots):
        if len(idx) >= bucket:
            break
        if s not in taken:
            idx.append(s)
    assert len(idx) == bucket, (tuple(buckets), tuple(wave), max_slots)
    active = np.zeros((bucket,), bool)
    active[:n] = True
    return np.asarray(idx, np.int32), active


@dataclasses.dataclass
class RequestStats:
    """Per-request serving latency record (continuous batching)."""
    uid: int
    ttft_s: float          # arrival -> first token
    tpot_s: float          # mean inter-token time after the first
    latency_s: float       # arrival -> completion
    new_tokens: int
    preemptions: int = 0
    queue_s: float = 0.0   # arrival -> first admission


def percentile(xs: Sequence[float], p: float) -> float:
    if not xs:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), p))


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One committed token, emitted by ``EngineLoop.step()`` the moment the
    sampling phase appends it to its request — before the step's decode
    compute even launches.  ``done`` marks the request's final token."""
    uid: int
    token: int
    index: int            # 0-based position in the request's completion
    done: bool
    request: Request = dataclasses.field(repr=False, compare=False)


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    # phase timers of ``Engine.generate`` only (it blocks at the end of
    # each phase); the serving loop never blocks to time a phase — its
    # phases are ``engine.*`` profiler spans
    prefill_s: float = 0.0
    decode_s: float = 0.0
    flash_bytes: int = 0
    # the serving loop's step counters, each incremented where the work
    # happens: steps run and steps that launched a decode; rows decoded
    # and the keys they attended over (a row at position p attends over
    # p + 1); rows of the decode programs launched, bucket padding
    # included; prompts whose last chunk ran and their causal keys
    # (t (t + 1) / 2 for a prompt of t tokens); of the decode programs
    # launched, the pages one full-attention layer's paged decode
    # attention computes (a row of length n: ceil(n / page), bucket
    # padding included) and its grid's pages (rows x the table's width);
    # at each launch of a step program that carries the KV pool, the
    # pool's bytes on the device (tile padding included) and its logical
    # bytes (pages x page x layers x bytes a token, no padding)
    steps: int = 0
    decode_steps: int = 0
    decode_rows: int = 0
    decode_ctx_keys: int = 0
    decode_bucket_rows: int = 0
    decode_attn_pages: int = 0
    decode_attn_grid_pages: int = 0
    kv_pool_device_bytes: int = 0
    kv_pool_logical_bytes: int = 0
    prompts_completed: int = 0
    prefill_causal_keys: int = 0
    # host arrays handed to the device (``Engine.h2d``): bytes, transfers
    h2d_bytes: int = 0
    h2d_transfers: int = 0
    # paged-KV spill tier: pool pages moved to / back from Flash
    spilled_pages: int = 0
    restored_pages: int = 0
    # proactive spill of running rows: cold pages moved to Flash while the
    # row keeps decoding, and the staging-gather accounting (a "hit" is a
    # needed cold page already staged or served through the prefetch
    # pipeline; a "miss" is a synchronous Flash read)
    cold_spilled_pages: int = 0
    flash_page_hits: int = 0
    flash_page_misses: int = 0
    flash_hit_rates: List[float] = dataclasses.field(default_factory=list)
    # prefix sharing: prompt tokens adopted from the page index (never
    # recomputed) and prompt chunks run by the unified step
    shared_prompt_tokens: int = 0
    prefill_chunks: int = 0
    # bucketed step graphs: total jit-cache entries across the loop's
    # step functions (one per (function, shape) compilation), and entries
    # added after warmup() — 0 is the headline gate: the hot loop never
    # compiles once warmed
    compile_events: int = 0
    recompiles_after_warmup: int = 0
    # weight streaming: layer groups served through the ring's prefetch
    # pipeline vs synchronous Flash reads, time blocked waiting on Flash,
    # and the DRAM bytes the resident weights + ring slots occupy
    weight_group_hits: int = 0
    weight_group_misses: int = 0
    weight_stall_s: float = 0.0
    dram_weight_bytes: int = 0
    # router-aware expert streaming (MoE stacks): per group visit on the
    # decode path, experts the router actually selected that the
    # router-history prediction had already installed (hits) vs cold
    # synchronous fetches (misses), and the Flash bytes fetched vs the
    # install-every-expert baseline of whole-group streaming
    expert_prefetch_hits: int = 0
    expert_prefetch_misses: int = 0
    expert_bytes_fetched: int = 0
    expert_bytes_baseline: int = 0
    # feature gates the loop resolved OFF at construction: feature name
    # -> human-readable reason.  Empty means every requested feature is
    # live.  Surfaced verbatim through /v1/stats so a deployment can see
    # why a knob it set is not in effect instead of silently losing it.
    disabled_features: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    # continuous batching: per-request TTFT/TPOT records
    requests: List[RequestStats] = dataclasses.field(default_factory=list)

    @property
    def prefill_tps(self) -> float:
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0

    @property
    def decode_tps(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def flash_hit_rate(self) -> float:
        """Aggregate staging hit rate for the proactive spill tier (1.0
        when no page was ever cold)."""
        total = self.flash_page_hits + self.flash_page_misses
        return self.flash_page_hits / total if total else 1.0

    @property
    def weight_stream_hit_rate(self) -> float:
        """Fraction of streamed weight groups served through the ring's
        layer-ahead prefetch pipeline (1.0 when nothing streams)."""
        total = self.weight_group_hits + self.weight_group_misses
        return self.weight_group_hits / total if total else 1.0

    @property
    def expert_prefetch_hit_rate(self) -> float:
        """Fraction of router-selected experts the router-history
        prediction had already installed when their group ran (1.0 when
        no expert-granular stack streams)."""
        total = self.expert_prefetch_hits + self.expert_prefetch_misses
        return self.expert_prefetch_hits / total if total else 1.0

    @property
    def expert_bytes_saved_frac(self) -> float:
        """Fraction of the whole-group streaming Flash traffic the
        router-aware per-expert fetches avoided on the decode path (0.0
        when no expert-granular stack streams)."""
        if not self.expert_bytes_baseline:
            return 0.0
        return 1.0 - self.expert_bytes_fetched / self.expert_bytes_baseline

    def ttft(self, p: float = 50.0) -> float:
        return percentile([r.ttft_s for r in self.requests], p)

    def tpot(self, p: float = 50.0) -> float:
        return percentile([r.tpot_s for r in self.requests], p)

    def latency(self, p: float = 50.0) -> float:
        return percentile([r.latency_s for r in self.requests], p)

    def queue_wait(self, p: float = 50.0) -> float:
        return percentile([r.queue_s for r in self.requests], p)

    # counters a step span carries as profiler arguments: their values
    # when the step began
    STEP_COUNTERS = ("steps", "decode_steps", "decode_rows",
                     "decode_ctx_keys", "decode_bucket_rows",
                     "decode_attn_pages", "decode_attn_grid_pages",
                     "kv_pool_device_bytes", "kv_pool_logical_bytes",
                     "prompts_completed", "prefill_causal_keys",
                     "h2d_bytes", "h2d_transfers")

    def step_counters(self) -> Dict[str, int]:
        return {k: getattr(self, k) for k in self.STEP_COUNTERS}


class WeightRing:
    """DRAM ring of device-resident layer groups for ONE streamed stack.

    Slot assignment is deterministic — group ``g`` installs into slot
    ``g % ring_groups`` — so with ``ring_groups >= 2`` (the policy floor)
    the group computing and the group installing always occupy distinct
    slots: no aliasing, and a group whose Flash fetch is still in flight
    is never named by any slot (``slot_group`` flips to ``g`` only after
    the fetch completes and the device buffers exist).  Installing over a
    slot drops the Python reference to the previous group's buffers — the
    steady-state DRAM footprint is exactly ``ring_groups * group_bytes``.
    """

    def __init__(self, store: HS.WeightGroupStore, stack: int, count: int,
                 ring_groups: int, treedef, skeleton, h2d: Callable):
        assert ring_groups >= 2, "the ring must double-buffer"
        self.store = store
        self.h2d = h2d                    # Engine.h2d: counted uploads
        self.stack = stack
        self.count = count
        self.ring_groups = ring_groups
        self.treedef = treedef
        self.skeleton = skeleton          # leaf ShapeDtypeStructs, flat order
        self.slots: List = [None] * ring_groups
        self.slot_group = [-1] * ring_groups
        self.stall_s = 0.0                # time blocked waiting on Flash
        self.installs = 0

    def slot_of(self, group: int) -> int:
        return group % self.ring_groups

    def prefetch(self, group: int) -> None:
        # skip groups already installed in their slot (a small stack can
        # leave a slot permanently holding its only mapped group) — a
        # prefetch nobody will consume just strands host memory
        if 0 <= group < self.count \
                and self.slot_group[self.slot_of(group)] != group:
            self.store.prefetch_group(self.stack, group)

    def obtain(self, group: int):
        """The group's device param tree, installing its ring slot if the
        slot holds another group (blocking on an in-flight prefetch —
        counted as ``stall_s`` — or a synchronous Flash read on a miss)."""
        r = self.slot_of(group)
        if self.slot_group[r] == group:
            return self.slots[r]
        t0 = time.perf_counter()
        arrays = self.store.fetch_group(self.stack, group)
        self.stall_s += time.perf_counter() - t0
        leaves = [self.h2d(a, s.dtype)
                  for a, s in zip(arrays, self.skeleton)]
        self.slot_group[r] = -1
        self.slots[r] = jax.tree.unflatten(self.treedef, leaves)
        self.slot_group[r] = group
        self.installs += 1
        return self.slots[r]


class ExpertWeightRing:
    """DRAM ring for ONE expert-granular streamed MoE stack.

    The ring slot of group ``g`` is still ``g % ring_groups``, but a slot
    now holds two granularities: the group's SHARED leaves (router, norms,
    attention — always installed, the router must be fresh before the
    expert tables matter) and one device piece per (expert leaf, expert)
    — installed only for the experts the router history predicts or the
    current step actually selects.  ``obtain`` assembles the fixed-shape
    ``[1, E, ...]`` param tree the group graphs were traced against by
    concatenating the per-expert pieces; experts never installed for this
    group contribute an all-zero (or stale) piece, which is safe by
    construction — the MoE combine only ever gathers the outputs of
    experts the router assigned, and the serving loop re-runs the group
    if any assigned expert's slice was cold (bitwise-exact fallback).
    Fixed leaf shapes mean the group graphs never retrace.
    """

    def __init__(self, store: HS.WeightGroupStore, stack: int, count: int,
                 ring_groups: int, experts: int, treedef, skeleton,
                 expert_flags: Sequence[bool], h2d: Callable):
        assert ring_groups >= 2, "the ring must double-buffer"
        self.store = store
        self.h2d = h2d                    # Engine.h2d: counted uploads
        self.stack = stack
        self.count = count
        self.ring_groups = ring_groups
        self.experts = experts
        self.treedef = treedef
        self.skeleton = skeleton          # full flat leaf SDS, [1, ...]
        self.flags = list(expert_flags)   # per flat leaf: expert table?
        self._shared_skel = [s for s, f in zip(skeleton, self.flags)
                             if not f]
        # one expert's piece of each expert leaf: [1, 1, *rest]
        self._expert_skel = [
            jax.ShapeDtypeStruct((1, 1, *s.shape[2:]), s.dtype)
            for s, f in zip(skeleton, self.flags) if f]
        self.slot_group = [-1] * ring_groups      # shared leaves' group
        self.shared_dev: List = [None] * ring_groups
        self.exp_group = [[-1] * experts for _ in range(ring_groups)]
        self.exp_dev: List = [[None] * experts for _ in range(ring_groups)]
        self._assembled: List = [None] * ring_groups
        self._assembled_group = [-1] * ring_groups
        self._zero_pieces: Optional[list] = None
        self.stall_s = 0.0
        self.installs = 0                 # shared-slab installs
        self.expert_installs = 0          # per-expert slice installs

    def slot_of(self, group: int) -> int:
        return group % self.ring_groups

    def installed(self, group: int) -> set:
        """Experts whose slices of ``group`` are device-resident now."""
        r = self.slot_of(group)
        return {e for e in range(self.experts)
                if self.exp_group[r][e] == group}

    def prefetch(self, group: int, experts) -> None:
        """Queue the group's shared slab + the given experts' slices for
        background Flash reads (skipping anything already installed)."""
        if not (0 <= group < self.count):
            return
        r = self.slot_of(group)
        if self.slot_group[r] != group:
            self.store.prefetch_group(self.stack, group)
        for e in experts:
            e = int(e)
            if 0 <= e < self.experts and self.exp_group[r][e] != group:
                self.store.prefetch_expert(self.stack, group, e)

    def ensure(self, group: int, experts) -> tuple:
        """Install the group's shared slab and the given experts' slices
        into the ring slot (blocking on in-flight prefetches — counted as
        ``stall_s`` — or synchronous Flash reads on misses).  Returns
        ``(n_expert_slices_fetched, shared_slab_fetched)``."""
        r = self.slot_of(group)
        shared_new = False
        if self.slot_group[r] != group:
            t0 = time.perf_counter()
            arrays = self.store.fetch_group(self.stack, group)
            self.stall_s += time.perf_counter() - t0
            self.slot_group[r] = -1
            self.shared_dev[r] = [self.h2d(a, s.dtype)
                                  for a, s in zip(arrays, self._shared_skel)]
            self.slot_group[r] = group
            self._assembled_group[r] = -1
            self.installs += 1
            shared_new = True
        n_new = 0
        for e in sorted({int(e) for e in experts}):
            if self.exp_group[r][e] == group:
                continue
            t0 = time.perf_counter()
            arrays = self.store.fetch_expert(self.stack, group, e)
            self.stall_s += time.perf_counter() - t0
            self.exp_group[r][e] = -1
            self.exp_dev[r][e] = [self.h2d(a, s.dtype)
                                  for a, s in zip(arrays, self._expert_skel)]
            self.exp_group[r][e] = group
            self._assembled_group[r] = -1
            self.expert_installs += 1
            n_new += 1
        return n_new, shared_new

    def _zero_piece(self, j: int):
        if self._zero_pieces is None:
            self._zero_pieces = [jnp.zeros(s.shape, s.dtype)
                                 for s in self._expert_skel]
        return self._zero_pieces[j]

    def obtain(self, group: int):
        """The group's assembled device param tree.  ``ensure`` must have
        installed the shared slab first; expert positions concatenate the
        installed pieces (zeros where an expert was never fetched for any
        group in this slot) into the fixed ``[1, E, ...]`` leaf shape."""
        r = self.slot_of(group)
        assert self.slot_group[r] == group, "ensure() the group first"
        if self._assembled_group[r] == group:
            return self._assembled[r]
        leaves, si, ei = [], 0, 0
        for i, s in enumerate(self.skeleton):
            if self.flags[i]:
                pieces = []
                for e in range(self.experts):
                    dev = self.exp_dev[r][e]
                    pieces.append(dev[ei] if dev is not None
                                  else self._zero_piece(ei))
                leaves.append(jnp.concatenate(pieces, axis=1))
                ei += 1
            else:
                leaves.append(self.shared_dev[r][si])
                si += 1
        self._assembled_group[r] = -1
        self._assembled[r] = jax.tree.unflatten(self.treedef, leaves)
        self._assembled_group[r] = group
        return self._assembled[r]


class Engine:
    """Single-device engine: the packed parameters, the dispatcher and the
    jitted prefill/decode steps that ``EngineLoop`` and the legacy
    ``generate`` path drive.  It never traces under a mesh."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 embedding: np.ndarray | HS.EmbeddingStore,
                 max_seq: int = 256,
                 flash_dir: Optional[str] = None,
                 backend: Optional[str] = None,
                 plan: Optional[RP.ExecutionPlan] = None,
                 weight_dram_budget_bytes: Optional[int] = None,
                 weight_ring_groups: int = 2,
                 expert_streaming: bool = True):
        self.cfg = cfg
        # the ExecutionPlan is built ONCE per model (paper §5.1): weights
        # repacked into the kernel-native layout, tiles solved per matmul
        # shape, DRAM/Flash placement recorded.  All forward passes run on
        # the packed params through the dispatcher.
        self.plan = plan if plan is not None else RP.build_plan(cfg, params)
        self.params = self.plan.params
        self.dispatch = RD.Dispatcher(plan=self.plan, backend=backend)
        self.max_seq = max_seq
        if isinstance(embedding, HS.EmbeddingStore):
            self.embedding = embedding
            self.flash = embedding.flash
        else:
            # put the embedding table on (simulated) Flash — C2
            self.flash = HS.FlashStore(
                flash_dir or os.path.join(tempfile.gettempdir(),
                                          "repro_flash"),
                HS.FlashSpec(simulate=False))
            self.embedding = HS.EmbeddingStore.create(
                self.flash, np.asarray(embedding, np.float32))
        self.stats = EngineStats()
        # multi-LoRA (C7): online-loaded adapter registries for q/v
        hd = cfg.resolved_head_dim
        self.lora_q = LR.LoraRegistry(cfg.d_model, cfg.num_heads * hd,
                                      max_rank=8)
        self.lora_v = LR.LoraRegistry(cfg.d_model, cfg.num_kv_heads * hd,
                                      max_rank=8)
        # jitted steps close over a per-engine StepCtx carrying the
        # dispatcher: switching backends builds a new Engine (fresh jit
        # cache), so a stale trace can never serve the wrong backend
        self._ctx = T.StepCtx(cfg, dispatch=self.dispatch)
        self._prefill = jax.jit(
            functools.partial(self._prefill_impl, cfg, self._ctx),
            static_argnames=("max_seq",))
        self._decode = jax.jit(
            functools.partial(self._decode_impl, cfg, self._ctx))
        # --- weight streaming (PR 8): plan-owned placement of per-stack
        # layer groups.  Stacks marked "stream" are exported to Flash as
        # per-layer packed slices and dropped from the DRAM param tree;
        # EngineLoop runs them group-by-group through a DRAM ring.
        self.weight_policy = self.plan.weight_placement(
            cfg, weight_dram_budget_bytes, ring_groups=weight_ring_groups,
            expert_granular=expert_streaming)
        self.weight_store: Optional[HS.WeightGroupStore] = None
        self._stream_skel: Dict[int, tuple] = {}
        self._expert_flags: Dict[int, list] = {}
        if self.weight_policy.active:
            self._export_streamed_stacks()
        self.stats.dram_weight_bytes = self.weight_policy.resident_bytes

    def _export_streamed_stacks(self) -> None:
        """Persist each streamed stack's per-layer weight slices to Flash
        (leading stacked axis sliced one layer-group at a time) and drop
        the DRAM copies — after this the streamed stacks live only on
        Flash + the EngineLoop's DRAM ring.

        Expert-granular stacks split further: a group's shared leaves
        (router, norms, attention) go into the usual group blob, and each
        expert's slice of the expert tables becomes its own blob — the
        serving loop then fetches only the experts the router selects."""
        self.weight_store = HS.WeightGroupStore(self.flash)
        stacks = list(self.params["stacks"])
        for sp in self.weight_policy.streamed:
            si = sp.stack
            pleaves, treedef = jax.tree_util.tree_flatten_with_path(
                stacks[si])
            leaves = [l for _p, l in pleaves]
            flags = ([RP.is_expert_path(p) for p, _l in pleaves]
                     if sp.experts else [False] * len(pleaves))
            for g in range(sp.count):
                self.weight_store.put_group(
                    si, g, [np.asarray(leaf[g:g + 1])
                            for leaf, f in zip(leaves, flags) if not f])
                for e in range(sp.experts):
                    self.weight_store.put_expert_group(
                        si, g, e,
                        [np.asarray(leaf[g:g + 1, e:e + 1])
                         for leaf, f in zip(leaves, flags) if f])
            self._stream_skel[si] = (treedef, [
                jax.ShapeDtypeStruct((1, *l.shape[1:]), l.dtype)
                for l in leaves])
            self._expert_flags[si] = flags
            stacks[si] = None
        self.params = dict(self.params, stacks=tuple(stacks))
        self.plan.params = self.params

    # --- jitted steps -------------------------------------------------------
    @staticmethod
    def _prefill_impl(cfg, ctx, params, embeds, src_embeds=None, lora=None,
                      *, max_seq):
        return T.prefill(params, cfg, embeds, max_seq=max_seq,
                         src_embeds=src_embeds, ctx=ctx, lora=lora)

    @staticmethod
    def _decode_impl(cfg, ctx, params, embeds, cache, lora=None):
        return T.decode_step(params, cfg, embeds, cache, ctx=ctx, lora=lora)

    # --- multi-LoRA (C7) ------------------------------------------------------
    def load_adapter(self, name: str, q_ab, v_ab) -> None:
        """Online-load one adapter: q_ab/v_ab = (A [d, r], B [r, out])."""
        self.lora_q.load(name, *q_ab)
        self.lora_v.load(name, *v_ab)

    def _lora_for(self, requests: Sequence[Optional[Request]],
                  rows: Optional[Sequence[int]] = None) -> Optional[dict]:
        """Per-row adapter tables; None entries (empty continuous-batching
        slots) select the zero adapter."""
        if not self.lora_q._names:
            return None
        ids = [self.lora_q.slot(r.adapter) if r is not None else 0
               for r in requests]
        if rows is not None:
            ids = [ids[i] for i in rows]
        qa, qb = self.lora_q.device_tables()
        va, vb = self.lora_v.device_tables()
        return {"wq_a": qa, "wq_b": qb, "wv_a": va, "wv_b": vb,
                "ids": self.h2d(ids, jnp.int32)}

    # --- host -> device ------------------------------------------------------
    def h2d(self, x, dtype=None) -> jax.Array:
        """Hand one host array to the device.  Every array a serving step
        uploads (embedding rows, page tables, ids, masks, slot indices,
        scalars, pages back from Flash, streamed weights) passes here, so
        ``stats.h2d_bytes`` and ``stats.h2d_transfers`` count the step's
        host-to-device traffic.  The cast to ``dtype`` happens on the
        host, as ``jnp.asarray(x, dtype)`` does it."""
        a = np.asarray(x, None if dtype is None else jnp.dtype(dtype))
        self.stats.h2d_bytes += a.nbytes
        self.stats.h2d_transfers += 1
        return jnp.asarray(a)

    # --- embedding via Flash (C2) --------------------------------------------
    def embed(self, token_ids: np.ndarray) -> jax.Array:
        rows = self.embedding.lookup(np.asarray(token_ids))
        self.stats.flash_bytes = self.flash.bytes_read
        return self.h2d(rows, jnp.bfloat16)

    # --- generation ------------------------------------------------------------
    def generate(self, requests: Sequence[Request],
                 sampling: SM.SamplingParams,
                 src_embeds: Optional[np.ndarray] = None,
                 key: Optional[jax.Array] = None) -> List[Request]:
        """Prefill each request, then batched decode until done/max."""
        assert not self.weight_policy.active, \
            "weight streaming requires the EngineLoop step path"
        cfg = self.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        caches, last_logits = [], []
        t0 = time.perf_counter()
        for ri, req in enumerate(requests):
            toks = np.asarray(req.prompt_tokens)[None, :]
            embeds = self.embed(toks)
            src = None
            if cfg.is_encdec:
                assert src_embeds is not None
                src = self.h2d(src_embeds[ri:ri + 1], jnp.bfloat16)
            logits, cache = self._prefill(
                self.params, embeds, src,
                self._lora_for(requests, rows=[ri]), max_seq=self.max_seq)
            caches.append(cache)
            last_logits.append(logits)
            self.stats.prefill_tokens += toks.size
        jax.block_until_ready(last_logits[-1])
        self.stats.prefill_s += time.perf_counter() - t0

        # batch the decode: concat caches on the batch axis
        cache = jax.tree.map(
            lambda *xs: (xs[0] if getattr(xs[0], "ndim", 0) <= 1
                         else jnp.concatenate(xs, axis=1)),
            *caches) if len(caches) > 1 else caches[0]
        if len(caches) > 1:
            cache["pos"] = caches[0]["pos"]
        logits = jnp.concatenate(last_logits, axis=0)

        t0 = time.perf_counter()
        for step in range(sampling.max_new_tokens):
            key, sub = jax.random.split(key)
            tok = SM.sample(logits, sampling, cfg.vocab_size, sub)
            tok_np = np.asarray(tok)
            for ri, req in enumerate(requests):
                if not req.done:
                    req.generated.append(int(tok_np[ri]))
                    if (sampling.eos_token >= 0
                            and tok_np[ri] == sampling.eos_token):
                        req.done = True
                    elif len(req.generated) >= req.max_new_tokens:
                        req.done = True
            if all(r.done for r in requests):
                break
            # C2: the next token's embedding row comes from Flash
            embeds = self.embed(tok_np[:, None])
            logits, cache = self._decode(self.params, embeds, cache,
                                         self._lora_for(requests))
            self.stats.decode_tokens += len(requests)
        jax.block_until_ready(logits)
        self.stats.decode_s += time.perf_counter() - t0
        return list(requests)


# one-shot notice for the run(sampling=...) default-for-all shim
_WARNED_RUN_SAMPLING_SHIM = False


class EngineLoop:
    """Step-driven continuous-batching serving loop on the paged KV pool —
    one *unified step* runs pending prompt chunks and the decode batch
    together.

    The serving API is *incremental*: ``submit(req)`` enqueues a request
    at any time (admission-checked — a request that can never fit raises
    ``AdmissionError``, a full bounded queue raises ``QueueFullError``),
    ``step()`` advances the whole loop by one unified step and emits a
    ``TokenEvent`` for every token it commits (also delivered through the
    optional ``on_token`` callback the moment the sampling phase appends
    it — a streaming consumer sees the first token while the rest of the
    completion is still decoding), ``poll(uid)`` drains a request's
    tokens cursor-style, and ``drain()`` steps until idle.  Requests
    carry their own ``SamplingParams`` plus QoS fields (``priority``,
    ``deadline_s``) the scheduler orders admission by.  ``run()`` remains
    as a thin batch-mode compatibility wrapper over submit/step/drain.

    One decode batch of ``max_slots`` rows over a block-paged pool
    (core/kv_pool.py) whose geometry the ExecutionPlan owns:

      * a request joins the moment a slot frees; its prompt KV is written
        *straight into freshly allocated pool pages* in chunks
        (``transformer.prefill_chunk_paged``) — no dense ``max_seq``
        transient, no prefill-then-scatter.  Chunks across all prefilling
        rows share a per-step token budget, so a long prompt trickles in
        over several steps while the decode batch keeps advancing;
      * prompt prefixes already in the pool's token-hash index (same
        tokens, same adapter) are adopted copy-free: the row's page table
        points at the shared refcounted pages and prefill starts past
        them — the many-users/shared-system-prompt workload;
      * every step advances all decodable rows by one token at their own
        per-row positions; pages are allocated on append at page
        boundaries, and EOS is a refcount decrement (indexed prefix pages
        survive for the next request);
      * admission accounts the *non-shared* pages a request actually
        needs now, not a max_seq reservation;
      * preemption (queue patience, or page pressure when the pool runs
        dry mid-decode) spills the victim's pages to Flash
        (hybrid_storage.PageSpillStore) and restores them page-exact on
        resume, so greedy decoding is bitwise-unaffected.  A row evicted
        *mid-prefill* is simply freed and requeued (recomputing a partial
        prompt is cheaper than round-tripping it through Flash);
      * proactive spill (paper Fig. 2 at page granularity): *running*
        rows' cold prompt pages — oldest, single-owner, outside the hot
        tail — move to Flash under page pressure while the row keeps
        decoding.  Before the paged kernels run, each decode step gathers
        the Flash-resident pages of the rows it advances into a small
        DRAM *staging reserve* (plan-owned geometry), with layer-ahead
        prefetch overlapping the Flash reads against the device writes;
        the kernels only ever see DRAM page ids, and a page whose fetch
        is still in flight is never visible to dispatch.  Admission may
        oversubscribe DRAM by the spillable-cold headroom up to a
        plan-owned Flash budget — the same DRAM pool carries strictly
        longer total context.

    Per-request TTFT/TPOT/latency land in ``engine.stats.requests``.
    """

    def __init__(self, engine: Engine, max_slots: int = 4,
                 token_budget: Optional[int] = None,
                 preempt_patience: int = 0,
                 dram_budget_bytes: Optional[int] = None,
                 prefill_chunk: int = 64,
                 prefill_token_budget: Optional[int] = None,
                 prefix_sharing: bool = True,
                 proactive_spill: bool = True,
                 bucketing: bool = True,
                 flash_budget_bytes: Optional[int] = None,
                 default_sampling: Optional[SM.SamplingParams] = None,
                 max_queue: Optional[int] = None,
                 on_token: Optional[Callable[[Request, int, bool], None]]
                 = None):
        cfg = engine.cfg
        assert not cfg.is_encdec, "continuous batching: decoder-only models"
        self.eng = engine
        self.cfg = cfg
        self.max_slots = max_slots
        # prefix sharing adopts whole KV pages by token hash — only
        # meaningful when every layer keeps full-cache attention (windowed
        # rings recycle pages and recurrent stacks carry state outside the
        # pool, so an adopted page would not reproduce the row's state)
        self._full_attn = all(pat.kind == "attn" and pat.window == 0
                              for pats, _ in cfg.layer_plan()
                              for pat in pats)
        # proactive spill runs the decode in staging waves; inactive rows'
        # recurrent state and windowed ring pages are frozen per wave
        # (freeze_inactive_rows), so every stack mix takes this tier
        self.proactive = proactive_spill
        self.geom = engine.plan.kv_pool_geometry(
            cfg, engine.max_seq, max_slots,
            dram_budget_bytes=dram_budget_bytes,
            staging_pages=None if self.proactive else 0)
        self.spill_policy = engine.plan.kv_spill_policy(
            cfg, self.geom, max_slots,
            flash_budget_bytes=flash_budget_bytes)
        # chunked prefill runs for EVERY stack mix: recurrent stacks pass
        # entry/exit state between chunks (chunk-invariant scans) and
        # windowed rings bound the chunk to one page, so the schedule only
        # aligns the cap — never collapses to whole-prompt
        self.prefill_chunk = RP.prefill_chunk_schedule(
            cfg, prefill_chunk, self.geom.page_size)
        self.prefill_token_budget = (prefill_token_budget
                                     if prefill_token_budget is not None
                                     else max(prefill_chunk, 64))
        self.pool = KP.KVPoolManager(
            self.geom, max_slots,
            prefix_sharing=prefix_sharing and self._full_attn)
        self.spill = HS.PageSpillStore(engine.flash)
        self.scheduler = ContinuousScheduler(
            max_slots, engine.max_seq, token_budget=token_budget,
            preempt_patience=preempt_patience, pool=self.pool,
            spill_headroom=self._spill_headroom if self.proactive else None)
        self.cache = T.init_paged_cache(cfg, max_slots, engine.max_seq,
                                        self.geom)
        # the pool's bytes on the device and without padding, taken once:
        # every step program that carries the pool counts them
        pools = [leaf for row in self.cache["stacks"] for leaf in row
                 if isinstance(leaf, KP.PagedLayerKV)]
        self.pool_bytes = (
            sum(x.on_device_size_in_bytes() for p in pools
                for x in p.fields().values()),
            sum(KP.logical_bytes(p) for p in pools))
        self.logits = jnp.zeros((max_slots, cfg.padded_vocab_size),
                                jnp.float32)
        # uid -> spill record of a preempted request (pages on Flash)
        self._spilled: Dict[int, dict] = {}
        # slot -> in-flight prompt state (chunked prefill across steps)
        self._prefilling: Dict[int, dict] = {}
        self._prefill_rr = 0          # round-robin cursor across steps
        # slots whose restored request still owes one decode of its last
        # generated token before sampling may continue (mid-step eviction
        # caught them between sampling and KV append)
        self._hold: set = set()
        self.peak_active = 0
        # peak total KV pages held by running rows (DRAM + Flash): the
        # oversubscription headline is peak_kv_pages > geom.num_pages
        self.peak_kv_pages = 0
        self._step_hits = 0
        self._step_misses = 0
        # --- incremental serving API state ---------------------------------
        # sampling applied to requests submitted without their own params
        self.default_sampling = default_sampling
        # bounded submit queue: submit() raises QueueFullError once this
        # many requests are waiting (None = unbounded, the batch-mode
        # default).  This is the gateway's backpressure signal (HTTP 429).
        self.max_queue = max_queue
        # per-token emission: called as on_token(request, token, done) the
        # moment step()'s sampling phase commits a token
        self.on_token = on_token
        self._step_no = 0             # monotonic unified-step counter
        self._key = jax.random.PRNGKey(0)
        # uid -> {"toks": [...], "cursor": consumed, "done": bool} for
        # poll(); entries drop once done AND fully consumed
        self._streams: Dict[int, dict] = {}
        self.rejected = 0             # submits refused by backpressure
        # the step programs donate the cache (argument 2): the pool is
        # updated in place, never copied whole
        self._decode = jax.jit(
            functools.partial(self._decode_impl, cfg, engine._ctx),
            donate_argnums=2)
        self._chunk = jax.jit(
            functools.partial(self._chunk_impl, cfg, engine._ctx),
            donate_argnums=2)
        # batch-size bucketing (flashinfer-style pre-planned step graphs):
        # the plan derives the ladder; dispatch gathers the active slots
        # into the smallest covering bucket so low-concurrency decode runs
        # at bucket shape, not max_slots.  Gated on full-attention stacks
        # (windowed rings and SSM states are batch-row addressed — a
        # gathered row order would read the wrong state; follow-on: route
        # ring/SSM rows through their true slot ids) and on MoE-free ones
        # (expert capacity couples tokens across the batch, so a bucketed
        # MoE step would not be bitwise-equal to the full-batch step).
        no_moe = not any(pat.moe for pats, _ in cfg.layer_plan()
                         for pat in pats)
        self._bucketed = (bucketing and self._full_attn and no_moe
                          and max_slots > 1)
        # --- weight streaming (PR 8) -----------------------------------
        # When the plan streams stacks, the monolithic whole-model step
        # graphs (which close over a fully resident param tree) cannot
        # run.  The step splits into per-stack jits: resident stacks keep
        # the scan, streamed stacks run group-by-group consuming DRAM
        # ring slots (same [1, ...] weight shapes every group — one graph
        # per (stack, mode, shape), so recompiles_after_warmup stays 0).
        # Bucketing is off in this mode: the split step runs at max_slots
        # shape only (bucketed streaming is a recorded follow-on).
        self.wpolicy = engine.weight_policy
        self._wstreams: Dict[int, WeightRing] = {}
        # expert-granular streamed MoE stacks (PR 9): per-expert rings,
        # their plans, and the router-history prediction — per (stack,
        # group), the union of the experts the last two decode visits
        # actually selected (initialized to every expert, so the first
        # visits install everything and prediction only ever narrows)
        self._expert_rings: Dict[int, ExpertWeightRing] = {}
        self._espl: Dict[int, RP.StreamedStackPlan] = {}
        self._expert_pred: Dict[tuple, set] = {}
        self._expert_last: Dict[tuple, set] = {}
        self._stack_dec: Dict[int, Any] = {}
        self._grp_dec: Dict[int, Any] = {}
        self._stack_pf: Dict[int, Any] = {}
        self._grp_pf: Dict[int, Any] = {}
        self._post_dec = None
        self._post_pf = None
        if self.wpolicy.active:
            self._bucketed = False
            store = engine.weight_store
            for spl in self.wpolicy.streamed:
                treedef, skel = engine._stream_skel[spl.stack]
                if spl.experts:
                    self._expert_rings[spl.stack] = ExpertWeightRing(
                        store, spl.stack, spl.count, spl.ring_groups,
                        spl.experts, treedef, skel,
                        engine._expert_flags[spl.stack], h2d=engine.h2d)
                    self._espl[spl.stack] = spl
                    for g in range(spl.count):
                        allE = set(range(spl.experts))
                        self._expert_pred[(spl.stack, g)] = set(allE)
                        self._expert_last[(spl.stack, g)] = set(allE)
                else:
                    self._wstreams[spl.stack] = WeightRing(
                        store, spl.stack, spl.count, spl.ring_groups,
                        treedef, skel, h2d=engine.h2d)
            # the layer-ahead prefetch chain walks the global group
            # sequence in execution order; the last group wraps to the
            # first so the next step's leading fetch is already in
            # flight when the step starts (steady-state hit rate 1.0)
            self._stream_seq = [(spl.stack, g)
                                for spl in self.wpolicy.streamed
                                for g in range(spl.count)]
            self._stream_next = {
                self._stream_seq[i]:
                    self._stream_seq[(i + 1) % len(self._stream_seq)]
                for i in range(len(self._stream_seq))}
            self._head_params = {
                "final_norm": engine.params["final_norm"],
                "lm_head": engine.params["lm_head"]}
            for si in range(len(cfg.layer_plan())):
                if si in self._expert_rings:
                    # MoE group graphs additionally return the router
                    # top-k ids — the loop's router-aware streaming and
                    # its cold-miss re-run key off them
                    self._grp_dec[si] = jax.jit(functools.partial(
                        self._group_moe_impl, cfg, engine._ctx, si,
                        "decode"))
                    self._grp_pf[si] = jax.jit(functools.partial(
                        self._group_moe_impl, cfg, engine._ctx, si,
                        "prefill_paged"))
                elif si in self._wstreams:
                    self._grp_dec[si] = jax.jit(functools.partial(
                        self._group_impl, cfg, engine._ctx, si, "decode"))
                    self._grp_pf[si] = jax.jit(functools.partial(
                        self._group_impl, cfg, engine._ctx, si,
                        "prefill_paged"))
                else:
                    self._stack_dec[si] = jax.jit(functools.partial(
                        self._stack_impl, cfg, engine._ctx, si, "decode"))
                    self._stack_pf[si] = jax.jit(functools.partial(
                        self._stack_impl, cfg, engine._ctx, si,
                        "prefill_paged"))
            self._post_dec = jax.jit(functools.partial(
                self._post_decode_impl, cfg, engine._ctx))
            self._post_pf = jax.jit(functools.partial(
                self._post_chunk_impl, cfg, engine._ctx))
            # prime the chain: the very first obtain must already be a hit
            self._prefetch_sg(*self._stream_seq[0])
        self.buckets = engine.plan.decode_buckets(
            max_slots, uniform=self._bucketed)
        # every gate that silently narrowed a requested feature records
        # itself here (name -> reason); mirrored into EngineStats so
        # /v1/stats shows WHY a knob is not in effect
        self.disabled_features: Dict[str, str] = {}
        if prefix_sharing and not self._full_attn:
            self.disabled_features["prefix_sharing"] = (
                "windowed/recurrent stacks: an adopted KV page cannot "
                "reproduce ring contents or recurrent state")
        if bucketing and not self._bucketed:
            if self.wpolicy.active:
                reason = ("weight streaming: the split step runs at "
                          "max_slots shape only")
            elif not self._full_attn:
                reason = ("windowed/recurrent stacks: a gathered row "
                          "order would read the wrong batch-addressed "
                          "ring/recurrent state")
            elif not no_moe:
                reason = ("MoE: expert capacity couples tokens across "
                          "the batch")
            else:
                reason = "max_slots == 1: nothing to bucket"
            self.disabled_features["decode_bucketing"] = reason
        engine.stats.disabled_features = dict(self.disabled_features)
        self._decode_b = jax.jit(
            functools.partial(self._decode_bucket_impl, cfg, engine._ctx),
            donate_argnums=2)
        # warmup() pre-traces every bucket/chunk graph it can need; the
        # jit caches' entry counts make post-warmup compilation gateable
        self.warmed = False
        self._warmup_graphs = 0
        self._warmup_report: Optional[dict] = None

    @staticmethod
    def _decode_impl(cfg, ctx, params, embeds, cache, lora, active):
        return T.decode_step(params, cfg, embeds, cache, ctx=ctx, lora=lora,
                             active=active)

    @staticmethod
    def _decode_bucket_impl(cfg, ctx, params, embeds, cache, lora, active,
                            slot_idx, logits_prev):
        logits_b, cache = T.decode_step_bucketed(
            params, cfg, embeds, cache, slot_idx, ctx=ctx, lora=lora,
            active=active)
        # scatter the bucket's logits back to their slots inside the jit;
        # pad rows (active=False) keep the previous value — _spill_row
        # reads self.logits[slot] later, so garbage must never land there
        logits_full = logits_prev.at[slot_idx].set(
            jnp.where(active[:, None], logits_b, logits_prev[slot_idx]))
        return logits_full, cache

    @staticmethod
    def _chunk_impl(cfg, ctx, params, embeds, cache, slot, pos0, last_idx,
                    lora):
        return T.prefill_chunk_paged(params, cfg, embeds, cache, slot, pos0,
                                     last_idx, ctx=ctx, lora=lora)

    # --- weight-streamed split step (PR 8) ---------------------------------
    @staticmethod
    def _stack_impl(cfg, ctx, si, mode, sp, x, scache, pos, table,
                    positions, slot, lora, vlen):
        if lora is not None:
            ctx = dataclasses.replace(ctx, lora=lora)
        x, nsc, _ = T.run_stack(sp, cfg, si, mode, x, positions, scache,
                                None, pos, table, ctx, slot=slot,
                                valid_len=vlen)
        return x, nsc

    @staticmethod
    def _group_impl(cfg, ctx, si, mode, gp, x, scache, gidx, pos, table,
                    positions, slot, lora, vlen):
        if lora is not None:
            ctx = dataclasses.replace(ctx, lora=lora)
        x, nsc, _ = T.run_stack_group(gp, cfg, si, mode, x, positions,
                                      scache, gidx, pos, table, ctx,
                                      slot=slot, valid_len=vlen)
        return x, nsc

    @staticmethod
    def _group_moe_impl(cfg, ctx, si, mode, gp, x, scache, gidx, pos,
                        table, positions, slot, lora, vlen):
        """Like ``_group_impl`` but also returns the group's router top-k
        expert ids ``[n_moe, B, T, K]`` — the host reads them to track
        which experts this step actually needed (pure function of the
        inputs, so re-running it after a cold-expert install reproduces
        the exact all-weights-resident result)."""
        if lora is not None:
            ctx = dataclasses.replace(ctx, lora=lora)
        collect: dict = {}
        x, nsc, _ = T.run_stack_group(gp, cfg, si, mode, x, positions,
                                      scache, gidx, pos, table, ctx,
                                      slot=slot, collect=collect,
                                      valid_len=vlen)
        return x, nsc, collect["moe_ids"]

    @staticmethod
    def _post_decode_impl(cfg, ctx, head, x, pos, active):
        logits = T._logits(x, head, cfg, ctx.dispatch)[:, -1]
        return logits, jnp.where(active, pos + 1, pos)

    @staticmethod
    def _post_chunk_impl(cfg, ctx, head, x, last_idx):
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_idx, jnp.int32), 1, axis=1)
        return T._logits(last, head, cfg, ctx.dispatch)[:, 0]

    def _prefetch_sg(self, si: int, g: int) -> None:
        """Queue the chain successor's Flash reads on whichever ring kind
        owns it (expert rings prefetch the shared slab + the predicted
        experts' slices)."""
        ring = self._wstreams.get(si)
        if ring is not None:
            ring.prefetch(g)
            return
        self._expert_rings[si].prefetch(g, self._expert_pred[(si, g)])

    def _run_expert_group(self, fn, ering, spl, si, g, mode, x, scache,
                          pos, table, positions, slot, lora, vlen,
                          active):
        """One expert-granular group: install the shared slab + the
        router-history-predicted experts, run the group, then compare the
        router's ACTUAL selection against what was installed.  A cold
        expert (routed but not installed) re-runs the group — the graph
        is a pure function of (params, activations), so the re-run with
        the fresh slices is bitwise what an all-resident step computes;
        the discarded first pass only ever touched experts whose outputs
        the combine would have dropped anyway.  Prefill installs every
        expert up front (capacity dispatch multiplies all slabs) and is
        excluded from the hit/byte accounting."""
        gi = self.eng.h2d(g, jnp.int32)
        if mode != "decode":
            ering.ensure(g, range(spl.experts))
            nx, nsc, _ = fn(ering.obtain(g), x, scache, gi, pos, table,
                            positions, slot, lora, vlen)
            return nx, nsc
        stats = self.eng.stats
        pred = self._expert_pred[(si, g)]
        n_new, shared_new = ering.ensure(g, pred)
        installed = ering.installed(g)
        nx, nsc, ids = fn(ering.obtain(g), x, scache, gi, pos, table,
                          positions, slot, lora, vlen)
        act = None if active is None else np.asarray(active, bool)
        if act is None or not act.any():
            # warmup / all-masked step: nothing the router chose is real
            # — no accounting, no prediction update (the install above
            # still pre-populates the ring)
            return nx, nsc
        actual = {int(e) for e in np.unique(np.asarray(ids)[:, act])}
        stats.expert_prefetch_hits += len(actual & installed)
        stats.expert_prefetch_misses += len(actual - installed)
        # cold-expert fallback: install what the router actually picked
        # and re-run until the selection is fully resident.  More than
        # one pass only happens in multi-MoE groups, where a later
        # router's input depends on an earlier layer's (stale) experts.
        for _ in range(spl.experts):
            missing = actual - ering.installed(g)
            if not missing:
                break
            ne2, sn2 = ering.ensure(g, missing)
            n_new += ne2
            nx, nsc, ids = fn(ering.obtain(g), x, scache, gi, pos, table,
                              positions, slot, lora, vlen)
            actual = {int(e) for e in np.unique(np.asarray(ids)[:, act])}
        fetched = ((spl.shared_bytes if shared_new else 0)
                   + n_new * spl.expert_bytes)
        stats.expert_bytes_fetched += fetched
        # baseline: whole-group streaming refetches the full group slab
        # whenever the slot was stale; when it wasn't, neither scheme
        # moves bytes and the visit contributes zero savings
        stats.expert_bytes_baseline += (
            spl.shared_bytes + spl.experts * spl.expert_bytes
            if shared_new else fetched)
        self._expert_pred[(si, g)] = actual | self._expert_last[(si, g)]
        self._expert_last[(si, g)] = actual
        return nx, nsc

    def _stream_stacks(self, mode, x, cache, pos, table, positions, slot,
                       lora, vlen=None, active=None):
        """Run every stack for one step in the split streamed mode —
        resident stacks scan, streamed stacks run group-by-group out of
        their DRAM ring, prefetching the chain successor before each
        obtain so Flash reads overlap the group that is computing.
        Expert-granular MoE stacks route through ``_run_expert_group``
        (``active`` marks the decode rows whose routing is real)."""
        eng = self.eng
        new_stacks = []
        for si in range(len(self.cfg.layer_plan())):
            scache = cache["stacks"][si]
            ring = self._wstreams.get(si)
            ering = self._expert_rings.get(si)
            if ring is None and ering is None:
                fn = (self._stack_dec if mode == "decode"
                      else self._stack_pf)[si]
                x, nsc = fn(eng.params["stacks"][si], x, scache, pos,
                            table, positions, slot, lora, vlen)
            elif ring is not None:
                fn = (self._grp_dec if mode == "decode"
                      else self._grp_pf)[si]
                nsc = scache
                for g in range(ring.count):
                    self._prefetch_sg(*self._stream_next[(si, g)])
                    gp = ring.obtain(g)
                    x, nsc = fn(gp, x, nsc, self.eng.h2d(g, jnp.int32),
                                pos, table, positions, slot, lora, vlen)
            else:
                fn = (self._grp_dec if mode == "decode"
                      else self._grp_pf)[si]
                spl = self._espl[si]
                nsc = scache
                for g in range(ering.count):
                    self._prefetch_sg(*self._stream_next[(si, g)])
                    x, nsc = self._run_expert_group(
                        fn, ering, spl, si, g, mode, x, nsc, pos, table,
                        positions, slot, lora, vlen, active)
            new_stacks.append(nsc)
        return x, tuple(new_stacks)

    def _decode_streamed(self, embeds, active, lora, cache=None):
        """One decode step, split per stack (the streamed counterpart of
        ``_decode``); the eager shell computes the same values the
        monolithic graph would (int position math, bf16 cast), so the
        logits are bitwise-equal to the all-DRAM step.  ``active`` is
        the rows' mask, already on the device."""
        cache = self.cache if cache is None else cache
        x = embeds.astype(jnp.bfloat16)
        pos = cache["pos"]
        positions = pos[:, None] + jnp.arange(1, dtype=jnp.int32)[None]
        x, new_stacks = self._stream_stacks(
            "decode", x, cache, pos, cache.get("table"), positions, None,
            lora, active=active)
        # inactive rows (mid-prefill neighbours, staged-out wave rows)
        # must not have their recurrent state advanced or their windowed
        # ring pages appended to by this step's ride-along lanes
        new_stacks = T.freeze_inactive_rows(self.cfg, cache["stacks"],
                                            new_stacks, active)
        logits, npos = self._post_dec(self._head_params, x, pos, active)
        new_cache = dict(cache)
        new_cache["stacks"] = new_stacks
        new_cache["pos"] = npos
        return logits, new_cache

    def _chunk_streamed(self, embeds, slot, pos0, last_idx, lora,
                        cache=None):
        """One prompt chunk, split per stack (the streamed counterpart of
        ``_chunk``).  Does not advance ``pos`` — the engine does that
        once the whole prompt is in, exactly like the monolithic path."""
        cache = self.cache if cache is None else cache
        x = embeds.astype(jnp.bfloat16)
        C = x.shape[1]
        h2d = self.eng.h2d
        positions = (h2d(pos0, jnp.int32)
                     + jnp.arange(C, dtype=jnp.int32))[None]
        slot_t = h2d(slot, jnp.int32)
        table = cache["table"][slot_t]
        last = h2d(last_idx, jnp.int32)
        x, new_stacks = self._stream_stacks(
            "prefill_paged", x, cache, cache["pos"], table, positions,
            slot_t, lora, vlen=last + 1)
        logits = self._post_pf(self._head_params, x, last)
        new_cache = dict(cache)
        new_cache["stacks"] = new_stacks
        return logits, new_cache

    # --- helpers -----------------------------------------------------------
    def _next_chunk(self, remaining: int) -> int:
        """Chunk-size schedule: full ``prefill_chunk`` slabs, then one
        pow2 final chunk (padded; min 8) — one jit compilation per size.
        Every stack mix chunks: recurrent stacks hand their entry/exit
        state between chunks, so the schedule never needs a whole-prompt
        special case."""
        cap = self.prefill_chunk
        if remaining >= cap:
            return cap
        c = 8
        while c < remaining:
            c *= 2
        return c

    def _chunk_sizes(self) -> tuple:
        """Every chunk size ``_next_chunk`` can emit (full slabs + the
        pow2 final-chunk grid) — the prefill graphs warmup() pre-traces,
        one compilation per size."""
        return tuple(sorted({self._next_chunk(r)
                             for r in range(1, self.prefill_chunk + 1)}))

    def compile_events(self) -> int:
        """Total jit-cache entries across the loop's step functions — one
        per (function, argument-shape) compilation, monotonic.  step()
        mirrors it into EngineStats, so any post-warmup trace shows up as
        ``stats.recompiles_after_warmup`` > 0."""
        total = 0
        split = (*self._stack_dec.values(), *self._grp_dec.values(),
                 *self._stack_pf.values(), *self._grp_pf.values())
        post = ((self._post_dec, self._post_pf)
                if self._post_dec is not None else ())
        for fn in (self._decode, self._decode_b, self._chunk,
                   *split, *post):
            total += fn._cache_size()
        return total

    def warmup(self) -> dict:
        """Trace every step graph the hot loop can need — one bucketed
        decode per ladder bucket (or the one full-batch step when
        bucketing is off), one prefill graph per reachable chunk size —
        and pre-solve each bucket's matmul tiles.  The traced steps
        actually execute on the live pool with an all-trash page table
        and every row inactive: full-attention writes land in the trash
        page and no row's position moves.  The step programs donate the
        cache, so each warm-up step hands its pool to the next and the
        last one's becomes the live pool again, under the live table.
        Writes into a row's windowed ring or recurrent state are garbage
        a later request never reads: admission resets the recurrent
        state and the ring masks every key the row has not written.

        After this, a churny-concurrency trace only ever hits cache
        entries: ``stats.recompiles_after_warmup`` stays 0 (the CI gate).
        Idempotent — a second call hits the jit caches and returns fast.
        Returns {"warmup_s", "graphs", "decode_buckets", "chunk_sizes"}.
        """
        t0 = time.perf_counter()
        eng, cfg = self.eng, self.cfg
        wcache = dict(self.cache)
        wcache["table"] = jnp.full(
            (self.max_slots, self.geom.pages_per_row),
            self.geom.trash_page, jnp.int32)
        d = cfg.d_model
        outs = []
        if self.wpolicy.active:
            # streamed split step: one decode graph per stack (or per
            # streamed group shape) + one prefill graph per stack per
            # chunk size, plus the two small post graphs
            eng.plan.presolve_tiles(self.max_slots)
            lg, _ = self._decode_streamed(
                jnp.zeros((self.max_slots, 1, d), jnp.bfloat16),
                jnp.zeros((self.max_slots,), bool),
                eng._lora_for([None] * self.max_slots), cache=wcache)
            outs.append(lg)
            for c in self._chunk_sizes():
                eng.plan.presolve_tiles(c)
                lg, _ = self._chunk_streamed(
                    jnp.zeros((1, c, d), jnp.bfloat16), 0, 0, c - 1,
                    eng._lora_for([None]), cache=wcache)
                outs.append(lg)
            jax.block_until_ready(outs)
            self.warmed = True
            self._warmup_graphs = self.compile_events()
            eng.stats.compile_events = self._warmup_graphs
            self._warmup_report = {
                "warmup_s": time.perf_counter() - t0,
                "graphs": self._warmup_graphs,
                "decode_buckets": [],
                "chunk_sizes": [int(c) for c in self._chunk_sizes()]}
            return self._warmup_report
        if self._bucketed:
            for b in self.buckets:
                eng.plan.presolve_tiles(b)
                lg, wcache = self._decode_b(
                    eng.params, jnp.zeros((b, 1, d), jnp.bfloat16), wcache,
                    eng._lora_for([None] * b), jnp.zeros((b,), bool),
                    jnp.arange(b, dtype=jnp.int32), self.logits)
                outs.append(lg)
        else:
            eng.plan.presolve_tiles(self.max_slots)
            lg, wcache = self._decode(
                eng.params, jnp.zeros((self.max_slots, 1, d), jnp.bfloat16),
                wcache, eng._lora_for([None] * self.max_slots),
                jnp.zeros((self.max_slots,), bool))
            outs.append(lg)
        chunks = self._chunk_sizes()
        for c in chunks:
            eng.plan.presolve_tiles(c)
            lg, wcache = self._chunk(
                eng.params, jnp.zeros((1, c, d), jnp.bfloat16), wcache,
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(c - 1, jnp.int32), eng._lora_for([None]))
            outs.append(lg)
        self.cache = dict(wcache, table=self.cache["table"])
        jax.block_until_ready(outs)
        self.warmed = True
        self._warmup_graphs = self.compile_events()
        eng.stats.compile_events = self._warmup_graphs
        self._warmup_report = {
            "warmup_s": time.perf_counter() - t0,
            "graphs": self._warmup_graphs,
            "decode_buckets": [int(b) for b in self.buckets],
            "chunk_sizes": [int(c) for c in chunks]}
        return self._warmup_report

    def _slot_lora(self) -> Optional[dict]:
        return self.eng._lora_for(self.scheduler.running)

    def _row_lora(self, req: Request) -> Optional[dict]:
        return self.eng._lora_for([req])

    # --- row snapshot / restore (the spill tier) ---------------------------
    def _row_groups(self, slot: int, phys: np.ndarray):
        """Yield (group_name, leaf, snapshot_arrays) for every per-row
        piece of decode state: pooled pages for full-attention layers
        (only the DRAM-resident physical pages in ``phys`` — cold pages
        already live on Flash), the fixed ring for windowed layers, the
        row slice for SSM states."""
        for si, (patterns, _count) in enumerate(self.cfg.layer_plan()):
            for pi, _pat in enumerate(patterns):
                leaf = self.cache["stacks"][si][pi]
                group = f"s{si}p{pi}"
                if isinstance(leaf, KP.PagedLayerKV):
                    if leaf.window:
                        sl = slice(slot * leaf.ppw, (slot + 1) * leaf.ppw)
                        arrays = {f: np.asarray(getattr(leaf, f)[:, sl])
                                  for f in KP.KV_FIELDS}
                    else:
                        arrays = {f: np.asarray(getattr(leaf, f)[:, phys])
                                  for f in KP.KV_FIELDS}
                else:
                    leaves = jax.tree.leaves(leaf)
                    arrays = {f"x{i}": np.asarray(x[:, slot:slot + 1])
                              for i, x in enumerate(leaves)}
                yield group, leaf, arrays

    def _pooled_groups(self):
        """(stack, pattern, group_name, leaf) for every full-attention
        page pool — the layer groups that carry per-page bytes (windowed
        rings and SSM states are per-slot, never per-page)."""
        for si, (patterns, _count) in enumerate(self.cfg.layer_plan()):
            for pi, _pat in enumerate(patterns):
                leaf = self.cache["stacks"][si][pi]
                if isinstance(leaf, KP.PagedLayerKV) and not leaf.window:
                    yield si, pi, f"s{si}p{pi}", leaf

    def _spill_row(self, slot: int, req: Request, pending: bool) -> None:
        """Move a preempted row's DRAM pages to Flash and free them.
        Pages the proactive tier already spilled stay where they are —
        their blobs are keyed by uid and survive the preemption; the
        restore leaves them on Flash.  ``pending``: the row was evicted
        mid-step, after sampling but before its token's KV append — the
        token replays through decode on resume instead of carrying saved
        logits."""
        n_kv = int(self.pool.row_pos[slot])
        n_pages = self.pool.pages_for(n_kv)
        held = self.pool.row_pages[slot]
        dram_idxs = [i for i in range(n_pages) if held[i] >= 0]
        flash_idxs = [i for i in range(n_pages) if held[i] < 0]
        phys = np.asarray([held[i] for i in dram_idxs], np.int64)
        groups = []
        for gi, (group, _leaf, arrays) in enumerate(
                self._row_groups(slot, phys)):
            self.spill.put(req.uid, group, arrays,
                           pages=len(dram_idxs) if gi == 0 else 0)
            groups.append(group)
        self._spilled[req.uid] = {
            "n_kv": n_kv, "pending": pending, "groups": groups,
            "dram_idxs": dram_idxs, "flash_idxs": flash_idxs,
            "logits": None if pending else np.asarray(self.logits[slot])}
        req.spilled_flash_pages = len(flash_idxs)
        self.pool.free_row(slot)
        # count the pages written to Flash (free_row may also return a
        # boundary page ensure() pre-allocated this step but never filled)
        self.eng.stats.spilled_pages += len(dram_idxs)
        # residency accounting: the whole row now lives on Flash (its cold
        # pages left flash_page_count when free_row cleared the row)
        self.pool.spilled_pages += n_pages
        self.cache = T.free_slots(self.cache,
                                  self.eng.h2d([slot], jnp.int32))
        self._hold.discard(slot)

    def _restore_into_slot(self, req: Request, slot: int, rec: dict) -> None:
        """Bring a spilled row back page-exact: allocate fresh DRAM pages
        for the snapshot part (cold pages the proactive tier had already
        spilled STAY on Flash — they rejoin through the staging reserve),
        read each layer group from Flash (group-ahead prefetch overlapping
        the device writes), and resume sampling from the saved logits — or
        hold the slot one step to replay a pending token through decode."""
        n_kv = rec["n_kv"]
        flash_idxs = rec["flash_idxs"]
        # a mid-prefill victim resumes chunking, so the row needs pages
        # for the WHOLE prompt again (further chunks write past the
        # snapshot); only the first pages_for(n_kv) get bytes restored
        pf = rec.get("prefill")
        alloc_tokens = pf["t"] if pf is not None else n_kv
        ok = self.pool.alloc_row(slot, alloc_tokens, flash_idxs=flash_idxs)
        while not ok and self._spill_one_cold(exclude={slot}):
            ok = self.pool.alloc_row(slot, alloc_tokens,
                                     flash_idxs=flash_idxs)
        assert ok, "admission checked the pages were free/spillable"
        req.spilled_flash_pages = 0
        self.pool.spilled_pages -= self.pool.pages_for(n_kv)
        phys = np.asarray([self.pool.row_pages[slot][i]
                           for i in rec["dram_idxs"]], np.int64)
        groups = rec["groups"]
        self.spill.prefetch_async(req.uid, groups[0])
        gi = 0
        new_stacks = [list(row) for row in self.cache["stacks"]]
        for si, (patterns, _count) in enumerate(self.cfg.layer_plan()):
            for pi, _pat in enumerate(patterns):
                if gi + 1 < len(groups):
                    self.spill.prefetch_async(req.uid, groups[gi + 1])
                arrays = self.spill.fetch(req.uid, groups[gi])
                leaf = self.cache["stacks"][si][pi]
                if isinstance(leaf, KP.PagedLayerKV):
                    fields = {}
                    for f in KP.KV_FIELDS:
                        big = getattr(leaf, f)
                        val = self.eng.h2d(arrays[f]).astype(big.dtype)
                        if leaf.window:
                            sl = slot * leaf.ppw
                            big = jax.lax.dynamic_update_slice_in_dim(
                                big, val, sl, axis=1)
                        else:
                            # in place: the live leaf is donated
                            big = KP.write_pages(
                                big, self.eng.h2d(phys, jnp.int32), val)
                        fields[f] = big
                    leaf = leaf.replace(**fields)
                else:
                    flat, treedef = jax.tree.flatten(leaf)
                    flat = [jax.lax.dynamic_update_slice_in_dim(
                                x,
                                self.eng.h2d(arrays[f"x{i}"]).astype(x.dtype),
                                slot, axis=1)
                            for i, x in enumerate(flat)]
                    leaf = jax.tree.unflatten(treedef, flat)
                new_stacks[si][pi] = leaf
                gi += 1
        self.cache = dict(self.cache,
                          stacks=tuple(tuple(r) for r in new_stacks))
        # the row snapshot is consumed; page-granular cold blobs stay on
        # Flash (the row's Flash-resident pages stage on demand)
        self.spill.drop_groups(req.uid, groups)
        self.eng.stats.restored_pages += len(rec["dram_idxs"])
        if pf is not None:
            # resume chunked prefill from the last chunk boundary: the
            # restored recurrent state / KV pages carry every chunk
            # already run, and — exactly like a fresh admission — pos and
            # row_pos stay 0 until the whole prompt is in
            req.resume_prefill = False
            self._prefilling[slot] = {"req": req, "tokens": pf["tokens"],
                                      "t": pf["t"], "next": n_kv}
            return
        self.cache["pos"] = self.cache["pos"].at[slot].set(n_kv)
        self.pool.row_pos[slot] = n_kv
        if rec["pending"]:
            self._hold.add(slot)
        else:
            self.logits = self.logits.at[slot].set(
                self.eng.h2d(rec["logits"]))

    # --- proactive spill: cold pages of running rows -----------------------
    def _cold_candidates(self) -> List:
        """(logical_idx, slot) spill candidates over running decode rows,
        oldest page first: DRAM-resident, full, single-owner pages outside
        the hot tail, from rows with staging room left (a row's Flash
        pages must fit the staging reserve for one decode wave), capped by
        the plan's Flash budget."""
        if not self.proactive:
            return []
        pol = self.spill_policy
        budget_left = pol.flash_budget_pages - self.spill.pages_on_flash
        if budget_left <= 0:
            return []
        out = []
        for slot, req in enumerate(self.scheduler.running):
            if req is None or slot in self._prefilling:
                continue
            room = pol.staging_pages - self.pool.flash_pages_of(slot)
            if room <= 0:
                continue
            idxs = self.pool.cold_pages(slot, pol.hot_pages)[:room]
            out.extend((i, slot) for i in idxs)
        out.sort()
        return out[:budget_left]

    def _spill_headroom(self) -> int:
        """Pages admission may oversubscribe DRAM by right now (the
        scheduler calls this through ``_fits``)."""
        return len(self._cold_candidates())

    def _spill_cold_page(self, slot: int, idx: int) -> None:
        """One cold page of a running row: snapshot every pooled layer
        group's page bytes to Flash, then release the DRAM page.  The row
        keeps decoding — the page rejoins each step through the staging
        reserve."""
        req = self.scheduler.running[slot]
        phys = self.pool.row_pages[slot][idx]
        for gi, (_si, _pi, group, leaf) in enumerate(self._pooled_groups()):
            arrays = {f: np.asarray(getattr(leaf, f)[:, phys])
                      for f in KP.KV_FIELDS}
            self.spill.put_page(req.uid, idx, group, arrays,
                                count_page=(gi == 0))
        self.pool.spill_page(slot, idx)
        self.eng.stats.cold_spilled_pages += 1

    def _spill_one_cold(self, exclude: set = frozenset()) -> bool:
        """Spill the globally-oldest cold candidate; False when none is
        eligible (callers fall back to full-row preemption)."""
        for idx, slot in self._cold_candidates():
            if slot not in exclude:
                self._spill_cold_page(slot, idx)
                return True
        return False

    def _proactive_spill(self) -> None:
        """Watermark pump: when the free list drops below the plan's low
        watermark, spill cold pages of running rows until the high
        watermark (or the candidates run out)."""
        if not self.proactive \
                or self.pool.free_pages >= self.spill_policy.low_watermark:
            return
        while self.pool.free_pages < self.spill_policy.high_watermark \
                and self._spill_one_cold():
            pass

    # --- decode-time staging: gather Flash pages for a wave ----------------
    def _stage_wave(self, needed: List) -> None:
        """Make every (slot, idx) in ``needed`` kernel-visible: already
        STAGED pages are LRU-touched (staging-cache hits); FLASH pages
        claim a staging device page (evicting LRU pages the wave doesn't
        need), then their layer-group blobs stream in from Flash with
        layer-ahead prefetch — while group g's bytes install on the
        device, the worker is already reading group g+1 (and the next
        page's first group).  Table entries flip to the staging page only
        at commit: an in-flight page is never visible to dispatch."""
        to_fetch = []
        for slot, idx in needed:
            if self.pool.row_res[slot][idx] == KP.RES_STAGED:
                self.pool.begin_stage(slot, idx)       # LRU touch
                self._step_hits += 1
                self.eng.stats.flash_page_hits += 1
            else:
                to_fetch.append((slot, idx))
        if not to_fetch:
            return
        groups = [g for _si, _pi, g, _leaf in self._pooled_groups()]
        uid_of = {slot: self.scheduler.running[slot].uid
                  for slot, _ in to_fetch}
        # page-ahead: the first group of every needed page is requested up
        # front, so the worker reads while we claim staging slots
        for slot, idx in to_fetch:
            self.spill.prefetch_page(uid_of[slot], idx, groups[0])
        protect = set(needed)
        updates: Dict[tuple, list] = {}
        for n, (slot, idx) in enumerate(to_fetch):
            sid = self.pool.begin_stage(slot, idx)
            while sid is None:
                victim = self.pool.stage_victim(protect)
                assert victim is not None, \
                    "staging reserve cannot hold the wave (planner bug)"
                self.pool.unstage(*victim)
                sid = self.pool.begin_stage(slot, idx)
            uid = uid_of[slot]
            m0 = self.spill.prefetch_misses
            for gi, group in enumerate(groups):
                # layer-ahead: while this group's blob is consumed, the
                # worker already reads group g+1 (every page's group 0 was
                # requested up front)
                if gi + 1 < len(groups):
                    self.spill.prefetch_page(uid, idx, groups[gi + 1])
                arrays = self.spill.fetch_page(uid, idx, group)
                updates.setdefault(group, []).append((sid, arrays))
            # page-granular accounting: a page whose every blob came
            # through the prefetch pipeline is a hit; any synchronous
            # Flash read makes it a miss
            if self.spill.prefetch_misses > m0:
                self._step_misses += 1
                self.eng.stats.flash_page_misses += 1
            else:
                self._step_hits += 1
                self.eng.stats.flash_page_hits += 1
        new_stacks = [list(row) for row in self.cache["stacks"]]
        for si, pi, group, leaf in list(self._pooled_groups()):
            if group not in updates:
                continue
            # one batched scatter per field (not one whole-array copy per
            # staged page): all the wave's pages land in a single .set
            sids = self.eng.h2d([sid for sid, _ in updates[group]],
                                jnp.int32)
            fields = {}
            for f in KP.KV_FIELDS:
                vals = np.stack([np.asarray(arrays[f])
                                 for _, arrays in updates[group]], axis=1)
                # in place: the live leaf is donated
                fields[f] = KP.write_pages(getattr(leaf, f), sids,
                                           self.eng.h2d(vals))
            new_stacks[si][pi] = leaf.replace(**fields)
        self.cache = dict(self.cache,
                          stacks=tuple(tuple(r) for r in new_stacks))
        for slot, idx in to_fetch:
            self.pool.commit_stage(slot, idx)

    def _plan_waves(self, slots: List[int]) -> List[List[int]]:
        """Partition the decodable slots into staging waves: each wave's
        total Flash-resident pages fit the staging reserve at once.  Rows
        with no Flash pages ride along in the first wave for free — the
        no-spill steady state is exactly one wave (one decode call, as
        before)."""
        flashy = {s: self.pool.flash_pages_of(s) for s in slots}
        plain = [s for s in slots if not flashy[s]]
        cap = max(1, self.spill_policy.staging_pages)
        waves: List[List[int]] = []
        cur: List[int] = []
        load = 0
        for s in sorted(s for s in slots if flashy[s]):
            n = flashy[s]
            assert n <= cap, \
                f"row {s} holds {n} Flash pages > staging reserve {cap}"
            if cur and load + n > cap:
                waves.append(cur)
                cur, load = [], 0
            cur.append(s)
            load += n
        if cur:
            waves.append(cur)
        if not waves:
            return [plain]
        waves[0] = plain + waves[0]
        return waves

    def _upload_table(self, visible) -> None:
        """Upload the page table with every slot OUTSIDE ``visible``
        masked to the trash page: rows mid-prefill, rows waiting for a
        later staging wave (their Flash pages are not resident yet) and
        empty rows are never visible to dispatch, and their ride-along
        appends land in the trash."""
        table = self.pool.table
        hidden = [s for s in range(self.max_slots) if s not in visible]
        if hidden:
            table = table.copy()
            table[hidden] = self.geom.trash_page
        self.cache["table"] = self.eng.h2d(table)

    # --- admission + the unified prefill step ------------------------------
    def _admit_into_slot(self, req: Request, slot: int) -> None:
        rec = self._spilled.pop(req.uid, None)
        if rec is not None:
            self._restore_into_slot(req, slot, rec)
            return
        assert not req.generated, \
            "a preempted request must resume from its spill record"
        toks = list(req.prompt_tokens)
        t = len(toks)
        sharing = self.pool.prefix_sharing
        ok = self.pool.alloc_row(slot, t,
                                 token_ids=toks if sharing else None,
                                 salt=req.adapter or "")
        while not ok and self._spill_one_cold(exclude={slot}):
            # admission oversubscribed DRAM against the spillable-cold
            # headroom — deliver it: cold pages of running rows move to
            # Flash until the prompt's pages fit
            ok = self.pool.alloc_row(slot, t,
                                     token_ids=toks if sharing else None,
                                     salt=req.adapter or "")
        assert ok, "admission checked the pages were free/spillable"
        # state-passing chunked prefill reads the row's recurrent state at
        # chunk 0 — a fresh prompt must enter with the clean initial state,
        # not the previous occupant's exit state
        self.cache = T.reset_row_recurrent(self.cache, self.cfg, slot)
        shared = int(self.pool.row_shared[slot])
        self.eng.stats.shared_prompt_tokens += shared
        # prompt KV goes straight into the allocated pages, chunk by
        # chunk, starting past any adopted prefix — _run_prefill_chunks
        # does the work under the per-step token budget
        self._prefilling[slot] = {"req": req, "tokens": toks, "t": t,
                                  "next": shared}

    def _run_prefill_chunks(self) -> None:
        """Advance prefilling rows by whole chunks until the per-step
        token budget runs out — ROUND-ROBIN, one chunk per row per pass,
        so a long prompt in a low slot can never head-of-line-block other
        rows' first chunks (that wait is exactly the TTFT tail the CI
        gate protects).  A row whose final chunk lands here becomes
        decodable this very step (its first token samples immediately —
        TTFT is unchanged for prompts that fit the budget)."""
        if not self._prefilling:
            return
        budget = self.prefill_token_budget
        h2d, stats = self.eng.h2d, self.eng.stats
        ran = False
        # allocation only happens at admission, so the table is constant
        # for the whole chunk phase — upload it once
        self.cache["table"] = h2d(self.pool.table)
        while budget > 0 and self._prefilling:
            advanced = False
            order = sorted(self._prefilling)
            # the rotation cursor persists ACROSS steps: when the budget
            # only covers one chunk per step, consecutive steps still
            # serve different rows instead of always restarting at the
            # lowest slot
            pivot = sum(1 for s in order if s < self._prefill_rr)
            for slot in order[pivot:] + order[:pivot]:
                if budget <= 0:
                    break
                st = self._prefilling[slot]
                req, toks, t = st["req"], st["tokens"], st["t"]
                c = self._next_chunk(t - st["next"])
                valid = min(c, t - st["next"])
                if ran and valid > budget:
                    # hard per-step budget: only the step's FIRST chunk
                    # may overshoot (so a budget set below one chunk
                    # still guarantees progress); every later chunk must
                    # fit what is left
                    continue
                self._prefill_rr = slot + 1
                ids = np.zeros((1, c), np.int64)
                ids[0, :valid] = np.asarray(toks[st["next"]:st["next"] + valid])
                embeds = self.eng.embed(ids)
                last_idx = (t - 1 - st["next"]
                            if st["next"] + c >= t else c - 1)
                self._count_pool()
                if self.wpolicy.active:
                    logits1, self.cache = self._chunk_streamed(
                        embeds, slot, st["next"], last_idx,
                        self._row_lora(req))
                else:
                    logits1, self.cache = self._chunk(
                        self.eng.params, embeds, self.cache,
                        h2d(slot, jnp.int32), h2d(st["next"], jnp.int32),
                        h2d(last_idx, jnp.int32), self._row_lora(req))
                st["next"] += valid
                budget -= valid
                ran = advanced = True
                stats.prefill_tokens += valid
                stats.prefill_chunks += 1
                if st["next"] >= t:     # prompt complete: row is decodable
                    stats.prompts_completed += 1
                    stats.prefill_causal_keys += t * (t + 1) // 2
                    self.logits = self.logits.at[slot].set(logits1[0])
                    self.cache["pos"] = self.cache["pos"].at[slot].set(t)
                    self.pool.row_pos[slot] = t
                    self.pool.register_prefix(slot, toks,
                                              salt=req.adapter or "")
                    del self._prefilling[slot]
            if not advanced:
                break

    def _spill_prefilling_row(self, victim: Request) -> None:
        """Evict a mid-prefill row under page pressure.  A row with at
        least one finished chunk spills its written pages AND its
        recurrent chunk-boundary state (SSM/conv/shift/wkv leaves ride
        the same spill record as windowed ring slices) — on re-admission
        it resumes from the last chunk boundary, bitwise-identical to an
        uninterrupted prefill.  A row with no finished chunk just frees
        and requeues: there is nothing worth round-tripping, and the
        adoption stats recorded at admission are retracted so the restart
        never inflates the prefix-cache numbers."""
        vslot = victim.slot
        st = self._prefilling[vslot]
        done = st["next"]
        if done <= 0:
            self.eng.stats.shared_prompt_tokens -= int(
                self.pool.row_shared[vslot])
            self.pool.retract_prompt_stats(vslot, st["t"])
            self.scheduler.evict(victim)
            del self._prefilling[vslot]
            self.pool.free_row(vslot)
            self.cache = T.free_slots(self.cache,
                                      self.eng.h2d([vslot], jnp.int32))
            return
        n_pages = self.pool.pages_for(done)
        held = self.pool.row_pages[vslot]
        dram_idxs = list(range(n_pages))
        assert all(held[i] >= 0 for i in dram_idxs), \
            "prefilling rows are excluded from the proactive spill tier"
        phys = np.asarray([held[i] for i in dram_idxs], np.int64)
        groups = []
        for gi, (group, _leaf, arrays) in enumerate(
                self._row_groups(vslot, phys)):
            self.spill.put(victim.uid, group, arrays,
                           pages=n_pages if gi == 0 else 0)
            groups.append(group)
        self._spilled[victim.uid] = {
            "n_kv": done, "pending": False, "groups": groups,
            "dram_idxs": dram_idxs, "flash_idxs": [], "logits": None,
            "prefill": {"t": st["t"], "tokens": st["tokens"]}}
        # admission must charge the resume the full prompt's pages: the
        # restore adopts nothing (bytes come back from Flash), so the
        # fresh-prompt adoption discount would under-reserve
        victim.resume_prefill = True
        self.scheduler.evict(victim)
        del self._prefilling[vslot]
        # NO stats retraction: the adopted/computed tokens round-trip
        # through Flash byte-exact — nothing is ever recomputed
        self.pool.free_row(vslot)
        self.eng.stats.spilled_pages += n_pages
        self.pool.spilled_pages += n_pages
        self.cache = T.free_slots(self.cache,
                                  self.eng.h2d([vslot], jnp.int32))

    def _pick_page_victim(self, exclude: set) -> Optional[Request]:
        """Page pressure: evict the row holding the most DRAM pool pages
        (frees the most DRAM per spill), excluding the row asking for the
        page and rows still prefilling (those restart instead of
        spilling).  Rows restored this very step (``_hold``) only lose
        their pages as a last resort — re-spilling one before its pending
        decode would round-trip Flash for zero tokens of progress."""
        cands = [r for r in self.scheduler.running
                 if r is not None and r.slot not in exclude
                 and r.slot not in self._prefilling]
        fresh = [r for r in cands if r.slot not in self._hold]
        cands = fresh or cands
        if not cands:
            return None
        return max(cands, key=lambda r: (self.pool.dram_pages_held(r.slot),
                                         len(r.generated)))

    def close(self) -> None:
        """Stop the spill tier's prefetch worker (loops are cheap to build;
        long-lived processes that rebuild them should close the old one).
        The weight-group store belongs to the Engine (it owns the Flash
        export), so it is NOT closed here — rebuilt loops reuse it."""
        self.spill.close()

    # --- the incremental serving API ---------------------------------------
    def _validate(self, req: Request) -> None:
        """Static admissibility — a request this loop can never serve is
        refused up front with a typed error (the gateway's HTTP 400)."""
        need = req.length + req.max_new_tokens
        if need > self.eng.max_seq:
            raise AdmissionError(
                f"request {req.uid}: prompt+decode budget {need} exceeds "
                f"max_seq={self.eng.max_seq}", uid=req.uid)
        if need > self.scheduler.token_budget:
            raise AdmissionError(
                f"request {req.uid}: {need} tokens exceed the scheduler "
                f"token budget {self.scheduler.token_budget}", uid=req.uid)
        if self.pool.pages_for(need) > self.geom.num_pages:
            raise AdmissionError(
                f"request {req.uid}: needs {self.pool.pages_for(need)} KV "
                f"pages, pool holds {self.geom.num_pages}", uid=req.uid)

    def submit(self, req: Request,
               arrival_step: Optional[int] = None) -> int:
        """Enqueue one request; callable at any time, including between
        steps while other requests decode.  Resolves the request's
        sampling params (falling back to ``default_sampling``), checks
        static admissibility (``AdmissionError``) and the bounded queue
        (``QueueFullError``) — a rejected request touches no pool, slot,
        or prefix-index state.  Returns the uid."""
        if req.sampling is None:
            if self.default_sampling is None:
                raise ValueError(
                    f"request {req.uid} has no SamplingParams and the loop "
                    f"has no default_sampling")
            req.sampling = self.default_sampling
        self._validate(req)
        if self.max_queue is not None \
                and len(self.scheduler.waiting) >= self.max_queue:
            self.rejected += 1
            raise QueueFullError(
                f"request {req.uid}: submit queue full "
                f"({len(self.scheduler.waiting)} waiting, "
                f"bound {self.max_queue})", uid=req.uid)
        if req.arrival_t == 0.0:
            req.arrival_t = time.perf_counter()
        self.scheduler.submit(
            req, arrival_step=self._step_no if arrival_step is None
            else arrival_step)
        self._streams[req.uid] = {"toks": [], "cursor": 0, "done": False}
        return req.uid

    def poll(self, uid: int):
        """Tokens committed for ``uid`` since the last poll, plus the done
        flag.  The stream record drops once the request is done and fully
        consumed (a later poll raises KeyError)."""
        st = self._streams[uid]
        new = st["toks"][st["cursor"]:]
        st["cursor"] = len(st["toks"])
        if st["done"] and st["cursor"] == len(st["toks"]):
            del self._streams[uid]
        return new, st["done"]

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def _emit(self, req: Request, token: int, done: bool,
              events: List[TokenEvent]) -> None:
        ev = TokenEvent(uid=req.uid, token=token,
                        index=len(req.generated) - 1, done=done, request=req)
        events.append(ev)
        st = self._streams.get(req.uid)
        if st is not None:
            st["toks"].append(token)
            st["done"] = done
        if self.on_token is not None:
            self.on_token(req, token, done)

    def _sample(self, sub: jax.Array) -> np.ndarray:
        """One sampled token per slot, honoring per-request sampling
        params.  Rows are grouped by their request's ``SamplingParams``;
        each distinct group samples the full logits matrix (a row's draw
        never depends on which other rows are active) and contributes its
        own rows.  A single group consumes ``sub`` directly, so uniform
        traces are bit-identical to the old loop-wide-sampling path."""
        groups: Dict[SM.SamplingParams, List[int]] = {}
        for slot, req in enumerate(self.scheduler.running):
            if req is None or slot in self._prefilling \
                    or slot in self._hold:
                continue
            groups.setdefault(req.sampling, []).append(slot)
        tok = np.zeros((self.max_slots,), np.int64)
        for gi, (sp, slots) in enumerate(
                sorted(groups.items(), key=lambda kv: kv[1][0])):
            k = sub if len(groups) == 1 else jax.random.fold_in(sub, gi)
            t = np.asarray(SM.sample(self.logits, sp, self.cfg.vocab_size,
                                     k))
            tok[slots] = t[slots]
        return tok

    def step(self) -> List[TokenEvent]:
        """Advance the loop by ONE unified step: preempt/spill under
        pressure, admit from the queue (priority + deadline order), run
        prompt chunks under the token budget, sample one token for every
        decodable row (committed tokens are emitted HERE — streaming
        consumers see them before the decode compute below even runs),
        then the batched decode in staging waves, each wave gathered into
        its smallest covering batch bucket.

        The step is the profiler span ``engine.step``; its arguments are
        the step number and ``EngineStats``' step counters as they stood
        when it began.  One span per phase the step runs tiles it, in
        order: ``engine.admit``, ``engine.prefill``, ``engine.sample``,
        ``engine.commit``, ``engine.decode``."""
        stats = self.eng.stats
        try:
            with jax.profiler.StepTraceAnnotation(
                    "engine.step", step_num=self._step_no,
                    **stats.step_counters()):
                stats.steps += 1
                return self._step_inner()
        finally:
            # mirror the jit caches into the stats at EVERY exit path, so
            # a compile on any phase of this step is immediately visible
            ev = self.compile_events()
            stats.compile_events = ev
            if self.warmed:
                stats.recompiles_after_warmup = ev - self._warmup_graphs
            if self.wpolicy.active:
                store = self.eng.weight_store
                stats.weight_group_hits = store.prefetch_hits
                stats.weight_group_misses = store.prefetch_misses
                stats.weight_stall_s = (
                    sum(r.stall_s for r in self._wstreams.values())
                    + sum(r.stall_s
                          for r in self._expert_rings.values()))
                # resident_bytes already counts the rings' slots
                stats.dram_weight_bytes = self.wpolicy.resident_bytes

    def _step_inner(self) -> List[TokenEvent]:
        sched = self.scheduler
        events: List[TokenEvent] = []
        sched.step = self._step_no
        with _span("engine.admit"):
            self._admit()
        if self._prefilling:
            # the unified step, phase 1: pending prompt chunks go straight
            # into pool pages under the per-step token budget (rows whose
            # final chunk lands here decode below, in the same step)
            with _span("engine.prefill"):
                self._run_prefill_chunks()
        running = list(sched.running)
        n_active = sum(r is not None for r in running)
        self.peak_active = max(self.peak_active, n_active)
        if n_active:
            # one token for every decodable slot (rows that just finished
            # prefilling sample from their final chunk's logits — TTFT is
            # measured right here)
            with _span("engine.sample"):
                self._key, sub = jax.random.split(self._key)
                tok_np = self._sample(sub)
            with _span("engine.commit"):
                self._commit(running, tok_np, events)
            if any(r is not None and slot not in self._prefilling
                   for slot, r in enumerate(sched.running)):
                with _span("engine.decode"):
                    self._decode_phase()
        self._step_no += 1
        return events

    def _admit(self) -> None:
        """Preempt and spill under pressure, then admit from the queue."""
        sched = self.scheduler
        # hold rows owe a pending decode before their logits are valid;
        # preempting one mid-replay would re-spill an unchanged row
        preempted = sched.maybe_preempt(
            exclude_slots=set(self._hold) | set(self._prefilling))
        if preempted is not None:
            freed_slot, victim = preempted
            self._spill_row(freed_slot, victim, pending=False)
        # proactive spill ahead of demand: keep the free list above
        # the plan's low watermark by moving running rows' cold pages
        # to Flash (decode stages them back page-granularly)
        self._proactive_spill()
        admitted = sched.admit()
        now = time.perf_counter()
        for slot, req in admitted:
            if req.admitted_t == 0.0:     # the first admission ends the wait
                req.admitted_t = now
            self._admit_into_slot(req, slot)
        self.peak_kv_pages = max(
            self.peak_kv_pages,
            sum(self.pool.pages_held(s) for s in range(self.max_slots)))

    def _commit(self, running: List[Optional[Request]], tok_np: np.ndarray,
                events: List[TokenEvent]) -> None:
        """Append each decodable row's sampled token to its request and
        emit it; finish and free the rows that are done."""
        stats, sched = self.eng.stats, self.scheduler
        now = time.perf_counter()
        for slot, req in enumerate(running):
            if req is None or slot in self._hold \
                    or slot in self._prefilling:
                continue
            t_id = int(tok_np[slot])
            req.generated.append(t_id)
            if req.first_token_t == 0.0:
                req.first_token_t = now
            sp = req.sampling
            finished = ((sp.eos_token >= 0 and t_id == sp.eos_token)
                        or len(req.generated) >= req.decode_cap)
            self._emit(req, t_id, finished, events)
            if finished:
                req.finish_t = now
                sched.finish(req)
                # refcount-decrement reclaim: private pages return to
                # the free list; indexed prefix pages survive EOS for
                # the next request with the same prompt head.  Cold
                # blobs the proactive tier parked on Flash are dropped
                # with the request.
                self.pool.free_row(slot)
                self.spill.drop(req.uid)
                self.cache = T.free_slots(
                    self.cache, self.eng.h2d([slot], jnp.int32))
                stats.requests.append(RequestStats(
                    uid=req.uid, ttft_s=req.ttft, tpot_s=req.tpot,
                    latency_s=req.finish_t - req.arrival_t,
                    new_tokens=len(req.generated),
                    preemptions=req.preemptions,
                    queue_s=req.admitted_t - req.arrival_t))

    def _decode_phase(self) -> None:
        """The unified step, phase 2: allocate-on-append, then the batched
        decode of every decodable row in staging waves."""
        eng, sched = self.eng, self.scheduler
        # allocate-on-append: every surviving decodable row appends one
        # token at its position this decode — rows crossing a page
        # boundary take a page from the free list (index pins are
        # evicted first).  When the pool still runs dry, cold pages of
        # running rows spill FIRST (the row keeps decoding through the
        # staging reserve — no token of progress is lost), then the
        # biggest page-holder is preempted wholesale, and only then are
        # mid-prefill rows spilled — they resume from their last chunk
        # boundary (state-passing chunked prefill), so no prompt work
        # is ever forfeited
        for slot, req in enumerate(sched.running):
            if req is None or slot in self._prefilling:
                continue
            while not self.pool.ensure(slot, int(self.pool.row_pos[slot])):
                if self._spill_one_cold():
                    continue
                victim = self._pick_page_victim(exclude={slot})
                if victim is None:
                    pref = [r for r in sched.running
                            if r is not None and r.slot != slot
                            and r.slot in self._prefilling]
                    assert pref, \
                        "pool cannot hold a single request (geometry bug)"
                    self._spill_prefilling_row(max(
                        pref, key=lambda r: self.pool.pages_held(r.slot)))
                    continue
                vslot = victim.slot
                sched.evict(victim)
                self._spill_row(vslot, victim, pending=True)

        # batched decode in staging waves: every decodable row advances
        # at its own pos (hold rows feed their pending token — same
        # shape, no re-jit).  Rows whose cold pages sit on Flash first
        # gather them into the staging reserve (layer-ahead prefetch);
        # when the reserve cannot hold everyone's cold pages at once the
        # decode runs in waves, each wave's rows active while the others
        # ride along masked to the trash page (mid-prefill rows always
        # are) — one wave, one decode call, in the no-spill steady state.
        ids = np.zeros((self.max_slots, 1), np.int64)
        active = np.zeros((self.max_slots,), bool)
        for slot, req in enumerate(sched.running):
            if req is None or slot in self._prefilling:
                continue
            ids[slot, 0] = req.generated[-1]
            active[slot] = True
        self._hold.clear()
        if not active.any():
            return
        act_slots = [int(s) for s in np.nonzero(active)[0]]
        flash_needs = sum(self.pool.flash_pages_of(s) for s in act_slots)
        self._step_hits = self._step_misses = 0
        waves = self._plan_waves(act_slots)
        embeds = None if self._bucketed else eng.embed(ids)
        # each row's attention length as the device sees it at a wave's
        # launch: its position + 1, and + 1 more once an earlier wave
        # has stepped it
        attn_len = self.pool.row_pos + 1
        for wave in waves:
            needed = [(s, i) for s in wave
                      for i in self.pool.flash_idxs(s)]
            if needed:
                self._stage_wave(needed)
            self._upload_table(visible=set(wave))
            if self._bucketed:
                # gather the wave into its smallest covering bucket: only
                # embeds/lora-ids/masks shrink to bucket shape — the
                # pooled KV never moves, and appends route through the
                # gathered table rows to each slot's own physical pages.
                # Pad rows' table rows upload as all-trash (they are
                # outside ``visible``), so their ride-along appends land
                # in the trash page exactly like masked full-batch rows.
                slot_idx, act_b = bucket_cover(self.buckets, wave,
                                               self.max_slots)
                self._count_launch(attn_len[slot_idx])
                attn_len[wave] += 1
                self.logits, self.cache = self._decode_b(
                    eng.params, eng.embed(ids[slot_idx]), self.cache,
                    eng._lora_for(sched.running,
                                  rows=[int(s) for s in slot_idx]),
                    eng.h2d(act_b), eng.h2d(slot_idx), self.logits)
                continue
            wmask = np.zeros((self.max_slots,), bool)
            wmask[wave] = True
            am = eng.h2d(wmask)
            self._count_launch(attn_len)
            attn_len[wave] += 1
            if self.wpolicy.active:
                logits_w, self.cache = self._decode_streamed(
                    embeds, am, self._slot_lora())
            else:
                logits_w, self.cache = self._decode(
                    eng.params, embeds, self.cache, self._slot_lora(), am)
            if len(waves) == 1:
                # the no-spill steady state: one wave covers every
                # active row — keep the old direct assignment (empty
                # rows' logits are never read)
                self.logits = logits_w
            else:
                self.logits = jnp.where(am[:, None], logits_w,
                                        self.logits)
        if flash_needs:
            total = self._step_hits + self._step_misses
            eng.stats.flash_hit_rates.append(
                self._step_hits / total if total else 1.0)
        for slot in act_slots:
            self.pool.row_pos[slot] += 1
        stats = eng.stats
        stats.decode_steps += 1
        stats.decode_rows += len(act_slots)
        stats.decode_ctx_keys += sum(int(self.pool.row_pos[s])
                                     for s in act_slots)
        stats.decode_tokens += len(act_slots)

    def _count_pool(self) -> None:
        """Count one launch of a step program that carries the KV pool:
        the pool's device and logical bytes, from host state."""
        stats = self.eng.stats
        stats.kv_pool_device_bytes += self.pool_bytes[0]
        stats.kv_pool_logical_bytes += self.pool_bytes[1]

    def _count_launch(self, attn_len: np.ndarray) -> None:
        """Count one decode program's rows and the pages its paged decode
        attention computes (``attn_len``: each row's length) and walks."""
        stats, ps = self.eng.stats, self.geom.page_size
        n_p = self.geom.pages_per_row
        self._count_pool()
        stats.decode_bucket_rows += len(attn_len)
        stats.decode_attn_pages += int(np.minimum(-(-attn_len // ps),
                                                  n_p).sum())
        stats.decode_attn_grid_pages += len(attn_len) * n_p

    def drain(self) -> None:
        """Step until the loop is idle (queue empty, no running rows)."""
        while self.scheduler.has_work():
            self.step()
        jax.block_until_ready(self.logits)

    # --- batch-mode compatibility wrapper ----------------------------------
    def run(self, requests: Sequence[Request],
            sampling: Optional[SM.SamplingParams] = None,
            arrivals: Optional[Sequence[int]] = None,
            key: Optional[jax.Array] = None) -> List[Request]:
        """Serve a whole trace to completion — a thin wrapper over
        ``submit()``/``step()``.  ``arrivals``: per-request arrival step
        relative to the call (trace replay); default: everything queued
        at step 0.

        .. deprecated:: the batch-mode entry point is kept for benchmarks
           and trace replay.  ``sampling`` acts as a default-for-all shim:
           it applies only to requests without their own
           ``req.sampling``.  New serving code should drive
           ``submit()``/``step()`` (or the HTTP gateway) directly."""
        global _WARNED_RUN_SAMPLING_SHIM
        self._key = key if key is not None else jax.random.PRNGKey(0)
        arrivals = list(arrivals) if arrivals is not None \
            else [0] * len(requests)
        assert len(arrivals) == len(requests)
        if sampling is not None and not _WARNED_RUN_SAMPLING_SHIM:
            _WARNED_RUN_SAMPLING_SHIM = True
            warnings.warn(
                "EngineLoop.run(sampling=...) is a default-for-all shim; "
                "put SamplingParams on each Request (req.sampling) or use "
                "submit()/step()", DeprecationWarning, stacklevel=2)
        for req in requests:
            if req.sampling is None:
                req.sampling = sampling
        # validate the whole trace up front (the old hard-assert contract,
        # now typed): a bad request raises before anything is served
        for req in requests:
            if req.sampling is None:
                raise ValueError(f"request {req.uid} has no SamplingParams "
                                 f"(pass sampling= or set req.sampling)")
            self._validate(req)
        base = self._step_no
        pending = sorted(zip(arrivals, requests),
                         key=lambda p: (p[0], p[1].uid))
        pending = list(pending)
        self.peak_active = 0
        while pending or self.scheduler.has_work():
            now = time.perf_counter()
            while pending and pending[0][0] + base <= self._step_no:
                _, req = pending.pop(0)
                req.arrival_t = now
                self.submit(req)
            self.step()
        jax.block_until_ready(self.logits)
        # batch traces are not polled; drop their stream records
        for req in requests:
            self._streams.pop(req.uid, None)
        return list(requests)


def build_engine(cfg: ModelConfig, key: Optional[jax.Array] = None,
                 max_seq: int = 256,
                 flash_dir: Optional[str] = None,
                 backend: Optional[str] = None,
                 weight_dram_budget_bytes: Optional[int] = None,
                 weight_ring_groups: int = 2,
                 expert_streaming: bool = True) -> Engine:
    """Random-weights engine for examples/tests: quantized serving params
    built directly in the kernel-native packed layout + a bf16 embedding
    table exported to Flash (the paper's conversion flow).  ``backend``
    picks the dispatch backend (REPRO_BACKEND env overrides)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    params = T.init_params(cfg, key=k1, quantized=True, pack=True)
    emb = np.asarray(
        jax.random.normal(k2, (cfg.padded_vocab_size, cfg.d_model)) * 0.02,
        np.float32)
    return Engine(cfg, params, emb, max_seq=max_seq, flash_dir=flash_dir,
                  backend=backend,
                  weight_dram_budget_bytes=weight_dram_budget_bytes,
                  weight_ring_groups=weight_ring_groups,
                  expert_streaming=expert_streaming)

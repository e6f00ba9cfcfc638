"""Model assembly: heterogeneous layer stacks with scan, caches, and the
train / prefill / decode entry points.

A config's ``layer_plan()`` yields (period_patterns, repeat) stacks; each
stack's params are stacked on a leading axis and scanned (MaxText-style —
keeps HLO size O(period), not O(layers)).  Heterogeneous periods (jamba's
1-attn-7-mamba, gemma3's 5-local-1-global) are one scan whose body applies
each pattern element in order.

Caches mirror the stacks: for every attention element a LayerKVCache stacked
[repeat, ...]; for mamba/rwkv elements a state dict stacked [repeat, ...].
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerPattern, ModelConfig
from repro.core import kv_cache as kvc
from repro.core import kv_pool as KP
from repro.core import quantization as q
from repro.core.precision import DEFAULT_POLICY, PrecisionPolicy
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Array = jax.Array


# ===========================================================================
# Parameters
# ===========================================================================

def _pattern_params(b: L.ParamBuilder, cfg: ModelConfig, pat: LayerPattern,
                    cross: bool = False) -> dict:
    p: dict = {"ln1": b.norm(cfg.d_model)}
    if pat.kind == "attn":
        p["attn"] = A.attn_params(b, cfg)
        p["ln2"] = b.norm(cfg.d_model)
        p["ffn" if not pat.moe else "moe"] = (
            M.moe_params(b, cfg) if pat.moe else L.ffn_params(b, cfg))
        if cross:
            p["ln_cross"] = b.norm(cfg.d_model)
            p["cross"] = A.attn_params(b, cfg, cross=True)
    elif pat.kind == "mamba":
        p["mamba"] = S.mamba_params(b, cfg)
        p["ln2"] = b.norm(cfg.d_model)
        p["ffn" if not pat.moe else "moe"] = (
            M.moe_params(b, cfg) if pat.moe else L.ffn_params(b, cfg))
    elif pat.kind == "rwkv":
        p["tm"] = S.rwkv_params(b, cfg)
        # rwkv_params carries its own channel-mix; ln2 norms it
        p["ln2"] = b.norm(cfg.d_model)
    else:
        raise ValueError(pat.kind)
    return p


def _stack_trees(trees: List[Any]) -> Any:
    """Stack per-layer trees on a new leading axis, one leaf at a time,
    emptying ``trees`` as it goes: a leaf's per-layer arrays are released
    once stacked, so the peak is the layers plus one stacked leaf rather
    than two copies of the whole stack (what lets a full-width 7B model
    initialize inside one chip's HBM)."""
    if len(trees) == 1:
        return jax.tree.map(lambda x: x[None], trees.pop())
    treedef = jax.tree.structure(trees[0])
    columns = [list(c) for c in zip(*(jax.tree.leaves(t) for t in trees))]
    trees.clear()
    stacked = []
    for i in range(len(columns)):
        stacked.append(jnp.stack(columns[i]))
        columns[i] = None
    return jax.tree.unflatten(treedef, stacked)


def _lead_axis(tree: Any, count: int, mode: str) -> Any:
    """Abstract/spec modes: add a [count] lead axis to every leaf."""
    def add(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((count, *x.shape), x.dtype)
        if isinstance(x, P):
            return P(None, *x)
        return x
    return jax.tree.map(add, tree,
                        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)))


def init_params(cfg: ModelConfig, *, mode: str = "init",
                key: Optional[jax.Array] = None, quantized: bool = False,
                fsdp: bool = False, include_embedding: Optional[bool] = None,
                mesh_model: int = 16, pack: bool = False) -> dict:
    """Build the full parameter tree (or its SDS / PartitionSpec mirror).

    include_embedding: default True for float (training) params, False for
    quantized (serving) params — the embedding lives on Flash (C2).
    pack: emit kernel-native PackedLinear weights (runtime/plan.py) for the
    per-layer linears — the serving engines build params this way so no
    repacking happens at plan time.
    """
    if include_embedding is None:
        include_embedding = not quantized
    b = L.ParamBuilder(mode, key=key, quantized=quantized, qcfg=cfg.quant,
                       fsdp=fsdp, pack=pack)
    params: dict = {}
    if include_embedding:
        params["embedding"] = b.param((cfg.padded_vocab_size, cfg.d_model),
                                      ("model", None))
    # encoder (enc-dec archs)
    if cfg.is_encdec:
        enc_stack = []
        for _ in range(cfg.encoder_layers):
            if mode == "init":
                enc_stack.append(_pattern_params(b, cfg, LayerPattern("attn")))
        if mode == "init":
            params["encoder"] = _stack_trees(enc_stack)
        else:
            one = _pattern_params(b, cfg, LayerPattern("attn"))
            params["encoder"] = _lead_axis(one, cfg.encoder_layers, mode)
        params["enc_norm"] = b.norm(cfg.d_model)
    # decoder stacks
    stacks = []
    for patterns, count in cfg.layer_plan():
        if mode == "init":
            periods = []
            for _ in range(count):
                periods.append(tuple(
                    _pattern_params(b, cfg, pat, cross=cfg.is_encdec)
                    for pat in patterns))
            stacks.append(_stack_trees(periods))
        else:
            one = tuple(_pattern_params(b, cfg, pat, cross=cfg.is_encdec)
                        for pat in patterns)
            stacks.append(_lead_axis(one, count, mode))
    params["stacks"] = tuple(stacks)
    params["final_norm"] = b.norm(cfg.d_model)
    params["lm_head"] = b.linear(cfg.d_model, cfg.padded_vocab_size,
                                 (None, "model"), bits=cfg.quant.lm_head_bits)
    return params


def param_specs(cfg: ModelConfig, *, quantized: bool = False,
                fsdp: bool = False,
                include_embedding: Optional[bool] = None) -> dict:
    return init_params(cfg, mode="spec", quantized=quantized, fsdp=fsdp,
                       include_embedding=include_embedding)


def abstract_params(cfg: ModelConfig, *, quantized: bool = False,
                    fsdp: bool = False,
                    include_embedding: Optional[bool] = None) -> dict:
    return init_params(cfg, mode="abstract", quantized=quantized, fsdp=fsdp,
                       include_embedding=include_embedding)


# ===========================================================================
# Caches
# ===========================================================================

def _cache_for_pattern(cfg: ModelConfig, pat: LayerPattern, batch: int,
                       max_seq: int, abstract: bool, per_row: bool = False):
    if pat.kind == "attn":
        fn = kvc.abstract_layer_cache if abstract else kvc.init_layer_cache
        return fn(batch, max_seq, cfg.num_kv_heads, cfg.resolved_head_dim,
                  window=pat.window, key_bits=cfg.quant.kv_key_bits,
                  value_fp8=cfg.quant.kv_value_fp8, per_row=per_row)
    if pat.kind == "mamba":
        fn = S.abstract_mamba_state if abstract else S.init_mamba_state
        return fn(batch, cfg)
    if pat.kind == "rwkv":
        fn = S.abstract_rwkv_state if abstract else S.init_rwkv_state
        return fn(batch, cfg)
    raise ValueError(pat.kind)


def _stack_cache(tree, count: int, abstract: bool):
    def add(x):
        if abstract or isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((count, *x.shape), x.dtype)
        return jnp.broadcast_to(x[None], (count, *x.shape))
    if isinstance(tree, kvc.LayerKVCache):
        return kvc.LayerKVCache(
            k_q=add(tree.k_q), k_scale=add(tree.k_scale),
            k_zero=add(tree.k_zero), v=add(tree.v),
            length=add(tree.length), window=tree.window,
            key_bits=tree.key_bits)
    return jax.tree.map(add, tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               *, abstract: bool = False,
               cross_len: int = 0, per_row: bool = False) -> dict:
    """The full decode state: per-stack tuples of stacked per-pattern caches
    (+ cross-attention caches for enc-dec archs).

    per_row=True builds a continuous-batching cache: ``pos`` is a [B] int32
    vector (one decode offset per slot) instead of a scalar, and each
    LayerKVCache tracks per-row lengths.
    """
    stacks = []
    for patterns, count in cfg.layer_plan():
        stacks.append(tuple(
            _stack_cache(_cache_for_pattern(cfg, pat, batch, max_seq, abstract,
                                            per_row=per_row),
                         count, abstract)
            for pat in patterns))
    pos_shape = (batch,) if per_row else ()
    cache: dict = {"stacks": tuple(stacks),
                   "pos": (jax.ShapeDtypeStruct(pos_shape, jnp.int32) if abstract
                           else jnp.zeros(pos_shape, jnp.int32))}
    if cfg.is_encdec and cross_len:
        cross = _cache_for_pattern(cfg, LayerPattern("attn"), batch,
                                   cross_len, abstract)
        # one cross cache per decoder layer, stacked per decoder stack
        cross_stacks = []
        for patterns, count in cfg.layer_plan():
            cross_stacks.append(tuple(
                _stack_cache(cross, count, abstract) for _ in patterns))
        cache["cross"] = tuple(cross_stacks)
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     geom: KP.PoolGeometry) -> dict:
    """Paged decode state for the continuous-batching EngineLoop: every
    attention pattern gets a page pool (full layers share the one
    ``table``; windowed layers use per-row rings), SSM patterns keep their
    per-row state dicts.  ``table`` starts all-trash — rows hold no pages
    until the host-side KVPoolManager allocates some."""
    stacks = []
    for patterns, count in cfg.layer_plan():
        row = []
        for pat in patterns:
            if pat.kind == "attn":
                row.append(KP.init_paged_layer(
                    geom, cfg.num_kv_heads, cfg.resolved_head_dim,
                    layers=count, batch=batch, window=pat.window,
                    key_bits=cfg.quant.kv_key_bits,
                    value_fp8=cfg.quant.kv_value_fp8))
            else:
                row.append(_stack_cache(
                    _cache_for_pattern(cfg, pat, batch, max_seq, False),
                    count, False))
        stacks.append(tuple(row))
    return {"stacks": tuple(stacks),
            "pos": jnp.zeros((batch,), jnp.int32),
            "table": jnp.full((batch, geom.pages_per_row), geom.trash_page,
                              jnp.int32)}


def free_slots(cache: dict, rows: Array) -> dict:
    """Reset the positions of finished/preempted rows to zero. The KV bytes
    stay in place; per-row masks make them unreachable until the next
    prefill scatter reuses the row."""
    new = dict(cache)
    new["pos"] = cache["pos"].at[rows].set(0)
    return new


def reset_row_recurrent(cache: dict, cfg: ModelConfig, slot: int) -> dict:
    """Zero one row's recurrent (SSM/RWKV) state across every stack.

    The engine calls this when a fresh request is admitted into a decode
    slot so the first prefill chunk enters with the clean initial state —
    state-passing chunked prefill then threads the carried state through
    every later chunk.  Attention pools are untouched (page allocation and
    per-row masks already isolate rows).  Leaves are [count, B, ...]."""
    new_stacks = []
    for si, (patterns, _count) in enumerate(cfg.layer_plan()):
        row = []
        for pi, pat in enumerate(patterns):
            entry = cache["stacks"][si][pi]
            if pat.kind == "attn":
                row.append(entry)
            else:
                row.append(jax.tree.map(
                    lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)),
                    entry))
        new_stacks.append(tuple(row))
    out = dict(cache)
    out["stacks"] = tuple(new_stacks)
    return out


def freeze_inactive_rows(cfg: ModelConfig, old_stacks, new_stacks,
                         active: Array):
    """Roll inactive rows' per-row sequence state back to its pre-step
    value after a decode step.

    Inactive rows (empty slots, rows mid-prefill under proactive staging)
    still flow through the fixed-shape batch, but nothing of theirs may
    advance: recurrent (SSM/RWKV) states are batch-row addressed and
    windowed rings write pages derived from the frozen ``pos`` — both
    would absorb garbage from the dummy row.  Full-attention pools are
    already safe (inactive rows' page tables point at the trash page) and
    pass through untouched, as do dense LayerKVCaches (per-row length
    masks).  ``active``: [B] bool.  Returns the repaired stacks tuple."""
    out = []
    for si, (patterns, _count) in enumerate(cfg.layer_plan()):
        row = []
        for pi, pat in enumerate(patterns):
            old, new = old_stacks[si][pi], new_stacks[si][pi]
            if isinstance(new, KP.PagedLayerKV):
                if new.window:
                    # leaves [L, B*ppw, page, ...]: page p belongs to row
                    # p // ppw — keep only active rows' ring writes
                    pa = jnp.repeat(active, new.ppw)

                    def sel(o, n, _pa=pa):
                        m = _pa.reshape((1, -1) + (1,) * (n.ndim - 2))
                        return jnp.where(m, n, o)
                    row.append(new.replace(**{
                        f: sel(o, n) for (f, o), n in
                        zip(old.fields().items(), new.fields().values())}))
                else:
                    row.append(new)
            elif isinstance(new, kvc.LayerKVCache):
                row.append(new)
            else:
                # SSM/RWKV state dict, leaves [count, B, ...]
                def selb(o, n):
                    m = active.reshape((1, -1) + (1,) * (n.ndim - 2))
                    return jnp.where(m, n, o)
                row.append(jax.tree.map(selb, old, new))
        out.append(tuple(row))
    return tuple(out)


# ===========================================================================
# Forward passes
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class StepCtx:
    cfg: ModelConfig
    policy: PrecisionPolicy = DEFAULT_POLICY
    remat: bool = False
    act_spec: Optional[P] = None      # sharding constraint for the residual
    # kernel dispatcher (runtime/dispatch.py): trace-time static — the
    # Engine binds its Dispatcher here so every linear/rmsnorm/attention
    # call resolves through the (op, backend, quant tag) registry; None
    # resolves to the reference (or REPRO_BACKEND-selected) default.
    dispatch: Optional[Any] = None
    # multi-LoRA (paper §5.5): {"wq_a","wq_b","wv_a","wv_b": [K,...],
    # "ids": [B]} — shared across layers; applied in attention q/v.
    # NOTE: arrays here are closed over by the jitted step — the serving
    # engine re-jits when adapter TABLES change (rare: on adapter load);
    # per-request "ids" still vary per call without retrace via the cache
    # of identical-shape constants... pass lora via decode_step's arg for
    # per-call ids instead (Engine does).
    lora: Optional[dict] = None


def _constrain(x: Array, ctx: StepCtx) -> Array:
    if ctx.act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, ctx.act_spec)
    return x


def _row_state(state: Any, slot: Array) -> Any:
    """Slice one row of a per-row SSM state tree ([B, ...] leaves)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0), state)


def _put_row_state(state: Any, row: Any, slot: Array) -> Any:
    return jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=0), state, row)


def _apply_pattern(x: Array, pp: dict, cfg: ModelConfig, pat: LayerPattern,
                   mode: str, positions, cache, cross_cache, pos, table,
                   ctx: StepCtx, slot=None,
                   collect: Optional[dict] = None,
                   valid_len=None) -> Tuple[Array, Any, Array]:
    """One layer. Returns (x, new_cache, moe_aux).  ``table``: the shared
    page table when the decode cache is paged (kv_pool), else None; in
    ``prefill_paged`` mode it is the single row's table and ``slot`` the
    decode row receiving the prompt chunk.  ``collect``: trace-time dict the
    MoE layer stores its router top-k ids into (expert-streaming signal).
    ``valid_len``: real-token count of a padded prefill chunk — recurrent
    layers mask padded positions out of their carried state, windowed
    attention clamps ring writes/reads to it."""
    aux = jnp.zeros((2,), jnp.float32)
    dsp = ctx.dispatch
    # expert capacity at inference covers every routed token — token drops
    # would make outputs depend on the prefill chunk partition
    full_cap = mode != "train"
    h = L.rms_norm(x, pp["ln1"], cfg.rms_eps, dispatch=dsp)
    if pat.kind == "attn":
        if mode == "train":
            att = A.attention_train(h, pp["attn"], cfg, pat, positions,
                                    ctx.policy, lora=ctx.lora, dispatch=dsp)
            new_cache = cache
        elif mode == "prefill":
            att, new_cache = A.attention_prefill(
                h, pp["attn"], cfg, pat, positions, cache.max_seq, ctx.policy,
                lora=ctx.lora, dispatch=dsp)
        elif mode == "prefill_paged":
            att, new_cache = A.attention_prefill_paged(
                h, pp["attn"], cfg, pat, cache, table, slot, positions,
                ctx.policy, lora=ctx.lora, dispatch=dsp,
                valid_len=valid_len)
        elif isinstance(cache, KP.PagedLayerKV):
            att, new_cache = A.attention_decode_paged(
                h, pp["attn"], cfg, pat, cache, table, pos, positions,
                ctx.policy, lora=ctx.lora, dispatch=dsp)
        else:
            att, new_cache = A.attention_decode(
                h, pp["attn"], cfg, pat, cache, pos, positions, ctx.policy,
                lora=ctx.lora, dispatch=dsp)
        x = x + att
        if cross_cache is not None:
            hc = L.rms_norm(x, pp["ln_cross"], cfg.rms_eps, dispatch=dsp)
            x = x + A.cross_attention(hc, pp["cross"], cfg, cross_cache,
                                      ctx.policy, dispatch=dsp)
        h2 = L.rms_norm(x, pp["ln2"], cfg.rms_eps, dispatch=dsp)
        if pat.moe:
            y, aux = M.apply_moe(h2, pp["moe"], cfg, dispatch=dsp,
                                 collect=collect, full_capacity=full_cap)
        else:
            y = L.apply_ffn(h2, pp["ffn"], cfg, dispatch=dsp)
        x = x + y
    elif pat.kind == "mamba":
        if mode == "train":
            st = S.init_mamba_state(x.shape[0], cfg)
            y, _ = S.mamba_forward(h, pp["mamba"], cfg, st)
            new_cache = cache          # None in train mode
        elif mode == "prefill_paged":
            # state-passing chunked prefill: the chunk enters with the
            # row's carried state (zeroed by the engine at admission) and
            # leaves its exit state behind — any chunk partition is
            # bitwise-equal to one whole-prompt pass
            y, st1 = S.mamba_forward(h, pp["mamba"], cfg,
                                     _row_state(cache, slot),
                                     valid_len=valid_len)
            new_cache = _put_row_state(cache, st1, slot)
        else:
            y, new_cache = S.mamba_forward(h, pp["mamba"], cfg, cache,
                                           valid_len=valid_len)
        x = x + y
        h2 = L.rms_norm(x, pp["ln2"], cfg.rms_eps, dispatch=dsp)
        if pat.moe:
            y2, aux = M.apply_moe(h2, pp["moe"], cfg, dispatch=dsp,
                                  collect=collect, full_capacity=full_cap)
        else:
            y2 = L.apply_ffn(h2, pp["ffn"], cfg, dispatch=dsp)
        x = x + y2
    elif pat.kind == "rwkv":
        if mode == "train":
            st = S.init_rwkv_state(x.shape[0], cfg)
        elif mode == "prefill_paged":
            st = _row_state(cache, slot)       # carried chunk state
        else:
            st = cache
        y, st = S.rwkv_time_mix(h, pp["tm"], cfg, st, valid_len=valid_len)
        x = x + y
        h2 = L.rms_norm(x, pp["ln2"], cfg.rms_eps, dispatch=dsp)
        y2, st = S.rwkv_channel_mix(h2, pp["tm"], cfg, st,
                                    valid_len=valid_len)
        x = x + y2
        if mode == "train":
            new_cache = cache
        elif mode == "prefill_paged":
            new_cache = _put_row_state(cache, st, slot)
        else:
            new_cache = st
    else:
        raise ValueError(pat.kind)
    return _constrain(x, ctx), new_cache, aux


def run_stack(sp, cfg: ModelConfig, stack_idx: int, mode: str, x: Array,
              positions, scache, cross, pos, table, ctx: StepCtx,
              slot=None, aux0: Optional[Array] = None,
              valid_len=None) -> Tuple[Array, Any, Array]:
    """Scan ONE stack's layer groups over its fully-resident stacked
    params ``sp`` ([count, ...] leaves).  Returns (x, new_scache, aux).
    ``aux0`` continues a running moe-aux accumulator across stacks (the
    float addition order matches the old fused multi-stack scan).

    Paged KV pools ride in the scan's carry whole: each layer reads and
    writes its own index of the stacked pool (``kv_pool.at_layer``), so a
    donated pool is updated in place and never sliced per layer.  Every
    other cache (dense KV, recurrent state) is scanned per layer."""
    patterns, count = cfg.layer_plan()[stack_idx]
    xcache = tuple(None for _ in patterns) if scache is None else scache
    aux0 = jnp.zeros((2,), jnp.float32) if aux0 is None else aux0
    carried = tuple(isinstance(c, KP.PagedLayerKV) for c in xcache)
    pools = tuple(c if k else None for c, k in zip(xcache, carried))
    scanned = tuple(None if k else c for c, k in zip(xcache, carried))

    def body(xc, slices, _patterns=patterns):
        xx, auxc, pls = xc
        pslice, cslice, crslice, li = slices
        new_cs, new_pls = [], list(pls)
        for pi, pat in enumerate(_patterns):
            if carried[pi]:
                cc = KP.at_layer(pls[pi], li)
            else:
                cc = None if cslice is None else cslice[pi]
            cr = None if crslice is None else crslice[pi]
            xx, nc, aux = _apply_pattern(
                xx, pslice[pi], cfg, pat, mode, positions, cc, cr, pos,
                table, ctx, slot=slot, valid_len=valid_len)
            if carried[pi]:
                new_pls[pi], nc = nc, None
            new_cs.append(nc)
            auxc = auxc + aux
        return (xx, auxc, tuple(new_pls)), tuple(new_cs)

    if ctx.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux, pools), new_scanned = jax.lax.scan(
        body, (x, aux0, pools),
        (sp, scanned, cross, jnp.arange(count, dtype=jnp.int32)))
    new_scache = tuple(p if k else s
                       for p, s, k in zip(pools, new_scanned, carried))
    return x, new_scache, aux


def run_stack_group(gp, cfg: ModelConfig, stack_idx: int, mode: str,
                    x: Array, positions, scache, gidx, pos, table,
                    ctx: StepCtx, slot=None,
                    collect: Optional[dict] = None,
                    valid_len=None) -> Tuple[Array, Any, Array]:
    """ONE layer group of one stack — the streamed execution mode.  ``gp``
    is the group's weight slice ([1, ...] leaves, installed in a DRAM ring
    slot by the engine's weight-streaming tier), NOT indexed from resident
    stacked params.  ``gidx`` is the group's index into the stack cache —
    traced, so every group of the stack reuses the one jit graph (same
    weight shapes, dynamic_slice/update at gidx; no recompiles).

    Applying the period body once per group in index order runs exactly
    the primitive sequence of ``run_stack``'s scan iterations, so a full
    group-by-group pass is bitwise-equal to the resident scan.

    When ``collect`` is a dict and the group has MoE patterns, their
    router top-k ids are stacked into ``collect["moe_ids"]`` as
    [n_moe, B, T, K] int32 — the expert-streaming prefetch signal."""
    patterns, _count = cfg.layer_plan()[stack_idx]
    gidx = jnp.asarray(gidx, jnp.int32)
    cslice = None
    if scache is not None:
        # paged pools are addressed at the group's layer in place; the
        # other caches are sliced out and written back
        cslice = tuple(
            KP.at_layer(c, gidx) if isinstance(c, KP.PagedLayerKV)
            else jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, gidx, 1,
                                                       axis=0)[0], c)
            for c in scache)
    pslice = jax.tree.map(lambda a: a[0], gp)
    aux = jnp.zeros((2,), jnp.float32)
    new_cs = []
    ids_list = []
    for pi, pat in enumerate(patterns):
        cc = None if cslice is None else cslice[pi]
        sub = None if collect is None else {}
        x, nc, a = _apply_pattern(x, pslice[pi], cfg, pat, mode, positions,
                                  cc, None, pos, table, ctx, slot=slot,
                                  collect=sub, valid_len=valid_len)
        if sub is not None and "moe_ids" in sub:
            ids_list.append(sub["moe_ids"])
        new_cs.append(nc)
        aux = aux + a
    if collect is not None and ids_list:
        collect["moe_ids"] = jnp.stack(ids_list)
    new_scache = scache
    if scache is not None:
        new_scache = tuple(
            small if isinstance(small, KP.PagedLayerKV)
            else jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                    b, s[None].astype(b.dtype), gidx, axis=0), big, small)
            for big, small in zip(scache, new_cs))
    return x, new_scache, aux


def _run_stacks(x: Array, params: dict, cfg: ModelConfig, mode: str,
                positions, cache: Optional[dict], ctx: StepCtx,
                slot=None, valid_len=None
                ) -> Tuple[Array, Optional[dict], Array]:
    """Scan every stack; returns (x, new_cache, moe_aux_sum).  ``slot``:
    the decode row a ``prefill_paged`` chunk targets.  ``valid_len``:
    real-token count of a padded chunk (recurrent state / windowed ring
    hygiene; see _apply_pattern)."""
    new_stacks = []
    aux_total = jnp.zeros((2,), jnp.float32)
    pos = None if cache is None else cache["pos"]
    table = None if cache is None else cache.get("table")
    if mode == "prefill_paged":
        table = table[slot]              # [pages_per_row] — this row's pages
    for si, (patterns, count) in enumerate(cfg.layer_plan()):
        sp = params["stacks"][si]
        scache = None if cache is None else cache["stacks"][si]
        cross = None
        if cfg.is_encdec and cache is not None and "cross" in cache:
            cross = cache["cross"][si]
        x, new_scache, aux_total = run_stack(
            sp, cfg, si, mode, x, positions, scache, cross, pos, table,
            ctx, slot=slot, aux0=aux_total, valid_len=valid_len)
        new_stacks.append(new_scache)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["stacks"] = tuple(new_stacks)
    return x, new_cache, aux_total


def _logits(x: Array, params: dict, cfg: ModelConfig,
            dispatch=None) -> Array:
    h = L.rms_norm(x, params["final_norm"], cfg.rms_eps, dispatch=dispatch)
    return L.apply_linear(h, params["lm_head"], cfg.quant,
                          out_dtype=jnp.float32, dispatch=dispatch)


def embed_tokens(params: dict, cfg: ModelConfig, tokens: Array) -> Array:
    emb = params["embedding"]
    return emb.astype(jnp.bfloat16)[tokens]


# --- encoder ---------------------------------------------------------------

def encode(params: dict, cfg: ModelConfig, src_embeds: Array,
           positions: Array, ctx: StepCtx) -> Array:
    """Bidirectional encoder (enc-dec archs). src_embeds: [B, S, d]."""
    x = src_embeds.astype(jnp.bfloat16)

    from repro.runtime import dispatch as RD

    def body(xc, pslice):
        xx = xc
        dsp = ctx.dispatch
        h = L.rms_norm(xx, pslice["ln1"], cfg.rms_eps, dispatch=dsp)
        qh, kh, vh = A._project_qkv(h, pslice["attn"], cfg, dispatch=dsp)
        qh = L.positional(qh, cfg, positions)
        kh = L.positional(kh, cfg, positions)
        qh = A._prescale(qh, cfg.resolved_head_dim, ctx.policy)
        att = RD.resolve(dsp).prefill_attention(qh, kh, vh, causal=False,
                                                window=0, policy=ctx.policy)
        att = att.reshape(*xx.shape[:2], -1)
        xx = xx + L.apply_linear(att, pslice["attn"]["wo"], cfg.quant,
                                 dispatch=dsp)
        h2 = L.rms_norm(xx, pslice["ln2"], cfg.rms_eps, dispatch=dsp)
        xx = xx + L.apply_ffn(h2, pslice["ffn"], cfg, dispatch=dsp)
        return _constrain(xx, ctx), None

    if ctx.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["enc_norm"], cfg.rms_eps,
                      dispatch=ctx.dispatch)


def build_cross_caches(params: dict, cfg: ModelConfig, enc_out: Array,
                       abstract: bool = False, dispatch=None) -> tuple:
    """Per-decoder-layer quantized cross KV (scanned per stack)."""
    cross_stacks = []
    for si, (patterns, count) in enumerate(cfg.layer_plan()):
        sp = params["stacks"][si]

        def body(_, pslice, _patterns=patterns):
            caches = tuple(
                A.build_cross_cache(enc_out, pslice[pi]["cross"], cfg,
                                    dispatch=dispatch)
                for pi in range(len(_patterns)))
            return None, caches

        _, caches = jax.lax.scan(body, None, sp)
        cross_stacks.append(caches)
    return tuple(cross_stacks)


# ===========================================================================
# Public step functions
# ===========================================================================

def forward_hidden(params: dict, cfg: ModelConfig, batch: dict,
                   ctx: Optional[StepCtx] = None) -> Tuple[Array, Array]:
    """Training forward up to the final norm (pre-lm_head).

    Returns (hidden [B,T,d] fp-normed, moe_aux[2]).  The training loss uses
    this with a CHUNKED lm_head+CE (train_loop.chunked_cross_entropy) so the
    [B,T,V] logits never fully materialize."""
    ctx = ctx or StepCtx(cfg)
    if "tokens" in batch:
        x = embed_tokens(params, cfg, batch["tokens"])
    else:
        x = batch["embeds"].astype(jnp.bfloat16)
    B, T = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if cfg.is_encdec:
        src = batch["src_embeds"]
        spos = jnp.broadcast_to(jnp.arange(src.shape[1])[None],
                                (B, src.shape[1]))
        enc_out = encode(params, cfg, src, spos, ctx)
        cross = build_cross_caches(params, cfg, enc_out, dispatch=ctx.dispatch)
        cache = {"pos": jnp.zeros((), jnp.int32), "cross": cross,
                 "stacks": tuple(tuple(None for _ in pats)
                                 for pats, _ in cfg.layer_plan())}
        x, _, aux = _run_stacks(x, params, cfg, "train", positions, cache, ctx)
    else:
        x, _, aux = _run_stacks(x, params, cfg, "train", positions, None, ctx)
    return L.rms_norm(x, params["final_norm"], cfg.rms_eps,
                      dispatch=ctx.dispatch), aux


def forward_train(params: dict, cfg: ModelConfig, batch: dict,
                  ctx: Optional[StepCtx] = None) -> Tuple[Array, Array]:
    """Training forward. batch: {"tokens" | "embeds", "positions"?,
    "src_embeds"? (encdec/audio/vlm)} -> (logits [B,T,V], moe_aux[2])."""
    ctx = ctx or StepCtx(cfg)
    if "tokens" in batch:
        x = embed_tokens(params, cfg, batch["tokens"])
    else:
        x = batch["embeds"].astype(jnp.bfloat16)
    B, T = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    cache = None
    if cfg.is_encdec:
        src = batch["src_embeds"]
        spos = jnp.broadcast_to(jnp.arange(src.shape[1])[None],
                                (B, src.shape[1]))
        enc_out = encode(params, cfg, src, spos, ctx)
        cross = build_cross_caches(params, cfg, enc_out, dispatch=ctx.dispatch)
        # train-mode "cache": only cross KV, no self-KV allocation
        cache = {"pos": jnp.zeros((), jnp.int32), "cross": cross,
                 "stacks": tuple(tuple(None for _ in pats)
                                 for pats, _ in cfg.layer_plan())}
        x, _, aux = _run_stacks(x, params, cfg, "train", positions, cache, ctx)
        return _logits(x, params, cfg, ctx.dispatch), aux
    x, _, aux = _run_stacks(x, params, cfg, "train", positions, None, ctx)
    return _logits(x, params, cfg, ctx.dispatch), aux


def prefill(params: dict, cfg: ModelConfig, embeds: Array, max_seq: int,
            positions: Optional[Array] = None,
            src_embeds: Optional[Array] = None,
            ctx: Optional[StepCtx] = None,
            lora: Optional[dict] = None,
            valid_len: Optional[Array] = None) -> Tuple[Array, dict]:
    """Prefill: embeds [B, T, d] (token rows come from Flash, C2).
    Returns (last-token logits [B, V], cache).

    valid_len (scalar int32): true prompt length when ``embeds`` is padded
    to a jit bucket — logits are taken at valid_len-1 and the cache position
    is set to valid_len, so the padded tail stays masked.  Recurrent (SSM /
    RWKV) states exclude the padded tail too.  Only windowed dense ring
    caches still require an exact-length prompt (padding would wrap the
    ring past real keys).
    """
    ctx = ctx or StepCtx(cfg)
    if lora is not None:
        ctx = dataclasses.replace(ctx, lora=lora)
    x = embeds.astype(jnp.bfloat16)
    B, T = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    cross_len = 0
    cache = init_cache(cfg, B, max_seq)
    if cfg.is_encdec:
        assert src_embeds is not None
        spos = jnp.broadcast_to(jnp.arange(src_embeds.shape[1])[None],
                                (B, src_embeds.shape[1]))
        enc_out = encode(params, cfg, src_embeds, spos, ctx)
        cache["cross"] = build_cross_caches(params, cfg, enc_out,
                                            dispatch=ctx.dispatch)
    x, cache, _ = _run_stacks(x, params, cfg, "prefill", positions, cache,
                              ctx, valid_len=valid_len)
    if valid_len is None:
        cache["pos"] = jnp.asarray(T, jnp.int32)
        last = x[:, -1:]
    else:
        vl = jnp.asarray(valid_len, jnp.int32)
        cache["pos"] = vl
        last = jax.lax.dynamic_slice_in_dim(x, vl - 1, 1, axis=1)
    logits = _logits(last, params, cfg, ctx.dispatch)[:, 0]
    return logits, cache


def prefill_chunk_paged(params: dict, cfg: ModelConfig, embeds: Array,
                        cache: dict, slot: Array, pos0: Array,
                        last_idx: Array,
                        ctx: Optional[StepCtx] = None,
                        lora: Optional[dict] = None) -> Tuple[Array, dict]:
    """One prompt chunk for decode row ``slot``, written straight into the
    paged pool — the unified prefill path (no dense ``max_seq`` transient,
    no scatter).  embeds: [1, C, d] at absolute positions [pos0, pos0+C);
    ``pos0`` > 0 either continues an earlier chunk or skips a prefix
    adopted from the page index.  ``last_idx``: chunk-local index of the
    chunk's final real token (its logits are returned; mid-prompt chunks
    just ignore them).  The final chunk may be padded past the prompt —
    padded keys land in causally-dead positions, padded queries' outputs
    are never read, and recurrent (SSM/RWKV) states stop advancing at
    ``last_idx`` so the carried chunk state is partition-invariant.

    ``slot``/``pos0``/``last_idx`` are traced: one compilation per chunk
    *size* serves every row, offset and allocation.  The engine advances
    ``cache["pos"]`` itself once the whole prompt is in."""
    ctx = ctx or StepCtx(cfg)
    if lora is not None:
        ctx = dataclasses.replace(ctx, lora=lora)
    x = embeds.astype(jnp.bfloat16)
    B, C = x.shape[:2]
    assert B == 1, "prompt chunks are per-row"
    positions = (jnp.asarray(pos0, jnp.int32)
                 + jnp.arange(C, dtype=jnp.int32))[None]
    vlen = jnp.asarray(last_idx, jnp.int32) + 1
    x, cache, _ = _run_stacks(x, params, cfg, "prefill_paged", positions,
                              cache, ctx, slot=slot, valid_len=vlen)
    last = jax.lax.dynamic_slice_in_dim(x, jnp.asarray(last_idx, jnp.int32),
                                        1, axis=1)
    logits = _logits(last, params, cfg, ctx.dispatch)[:, 0]
    return logits, cache


def decode_step_bucketed(params: dict, cfg: ModelConfig, embeds: Array,
                         cache: dict, slot_idx: Array,
                         ctx: Optional[StepCtx] = None,
                         lora: Optional[dict] = None,
                         active: Optional[Array] = None) -> Tuple[Array, dict]:
    """One decode step over a *bucket* of rows gathered from the full slot
    set (serving-loop batch bucketing).  embeds: [b, 1, d] for bucket size
    b <= max_slots, already gathered; ``slot_idx`` [b] int32 names the slot
    each bucket row came from (the caller pads to bucket size with distinct
    idle slots and masks them via ``active`` [b]).

    Only ``pos`` and the shared page table are gathered — the pooled KV
    pages are physical-page addressed, so the pool never moves: appends
    route through the gathered table rows straight to each slot's pages,
    exactly where the full-batch step would put them.  That plus per-row-
    independent math (matmul rows, rmsnorm, attention never mix batch
    rows) makes the bucketed step bitwise equal to the full-batch step on
    the active rows.

    Requires a paged uniform stack (full-attention, window 0 — the engine
    gates on this): windowed rings and SSM states are *batch-row*
    addressed, so a gathered row order would read the wrong state.

    Returns (logits [b, V] in bucket order, new cache with full-shape
    ``pos`` scattered back).  The caller scatters logits to slots.
    """
    ctx = ctx or StepCtx(cfg)
    if lora is not None:
        ctx = dataclasses.replace(ctx, lora=lora)
    x = embeds.astype(jnp.bfloat16)
    b, T = x.shape[:2]
    slot_idx = jnp.asarray(slot_idx, jnp.int32)
    pos_full = cache["pos"]                    # [max_slots]
    pos = pos_full[slot_idx]                   # [b]
    positions = pos[:, None] + jnp.arange(T)[None]
    small = dict(cache)
    small["pos"] = pos
    small["table"] = cache["table"][slot_idx]  # [b, pages_per_row]
    x, small, _ = _run_stacks(x, params, cfg, "decode", positions, small, ctx)
    new_cache = dict(cache)
    new_cache["stacks"] = small["stacks"]      # pool-wide: full shape
    stepped = pos + T if active is None else jnp.where(active, pos + T, pos)
    new_cache["pos"] = pos_full.at[slot_idx].set(stepped)
    logits = _logits(x, params, cfg, ctx.dispatch)[:, -1]
    return logits, new_cache


def decode_step(params: dict, cfg: ModelConfig, embeds: Array, cache: dict,
                positions: Optional[Array] = None,
                ctx: Optional[StepCtx] = None,
                lora: Optional[dict] = None,
                active: Optional[Array] = None) -> Tuple[Array, dict]:
    """One decode step. embeds: [B, 1, d] (row fetched from Flash — C2).
    Returns (logits [B, V], new cache).  ``lora``: per-call multi-LoRA
    tables + per-request adapter ids (C7).

    With a per-row cache (``pos`` of shape [B]) each row decodes at its own
    offset — continuous batching.  ``active`` ([B] bool) freezes inactive
    slots entirely: their rows still flow through the batch (cheap on a
    fixed-shape step) but their positions, recurrent states and windowed
    ring pages are rolled back, so a slot mid-prefill keeps its carried
    chunk state intact while co-resident rows decode."""
    ctx = ctx or StepCtx(cfg)
    if lora is not None:
        ctx = dataclasses.replace(ctx, lora=lora)
    x = embeds.astype(jnp.bfloat16)
    B, T = x.shape[:2]
    pos = cache["pos"]
    if positions is None:
        if jnp.ndim(pos) == 1:
            positions = pos[:, None] + jnp.arange(T)[None]
        else:
            positions = jnp.broadcast_to(pos[None, None], (B, T))
    old_stacks = cache["stacks"]
    x, cache, _ = _run_stacks(x, params, cfg, "decode", positions, cache, ctx)
    if active is not None:
        cache["pos"] = jnp.where(active, pos + T, pos)
        cache["stacks"] = freeze_inactive_rows(cfg, old_stacks,
                                               cache["stacks"], active)
    else:
        cache["pos"] = pos + T
    logits = _logits(x, params, cfg, ctx.dispatch)[:, -1]
    return logits, cache

"""Shared layers + the ParamBuilder used to describe parameter trees once.

A model's parameter tree is described by init functions written against a
``ParamBuilder``; running the same description in different modes yields:
  * mode="init"     — real initialized arrays (float or quantized),
  * mode="abstract" — jax.ShapeDtypeStruct stand-ins (dry-run, no alloc),
  * mode="spec"     — PartitionSpec tree (for in_shardings).

Quantization policy is applied here (C1): Linear weights become
``QuantizedTensor``s when the builder is in quantized mode; lm_head gets
``lm_head_bits`` (int8-prioritized per the paper); biases/norms stay float.
With ``pack=True`` (serving) the builder emits plan-aware
``runtime.plan.PackedLinear`` weights — the kernel-native padded layout,
built once at init instead of repacked at plan time.

Hot ops (linear matmul, rmsnorm) route through ``runtime.dispatch``: the
registry — not this module — decides between the Pallas kernels and the
reference paths.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import quantization as q
from repro.core.precision import PrecisionPolicy, DEFAULT_POLICY
from repro.runtime import dispatch as D
from repro.runtime import plan as RP

Array = jax.Array

FSDP_MIN_ELEMENTS = 16 * 2 ** 20   # 2-D-shard only weights >= 16M elements


class ParamBuilder:
    """Describes params once; materializes arrays / SDS / PartitionSpecs."""

    def __init__(self, mode: str, key: Optional[jax.Array] = None,
                 quantized: bool = False, qcfg: Optional[q.QuantConfig] = None,
                 fsdp: bool = False, dtype=jnp.bfloat16, pack: bool = False):
        assert mode in ("init", "abstract", "spec")
        self.mode = mode
        self._key = key
        self.quantized = quantized
        self.qcfg = qcfg or q.QuantConfig()
        self.fsdp = fsdp          # shard big weights over "data" too (ZeRO-3)
        self.dtype = dtype
        self.pack = pack          # emit kernel-native PackedLinear weights

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    def param(self, shape, spec, *, scale: float = 0.02, dtype=None):
        """A plain (never-quantized) float parameter."""
        dtype = dtype or self.dtype
        if self.mode == "spec":
            return P(*spec)
        if self.mode == "abstract":
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        if scale == 0.0:
            return jnp.zeros(shape, dtype)
        if scale == 1.0 and len(shape) <= 1:
            return jnp.ones(shape, dtype)
        return (jax.random.normal(self._next_key(), shape, jnp.float32)
                * scale).astype(dtype)

    def linear(self, in_dim: int, out_dim: int, spec, *, bits: Optional[int] = None,
               scale: Optional[float] = None, lead: tuple = ()):
        """A Linear weight [*(lead), in, out]; quantized per policy when the
        builder is in quantized mode.  ``spec`` is the 2-D (in, out) spec;
        lead dims get spec entries from ``spec[:-2]`` if provided as longer.
        """
        shape = (*lead, in_dim, out_dim)
        full_spec = spec if len(spec) == len(shape) else ((None,) * len(lead)) + tuple(spec)
        numel = 1
        for d in shape:
            numel *= d
        flat_axes = set()
        for e in full_spec:
            flat_axes.update(e if isinstance(e, tuple) else (e,))
        if self.fsdp and numel >= FSDP_MIN_ELEMENTS and "data" not in flat_axes:
            # ZeRO-3-style: also shard big weights over "data" on whichever
            # of the last two dims is free (all-gathered per layer in use).
            # Small weights (e.g. mamba x_proj) stay 1-D sharded: 2-D
            # sharding them is pure collective overhead and their packed
            # int4 dims need not divide pod x data.
            fs = list(full_spec)
            if fs[-2] is None:
                fs[-2] = "data"
            elif fs[-1] is None:
                fs[-1] = "data"
            full_spec = tuple(fs)
        bits = bits if bits is not None else self.qcfg.weight_bits
        scale = 0.02 if scale is None else scale
        if not (self.quantized and bits < 16):
            return {"w": self.param(shape, full_spec, scale=scale)}
        gs = self.qcfg.group_size
        g = (in_dim // gs) if (gs and gs < in_dim) else 1
        # per-layer 2-D linears pack into PackedLinear; expert tables (lead
        # dims) pack into PackedExpertLinear — the grouped kernel's padded
        # layout, expert axis kept directly indexable for the MoE gathers
        # and per-expert weight streaming
        pack = self.pack
        _spec = RP.spec_packed_expert if lead else RP.spec_packed
        _abstract = RP.abstract_packed_expert if lead else RP.abstract_packed
        _pack = RP.pack_expert_linear if lead else RP.pack_linear
        if self.mode == "spec":
            data_spec = full_spec
            sz_spec = (*full_spec[:-2], None, full_spec[-1])
            if pack:
                return {"w": _spec(data_spec, sz_spec, bits, shape)}
            return {"w": q.QuantizedTensor(
                data=P(*data_spec), scale=P(*sz_spec), zero=P(*sz_spec),
                bits=bits, shape=shape)}
        if self.mode == "abstract":
            if pack:
                return {"w": _abstract(shape, bits, gs)}
            return {"w": q.abstract_quantized(shape, bits, gs)}
        init = (_init_quantized_jit if math.prod(shape) >= FUSED_INIT_MIN
                else _init_quantized)
        return {"w": init(self._next_key(), shape=tuple(shape),
                          scale=float(scale), bits=bits, group_size=gs,
                          pack=_pack if pack else None)}

    def bias(self, dim: int, spec=("model",)):
        return self.param((dim,), spec, scale=0.0)

    def norm(self, dim: int):
        return self.param((dim,), (None,), scale=1.0, dtype=jnp.float32)


# linears with at least this many weights draw and quantize in one compiled
# program (64M elements = 256 MiB of f32 draw)
FUSED_INIT_MIN = 1 << 26


def _init_quantized(key, *, shape, scale, bits, group_size, pack):
    """Random float weight -> quantized (and optionally packed)."""
    wf = jax.random.normal(key, shape, jnp.float32) * scale
    qt = q.quantize(wf, bits, group_size=group_size)
    return pack(qt) if pack is not None else qt


# The same in one program, for the large linears of a full-width model:
# their float transients (the lm_head is ~2.2 GB in f32 at a 150k vocab)
# live inside XLA instead of as a chain of eager full-size temporaries.
# Fusion may round the draw differently from the eager ops, so small
# weights stay eager and keep their values.
_init_quantized_jit = jax.jit(
    _init_quantized,
    static_argnames=("shape", "scale", "bits", "group_size", "pack"))


def apply_linear(x: Array, p: dict, qcfg: q.QuantConfig,
                 out_dtype=jnp.bfloat16,
                 dispatch: Optional[D.Dispatcher] = None) -> Array:
    """y = x @ w (+b), routed through the kernel dispatcher (C1/C3)."""
    y = D.resolve(dispatch).linear(x, p["w"], qcfg, out_dtype=out_dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def rms_norm(x: Array, weight: Array, eps: float = 1e-5,
             dispatch: Optional[D.Dispatcher] = None) -> Array:
    """RMSNorm, routed through the kernel dispatcher (fused Pallas kernel
    on the kernel backends, fp32 reference otherwise)."""
    return D.resolve(dispatch).rmsnorm(x, weight, eps)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float,
               fraction: float = 1.0, interleaved: bool = False) -> Array:
    """x: [B, T, H, D]; positions: [B, T] int32.  Rotates the first
    ``fraction`` x D dims of each head with frequencies over that width:
    in rotate-half form (dim i with dim i + r/2), or ``interleaved`` on
    the pairs (2i, 2i + 1).  The other dims pass through unchanged."""
    d = x.shape[-1]
    r = int(round(d * fraction))
    assert r % 2 == 0 and 0 < r <= d, (d, fraction)
    freqs = jnp.asarray(rope_freqs(r, theta), jnp.float32)          # [r/2]
    ang = positions[..., None].astype(jnp.float32) * freqs          # [B,T,r/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    rot = xf[..., :r] if r < d else xf
    if interleaved:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    if r < d:
        out = jnp.concatenate([out, xf[..., r:]], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions3: Array, theta: float,
                sections: Sequence[int]) -> Array:
    """Qwen2-VL multimodal RoPE. positions3: [B, T, 3] (t, h, w) ids;
    rotary dims are split into per-axis sections (sum(sections) == D/2)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)          # [D/2]
    sec = np.asarray(sections)
    assert sec.sum() == d // 2, (sections, d)
    axis_of = np.repeat(np.arange(3), sec)                          # [D/2]
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(jnp.asarray(axis_of)[None, None, :],
                         (*positions3.shape[:2], d // 2)),
        axis=-1)                                                    # [B,T,D/2]
    ang = pos * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positional(qk: Array, cfg: ModelConfig, positions: Array) -> Array:
    if cfg.rope_kind == "none":
        return qk
    if cfg.rope_kind == "mrope":
        if positions.ndim == 2:   # text-only: same ids on all 3 axes
            positions = jnp.repeat(positions[..., None], 3, axis=-1)
        return apply_mrope(qk, positions, cfg.rope_theta, cfg.mrope_sections)
    if positions.ndim == 3:
        positions = positions[..., 0]
    return apply_rope(qk, positions, cfg.rope_theta, cfg.rope_fraction,
                      cfg.rope_interleaved)


def swiglu(x: Array, gate: Array) -> Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * x


def ffn_params(b: ParamBuilder, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        return {"w_gate": b.linear(d, f, (None, "model")),
                "w_up": b.linear(d, f, (None, "model")),
                "w_down": b.linear(f, d, ("model", None))}
    return {"w_up": b.linear(d, f, (None, "model")),
            "w_down": b.linear(f, d, ("model", None))}


def apply_ffn(x: Array, p: dict, cfg: ModelConfig,
              dispatch: Optional[D.Dispatcher] = None) -> Array:
    if cfg.act == "swiglu":
        g = apply_linear(x, p["w_gate"], cfg.quant, dispatch=dispatch)
        u = apply_linear(x, p["w_up"], cfg.quant, dispatch=dispatch)
        h = swiglu(u, g)
    else:
        u = apply_linear(x, p["w_up"], cfg.quant, dispatch=dispatch)
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(u.dtype)
    return apply_linear(h, p["w_down"], cfg.quant, dispatch=dispatch)

"""Combined quantization (paper §4.2, C1).

Implements the paper's asymmetric quantization (Eq. 1):

    w_asy = round((w_float - w_min) / ((w_max - w_min) / (clip_max - clip_min))) + clip_min

for int4 (clip [0, 15], stored packed two-nibbles-per-uint8) and int8
(clip [-128, 127]). Scales/zeros are per-output-channel, optionally
per-(group x channel) with a group size along the reduction dim.

Compute paths (paper Table-free, §4.2 prose):
  * W4A8 / W8A8  — "CPU" path: activations dynamically quantized to int8
    per row, integer dot via lax.dot_general(int8, int8 -> int32), then
    rescale.  On TPU this is the MXU int8 path (Pallas kernel in
    repro/kernels/w4a8_matmul.py; this module is the reference/runtime
    fallback used inside jitted models).
  * W4A16 / W8A16 — "GPU" path: dequantize weights to bf16 and matmul.
  * KV cache: keys int8 (reduction dim = head_dim, fixed), values fp8
    e4m3 (scale-free so appending never requantizes history) — see
    repro/core/kv_cache.py.
  * lm_head prioritized to int8 (higher accuracy impact than layers).
  * embedding: bf16, lives on Flash (repro/core/hybrid_storage.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

INT4_CLIP_MIN, INT4_CLIP_MAX = 0, 15         # stored as unsigned nibbles
INT8_CLIP_MIN, INT8_CLIP_MAX = -128, 127


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Quantization policy for one model (paper's 'combined quantization')."""
    weight_bits: int = 4            # 4 or 8 (or 16 = no quant) for Layer weights
    act_bits: int = 8               # 8 => WxA8 integer path, 16 => WxA16 float path
    lm_head_bits: int = 8           # paper: lm_head prioritized for int8
    kv_key_bits: int = 8            # int4/int8 keys
    kv_value_fp8: bool = True       # fp8 e4m3 values
    group_size: int = 0             # 0 => per-channel only; else per-(group, channel)
    embed_dtype: str = "bfloat16"   # embedding kept float (on Flash)

    def tag(self) -> str:
        return f"W{self.weight_bits}A{self.act_bits}"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """An asymmetric-quantized tensor.

    data: int8 carrier. For 4-bit, two nibbles packed per int8 along the
      *last* axis (so data.shape[-1] == logical[-1] // 2).
    scale, zero: per-channel (or per-group x channel) float params s.t.
      w_float ~= scale * (q - zero)  with q in clip range.
    shape/bits record the logical layout.
    """
    data: Array
    scale: Array
    zero: Array
    bits: int
    shape: tuple  # logical float shape

    def tree_flatten(self):
        return (self.data, self.scale, self.zero), (self.bits, tuple(self.shape))

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, zero = children
        bits, shape = aux
        return cls(data=data, scale=scale, zero=zero, bits=bits, shape=shape)

    @property
    def nbytes_logical(self) -> int:
        n = int(np.prod(self.shape))
        return n * self.bits // 8


def _clip_range(bits: int):
    if bits == 4:
        return INT4_CLIP_MIN, INT4_CLIP_MAX
    if bits == 8:
        return INT8_CLIP_MIN, INT8_CLIP_MAX
    raise ValueError(f"unsupported bits={bits}")


def pack_int4(q: Array) -> Array:
    """Pack unsigned 4-bit values (0..15, int32/int8) pairwise along last axis."""
    assert q.shape[-1] % 2 == 0, q.shape
    lo = q[..., 0::2].astype(jnp.uint8)
    hi = q[..., 1::2].astype(jnp.uint8)
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: Array) -> Array:
    """Inverse of pack_int4 -> values 0..15 as int8."""
    p = packed.astype(jnp.uint8)
    lo = (p & 0x0F).astype(jnp.int8)
    hi = ((p >> 4) & 0x0F).astype(jnp.int8)
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], packed.shape[-1] * 2)


INT4_LANE_GROUP = 128   # lanes per nibble half in the kernel-native layout


def pack_int4_lanes(q: Array) -> Array:
    """Kernel-native int4 packing (values 0..15 along the last axis, a
    multiple of 256): packed column ``g*128 + l`` holds value column
    ``g*256 + l`` in its low nibble and ``g*256 + 128 + l`` in its high
    nibble.  Unpacking a 128-lane packed slice yields two contiguous
    128-lane output slices — no lane interleave, which Mosaic cannot
    lower."""
    *lead, n = q.shape
    lg = INT4_LANE_GROUP
    assert n % (2 * lg) == 0, q.shape
    q4 = q.reshape(*lead, n // (2 * lg), 2, lg).astype(jnp.uint8)
    packed = q4[..., 0, :] | (q4[..., 1, :] << 4)
    return packed.astype(jnp.int8).reshape(*lead, n // 2)


def unpack_int4_lanes(packed: Array) -> Array:
    """Inverse of pack_int4_lanes -> values 0..15 as int8."""
    *lead, m = packed.shape
    lg = INT4_LANE_GROUP
    p = packed.astype(jnp.uint8).reshape(*lead, m // lg, lg)
    q = jnp.stack([p & 0x0F, (p >> 4) & 0x0F], axis=-2)
    return q.astype(jnp.int8).reshape(*lead, 2 * m)


def quantize(w: Array, bits: int, *, group_size: int = 0,
             axis: int = -2) -> QuantizedTensor:
    """Asymmetric quantization per Eq. 1 of the paper.

    ``w`` is the float weight of shape [..., l, h] (reduction dim l at
    ``axis``, output channels last).  Scales are per output channel, and per
    group of ``group_size`` along the reduction dim when group_size > 0.
    """
    if axis != -2:
        w = jnp.moveaxis(w, axis, -2)
    *lead, l, h = w.shape
    cmin, cmax = _clip_range(bits)
    if group_size and group_size < l:
        assert l % group_size == 0, (l, group_size)
        g = l // group_size
        wg = w.reshape(*lead, g, group_size, h)
        wmin = wg.min(axis=-2, keepdims=True)
        wmax = wg.max(axis=-2, keepdims=True)
    else:
        wg = w.reshape(*lead, 1, l, h)
        wmin = wg.min(axis=-2, keepdims=True)
        wmax = wg.max(axis=-2, keepdims=True)
    scale = (wmax - wmin) / (cmax - cmin)
    scale = jnp.where(scale == 0, jnp.ones_like(scale), scale)
    # Eq. 1: q = round((w - wmin)/scale) + clip_min
    q = jnp.round((wg - wmin) / scale) + cmin
    q = jnp.clip(q, cmin, cmax)
    # zero point z s.t. w ~= scale * (q - z):  w = scale*(q - cmin) + wmin
    # => z = cmin - wmin/scale
    zero = cmin - wmin / scale
    q = q.reshape(*lead, l, h)
    if bits == 4:
        # pack along the output-channel (last) axis
        data = pack_int4(q)
    else:
        data = q.astype(jnp.int8)
    scale = scale.squeeze(-2).astype(jnp.float32)   # [..., g, h]
    zero = zero.squeeze(-2).astype(jnp.float32)
    return QuantizedTensor(data=data, scale=scale, zero=zero, bits=bits,
                           shape=tuple((*lead, l, h)))


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> Array:
    """Inverse map: w = scale * (q - zero).

    Shapes derive from ``qt.data`` (not the static aux ``shape``) so that
    scan/vmap slices of stacked QuantizedTensors work unchanged."""
    if qt.bits == 4:
        q = unpack_int4(qt.data)
    else:
        q = qt.data
    *lead, l, h = q.shape
    g = qt.scale.shape[-2]
    qf = q.reshape(*lead, g, l // g, h).astype(jnp.float32)
    w = qt.scale[..., :, None, :] * (qf - qt.zero[..., :, None, :])
    return w.reshape(*lead, l, h).astype(dtype)


# ---------------------------------------------------------------------------
# Activation quantization (dynamic, per-row) — W4A8/W8A8 integer path
# ---------------------------------------------------------------------------

def quantize_activations(x: Array) -> tuple[Array, Array]:
    """Symmetric per-row int8 quantization of activations.

    Symmetric (not asymmetric) for activations keeps the integer matmul a
    single dot: x ~= sx * xq. Per-row scale over the reduction (last) axis.
    """
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.where(amax == 0, 1.0, amax / 127.0).astype(jnp.float32)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -127, 127).astype(jnp.int8)
    return xq, sx


def _int_matmul(xq: Array, wq_centered: Array) -> Array:
    """int8 x int8 -> int32 dot along last/first."""
    return jax.lax.dot_general(
        xq, wq_centered,
        dimension_numbers=(((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def quant_matmul(x: Array, qt: QuantizedTensor, cfg: QuantConfig,
                 out_dtype=jnp.bfloat16) -> Array:
    """y = x @ dequant(qt), via the configured path.

    A8 path (CPU/int8 analogue): dynamic int8 activations, integer dot,
    rescale with asymmetric correction term:
        y = sx * scale * (xq @ qw - zero * sum(xq))
    A16 path (GPU/float analogue): dequant to bf16 and matmul with fp32 acc.
    """
    *lead, l = x.shape
    assert l == qt.data.shape[-2], (x.shape, qt.data.shape)
    if cfg.act_bits == 16 or qt.scale.shape[-2] > 1:
        # float path (also used whenever group-wise scales make the integer
        # correction term group-dependent)
        w = dequantize(qt)
        return jnp.matmul(x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32).astype(out_dtype)
    # integer path, per-channel scales (g == 1)
    xq, sx = quantize_activations(x)
    if qt.bits == 4:
        qw = unpack_int4(qt.data)
    else:
        qw = qt.data
    acc = _int_matmul(xq, qw)                                  # [..., h] int32
    rowsum = jnp.sum(xq.astype(jnp.int32), axis=-1, keepdims=True)
    scale = qt.scale[..., 0, :]
    zero = qt.zero[..., 0, :]
    y = scale * (acc.astype(jnp.float32) - zero * rowsum.astype(jnp.float32))
    y = y * sx
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# fp8 (values of the KV cache)
# ---------------------------------------------------------------------------

FP8_DTYPE = jnp.float8_e4m3fn
FP8_MAX = 448.0


def to_fp8(x: Array) -> Array:
    """Scale-free fp8 e4m3 cast (paper: values quantized 'directly')."""
    return jnp.clip(x.astype(jnp.float32), -FP8_MAX, FP8_MAX).astype(FP8_DTYPE)


def from_fp8(x: Array, dtype=jnp.bfloat16) -> Array:
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Prequantized import (GPTQ-style adapter, paper §3)
# ---------------------------------------------------------------------------

def load_prequantized(qweight: np.ndarray, scales: np.ndarray,
                      zeros: np.ndarray, bits: int,
                      logical_shape: tuple) -> QuantizedTensor:
    """Adapter for externally-quantized weights (e.g. GPTQ exports).

    Expects qweight already in this module's layout (int8 carrier, packed
    for 4-bit); scales/zeros per-(group, channel).
    """
    scale = jnp.asarray(scales, dtype=jnp.float32)
    zero = jnp.asarray(zeros, dtype=jnp.float32)
    if scale.ndim == 1:
        scale = scale[None, :]
        zero = zero[None, :]
    return QuantizedTensor(data=jnp.asarray(qweight, dtype=jnp.int8),
                           scale=scale, zero=zero,
                           bits=bits, shape=tuple(logical_shape))


# ---------------------------------------------------------------------------
# Abstract (ShapeDtypeStruct) construction for dry-runs
# ---------------------------------------------------------------------------

def abstract_quantized(shape, bits: int, group_size: int = 0) -> QuantizedTensor:
    """Build a QuantizedTensor of ShapeDtypeStructs (no allocation)."""
    *lead, l, h = shape
    data_shape = (*lead, l, h // 2) if bits == 4 else (*lead, l, h)
    g = (l // group_size) if (group_size and group_size < l) else 1
    sds = jax.ShapeDtypeStruct
    return QuantizedTensor(
        data=sds(data_shape, jnp.int8),
        scale=sds((*lead, g, h), jnp.float32),
        zero=sds((*lead, g, h), jnp.float32),
        bits=bits, shape=tuple(shape))


def maybe_quantize(w: Array, bits: int, group_size: int = 0):
    """Quantize unless bits==16 (keep bf16)."""
    if bits >= 16:
        return w.astype(jnp.bfloat16)
    return quantize(w, bits, group_size=group_size)

"""Pallas fused RMSNorm (the paper fuses RMSNorm at model conversion, §3).

Row-blocked: each grid step normalizes a [bm, D] tile fully in VMEM
(fp32 math, bf16 in/out) — one HBM read + one write per element instead of
the unfused mean-square / rsqrt / scale chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # [bm, D]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 256, interpret: bool) -> jax.Array:
    """x: [..., D] bf16/f32; weight: [D]."""
    orig_shape = x.shape
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    M = x2.shape[0]
    bm = min(block_rows, M)
    pad = (-M) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    Mp = x2.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, D), x.dtype),
        interpret=interpret,
    )(x2, weight.reshape(1, D))
    return out[:M].reshape(orig_shape)

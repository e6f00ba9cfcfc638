"""jit'd public wrappers for the Pallas kernels.

``interpret`` is a required static argument: ``True`` runs the kernel body
through the Pallas interpreter (CPU tests), ``False`` compiles it with
Mosaic for the TPU.  The serving path reaches the kernels through
``runtime/dispatch.py`` instead, which picks the mode from its backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.kernels import flash_prefill as _fp
from repro.kernels import quant_attention as _qa
from repro.kernels import rmsnorm as _rn
from repro.kernels import w4a8_matmul as _wm


@functools.partial(jax.jit, static_argnames=("bits", "blocks", "interpret"))
def quant_matmul_kernel(x: jax.Array, wq_packed: jax.Array,
                        w_scale: jax.Array, w_zero: jax.Array, *,
                        interpret: bool, bits: int = 4,
                        blocks=None) -> jax.Array:
    """Float activations in; dynamic int8 activation quant + W4A8 kernel.
    ``wq_packed`` is lane-packed (``quantization.pack_int4_lanes``)."""
    xq, sx = q.quantize_activations(x)
    return _wm.w4a8_matmul(xq, sx, wq_packed, w_scale, w_zero, bits=bits,
                           blocks=blocks, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def quant_decode_attention(qh: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                           k_zero: jax.Array, v: jax.Array,
                           length: jax.Array, *, interpret: bool,
                           block_s: int = 512) -> jax.Array:
    return _qa.quant_decode_attention(qh, k_q, k_scale, k_zero, v, length,
                                      block_s=block_s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, weight: jax.Array, *, interpret: bool,
            eps: float = 1e-5, block_rows: int = 256) -> jax.Array:
    return _rn.rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                       interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk",
                                    "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  interpret: bool, causal: bool = True, window: int = 0,
                  bq: int = 128, bk: int = 256) -> jax.Array:
    return _fp.flash_prefill_attention(q, k, v, causal=causal, window=window,
                                       bq=bq, bk=bk, interpret=interpret)

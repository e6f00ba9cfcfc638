"""Pallas grouped (per-expert) W4A8 / W8A8 matmul kernel (paper C1 + C3).

The MoE analogue of ``w4a8_matmul.py``: one int8 activation slab and one
int4/int8 asymmetric weight slab per expert, multiplied on the MXU int8
path with the dequant fused into the epilogue.  The leading grid dimension
selects the expert (a squeezed block dim, so the kernel body sees the same
2-D tiles as the single-matmul kernel and shares its int4 unpack + dot);
the VMEM int32 accumulator + row-sum scratch and the asymmetric-zero
correction

    y[e] = sx[e] * w_scale[e] * (acc[e] - w_zero[e] * rowsum[e])

are identical to the single-matmul kernel, so one tile plan (solved per
(M, N, K) shape by ``solve_tpu_blocks``) serves every expert.

Layout: int4 weights lane-packed exactly as in ``w4a8_matmul`` (one
[K, N//2] slab per expert) — the per-expert instance of the paper's
load-time weight reorder (§5.1).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tiling
from repro.kernels.w4a8_matmul import accumulate_int_dot, check_blocks

_SQ = pl.Squeezed()


def _kernel(x_ref, w_ref, sx_ref, ws_ref, wz_ref, o_ref,
            acc_ref, rowsum_ref, *, n_k: int, bits: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)

    xq = x_ref[...]                                   # [bm, bk] int8
    accumulate_int_dot(xq, w_ref, acc_ref, bits)
    rowsum_ref[...] += jnp.sum(xq.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(k == n_k - 1)
    def _finalize():
        acc = acc_ref[...].astype(jnp.float32)        # [bm, bn]
        rs = rowsum_ref[...].astype(jnp.float32)      # [bm, 1]
        o_ref[...] = (sx_ref[...] * ws_ref[...]
                      * (acc - wz_ref[...] * rs)).astype(o_ref.dtype)


def grouped_matmul(xq: jax.Array, sx: jax.Array, wq_packed: jax.Array,
                   w_scale: jax.Array, w_zero: jax.Array, *,
                   bits: int = 4,
                   blocks: Optional[Tuple[int, int, int]] = None,
                   interpret: bool) -> jax.Array:
    """y[E, M, N] f32 = per-expert dequant-matmul of int8 activations.

    xq: int8 [E, M, K]; sx: f32 [E, M, 1] activation scales
    wq_packed: int8 [E, K, N//2] (bits=4, lane-packed) or [E, K, N] (bits=8)
    w_scale/w_zero: f32 [E, N]
    """
    E, M, K = xq.shape
    N = wq_packed.shape[-1] * (2 if bits == 4 else 1)
    if blocks is None:
        blocks = tiling.solve_tpu_blocks(M, N, K, in_bytes=1.0)
    bm, bn, bk, wn = check_blocks(M, N, K, blocks, bits)
    gm, gn, gk = M // bm, N // bn, K // bk

    kernel = functools.partial(_kernel, n_k=gk, bits=bits)
    return pl.pallas_call(
        kernel,
        name="grouped_matmul",
        grid=(E, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((_SQ, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((_SQ, bk, wn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((_SQ, bm, 1), lambda e, i, j, k: (e, i, 0)),
            pl.BlockSpec((_SQ, 1, bn), lambda e, i, j, k: (e, 0, j)),
            pl.BlockSpec((_SQ, 1, bn), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((_SQ, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),     # int32 accumulator tile
            pltpu.VMEM((bm, 1), jnp.int32),      # activation row sums
        ],
        interpret=interpret,
    )(xq, wq_packed, sx,
      w_scale.reshape(E, 1, N), w_zero.reshape(E, 1, N))

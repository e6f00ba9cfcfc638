"""Pallas W4A8 / W8A8 matmul kernel (paper C1 + C3).

The TPU adaptation of the paper's hardware-driven reorder: int8 activations
x int4/int8 asymmetric weights on the MXU int8 path, with BlockSpec tiles
chosen by repro.core.tiling.solve_tpu_blocks (the Eq. 2-4 optimizer with
R -> VMEM bytes, instruction width -> (8,128) lane alignment).

Layout: int4 weights are packed two-nibbles-per-int8 along the N (lane)
axis in 128-lane halves (``quantization.pack_int4_lanes``): packed lanes
``[g*128, g*128+128)`` carry output columns ``[g*256, g*256+128)`` in the
low nibbles and ``[g*256+128, g*256+256)`` in the high nibbles — the
analogue of the paper's [h/h_p, l/l_p, h_p, l_p] weight reorder done once
at load time (§5.1).  The kernel unpacks each 128-lane slice with two
mask/shift ops and runs one dot per half straight into the matching
accumulator slice.

Grid (gm, gn, gk), k innermost; int32 accumulator + int32 row-sum live in
VMEM scratch across the k steps; the asymmetric-zero correction
    y = sx * w_scale * (acc - w_zero * rowsum)
is applied once at the last k step.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantization as q
from repro.core import tiling


def accumulate_int_dot(xq, w_ref, acc_ref, bits: int) -> None:
    """acc_ref [bm, bn] += xq [bm, bk] @ weights; ``w_ref`` is a 2-D
    [bk, bn//2] lane-packed int4 ref (bits=4) or [bk, bn] int8 ref.
    Shared with the grouped expert kernel."""
    dims = (((1,), (0,)), ((), ()))
    if bits == 8:
        acc_ref[...] += jax.lax.dot_general(
            xq, w_ref[...], dims, preferred_element_type=jnp.int32)
        return
    lg = q.INT4_LANE_GROUP
    for g in range(w_ref.shape[1] // lg):
        # nibble math in int32: Mosaic has no int8 shifts
        p = w_ref[:, g * lg:(g + 1) * lg].astype(jnp.int32)
        for half, vals in enumerate((p & 0x0F, (p >> 4) & 0x0F)):
            c0 = (2 * g + half) * lg
            acc_ref[:, c0:c0 + lg] += jax.lax.dot_general(
                xq, vals.astype(jnp.int8), dims,
                preferred_element_type=jnp.int32)


def _kernel(x_ref, w_ref, sx_ref, ws_ref, wz_ref, o_ref,
            acc_ref, rowsum_ref, *, n_k: int, bits: int):
    k = pl.program_id(2)

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
        ws = ws_ref[...]                              # [1, bn]
        wz = wz_ref[...]
        sx = sx_ref[...]                              # [bm, 1]
        o_ref[...] = (sx * ws * (acc - wz * rs)).astype(o_ref.dtype)


def check_blocks(M: int, N: int, K: int, blocks, bits: int):
    """Clamp solver blocks to the operand and check the kernel's tiling
    rules; returns (bm, bn, bk, packed weight block width)."""
    bm, bn, bk = blocks
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, blocks)
    if bits == 4:
        assert bn % (2 * q.INT4_LANE_GROUP) == 0, \
            f"int4 tiles span whole 256-column lane groups, got bn={bn}"
    return bm, bn, bk, (bn // 2 if bits == 4 else bn)


def w4a8_matmul(xq: jax.Array, sx: jax.Array, wq_packed: jax.Array,
                w_scale: jax.Array, w_zero: jax.Array, *,
                bits: int = 4,
                blocks: Optional[Tuple[int, int, int]] = None,
                interpret: bool) -> jax.Array:
    """y[M, N] f32 = dequant-matmul of int8 activations with int4/int8 weights.

    xq: int8 [M, K]; sx: f32 [M, 1] activation scales
    wq_packed: int8 [K, N//2] lane-packed (bits=4, see module doc) or
    [K, N] (bits=8); w_scale/w_zero: f32 [N]
    """
    M, K = xq.shape
    N = wq_packed.shape[1] * (2 if bits == 4 else 1)
    if blocks is None:
        blocks = tiling.solve_tpu_blocks(M, N, K, in_bytes=1.0)
    bm, bn, bk, wn = check_blocks(M, N, K, blocks, bits)
    gm, gn, gk = M // bm, N // bn, K // bk

    kernel = functools.partial(_kernel, n_k=gk, bits=bits)
    return pl.pallas_call(
        kernel,
        name="w4a8_matmul",
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, wn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),     # int32 accumulator tile
            pltpu.VMEM((bm, 1), jnp.int32),      # activation row sums
        ],
        interpret=interpret,
    )(xq, wq_packed, sx, w_scale.reshape(1, N), w_zero.reshape(1, N))

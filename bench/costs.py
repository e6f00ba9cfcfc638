"""Operations and bytes of the model and of each kernel call, from shapes.

Kept with the benchmark so that every PR counts them the same way.  The
model's own counts come from the arch module that built its configuration
(``bench/arch/``); a kernel call's are read from the shapes the trace
names.  The packed layout pads a linear's reduction dim to 128 and its
output dim to 256; int4 weights take half a byte per value, int8 one byte,
and each output column carries an f32 scale and an f32 zero point.
"""
from __future__ import annotations

import re
from typing import List, Tuple

K_ALIGN, N_ALIGN = 128, 256


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def packed_linear_bytes(k: int, n: int, bits: int) -> int:
    kp, np_ = _ceil(k, K_ALIGN), _ceil(n, N_ALIGN)
    return kp * np_ * bits // 8 + 2 * 4 * np_


# --- the model's counts, from the arch module that built the configuration --

def _arch(cfg):
    from bench import model
    return model.arch_for(cfg)


def weight_bytes(cfg) -> int:
    """Bytes of the packed serving tree on the device."""
    return _arch(cfg).weight_bytes(cfg)


def token_linear_ops(cfg) -> int:
    """Multiply-adds x 2 of every layer's linears for one token."""
    return _arch(cfg).token_linear_ops(cfg)


def head_ops(cfg) -> int:
    """Multiply-adds x 2 of the output head for one row."""
    return _arch(cfg).head_ops(cfg)


def attention_ops(cfg, keys: int) -> int:
    """Attention's operations over ``keys`` attended positions, all
    layers."""
    return _arch(cfg).attention_ops(cfg, keys)


def decode_attention_bytes(cfg, context_tokens: int, rows: int) -> int:
    """Bytes one paged decode-attention pass needs, all layers."""
    return _arch(cfg).decode_attention_bytes(cfg, context_tokens, rows)


def least_time(ops: float, nbytes: float, ops_peak: float,
               bytes_peak: float) -> float:
    return max(ops / ops_peak, nbytes / bytes_peak)


# --- one call of the W4A8 kernel, from the shapes the trace names ----------

_SHAPE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def w4a8_call(m: int, k: int, n: int, bits: int) -> Tuple[int, int]:
    """(int8 ops, bytes) of one ``w4a8_matmul`` call: int8 activations
    [m, k] and their f32 row scales, the packed weight [k, n] with its f32
    scale and zero rows, and the f32 output [m, n]."""
    ops = 2 * m * k * n
    nbytes = m * k + k * n * bits // 8 + 4 * m + 2 * 4 * n + 4 * m * n
    return ops, nbytes


def w4a8_call_from_operands(operands, result) -> Tuple[int, int]:
    """The same from the call's operand and result shapes:
    (xq [m,k], w [k, n/2 or n], sx [m,1], scale [1,n], zero [1,n]) -> [m,n]."""
    (_, (m, k)), (_, (_, cols)) = operands[0], operands[1]
    n = result[1][-1]
    bits = 4 if 2 * cols == n else 8
    return w4a8_call(m, k, n, bits)


def w4a8_roofline(calls, kind: str, peaks: dict):
    """Roofline share, in percent, of the ``w4a8_matmul`` calls of one kind
    of step program: the sum of each call's least time (its int8 operations
    over the int8 peak or its bytes over the HBM bandwidth, whichever is
    larger, from the shapes the call names) over their device time."""
    least = spent = 0.0
    for kernel, k, text, secs in calls:
        if kernel != "w4a8_matmul" or k != kind:
            continue
        head, _, rest = text.partition(" custom-call(")
        operands = shapes(rest.split("operand_layout_constraints=")[-1])
        ops, nbytes = w4a8_call_from_operands(operands, shapes(head)[0])
        least += least_time(ops, nbytes, peaks["int8_ops"],
                            peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None

"""Hardware-driven data reorder / tile selection (paper §5.1, C3).

Two solvers:

1. ``solve_cpu_tiles`` — the paper's register-constrained optimizer
   (Eq. 2-4): minimize memory-access count

       e/e_p * h/h_p * (l*e_p + l*h_p + h_p*e_p)

   s.t.  e_p + h_p + e_p*h_p <= R   and   l_p = instruction width.
   Reproduces the paper's Table 2 for the four CPU ISAs.

2. ``solve_tpu_blocks`` — the TPU adaptation: pick Pallas BlockSpec tiles
   (b_m, b_n, b_k) for an [M,K]x[K,N] matmul minimizing HBM traffic

       M/b_m * N/b_n * (b_m*b_k + b_n*b_k)*in_bytes + M*N*out_bytes

   s.t. working set (x-tile + w-tile + acc-tile) fits the VMEM budget and
   tiles are (8,128)-aligned for the MXU.  The chosen tiles parameterize
   repro/kernels/w4a8_matmul.py.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class CPUISA:
    """R counts register *elements* available to the kernel (Eq. 3 budget);
    ``output_width`` pins h_p to the ISA's natural output-vector width
    (sdot: paired 4-lane int32 accumulators -> 8; smmla 2x2 tiles x4 -> 8;
    SSE 4-lane int32 pairs -> 8; AVX512-VNNI zmm = 64 int8 lanes -> 64)."""
    name: str
    register_budget: int        # R in Eq. 3
    instruction_width: int      # l_p in Eq. 4 (elements reduced per instr)
    output_width: int           # h_p pinned by the ISA's output vector


# The four ISAs of the paper's Table 2 (NEON sdot / NEON i8mm / SSE / AVX512)
PAPER_ISAS = (
    CPUISA("armv8-sdot", register_budget=116, instruction_width=4, output_width=8),
    CPUISA("armv8-i8mm", register_budget=106, instruction_width=8, output_width=8),
    CPUISA("x86-sse", register_budget=44, instruction_width=4, output_width=8),
    CPUISA("x86-avx512", register_budget=328, instruction_width=4, output_width=64),
)

PAPER_TABLE2 = {
    "armv8-sdot": (12, 8, 4),
    "armv8-i8mm": (10, 8, 8),
    "x86-sse": (4, 8, 4),
    "x86-avx512": (4, 64, 4),
}


def memory_access_count(e: int, h: int, l: int, ep: int, hp: int) -> float:
    """Eq. 2 objective (for the [e,l]x[h,l] -> [e,h] tiled matmul)."""
    return (e / ep) * (h / hp) * (l * ep + l * hp + hp * ep)


def solve_cpu_tiles(isa: CPUISA, *, e: int = 1024, h: int = 1024,
                    l: int = 1024,
                    ep_range: Iterable[int] = range(1, 129)) -> Tuple[int, int, int]:
    """Minimize Eq. 2 s.t. the Eq. 3 register constraint
    ``e_p + h_p + e_p*h_p <= R`` with h_p pinned to the ISA output width and
    l_p = instruction width (Eq. 4).  Reproduces the paper's Table 2."""
    hp = isa.output_width
    best, best_cost = None, float("inf")
    for ep in ep_range:
        # Eq. 3: activation tile elems + weight tile elems + accumulators
        if ep + hp + ep * hp > isa.register_budget:
            continue
        c = memory_access_count(e, h, l, ep, hp)
        if c < best_cost - 1e-9:
            best_cost, best = c, (ep, hp, isa.instruction_width)
    assert best is not None
    return best


def reorder_shape_cpu(e: int, l: int, ep: int, lp: int) -> tuple:
    """Paper's CPU activation layout [e/e_p, l/l_p, e_p, l_p]."""
    return (_ceil_div(e, ep), _ceil_div(l, lp), ep, lp)


def reorder_shape_gpu(l: int, h: int, lp: int = 32) -> tuple:
    """Paper's GPU weight layout [l/l_p, h, l_p] with l_p=32 (128-bit
    vectorized 4-bit loads)."""
    return (_ceil_div(l, lp), h, lp)


# ---------------------------------------------------------------------------
# TPU analogue
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPUSpec:
    vmem_bytes: int = 16 * 2 ** 20         # ~16 MiB usable VMEM hint
    sublane: int = 8                       # second-minor tiling
    lane: int = 128                        # minor tiling / MXU edge
    mxu: int = 128


def hbm_traffic(M: int, N: int, K: int, bm: int, bn: int, bk: int,
                in_bytes: float, out_bytes: float = 4.0) -> float:
    """Bytes moved HBM->VMEM for the tiled matmul (acc stays resident)."""
    gm, gn, gk = _ceil_div(M, bm), _ceil_div(N, bn), _ceil_div(K, bk)
    x_reads = gm * gn * gk * bm * bk * in_bytes
    w_reads = gm * gn * gk * bk * bn * in_bytes
    out_writes = gm * gn * bm * bn * out_bytes
    return x_reads + w_reads + out_writes


def vmem_working_set(bm: int, bn: int, bk: int, in_bytes: float,
                     acc_bytes: float = 4.0, buffers: int = 2,
                     out_bytes: float = 4.0) -> float:
    """x-tile + w-tile + fp32 output tile (each double-buffered by the
    Pallas pipeline) + the int32/fp32 accumulator tile + the fp32 tile
    the last K step computes the output in."""
    return (buffers * ((bm * bk + bk * bn) * in_bytes + bm * bn * out_bytes)
            + bm * bn * (acc_bytes + out_bytes))


def solve_tpu_blocks(M: int, N: int, K: int, *, in_bytes: float = 1.0,
                     spec: TPUSpec = TPUSpec(),
                     vmem_fraction: float = 0.8) -> Tuple[int, int, int]:
    """Choose (b_m, b_n, b_k) minimizing HBM traffic under the VMEM budget.

    Same optimization shape as the paper's Eq. 2-4 with R -> VMEM bytes and
    instruction_width -> (8,128) tile alignment.
    """
    budget = spec.vmem_bytes * vmem_fraction
    def cands(dim, align, cap):
        out = []
        v = align
        while v <= min(dim if dim % align == 0 else dim + align, cap):
            out.append(min(v, dim))
            v *= 2
        return sorted(set(out))
    best, best_cost = None, float("inf")
    for bm in cands(M, spec.sublane, 1024):
        for bn in cands(N, spec.lane, 2048):
            for bk in cands(K, spec.lane, 4096):
                if vmem_working_set(bm, bn, bk, in_bytes) > budget:
                    continue
                c = hbm_traffic(M, N, K, bm, bn, bk, in_bytes)
                # ties: prefer MXU-square-friendly tiles, then larger b_k
                # (traffic is b_k-invariant; larger b_k = fewer grid steps)
                tie = (abs(bm - spec.mxu) + abs(bn - spec.mxu), -bk)
                if (c, tie) < (best_cost, best[3] if best else ((1 << 60), 0)):
                    best_cost, best = c, (bm, bn, bk, tie)
    assert best is not None, "no feasible tile"
    return best[:3]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)

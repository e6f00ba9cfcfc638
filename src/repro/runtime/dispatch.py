"""Kernel dispatch: one registry keyed on (op, backend, quant tag).

Model code never imports ``repro.kernels`` — every hot op (linear, rmsnorm,
decode attention, prefill attention) goes through a ``Dispatcher`` that
resolves the implementation from this registry:

  backend "tpu"        — compiled Pallas kernels (requires a TPU device)
  backend "interpret"  — the same Pallas kernels, interpret mode (CPU
                         parity/CI; numerically the kernel path)
  backend "reference"  — the pure-JAX/XLA paths (core/quantization matmul,
                         fp32 rms, models/attention reference attention)

Backend selection: the ``REPRO_BACKEND`` env var overrides everything, then
the explicit ``Dispatcher(backend=...)`` argument, then the platform
default: "tpu" when JAX's default backend is a TPU, "reference"
otherwise.  The "tpu" backend on a host without a TPU raises at
construction.  Every kernel entry declares eligibility (shape/layout); an
entry that declines its operands with ``Ineligible`` falls back per-op to
the reference path and the reason is recorded on
``dispatcher.fallbacks``.  Any other exception from a kernel entry (a
Mosaic refusal, a shape bug) propagates — a chip run never turns a kernel
failure into a quiet reference run.

A Dispatcher is trace-time static: construct one per Engine (the jitted
step closes over it), so switching backends re-jits instead of silently
reusing a stale cache.  ``REPRO_BACKEND`` is read when the Dispatcher is
constructed.

MoE expert matmuls dispatch as their own op ``"grouped_matmul"``
(``kernels/grouped_matmul.py`` behind ``PackedExpertLinear`` operands), so
their fallbacks are recorded under that key — never the generic matmul key.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.runtime import plan as planlib

Array = jax.Array

BACKENDS = ("reference", "interpret", "tpu")

_REGISTRY: Dict[Tuple[str, str, str], Callable] = {}


class Ineligible(Exception):
    """A kernel entry declined these operands; fall back to the next
    backend in the chain."""


def register(op: str, backend: str, tag: str = "*"):
    """Register one implementation under (op, backend, quant tag)."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, backend, tag)] = fn
        return fn
    return deco


def platform_backend() -> str:
    """The backend a Dispatcher gets when nothing names one: the compiled
    kernels on a TPU, the pure-JAX reference anywhere else."""
    return "tpu" if jax.default_backend() == "tpu" else "reference"


def default_backend() -> str:
    env = os.environ.get("REPRO_BACKEND", "").strip().lower()
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"REPRO_BACKEND={env!r}; expected one of {BACKENDS}")
        return env
    return platform_backend()


def _require(cond: bool, why: str) -> None:
    if not cond:
        raise Ineligible(why)


class Dispatcher:
    """Resolves every hot op to its registered implementation.

    ``plan``: an ExecutionPlan for tile lookup (optional — plan-less
    dispatch solves tiles through a module-level cache).
    """

    def __init__(self, plan: Optional[planlib.ExecutionPlan] = None,
                 backend: Optional[str] = None):
        # env override wins (validated in default_backend); the explicit
        # argument fills in only when REPRO_BACKEND is unset
        env_set = bool(os.environ.get("REPRO_BACKEND", "").strip())
        self.backend = default_backend() if env_set \
            else (backend or platform_backend())
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend {self.backend!r}; expected one of {BACKENDS}")
        if self.backend == "tpu" and jax.default_backend() != "tpu":
            raise RuntimeError(
                f"backend 'tpu' needs a TPU, but JAX's default backend is "
                f"{jax.default_backend()!r} (use 'interpret' to run the "
                f"kernels on CPU)")
        self.plan = plan
        # (op, backend, reason) notes, recorded at trace time
        self.fallbacks: List[Tuple[str, str, str]] = []

    def _chain(self) -> Tuple[str, ...]:
        if self.backend == "reference":
            return ("reference",)
        return (self.backend, "reference")

    def _call(self, op: str, tag: str, *args, **kw):
        for be in self._chain():
            fn = _REGISTRY.get((op, be, tag)) or _REGISTRY.get((op, be, "*"))
            if fn is None:
                continue
            if be == "reference":
                return fn(self, *args, **kw)    # the floor — let it raise
            try:
                return fn(self, *args, **kw)
            except Ineligible as e:
                self.fallbacks.append((op, be, str(e)))
        raise RuntimeError(f"no implementation registered for op={op!r} "
                           f"tag={tag!r} backend={self.backend!r}")

    # --- the ops model code routes through ---------------------------------
    def linear(self, x: Array, w, qcfg: q.QuantConfig,
               out_dtype=jnp.bfloat16) -> Array:
        if isinstance(w, (planlib.PackedLinear, q.QuantizedTensor)):
            tag = f"W{w.bits}A{qcfg.act_bits}"
        else:
            tag = "bf16"
        return self._call("matmul", tag, x, w, qcfg, out_dtype)

    def grouped_matmul(self, x: Array, w, qcfg: q.QuantConfig,
                       out_dtype=jnp.bfloat16) -> Array:
        """Per-expert grouped matmul: ``x [G, E, C, K] @ w[e] [K, N] ->
        [G, E, C, N]`` with one quantized weight slab per expert (``w`` a
        ``PackedExpertLinear`` or a per-layer ``[E, K, N]``
        QuantizedTensor).  Fallbacks record under the ``grouped_matmul``
        key, distinct from the generic matmul op."""
        tag = f"W{w.bits}A{qcfg.act_bits}"
        return self._call("grouped_matmul", tag, x, w, qcfg, out_dtype)

    def rmsnorm(self, x: Array, weight: Array, eps: float = 1e-5) -> Array:
        return self._call("rmsnorm", "*", x, weight, eps)

    def decode_attention(self, qh: Array, cache, pos, policy) -> Array:
        return self._call("decode_attention", "*", qh, cache, pos, policy)

    def paged_decode_attention(self, qh: Array, pool, table, base, pos,
                               policy) -> Array:
        """Decode over the paged KV pool (core/kv_pool.py): ``table`` maps
        logical to physical pages per row; ``base`` offsets ring views
        (None for full-attention pools)."""
        return self._call("paged_decode_attention", "*", qh, pool, table,
                          base, pos, policy)

    def prefill_attention(self, qh: Array, kh: Array, vh: Array, *,
                          causal: bool, window: int, policy) -> Array:
        return self._call("prefill_attention", "*", qh, kh, vh,
                          causal, window, policy)

    def paged_prefill_attention(self, qh: Array, pool, table, pos0,
                                policy) -> Array:
        """Prompt-chunk attention over the paged KV pool (core/kv_pool.py):
        the chunk's queries (absolute positions pos0 + arange) attend over
        the row's stored pages through ``table`` [1, pages_per_row]."""
        return self._call("paged_prefill_attention", "*", qh, pool, table,
                          pos0, policy)


# one default (platform-or-env) dispatcher per backend value, for call
# sites that don't thread an engine dispatcher (training, tests, examples)
_DEFAULTS: Dict[str, Dispatcher] = {}


def resolve(dispatch: Optional[Dispatcher]) -> Dispatcher:
    if dispatch is not None:
        return dispatch
    be = default_backend()
    if be not in _DEFAULTS:
        _DEFAULTS[be] = Dispatcher(backend=be)
    return _DEFAULTS[be]


# ===========================================================================
# Reference entries (the floor every chain ends on)
# ===========================================================================

@register("matmul", "reference")
def _matmul_reference(disp, x, w, qcfg, out_dtype):
    if isinstance(w, planlib.PackedLinear):
        w = planlib.unpack_linear(w)
    if isinstance(w, q.QuantizedTensor):
        return q.quant_matmul(x, w, qcfg, out_dtype=out_dtype)
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).astype(out_dtype)


@register("grouped_matmul", "reference")
def _grouped_matmul_reference(disp, x, w, qcfg, out_dtype):
    """Per-expert quant_matmul vmap over the expert axis (x axis -3, w
    axis -3 of the logical [..., E, K, N] table)."""
    if isinstance(w, planlib.PackedExpertLinear):
        w = planlib.unpack_expert_linear(w)
    return jax.vmap(
        lambda xi, wi: q.quant_matmul(xi, wi, qcfg, out_dtype=out_dtype),
        in_axes=(-3, -3), out_axes=-3)(x, w)


@register("rmsnorm", "reference")
def _rmsnorm_reference(disp, x, weight, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


@register("decode_attention", "reference")
def _decode_attention_reference(disp, qh, cache, pos, policy):
    from repro.models import attention as A     # lazy: models import us
    return A.decode_attention_ref(qh, cache, pos, policy=policy)


@register("paged_decode_attention", "reference")
def _paged_decode_attention_reference(disp, qh, pool, table, base, pos,
                                      policy):
    from repro.core import kv_pool as KP
    return KP.paged_decode_attention_ref(qh, pool, table, base, pos,
                                         policy=policy)


@register("prefill_attention", "reference")
def _prefill_attention_reference(disp, qh, kh, vh, causal, window, policy):
    from repro.models import attention as A     # lazy: models import us
    return A.flash_attention(qh, kh, vh, causal=causal, window=window,
                             policy=policy)


@register("paged_prefill_attention", "reference")
def _paged_prefill_attention_reference(disp, qh, pool, table, pos0, policy):
    from repro.core import kv_pool as KP
    return KP.paged_prefill_attention_ref(qh, pool, table, pos0,
                                          policy=policy)


# ===========================================================================
# Pallas entries ("tpu" = compiled, "interpret" = same kernels on CPU)
# ===========================================================================

def _kernel_matmul(disp, x, w, qcfg, out_dtype, *, interpret):
    from repro.kernels import w4a8_matmul as WM
    if isinstance(w, q.QuantizedTensor):
        _require(w.data.ndim == 2, "stacked/expert weights: reference path")
        w = planlib.pack_linear(w)  # plan-less caller: repack inline
    _require(w.data.ndim == 2, "stacked/expert weights: reference path")
    _require(w.scale.shape[-2] == 1,
             "group-wise scales make the integer correction group-dependent")
    lead, K = x.shape[:-1], x.shape[-1]
    _require(K == w.k, f"reduction dim {K} != weight {w.k}")
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    mp = (disp.plan.matmul_plan(w.k, w.n, w.bits) if disp.plan is not None
          else planlib.matmul_plan(w.k, w.n, w.bits))
    bm, bn, bk = mp.blocks(M)
    xq, sx = q.quantize_activations(x2)
    Mp = -(-M // bm) * bm
    if Mp != M or mp.kp != K:
        xq = jnp.pad(xq, ((0, Mp - M), (0, mp.kp - K)))
        sx = jnp.pad(sx, ((0, Mp - M), (0, 0)), constant_values=1.0)
    y = WM.w4a8_matmul(xq, sx, w.data, w.scale[0], w.zero[0], bits=w.bits,
                       blocks=(min(bm, Mp), bn, bk), interpret=interpret)
    return y[:M, :w.n].reshape(*lead, w.n).astype(out_dtype)


def _kernel_grouped_matmul(disp, x, w, qcfg, out_dtype, *, interpret):
    from repro.kernels import grouped_matmul as GM
    if not isinstance(w, planlib.PackedExpertLinear):
        _require(isinstance(w, q.QuantizedTensor) and w.data.ndim == 3,
                 "per-layer [E, K, N] expert table expected")
        w = planlib.pack_expert_linear(w)   # plan-less caller: repack inline
    _require(w.data.ndim == 3,
             "expert table must be layer-sliced to [E, Kp, Np]")
    _require(w.scale.shape[-2] == 1,
             "group-wise scales make the integer correction group-dependent")
    _require(x.ndim == 4, "grouped matmul wants [G, E, C, K] activations")
    G, E, C, K = x.shape
    _require(E == w.data.shape[0], f"expert axis {E} != weight {w.data.shape[0]}")
    _require(K == w.k, f"reduction dim {K} != weight {w.k}")
    if G * C == 0:                          # empty capacity: no rows at all
        return jnp.zeros((G, E, C, w.n), out_dtype)
    x2 = jnp.moveaxis(x, 1, 0).reshape(E, G * C, K)
    M = G * C
    mp = (disp.plan.matmul_plan(w.k, w.n, w.bits) if disp.plan is not None
          else planlib.matmul_plan(w.k, w.n, w.bits))
    bm, bn, bk = mp.blocks(M)
    xq, sx = q.quantize_activations(x2)
    Mp = -(-M // bm) * bm
    if Mp != M or mp.kp != K:
        xq = jnp.pad(xq, ((0, 0), (0, Mp - M), (0, mp.kp - K)))
        sx = jnp.pad(sx, ((0, 0), (0, Mp - M), (0, 0)), constant_values=1.0)
    y = GM.grouped_matmul(xq, sx, w.data, w.scale[:, 0], w.zero[:, 0],
                          bits=w.bits, blocks=(min(bm, Mp), bn, bk),
                          interpret=interpret)
    y = y[:, :M, :w.n].reshape(E, G, C, w.n)
    return jnp.moveaxis(y, 0, 1).astype(out_dtype)


def _kernel_rmsnorm(disp, x, weight, eps, *, interpret):
    from repro.kernels import rmsnorm as RN
    return RN.rmsnorm(x, weight, eps=eps, interpret=interpret)


def _decode_block(s: int, cap: int = 512) -> int:
    for b in range(min(cap, s), 0, -1):
        if s % b == 0:
            return b
    return s


def _kernel_decode_attention(disp, qh, cache, pos, policy, *, interpret):
    from repro.kernels import quant_attention as QA
    B, T = qh.shape[:2]
    _require(T == 1, "decode kernel attends one query token")
    _require(cache.window == 0, "ring-buffer (windowed) cache: reference path")
    _require(cache.key_bits == 8, "int4 keys: reference path")
    lengths = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    out = QA.quant_decode_attention(
        qh[:, 0], cache.k_q, cache.k_scale, cache.k_zero, cache.v, lengths,
        block_s=_decode_block(cache.k_q.shape[1]), interpret=interpret)
    return out[:, None].astype(policy.compute_dtype)


def _kernel_paged_decode_attention(disp, qh, pool, table, base, pos, policy,
                                   *, interpret):
    from repro.kernels import quant_attention as QA
    B, T = qh.shape[:2]
    _require(T == 1, "decode kernel attends one query token")
    _require(pool.key_bits == 8, "int4 keys: reference path")
    lengths = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    base_arr = jnp.zeros((B,), jnp.int32) if base is None \
        else jnp.asarray(base, jnp.int32)
    out = QA.paged_quant_decode_attention(
        qh[:, 0], pool.k_q, pool.k_scale, pool.k_zero, pool.v, table,
        base_arr, lengths, window=pool.window, interpret=interpret)
    return out[:, None].astype(policy.compute_dtype)


def _kernel_prefill_attention(disp, qh, kh, vh, causal, window, policy, *,
                              interpret):
    from repro.kernels import flash_prefill as FP
    out = FP.flash_prefill_attention(qh, kh, vh, causal=causal,
                                     window=window, interpret=interpret)
    return out.astype(policy.compute_dtype)


def _kernel_paged_prefill_attention(disp, qh, pool, table, pos0, policy, *,
                                    interpret):
    from repro.kernels import flash_prefill as FP
    _require(pool.key_bits == 8, "int4 keys: reference path")
    _require(pool.window == 0,
             "windowed layers prefill chunk-locally, not via the table")
    B = qh.shape[0]
    pos0_arr = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32).reshape(-1), (B,))
    out = FP.paged_flash_prefill_attention(
        qh, pool.k_q, pool.k_scale, pool.k_zero, pool.v, table, pos0_arr,
        interpret=interpret)
    return out.astype(policy.compute_dtype)


for _be, _interp in (("interpret", True), ("tpu", False)):
    for _tag in ("W4A8", "W8A8"):
        register("matmul", _be, _tag)(
            lambda d, x, w, c, o, _i=_interp: _kernel_matmul(
                d, x, w, c, o, interpret=_i))
        register("grouped_matmul", _be, _tag)(
            lambda d, x, w, c, o, _i=_interp: _kernel_grouped_matmul(
                d, x, w, c, o, interpret=_i))
    register("rmsnorm", _be)(
        lambda d, x, w, e, _i=_interp: _kernel_rmsnorm(
            d, x, w, e, interpret=_i))
    register("decode_attention", _be)(
        lambda d, qh, c, p, pol, _i=_interp: _kernel_decode_attention(
            d, qh, c, p, pol, interpret=_i))
    register("paged_decode_attention", _be)(
        lambda d, qh, c, t, b, p, pol, _i=_interp:
            _kernel_paged_decode_attention(d, qh, c, t, b, p, pol,
                                           interpret=_i))
    register("prefill_attention", _be)(
        lambda d, qh, kh, vh, ca, w, pol, _i=_interp: _kernel_prefill_attention(
            d, qh, kh, vh, ca, w, pol, interpret=_i))
    register("paged_prefill_attention", _be)(
        lambda d, qh, c, t, p, pol, _i=_interp:
            _kernel_paged_prefill_attention(d, qh, c, t, p, pol,
                                            interpret=_i))

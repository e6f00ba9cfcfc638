#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip, at full model width.

    python3 chip_smoke.py

Builds full-width qwen2-7b (28 layers, d_model 3584, 28/4 heads, d_ff
18944, vocab 151646; random weights from a seed, W4A8 layers and an int8
lm_head) on the compiled Pallas kernels (dispatch backend ``tpu``), warms
an ``EngineLoop`` up, wraps it in the gateway's ``EngineService`` and
streams a handful of mixed requests through it: prompts from a few tokens
to more than one prefill chunk, more requests than decode slots, greedy
and temperature sampling.  Then it checks the kernel path against the
pure-JAX reference backend on the same packed parameters (last-position
prefill logits of two prompts plus one decode step), at full depth and
with the model cut to its first layers.

Fails — non-zero exit, no result line — when JAX finds no TPU, when a
kernel falls back to the reference path, when a step graph compiles after
warmup, when a request comes back short, or when the logits at some depth
drift from the reference by more than ``LOGITS_RTOL`` and more than
``FLOOR_FACTOR`` times the reference's own noise floor.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Wall times printed on the way are smoke timings of one cold run, not
benchmark numbers.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.configs import registry                  # noqa: E402
from repro.models import transformer as T           # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import engine as E               # noqa: E402
from repro.serving import gateway as G              # noqa: E402
from repro.serving import sampling as SM            # noqa: E402

ARCH = "qwen2-7b"
SEED = 0
SLOTS = 4
MAX_SEQ = 256
MAX_NEW = 16
# mixed lengths: a few tokens up to more than one prefill chunk (64), six
# requests over four slots so finished rows hand their slot on
PROMPT_LENS = (5, 12, 33, 64, 97, 150)
PROBE_LENS = (11, 16)            # both prefill as one 16-token chunk
# The kernel-vs-reference check runs on the model cut to its first layers
# as well as at full depth, on the same packed weights.  W4A8 re-quantizes
# every activation to int8, so one bf16 ulp of difference anywhere can
# flip int8 roundings downstream, and these random weights (std 0.02 at
# widths of 3584 and 18944, a gain above one per linear) amplify that
# with depth.  The reference's own spread under a one-ulp input nudge is
# measured on the same rows (the noise floor); a depth passes when the
# kernels stay within LOGITS_RTOL or FLOOR_FACTOR times that floor.  A
# layout or indexing bug moves the logits by O(1).
PROBE_DEPTHS = (1, 4)
LOGITS_RTOL = 2e-2
FLOOR_FACTOR = 2.0
STREAM_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def logits_probe(loop: E.EngineLoop, prompts, decode_tokens=None,
                 nudge: bool = False):
    """Last-position prefill logits of each prompt (one row each, one
    chunk) and one decode step over those rows, through ``loop``'s own
    jitted step graphs on a fresh paged cache.  ``nudge`` scales every
    input embedding by (1 + 2^-8), about one bf16 ulp.  Returns (prefill
    logits, decode logits, decode tokens), logits cut to the real
    vocabulary."""
    eng, cfg, geom = loop.eng, loop.cfg, loop.geom

    def embed(ids):
        x = eng.embed(ids)
        if nudge:
            x = (x.astype(jnp.float32) * (1 + 2.0 ** -8)).astype(x.dtype)
        return x

    n, V = len(prompts), cfg.vocab_size
    cache = T.init_paged_cache(cfg, loop.max_slots, eng.max_seq, geom)
    ppr = geom.pages_per_row
    table = np.full((loop.max_slots, ppr), geom.trash_page, np.int32)
    table[:n] = np.arange(n * ppr, dtype=np.int32).reshape(n, ppr)
    cache["table"] = jnp.asarray(table)
    prefill = []
    for row, toks in enumerate(prompts):
        c = loop._next_chunk(len(toks))
        check(c >= len(toks), "probe prompts must fit one chunk")
        ids = np.zeros((1, c), np.int64)
        ids[0, :len(toks)] = toks
        lg, cache = loop._chunk(eng.params, embed(ids), cache,
                                jnp.int32(row), jnp.int32(0),
                                jnp.int32(len(toks) - 1), None)
        prefill.append(np.asarray(lg[0, :V], np.float32))
    cache["pos"] = cache["pos"].at[:n].set(
        jnp.asarray([len(t) for t in prompts], jnp.int32))
    if decode_tokens is None:
        decode_tokens = [int(np.argmax(p)) for p in prefill]
    b = min(x for x in loop.buckets if x >= n)
    ids = np.zeros((b, 1), np.int64)
    ids[:n, 0] = decode_tokens
    logits, _ = loop._decode_b(
        eng.params, embed(ids), cache, None,
        jnp.arange(b) < n, jnp.arange(b, dtype=jnp.int32),
        jnp.zeros_like(loop.logits))
    decode = [np.asarray(logits[i, :V], np.float32) for i in range(n)]
    return prefill, decode, decode_tokens


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def cut_depth(eng: E.Engine, layers: int):
    """``eng``'s config and packed params cut to their first ``layers``
    layers; the lm_head and norms are the same arrays, so no second model
    is built."""
    cfg = eng.cfg
    if layers == cfg.num_layers:
        return cfg, eng.params
    (stack,) = eng.params["stacks"]
    return (dataclasses.replace(cfg, num_layers=layers),
            dict(eng.params,
                 stacks=(jax.tree.map(lambda x: x[:layers], stack),)))


def compare(eng: E.Engine, loop: E.EngineLoop, probe) -> list:
    """Kernel-vs-reference logits at each depth of ``PROBE_DEPTHS``, with
    the reference's own one-ulp noise floor on the same rows.  Returns
    [(depth, errs, floor)]."""
    out = []
    full = eng.cfg.num_layers
    for depth in sorted({d for d in PROBE_DEPTHS if d < full} | {full}):
        cfg, params = cut_depth(eng, depth)
        loops = {"reference": E.EngineLoop(E.Engine(
            cfg, params, eng.embedding, max_seq=MAX_SEQ,
            backend="reference"), max_slots=SLOTS)}
        loops["tpu"] = (loop if depth == full else
                        E.EngineLoop(E.Engine(cfg, params, eng.embedding,
                                              max_seq=MAX_SEQ,
                                              backend=eng.dispatch.backend),
                                     max_slots=SLOTS))
        k_pre, k_dec, toks = logits_probe(loops["tpu"], probe)
        r_pre, r_dec, _ = logits_probe(loops["reference"], probe,
                                       decode_tokens=toks)
        n_pre, n_dec, _ = logits_probe(loops["reference"], probe,
                                       decode_tokens=toks, nudge=True)
        check(all(np.isfinite(x).all() for x in k_pre + k_dec),
              f"non-finite logits at depth {depth}")
        errs = [rel_l2(a, b) for a, b in zip(k_pre + k_dec, r_pre + r_dec)]
        floor = [rel_l2(a, b) for a, b in zip(n_pre + n_dec, r_pre + r_dec)]
        log(f"depth {depth}: logits rel L2 vs reference (prefill "
            f"x{len(probe)}, decode x{len(probe)}) {errs}; reference noise "
            f"floor (inputs nudged one bf16 ulp) {floor}")
        for lp in loops.values():
            if lp is not loop:
                lp.close()
        check(not loops["tpu"].eng.dispatch.fallbacks,
              f"fallback during the depth-{depth} probe")
        out.append((depth, errs, floor))
    return out


def run(cfg, backend: str, flash_dir: str) -> dict:
    """Serve ``cfg`` on ``backend`` through the gateway's engine thread,
    then compare against the reference backend.  Raises on any failure."""
    t0 = time.perf_counter()
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(SEED), max_seq=MAX_SEQ,
                         flash_dir=flash_dir, backend=backend)
    jax.block_until_ready(eng.params)
    check(eng.dispatch.backend == backend,
          f"dispatcher resolved {eng.dispatch.backend!r}, not {backend!r}")
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}; quant {eng.plan.quant_tag} "
        f"(lm_head int{cfg.quant.lm_head_bits}); backend {backend}")
    log(f"weights on device: {weight_bytes} bytes; init {time.perf_counter() - t0:.1f}s "
        f"(smoke timing); peak_bytes_in_use after init {peak_bytes()}")

    loop = E.EngineLoop(eng, max_slots=SLOTS)
    rep = loop.warmup()
    log(f"warmup: {rep['graphs']} graphs (decode buckets "
        f"{rep['decode_buckets']}, prefill chunks {rep['chunk_sizes']}) in "
        f"{rep['warmup_s']:.1f}s (smoke timing)")

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in PROMPT_LENS]
    t1 = time.perf_counter()
    service = G.EngineService(loop).start()
    try:
        streams = []
        for i, toks in enumerate(prompts):
            sp = (SM.SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW)
                  if i % 2 == 0 else
                  SM.SamplingParams(temperature=0.8, top_k=50,
                                    max_new_tokens=MAX_NEW))
            streams.append(service.submit(toks, sampling=sp))
        outs = [s.collect(timeout=STREAM_TIMEOUT_S) for s in streams]
    finally:
        service.close()
    serve_s = time.perf_counter() - t1
    for n, toks in zip(PROMPT_LENS, outs):
        check(len(toks) == MAX_NEW,
              f"prompt of {n} tokens returned {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"token out of vocabulary: {toks}")
    log(f"served {len(outs)} requests on {SLOTS} slots in {serve_s:.1f}s "
        f"(smoke timing); tokens per request "
        f"{[len(t) for t in outs]} for prompt lengths {list(PROMPT_LENS)}")
    s = eng.stats
    log(f"prefill chunks {s.prefill_chunks}, prefill tokens "
        f"{s.prefill_tokens}, decode tokens {s.decode_tokens}")
    recompiles = s.recompiles_after_warmup
    log(f"recompiles_after_warmup {recompiles}")
    check(recompiles == 0, f"{recompiles} step graphs compiled after warmup")
    log(f"dispatch fallbacks {eng.dispatch.fallbacks}")
    check(not eng.dispatch.fallbacks,
          f"kernels fell back to the reference path: {eng.dispatch.fallbacks}")

    # kernels vs the pure-JAX reference on the same packed parameters
    probe = [rng.integers(1, cfg.vocab_size, size=n).tolist()
             for n in PROBE_LENS]
    results = compare(eng, loop, probe)
    for depth, errs, floor in results:
        bound = max(LOGITS_RTOL, FLOOR_FACTOR * max(floor))
        log(f"depth {depth}: max logits rel L2 {max(errs)} (bound {bound})")
        check(max(errs) <= bound,
              f"depth {depth}: kernel logits drift {max(errs)} > {bound} "
              f"from reference")
    check(not eng.dispatch.fallbacks, "fallback during the logits probe")
    log(f"peak_bytes_in_use {peak_bytes()}")
    return {"logits": results, "recompiles": recompiles}


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_flash_") as flash:
        run(registry.get(ARCH), "tpu", flash)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

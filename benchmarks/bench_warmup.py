"""Bucketed pre-compiled step graphs: warmup cost and what it buys.

Four measurements on a ``max_slots=8`` engine:

  * warmup wall-clock — the startup price of tracing every bucket/chunk
    graph (one jitted decode per ladder bucket, one prefill graph per
    pow2 chunk size) before traffic arrives;
  * cold vs warm first-token TTFT — a request hitting an un-warmed loop
    pays the chunk + decode compilations inside its TTFT; a warmed loop
    serves the same request from cache;
  * decode tokens/s at B=1/2/8 — bucketed dispatch gathers the active
    rows into the smallest covering bucket, so low-concurrency decode
    (the dominant edge regime) runs matmuls at bucket shape instead of
    max_slots.  The B=1 speedup vs a bucketing-disabled loop is the perf
    headline (``bucket_b1_speedup``);
  * the churny-concurrency trace (live rows 1 -> 8 -> 2 -> 5) — the
    compile-event counter must not move after warmup
    (``recompiles_after_warmup == 0``, the CI ceiling gate).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import numpy as np

from benchmarks.common import emit, is_smoke, record_fallbacks, summary
from repro.configs import registry
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

SLOTS = 8


def _reqs(cfg, n, p_len, d, uid0=0, seed=5, sp=None):
    rng = np.random.default_rng(seed)
    return [Request(uid=uid0 + i,
                    prompt_tokens=list(rng.integers(1, cfg.vocab_size,
                                                    size=p_len)),
                    max_new_tokens=d, sampling=sp)
            for i in range(n)]


def first_token_ttft(loop, req) -> float:
    """Submit one request into an idle loop and step until its first
    token lands — arrival-to-first-token, compiles included."""
    t0 = time.perf_counter()
    loop.submit(req)
    while True:
        for ev in loop.step():
            if ev.uid == req.uid:
                return time.perf_counter() - t0


def decode_tps(loop, cfg, b, d, uid0, sp) -> float:
    """Decode tokens/s at constant batch ``b`` over the run's wall clock
    (its 8-token prompts are one chunk each)."""
    s = loop.eng.stats
    tok0, t0 = s.decode_tokens, time.perf_counter()
    loop.run(_reqs(cfg, b, 8, d, uid0=uid0), sp)
    return (s.decode_tokens - tok0) / max(time.perf_counter() - t0, 1e-9)


def churny_trace(loop, cfg, sp, uid0) -> None:
    """Live-row churn 1 -> 8 -> 2 -> 5: one long-running request, a burst
    to full occupancy, a drain back to a couple of survivors, then a
    partial refill — every bucket transition the ladder has."""
    reqs = (_reqs(cfg, 1, 8, 40, uid0=uid0)           # lone row
            + _reqs(cfg, 7, 8, 10, uid0=uid0 + 1)     # burst to 8
            + _reqs(cfg, 3, 8, 8, uid0=uid0 + 8))     # refill to ~5
    arrivals = [0] + [6] * 7 + [24] * 3
    loop.run(reqs, sp, arrivals=arrivals)


def main() -> None:
    smoke = is_smoke()
    d_meas = 12 if smoke else 32
    cfg = registry.reduced(registry.get("qwen2-7b"))
    sp = SM.SamplingParams(temperature=0.0, max_new_tokens=64)
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(0), max_seq=64)

    # --- cold TTFT: a fresh loop, no warmup — the request pays the
    # chunk-graph and decode-graph compilations inside its TTFT
    cold_loop = E.EngineLoop(eng, max_slots=SLOTS)
    ttft_cold = first_token_ttft(cold_loop, _reqs(cfg, 1, 12, 4, sp=sp)[0])
    cold_loop.drain()
    cold_loop.close()

    # --- warmup wall-clock + warm TTFT on a fresh loop
    loop = E.EngineLoop(eng, max_slots=SLOTS)
    rep = loop.warmup()
    ttft_warm = first_token_ttft(loop, _reqs(cfg, 1, 12, 4, uid0=50,
                                             sp=sp)[0])
    loop.drain()
    emit("warmup_wall", rep["warmup_s"] * 1e6,
         f"{rep['graphs']} graphs buckets={rep['decode_buckets']} "
         f"chunks={rep['chunk_sizes']}")
    emit("ttft_cold_vs_warm", ttft_cold * 1e6,
         f"cold={ttft_cold * 1e3:.0f}ms warm={ttft_warm * 1e3:.0f}ms "
         f"({ttft_cold / max(ttft_warm, 1e-9):.1f}x)")
    summary("warmup_s", rep["warmup_s"])
    summary("warmup_graphs", rep["graphs"])
    summary("ttft_cold_s", ttft_cold)
    summary("ttft_warm_s", ttft_warm)

    # --- bucketed decode tokens/s per bucket (warmed: measured runs hit
    # only cached graphs)
    tps = {}
    for i, b in enumerate((1, 2, 8)):
        tps[b] = decode_tps(loop, cfg, b, d_meas, 100 + 20 * i, sp)
        emit(f"decode_b{b}_bucketed", 1e6 / max(tps[b], 1e-9),
             f"{tps[b]:.1f} tok/s at live batch {b} (slots={SLOTS})")
        summary(f"decode_tps_b{b}", tps[b])

    # --- the full-batch baseline: bucketing off, every decode step runs
    # at max_slots shape no matter how many rows are live
    base = E.EngineLoop(eng, max_slots=SLOTS, bucketing=False)
    base.warmup()
    tps_full = decode_tps(base, cfg, 1, d_meas, 200, sp)
    base.close()
    speedup = tps[1] / max(tps_full, 1e-9)
    emit("decode_b1_fullbatch", 1e6 / max(tps_full, 1e-9),
         f"{tps_full:.1f} tok/s; bucketed B=1 speedup {speedup:.2f}x")
    summary("decode_tps_b1_fullbatch", tps_full)
    summary("bucket_b1_speedup", speedup)

    # --- churny concurrency: the zero-recompiles headline gate
    churny_trace(loop, cfg, sp, 300)
    emit("churny_recompiles", 0.0,
         f"recompiles_after_warmup={eng.stats.recompiles_after_warmup} "
         f"compile_events={eng.stats.compile_events}")
    summary("recompiles_after_warmup", eng.stats.recompiles_after_warmup)
    summary("compile_events", eng.stats.compile_events)
    record_fallbacks("warmup", eng.dispatch)
    loop.close()


if __name__ == "__main__":
    main()

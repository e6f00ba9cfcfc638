"""Flash->DRAM weight streaming (PR 8): steady-state decode throughput
with the packed weights held under a DRAM budget.

Three weight-DRAM fractions of the same model: 1.0 (all resident — the
baseline), 0.6 and 0.35 (the stack streams per layer group through the
double-buffered DRAM ring, prefetching group i+1 while group i computes).
Greedy outputs must match the all-resident run bitwise; the summary
records tokens/s per fraction, the 0.6 fraction's relative throughput,
the prefetch hit rate, and the stall fraction of decode time (summary
keys ``weight_stream_hit_rate`` / ``weight_stream_equal_output`` gate in
compare_bench.py).

The bench model is a mid-size variant of ``qwen1.5-110b@tiny`` — large
enough that per-group compute dominates the split-step dispatch overhead,
small enough for the CI smoke job.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import numpy as np

from benchmarks.common import emit, is_smoke, record_fallbacks, summary
from repro.configs import registry
from repro.runtime import plan as RP
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

FRACTIONS = (1.0, 0.6, 0.35)


def _bench_cfg():
    base = registry.get("qwen1.5-110b@tiny")
    if is_smoke():
        return base
    return dataclasses.replace(base, name="qwen1.5-110b-bench",
                               d_model=512, d_ff=2048, num_layers=8,
                               vocab_size=2048)


def _trace(cfg, n, max_new):
    rng = np.random.default_rng(17)
    return [Request(uid=i,
                    prompt_tokens=list(rng.integers(
                        1, cfg.vocab_size, size=int(rng.integers(4, 12)))),
                    max_new_tokens=max_new,
                    sampling=SM.SamplingParams(temperature=0.0))
            for i in range(n)]


def _run(cfg, frac, n_req, max_new):
    root = tempfile.mkdtemp(prefix="bench_wstream_")
    try:
        eng = E.build_engine(cfg, max_seq=64, flash_dir=root)
        head = (RP._tree_nbytes(eng.params["final_norm"])
                + RP._tree_nbytes(eng.params["lm_head"]))
        stacks = sum(RP._tree_nbytes(s) for s in eng.params["stacks"])
        if frac < 1.0:
            del eng
            eng = E.build_engine(
                cfg, max_seq=64, flash_dir=root,
                weight_dram_budget_bytes=head + int(frac * stacks))
            assert eng.weight_policy.active, frac
        loop = E.EngineLoop(eng, max_slots=4, prefill_chunk=16)
        loop.warmup()
        reqs = _trace(cfg, n_req, max_new)
        d0, t0 = eng.stats.decode_tokens, time.perf_counter()
        loop.run(reqs)
        wall = time.perf_counter() - t0
        toks = eng.stats.decode_tokens - d0
        outs = [tuple(r.generated) for r in reqs]
        stats = {
            "tps": toks / wall if wall else 0.0,
            "wall_s": wall,
            "hit_rate": eng.stats.weight_stream_hit_rate,
            "stall_s": eng.stats.weight_stall_s,
            "dram_weight_bytes": eng.stats.dram_weight_bytes,
            "recompiles": eng.stats.recompiles_after_warmup,
        }
        record_fallbacks("bench_weight_stream", eng.dispatch)
        loop.close()
        return outs, stats
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    cfg = _bench_cfg()
    n_req, max_new = (6, 8) if is_smoke() else (8, 24)
    results = {}
    for frac in FRACTIONS:
        outs, st = _run(cfg, frac, n_req, max_new)
        results[frac] = (outs, st)
        emit(f"weight_stream_frac{frac:g}_decode",
             1e6 / st["tps"] if st["tps"] else 0.0,
             f"{st['tps']:.1f} tok/s hit={st['hit_rate']:.3f} "
             f"stall={st['stall_s'] * 1e3:.1f}ms "
             f"dramW={st['dram_weight_bytes'] / 1024:.0f}KiB "
             f"recompiles={st['recompiles']}")

    ref_outs, ref = results[1.0]
    equal = all(results[f][0] == ref_outs for f in FRACTIONS)
    s06 = results[0.6][1]
    stall_frac = (s06["stall_s"] / s06["wall_s"]
                  if s06["wall_s"] else 0.0)
    summary("weight_stream_tps_frac10", ref["tps"])
    summary("weight_stream_tps_frac06", s06["tps"])
    summary("weight_stream_tps_frac035", results[0.35][1]["tps"])
    summary("weight_stream_tps_frac06_rel",
            s06["tps"] / ref["tps"] if ref["tps"] else 0.0)
    summary("weight_stream_hit_rate",
            min(results[f][1]["hit_rate"] for f in (0.6, 0.35)))
    summary("weight_stream_stall_frac", stall_frac)
    summary("weight_stream_equal_output", 1.0 if equal else 0.0)
    emit("weight_stream_summary", 0.0,
         f"frac06 {s06['tps'] / ref['tps']:.2f}x of all-DRAM, "
         f"stall_frac={stall_frac:.3f}, equal={equal}")


if __name__ == "__main__":
    import benchmarks.common  # noqa: F401  (path bootstrap via run.py)
    main()

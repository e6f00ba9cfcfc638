"""Benchmark harness: one module per paper table/figure.

Each emits ``name,us_per_call,derived`` CSV rows:
  bench_prefill_decode       — Fig. 5 (quantization-path speed comparison)
  bench_kv_flash             — Fig. 2 (DRAM / Flash / prefetch / exceeding)
  bench_tile_sizes           — Table 2 (register solver) + TPU BlockSpec solver
  bench_lora_order           — Table 3 (LoRA computation order)
  bench_load_balance         — Fig. 4 (balanced vs uniform workload)
  bench_param_breakdown      — Table 1 (+ §4.1 Flash-embedding arithmetic)
  bench_quant_accuracy       — §4.2 (quantization error by scheme)
  bench_geometry             — §5.4 (Region fusion memory-op reduction)
  bench_continuous_batching  — continuous vs slot-synchronous serving
  bench_gateway              — streaming gateway goodput under Poisson load
  bench_warmup               — bucketed step graphs: warmup cost, cold vs
                               warm TTFT, B=1 speedup, zero-recompile gate
  bench_weight_stream        — Flash→DRAM weight streaming: tok/s at
                               1.0/0.6/0.35 weight-DRAM fractions, stall
                               fraction, prefetch hit rate, bitwise gate
  bench_moe                  — grouped expert matmul kernel vs reference +
                               router-aware per-expert streaming: hit
                               rate, bytes saved, bitwise gate
  bench_recurrent_prefill    — chunked vs whole-prompt prefill on a
                               hybrid recurrent model: TTFT, peak
                               transient bytes, bitwise gate

Flags:
  --smoke        reduced configurations (CI benchmark-smoke job)
  --json PATH    dump all emitted rows as a JSON artifact
  --only SUBSTR  run only modules whose name contains SUBSTR
"""
import argparse
import importlib
import json
import os
import platform
import sys
import traceback

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

MODULES = [
    "benchmarks.bench_param_breakdown",
    "benchmarks.bench_tile_sizes",
    "benchmarks.bench_geometry",
    "benchmarks.bench_lora_order",
    "benchmarks.bench_load_balance",
    "benchmarks.bench_quant_accuracy",
    "benchmarks.bench_prefill_decode",
    "benchmarks.bench_continuous_batching",
    "benchmarks.bench_gateway",
    "benchmarks.bench_warmup",
    # last: these build whole engines, and their jit/alloc churn must not
    # perturb the throughput numbers above
    "benchmarks.bench_weight_stream",
    "benchmarks.bench_moe",
    "benchmarks.bench_recurrent_prefill",
    "benchmarks.bench_kv_flash",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced benchmark configurations")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write emitted rows as JSON")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only modules matching SUBSTR")
    args = ap.parse_args()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from benchmarks import common
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for mod in MODULES:
        if args.only and args.only not in mod:
            continue
        try:
            importlib.import_module(mod).main()
        except Exception:
            failed.append(mod)
            traceback.print_exc()
    if args.json:
        # wall-clock numbers are only comparable across runs on similar
        # hosts; record enough to tell a hardware delta from a regression
        host = {"cpus": os.cpu_count(), "machine": platform.machine(),
                "python": platform.python_version()}
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "failed": failed,
                       "host": host, "rows": common.ROWS,
                       "fallbacks": common.FALLBACKS}, f, indent=2)
        print(f"[run] wrote {len(common.ROWS)} rows "
              f"({len(common.FALLBACKS)} dispatch fallbacks) to {args.json}",
              file=sys.stderr)
        # repo-root trajectory artifact: headline numbers per PR
        bench_path = os.path.join(_ROOT, "BENCH_pr10.json")
        with open(bench_path, "w") as f:
            json.dump({"suite": "mnn-llm-repro", "pr": 10,
                       "smoke": args.smoke, "host": host,
                       "summary": common.SUMMARY,
                       "fallbacks": common.FALLBACKS}, f, indent=2)
        print(f"[run] wrote summary to {bench_path}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Serving driver: batched requests against a quantized engine — or the
streaming HTTP gateway.

Continuous batching (default): step-driven EngineLoop with per-slot KV
management — requests join/leave the decode batch without draining it.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --requests 8 --max-new 16 --slots 4

``--reduced`` (the default) cuts the model to smoke width for CPU runs;
``--no-reduced`` serves the published width, e.g. on one TPU v5e:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --no-reduced \
      --requests 4 --slots 4

The dispatch backend follows the platform (compiled Pallas kernels on a
TPU, the pure-JAX reference elsewhere; ``REPRO_BACKEND`` overrides), and
compiles persist in JAX's compilation cache (``JAX_COMPILATION_CACHE_DIR``
or ``<repo>/.jax_cache``).

HTTP gateway mode (--http PORT): OpenAI-style ``POST /v1/completions``
with ``"stream": true`` SSE token streaming, ``GET /healthz`` and
``GET /v1/stats``, over the incremental submit/step EngineLoop API:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --http 8080 --slots 4 --max-queue 64

  curl -N http://127.0.0.1:8080/v1/completions -d \
      '{"prompt": "hello", "max_tokens": 16, "stream": true}'

Legacy slot-synchronous path: --no-continuous (the paper's two-phase
generate; kept as the benchmark baseline).
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from repro.configs import registry
from repro.runtime import dispatch as RD
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request, balance_requests, makespan, uniform_requests


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="cut the model to smoke width (CPU runs); "
                         "--no-reduced serves the published width")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="continuous batching (EngineLoop) vs the legacy "
                         "slot-synchronous two-phase generate")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-batch rows (continuous mode)")
    ap.add_argument("--preempt-patience", type=int, default=0,
                    help=">0: evict the longest-running request after a "
                         "queued request waits this many steps")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the streaming HTTP gateway on PORT "
                         "instead of replaying a trace")
    ap.add_argument("--host", default="127.0.0.1",
                    help="gateway bind address (with --http)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="gateway backpressure: waiting requests beyond "
                         "this bound are rejected with HTTP 429")
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pre-trace every bucketed decode / prefill-chunk "
                         "graph before serving (gateway /healthz answers "
                         "503 while warming); --no-warmup compiles lazily")
    ap.add_argument("--weight-dram-budget", type=int, default=None,
                    metavar="BYTES",
                    help="DRAM byte budget for the WEIGHTS: stacks that "
                         "overflow it stream per layer group from Flash "
                         "through a double-buffered DRAM ring "
                         "(default: everything resident)")
    return ap


def model_config(args):
    """The served config: the registry entry, cut to smoke width unless
    ``--no-reduced`` (or an explicit ``@variant``) says otherwise."""
    cfg = registry.get(args.arch)
    if args.reduced and "@" not in args.arch:
        cfg = registry.reduced(cfg)
    return cfg


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    with tempfile.TemporaryDirectory(prefix="repro_flash_") as flash_dir:
        serve(args, model_config(args), flash_dir)


def serve(args, cfg, flash_dir: str) -> None:
    backend = RD.default_backend()
    print(f"[serve] arch={cfg.name} quant={cfg.quant.tag()} "
          f"backend={backend} on {jax.devices()[0].device_kind} "
          f"(embedding on Flash, int8-K/fp8-V KV cache)")
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(args.seed),
                         max_seq=args.max_seq, flash_dir=flash_dir,
                         backend=backend,
                         weight_dram_budget_bytes=args.weight_dram_budget)
    if eng.weight_policy.active:
        pol = eng.weight_policy
        print(f"[serve] weight streaming: "
              f"{len(pol.streamed)} stack(s) on Flash, "
              f"ring {pol.ring_bytes / 1024:.0f} KiB, "
              f"resident {pol.resident_bytes / 1024:.0f} KiB "
              f"of budget {pol.dram_budget_bytes / 1024:.0f} KiB")

    if args.http is not None:
        from repro.data.tokenizer import ByteTokenizer
        from repro.serving import gateway as G
        assert not cfg.is_encdec, "gateway serves decoder-only models"
        loop = E.EngineLoop(eng, max_slots=args.slots,
                            preempt_patience=args.preempt_patience,
                            max_queue=args.max_queue)
        tok = ByteTokenizer(cfg.vocab_size) if cfg.vocab_size >= 258 else None
        print(f"[serve] gateway on http://{args.host}:{args.http} "
              f"({args.slots} slots, queue bound {args.max_queue}, "
              f"{'byte tokenizer' if tok else 'token-id prompts only'})")
        G.serve(G.EngineService(loop, warmup=args.warmup),
                host=args.host, port=args.http,
                tokenizer=tok, model_name=cfg.name)
        return

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt_tokens=list(rng.integers(
                        1, cfg.vocab_size, size=int(rng.integers(4, 32)))),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    sp = SM.SamplingParams(temperature=args.temperature, top_k=50,
                           max_new_tokens=args.max_new)

    if args.continuous and not cfg.is_encdec:
        loop = E.EngineLoop(eng, max_slots=args.slots,
                            preempt_patience=args.preempt_patience)
        if args.warmup:
            rep = loop.warmup()
            print(f"[serve] warmup: {rep['graphs']} graphs "
                  f"(buckets {rep['decode_buckets']}, "
                  f"chunks {rep['chunk_sizes']}) in {rep['warmup_s']:.2f}s")
        t0 = time.perf_counter()
        out = loop.run(reqs, sp)
        wall = time.perf_counter() - t0
        s = eng.stats
        done = sum(len(r.generated) for r in out)
        print(f"[serve] continuous: {len(out)} requests, {done} new tokens "
              f"in {wall:.2f}s ({done / wall:.1f} tok/s) on "
              f"{args.slots} slots")
        print(f"[serve] TTFT p50={s.ttft(50) * 1e3:.0f}ms "
              f"p95={s.ttft(95) * 1e3:.0f}ms; "
              f"TPOT p50={s.tpot(50) * 1e3:.0f}ms; "
              f"latency p50={s.latency(50):.2f}s p95={s.latency(95):.2f}s")
    else:
        # C4: balanced assignment report (vs uniform)
        bal = balance_requests(reqs, 4)
        uni = uniform_requests(reqs, 4)
        print(f"[serve] C4 makespan: balanced={makespan(bal):.0f} "
              f"uniform={makespan(uni):.0f}")
        src = None
        if cfg.is_encdec:
            src = np.asarray(
                rng.normal(size=(len(reqs), 16, cfg.d_model)) * 0.02,
                np.float32)
        out = eng.generate(reqs, sp, src_embeds=src)
        s = eng.stats
        print(f"[serve] prefill {s.prefill_tokens} toks @ "
              f"{s.prefill_tps:.1f} t/s; decode {s.decode_tokens} toks @ "
              f"{s.decode_tps:.1f} t/s")
    for r in out[:4]:
        print(f"[serve] req {r.uid}: prompt {len(r.prompt_tokens)} toks -> "
              f"{r.generated}")
    s = eng.stats
    print(f"[serve] flash reads {s.flash_bytes / 1024:.1f} KiB; "
          f"dispatch fallbacks {eng.dispatch.fallbacks}")


if __name__ == "__main__":
    main()

"""End-to-end streaming gateway demo: boot the HTTP server over the
incremental EngineLoop API, stream one completion over SSE (watch the
tokens arrive one by one while the engine is still decoding), then show
the non-streaming path, per-request priorities, and the bounded-queue
backpressure (HTTP 429).

    PYTHONPATH=src python examples/serve_http.py

Requires aiohttp + requests (the in-process EngineService API, shown
last, works without either).
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import json
import time

import jax
import requests

from repro.configs import registry
from repro.data.tokenizer import ByteTokenizer
from repro.serving import engine as E
from repro.serving import gateway as G
from repro.serving import sampling as SM


def sse_chunks(resp):
    for line in resp.iter_lines(chunk_size=1, decode_unicode=True):
        if line and line.startswith("data: "):
            data = line[len("data: "):]
            if data == "[DONE]":
                return
            yield json.loads(data)


def main() -> None:
    cfg = registry.reduced(registry.get("qwen2-7b"))
    eng = E.build_engine(cfg, key=jax.random.PRNGKey(0), max_seq=128)
    # the tight queue bound (2 waiting) makes the 429 backpressure section
    # below actually fire on a workstation-sized flood
    loop = E.EngineLoop(eng, max_slots=4, max_queue=2)
    tok = ByteTokenizer(cfg.vocab_size)

    with G.GatewayServer(G.EngineService(loop), tokenizer=tok) as gw:
        # /healthz answers 503 ("warming") until warmup() has traced the
        # bucketed decode + prefill-chunk graphs, then 200 ("ok")
        t0 = time.perf_counter()
        while True:
            hz = requests.get(gw.url + "/healthz")
            print(f"[http] +{time.perf_counter() - t0:5.1f}s "
                  f"healthz {hz.status_code}: {hz.json()}")
            if hz.status_code == 200:
                break
            time.sleep(2.0)

        # --- SSE streaming: tokens on the wire as the engine commits them
        t0 = time.perf_counter()
        with requests.post(
                f"{gw.url}/v1/completions",
                json={"prompt": "the quick brown fox", "max_tokens": 16,
                      "stream": True},
                stream=True) as resp:
            for i, chunk in enumerate(sse_chunks(resp)):
                c = chunk["choices"][0]
                print(f"[sse] +{time.perf_counter() - t0:6.2f}s "
                      f"token[{i}]={c['token']:4d} "
                      f"finish={c['finish_reason']}")

        # --- non-streaming: one JSON body with usage accounting
        r = requests.post(f"{gw.url}/v1/completions",
                          json={"prompt": "hello", "max_tokens": 8,
                                "temperature": 0.8, "top_k": 50})
        body = r.json()
        print(f"[json] {body['choices'][0]['tokens']} "
              f"usage={body['usage']}")

        # --- QoS: a priority-9 request with a 2s deadline jumps the queue
        r = requests.post(f"{gw.url}/v1/completions",
                          json={"prompt": "urgent", "max_tokens": 4,
                                "priority": 9, "deadline_ms": 2000})
        print(f"[qos] priority-9: {r.json()['choices'][0]['tokens']}")

        # --- backpressure: flooding past max_queue answers 429, not OOM.
        # stream=True makes each POST return at admission time, and the
        # keep-alive Session fires them faster than slots free up, so the
        # flood really lands on the bounded queue
        codes, opened = [], []
        with requests.Session() as s:
            for _ in range(48):
                resp = s.post(
                    f"{gw.url}/v1/completions",
                    json={"prompt": [1, 2, 3], "max_tokens": 64,
                          "stream": True}, stream=True)
                codes.append(resp.status_code)
                if resp.status_code == 200:
                    opened.append(resp)
                else:
                    resp.close()
            print(f"[429] flood of 48: {codes.count(200)} accepted, "
                  f"{codes.count(429)} backpressured "
                  f"(Retry-After honored by real clients)")
            for resp in opened:        # drain the accepted streams
                for _ in sse_chunks(resp):
                    pass
                resp.close()

        stats = requests.get(f"{gw.url}/v1/stats").json()
        print(f"[stats] step={stats['step']} rejected={stats['rejected']} "
              f"decode={stats['decode_tokens']} toks over "
              f"{stats['decode_steps']} decode steps, "
              f"queue_wait_p50={stats['queue_wait_p50_s'] * 1e3:.0f}ms, "
              f"ttft_p50={stats['ttft_p50_s'] * 1e3:.0f}ms")
        ws = stats["weight_streaming"]
        if ws["active"]:
            print(f"[stats] weight streaming: {ws['streamed_stacks']} "
                  f"streamed / {ws['resident_stacks']} resident stacks, "
                  f"ring {ws['ring_bytes'] / 1024:.0f} KiB, "
                  f"hit rate {ws['hit_rate']:.3f}, "
                  f"stall {ws['stall_s'] * 1e3:.1f}ms")
        else:
            print(f"[stats] weight streaming: off (all "
                  f"{ws['resident_stacks']} stacks resident, "
                  f"{ws['dram_weight_bytes'] / 1024:.0f} KiB DRAM)")

    # --- the same stack, in process: EngineService without HTTP ----------
    # (warmup=False: compile lazily, like --no-warmup on the CLI)
    loop2 = E.EngineLoop(eng, max_slots=2)
    with G.EngineService(loop2, warmup=False) as svc:
        stream = svc.submit(tok.encode("in-process"),
                            SM.SamplingParams(temperature=0.0,
                                              max_new_tokens=6))
        print(f"[svc] streamed: {[t for t, _ in stream]}")


if __name__ == "__main__":
    main()

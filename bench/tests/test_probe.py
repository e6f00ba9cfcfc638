"""The probe of a traced run counts from the loop's public state: a tiny
engine on the CPU serves a few requests step by step, and the counters
match what each request's prompt and tokens say they must be."""
import numpy as np

from bench import model
from bench import run as R
from bench.tests.conftest import TINY_MIX, TINY_SPEC
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request


def test_probe_counts_rows_contexts_and_prompts():
    cfg = model.model_config(TINY_SPEC)
    eng = E.Engine(cfg, model.make_params(cfg, 7), model.embedding_store(cfg),
                   max_seq=TINY_MIX["engine"]["max_seq"], backend="reference")
    loop = E.EngineLoop(eng, max_slots=4, prefill_chunk=32)
    probe = R.Probe(loop)
    rng = np.random.default_rng(3)
    lens, news = [9, 40, 70, 33, 12], [5, 9, 3, 7, 4]
    reqs = [Request(uid=i, prompt_tokens=rng.integers(1, 500, n).tolist(),
                    max_new_tokens=m,
                    sampling=SM.SamplingParams(temperature=0.0))
            for i, (n, m) in enumerate(zip(lens, news))]
    for r in reqs:
        loop.submit(r)
    c0 = probe.snapshot()
    while loop.has_work():
        loop.step()
    c = {k: v - c0[k] for k, v in probe.snapshot().items()}
    assert all(len(r.generated) == m for r, m in zip(reqs, news))
    # the token after the k-th (k < n) is decoded at position L + k - 1
    # and attends over L + k keys; the last token ends the request
    assert c["decode_rows"] == sum(m - 1 for m in news) == c["decode_tokens"]
    assert c["decode_ctx"] == sum(n + k for n, m in zip(lens, news)
                                  for k in range(1, m))
    assert c["prompts_done"] == len(reqs)
    assert c["prefill_keys"] == sum(n * (n + 1) // 2 for n in lens)
    assert c["prefill_tokens"] == sum(lens)
    assert c["prefill_chunks"] == sum(-(-n // 32) for n in lens)
    assert 0 < c["decode_steps"] <= c["steps"]

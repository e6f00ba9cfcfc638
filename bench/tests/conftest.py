"""Shared set-up of the benchmark's own tests: the repository root and the
program on the import path, and a tiny cell that runs on the CPU."""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_SPEC = {
    "name": "tiny-gqa", "source": "test", "num_hidden_layers": 2,
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 64, "intermediate_size": 512, "vocab_size": 500,
    "qkv_bias": True, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "quantization": {"weight_bits": 4, "act_bits": 8, "lm_head_bits": 8,
                     "kv_key_bits": 8, "kv_value_fp8": True},
    "reference": "dense_gqa"}

TINY_MIX = {
    "arrival": "poisson",
    "engine": {"slots": 4, "max_seq": 256, "prefill_chunk": 32},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8,
               "max": 96},
    "output": {"dist": "uniform", "min": 8, "max": 40},
    "greedy_share": 0.5, "sample": {"temperature": 0.8, "top_k": 50},
    "preroll_s": 2}

# a closed batch on the tiny engine: every slot filled in set-up, and
# answers long enough to decode through a 3 s window on the CPU
TINY_BATCH = {
    "arrival": "batch", "requests": 4,
    "engine": {"slots": 4, "max_seq": 1024, "prefill_chunk": 32},
    "prompt": {"dist": "uniform", "min": 40, "max": 72},
    "output": {"dist": "uniform", "min": 800, "max": 950},
    "greedy_share": 0.5, "sample": {"temperature": 0.8, "top_k": 50}}

# the tiny cell's limit on the widest served-token gap (std units): its
# sound runs read 0.02-0.09 on the CPU, the faults 2 and more
TINY_LIMIT = 0.5


def tiny_opts(seed: int, limit: float = TINY_LIMIT, **kw):
    from bench import run as R
    return R.Opts(
        workload="tiny", seed=seed, seconds=3, trace=False, spec=TINY_SPEC,
        metrics=[{"name": "setup_s", "unit": "s"},
                 {"name": "ttft_p50_s", "unit": "s"},
                 {"name": "itl_p95_ms", "unit": "ms"}],
        backend="reference", mix_spec=TINY_MIX,
        own={"rate_per_s": 8.0, "limits": {"served_logit_gap": limit}},
        **kw)

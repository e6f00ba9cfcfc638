"""GLM-4-9B enters the benchmark as new files: its arch module (dense
GQA with partial, interleaved rotary), its own float32 reference, the
long-context mix and cell, and the ``kv_pool_padding`` reader."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import costs, model, traffic
from bench import engine_steps as ES
from bench import run as R
from bench.reference import dense_gqa as DREF
from bench.reference import glm4 as REF
from bench.tests.conftest import TINY_BATCH, TINY_LIMIT, TINY_SPEC, tiny_opts
from bench.tests.test_engine_steps import synthetic

TINY_GLM = dict(
    {k: v for k, v in TINY_SPEC.items() if k != "qkv_bias"},
    name="tiny-glm", attention_bias=True, partial_rotary_factor=0.5,
    rope_interleaved=True, rms_norm_eps=1.5625e-7, arch="glm4",
    reference="glm4")


def test_the_glm4_configuration_is_the_published_one():
    spec = model.load_config("glm4-9b")
    cfg = model.model_config(spec)
    assert model.arch_for(cfg).__name__ == "bench.arch.glm4"
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (40, 4096, 32, 2, 128, 13696, 151552)
    assert (cfg.rope_fraction, cfg.rope_interleaved, cfg.rope_theta,
            cfg.qkv_bias, cfg.rms_eps) == (0.5, True, 10000.0, True,
                                           1.5625e-7)
    # the program's own registry entry has the same shape
    from repro.configs import registry
    reg = registry.get("glm4-9b")
    for f in ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "rope_theta",
              "rope_fraction", "rope_interleaved", "qkv_bias", "rms_eps"):
        assert getattr(cfg, f) == getattr(reg, f), f
    # counts are dense GQA's: the rotary adds no linear operation
    qwen = model.model_config(model.load_config("qwen2-7b"))
    assert costs.token_linear_ops(cfg) == 2 * cfg.num_layers * sum(
        k * n for _, k, n, _ in model.arch(spec).linears(cfg))
    assert model.arch(spec).kv_bytes_per_token(cfg) == 2 * (2 * 128 + 8)
    assert costs.weight_bytes(qwen) > 0
    # weights 4.75 GB and the cell's pool 5.71 GB
    assert costs.weight_bytes(cfg) == pytest.approx(4.75e9, rel=0.01)
    pages = 32 * 64 + 1 + 62
    assert pages * 128 * 40 * 528 == pytest.approx(5.71e9, rel=0.001)


def test_the_long_context_cell_is_a_closed_batch_that_fits_max_seq():
    mix, own = traffic.load("long-context", "glm4-9b.long-context")
    assert own["limits"]["served_logit_gap"] == 1.5
    tr = traffic.build(mix, own, 2 ** 33 + 5, 50.0, 151552, "long-context")
    assert tr.arrival == "batch" and len(tr.requests) == 32
    assert tr.engine == {"slots": 32, "max_seq": 8192, "prefill_chunk": 512}
    assert all(4096 <= len(r.prompt) <= 6144 and r.max_new == 2048
               for r in tr.requests)
    assert max(len(r.prompt) + r.max_new for r in tr.requests) <= 8192
    assert sum(r.greedy for r in tr.requests) == 16
    bench = json.loads((model.BENCH.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}["glm4-9b.long-context"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("glm4-9b", "long-context", 1)


def test_reference_rotary_turns_interleaved_pairs_of_the_first_half():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 8))
    pos = np.arange(3)[None]
    y = np.asarray(REF.rotary(x, pos, 10000.0, 0.5))
    x = np.asarray(x)
    assert np.array_equal(y[..., 4:], x[..., 4:])
    for i in range(2):
        ang = pos[0][:, None] * 10000.0 ** (-2 * i / 4)
        a, b = x[0, :, :, 2 * i], x[0, :, :, 2 * i + 1]
        np.testing.assert_allclose(y[0, :, :, 2 * i],
                                   a * np.cos(ang) - b * np.sin(ang),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y[0, :, :, 2 * i + 1],
                                   b * np.cos(ang) + a * np.sin(ang),
                                   rtol=1e-5, atol=1e-5)
    # at position 0 nothing turns; dense_gqa's full rotate-half differs
    assert np.array_equal(y[:, 0], x[:, 0])
    full = np.asarray(DREF._rope(x, pos, 10000.0))
    assert not np.allclose(full[:, 1:], y[:, 1:], atol=1e-3)


def test_reference_blocks_change_nothing(monkeypatch):
    """Rows and queries in blocks give the unblocked layer's logits."""
    cfg = model.model_config(TINY_GLM)
    w = model.logical_layers(cfg, model.make_params(cfg, 11))
    table = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                         (cfg.padded_vocab_size, 256)))
    rows = lambda ids: table[ids]  # noqa: E731
    seqs = [list(range(1, 130)), list(range(7, 71))]
    whole = REF.logits(w, TINY_GLM, rows, seqs, [120, 60])
    monkeypatch.setattr(REF, "ROW_BLOCK", 64)
    monkeypatch.setattr(REF, "QUERY_BLOCK", 16)
    REF._layer.clear_cache()        # the blocks are read when it traces
    blocked = REF.logits(w, TINY_GLM, rows, seqs, [120, 60])
    REF._layer.clear_cache()
    for a, b in zip(whole, blocked):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_a_tiny_glm4_batch_is_served_and_checked_against_its_reference():
    """The whole run at a tiny size: the program built by ``bench/arch/
    glm4.py`` serves a closed batch, and its served tokens lie within the
    tiny cell's limit of ``bench/reference/glm4.py``."""
    res = R.run(dataclasses.replace(
        tiny_opts(2 ** 34 + 1), spec=TINY_GLM, mix_spec=TINY_BATCH,
        own={"limits": {"served_logit_gap": TINY_LIMIT}},
        metrics=[{"name": "setup_s", "unit": "s"},
                 {"name": "itl_p95_ms", "unit": "ms"}]))
    assert res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] < TINY_LIMIT
    assert res["failed"] == 0


READ = "kv_pool_padding"


def _with_pool(device, logical):
    w, engine, args = synthetic()
    for ev, d, lg in zip(sorted(args, key=lambda e: e.start), device,
                         logical):
        args[ev].update(kv_pool_device_bytes=d, kv_pool_logical_bytes=lg)
    return ES.reduce(w, engine, args)


def test_kv_pool_padding_reads_the_step_arguments():
    # two launches between the steps: 2 x 1,050 device bytes for 2 x 1,000
    st = _with_pool([1050, 3150], [1000, 3000])
    assert R.load_reader(READ)({"engine_steps": st}) == pytest.approx(5.0)
    assert R.load_reader(READ)(
        {"engine_steps": _with_pool([0, 4000], [0, 4000])}) == 0.0


def test_kv_pool_padding_without_counters_reports_nothing():
    w, engine, args = synthetic()
    assert R.load_reader(READ)(
        {"engine_steps": ES.reduce(w, engine, args)}) is None
    assert R.load_reader(READ)({"engine_steps": None}) is None
    assert R.load_reader(READ)(
        {"engine_steps": _with_pool([5, 5], [7, 7])}) is None

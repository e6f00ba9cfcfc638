"""The comparison that decides ``correct``: a whole run of a tiny cell on
the CPU, past the look for a chip, is correct when sound, and comes out
not correct with the timed path broken underneath it in each way a served
model's cell can break; the 4-bit control fails the same limit."""
import jax.numpy as jnp

from bench import run as R
from bench.tests.conftest import TINY_LIMIT, tiny_opts
from repro.serving import engine as E

SEED = 2 ** 33 + 17


def _run(seed=SEED):
    res = R.run(tiny_opts(seed))
    gap = res["checks"]["served_logit_gap"]
    assert gap["limit"] == TINY_LIMIT
    assert list(res)[-1] == "checks"
    return res, gap["value"]


def test_a_sound_run_is_correct():
    res, gap = _run()
    assert res["correct"] and gap < TINY_LIMIT
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_s", "itl_p95_ms"}


def test_decode_that_returns_its_state_unchanged(monkeypatch):
    impl = E.EngineLoop._decode_bucket_impl

    def stale(cfg, ctx, params, embeds, cache, *rest):
        logits, _ = impl(cfg, ctx, params, embeds, cache, *rest)
        return logits, cache              # the KV cache never advances
    monkeypatch.setattr(E.EngineLoop, "_decode_bucket_impl",
                        staticmethod(stale))
    res, gap = _run()
    assert not res["correct"] and gap > TINY_LIMIT


def test_half_of_the_batch_left_out(monkeypatch):
    impl = E.EngineLoop._decode_bucket_impl

    def half(cfg, ctx, params, embeds, cache, lora, active, slot_idx,
             logits_prev):
        b = embeds.shape[0]
        keep = active & (jnp.arange(b) < max(1, b // 2))
        return impl(cfg, ctx, params, embeds, cache, lora, keep, slot_idx,
                    logits_prev)
    monkeypatch.setattr(E.EngineLoop, "_decode_bucket_impl",
                        staticmethod(half))
    res, gap = _run()
    assert not res["correct"] and gap > TINY_LIMIT


def test_a_token_altered_where_it_is_produced(monkeypatch):
    sample = E.EngineLoop._sample

    def altered(self, sub):
        tok = sample(self, sub)
        return (tok + 1) % self.cfg.vocab_size
    monkeypatch.setattr(E.EngineLoop, "_sample", altered)
    res, gap = _run()
    assert not res["correct"] and gap > TINY_LIMIT


def test_the_control_fails_the_limit():
    """With ``--control 1`` the reference at 4-bit activations takes the
    program's place in the comparison, on the prompts and tokens the run
    served, and the run comes out not correct on every seed tried."""
    for seed in (1, 2, 2 ** 34 + 5):
        res = R.run(tiny_opts(seed, control=True))
        gap = res["checks"]["served_logit_gap"]
        assert not res["correct"] and gap["value"] > TINY_LIMIT, (seed, gap)


def test_a_run_without_the_comparison_prints_no_result():
    assert R.run(tiny_opts(SEED, check=False)) is None

"""Seeded weights and traffic: the served layout, the reference's own
unpacking, and the same work for every seed."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, traffic
from bench.reference import dense_gqa as REF
from bench.tests.conftest import TINY_MIX, TINY_SPEC


@pytest.fixture(scope="module")
def tiny():
    cfg = model.model_config(dict(TINY_SPEC, intermediate_size=448))
    return cfg, model.make_params(cfg, 2 ** 40 + 3)


def test_params_have_the_program_tree(tiny):
    from repro.models import transformer as T
    cfg, params = tiny
    ab = T.init_params(cfg, mode="abstract", quantized=True, pack=True)
    assert jax.tree.structure(ab) == jax.tree.structure(params)
    for a, p in zip(jax.tree.leaves(ab), jax.tree.leaves(params)):
        assert (a.shape, a.dtype) == (p.shape, p.dtype)


def test_reference_unpacks_as_the_program_does(tiny):
    """d_ff 448 pads to 512 output columns: the padding holds zeros and
    the reference's dequantized weight equals the program's own."""
    from repro.core import quantization as q
    from repro.runtime import plan as RP
    cfg, params = tiny
    w = model.logical_layers(cfg, params)
    dims = REF.dims_of(dict(TINY_SPEC, intermediate_size=448))
    (layer,) = params["stacks"][0]
    for name, packed in (("w_gate", layer["ffn"]["w_gate"]["w"]),
                         ("wq", layer["attn"]["wq"]["w"])):
        for i in range(cfg.num_layers):
            one = jax.tree.map(lambda x: x[i], packed)
            theirs = q.dequantize(RP.unpack_linear(one), jnp.float32)
            ours = REF.dequant(w[name], *dims.shape(name), idx=i)
            np.testing.assert_allclose(ours, theirs, rtol=1e-6, atol=1e-6)
    head = params["lm_head"]["w"]
    theirs = q.dequantize(RP.unpack_linear(head), jnp.float32)
    ours = REF.dequant(w["lm_head"], *dims.shape("lm_head"))
    # the program's head spans the padded vocabulary; the rows past the
    # real vocabulary are never sampled and the reference leaves them out
    np.testing.assert_allclose(ours, theirs[:, :cfg.vocab_size], rtol=1e-6,
                               atol=1e-6)
    # every linear keeps its input's scale: std about 1/sqrt(fan_in)
    g = np.asarray(REF.dequant(w["w_gate"], *dims.shape("w_gate"), idx=0))
    assert g.std() * np.sqrt(cfg.d_model) == pytest.approx(1.0, rel=0.1)
    # padded output columns stay empty in the served layout
    u = q.unpack_int4_lanes(layer["ffn"]["w_gate"]["w"].data)
    assert not np.asarray(u[..., 448:]).any()


def test_params_follow_the_seed(tiny):
    cfg, params = tiny
    again = model.make_params(cfg, 2 ** 40 + 3)
    other = model.make_params(cfg, 2 ** 40 + 4)
    a, b, c = (np.asarray(p["lm_head"]["w"].data)
               for p in (params, again, other))
    assert (a == b).all() and (a != c).any()


def test_every_seed_gets_the_same_work():
    """Pre-roll, window and tail hold the same requests, by size and by
    count, for every seed; only their order and tokens differ."""
    def phases(tr):
        out = []
        for lo, hi in ((0, 2), (2, 12), (12, 1e9)):
            rs = [r for r in tr.requests if lo <= r.due_s < hi]
            out.append((len(rs), sorted(len(r.prompt) for r in rs),
                        sorted(r.max_new for r in rs),
                        sum(r.greedy for r in rs)))
        return out
    runs = [traffic.build(TINY_MIX, {"rate_per_s": 5.0}, s, 10.0, 500,
                          "tiny") for s in (1, 2, 2 ** 35 + 11)]
    for tr in runs:
        due = [r.due_s for r in tr.requests]
        assert due == sorted(due) and due[0] > 0.0
        assert phases(tr) == phases(runs[0])
    assert [len(r.prompt) for r in runs[0].requests] != \
        [len(r.prompt) for r in runs[1].requests]
    again = traffic.build(TINY_MIX, {"rate_per_s": 5.0}, 2, 10.0, 500, "t")
    assert all((a.prompt == b.prompt).all() and a.due_s == b.due_s
               for a, b in zip(again.requests, runs[1].requests))


def test_lengths_are_stratified_quantiles():
    q = traffic.quantiles({"dist": "lognormal", "median": 256, "sigma": 0.7,
                           "min": 32, "max": 1024}, 101)
    assert q[50] == 256 and q.min() >= 32 and q.max() <= 1024
    u = traffic.quantiles({"dist": "uniform", "min": 4096, "max": 6144}, 32)
    assert u.min() >= 4096 and u.max() <= 6144 and len(set(u)) == 32


# sha256 of the chat cell's requests and of the tiny configuration's
# weights (on the CPU), as the benchmark drew them before configurations
# got arch modules and mixes a batch arrival: neither change may move them
PINNED_CHAT = {
    1: "bc1dc1a9094c9b51b78df1fe64830f0e5a4cdee50a6a3d1ed489093782fbd49e",
    2 ** 31 + 11:
        "5d5ddfd87ac5ad59fd027cd6a94be10cd362f0f14c950c52d525fc8aafcb936b",
    2 ** 33 + 17:
        "3d4fdfd7a6d125033710f80689bbbeb234a17cb9dce2a1a667da97a0787224a5"}
PINNED_TINY_PARAMS = {
    7: "a5450c1f2d6fdf0b89a2edd6df444066af5c739f4b86265665854f114f53b387",
    2 ** 40 + 3:
        "2778fa4338bae5ded671e0841025c267b64b6de74394a2674b09c568d71d9d3b"}


def test_the_chat_cells_requests_are_pinned():
    spec, own = traffic.load("chat", "qwen2-7b.chat")
    vocab = model.load_config("qwen2-7b")["vocab_size"]
    for seed, want in PINNED_CHAT.items():
        h = hashlib.sha256()
        for r in traffic.build(spec, own, seed, 50.0, vocab, "chat").requests:
            h.update(np.asarray([r.index, r.max_new, int(r.greedy)],
                                np.int64).tobytes())
            h.update(np.float64(r.due_s).tobytes())
            h.update(np.asarray(r.prompt, np.int64).tobytes())
        assert h.hexdigest() == want, seed


def test_the_tiny_weights_are_pinned():
    cfg = model.model_config(TINY_SPEC)
    for seed, want in PINNED_TINY_PARAMS.items():
        h = hashlib.sha256()
        for x in jax.tree.leaves(model.make_params(cfg, seed)):
            a = np.asarray(x)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
        assert h.hexdigest() == want, seed

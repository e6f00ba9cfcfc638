"""A kind of model enters the benchmark as one new file under
``bench/arch/``: a module that only this test writes sets the
``ModelConfig``, a leaf's draw rule and the model's counts of a whole run,
with ``bench/model.py`` and ``bench/costs.py`` as they are."""
import dataclasses

import numpy as np

import bench.arch
from bench import costs, model
from bench import run as R
from bench.tests.conftest import TINY_SPEC, tiny_opts
from repro.serving import engine as E

UNIT_NORMS = '''
"""Dense GQA with unit norm gains, under a configuration name of its own."""
import dataclasses

import jax.numpy as jnp

from bench.arch import dense_gqa as D
from bench.arch.dense_gqa import (  # noqa: F401
    attention_ops, decode_attention_bytes, head_ops, kv_bytes_per_token,
    linears, logical_layers, token_linear_ops, weight_bytes)


def model_config(spec):
    return dataclasses.replace(D.model_config(spec),
                               name=spec["name"] + "-unit-norms")


def _ones(key, ab):
    return jnp.ones(ab.shape, ab.dtype)


DRAW = dict(D.DRAW, ln1=_ones, ln2=_ones)
'''


def test_an_arch_module_added_as_a_file_builds_the_run(tmp_path,
                                                       monkeypatch):
    (tmp_path / "unit_norms.py").write_text(UNIT_NORMS)
    monkeypatch.setattr(bench.arch, "__path__",
                        [*bench.arch.__path__, str(tmp_path)])
    seen = {}

    class Spy(E.Engine):
        def __init__(self, cfg, params, *a, **kw):
            (layer,) = params["stacks"][0]
            seen.update(cfg=cfg, ln1=np.asarray(layer["ln1"]),
                        ln2=np.asarray(layer["ln2"]),
                        final_norm=np.asarray(params["final_norm"]))
            super().__init__(cfg, params, *a, **kw)
    monkeypatch.setattr(E, "Engine", Spy)
    spec = dict(TINY_SPEC, arch="unit_norms")
    res = R.run(dataclasses.replace(tiny_opts(2 ** 33 + 9), spec=spec))
    assert res["correct"], res["checks"]
    cfg = seen["cfg"]
    assert cfg.name == "tiny-gqa-unit-norms"
    assert model.arch_for(cfg).__name__ == "bench.arch.unit_norms"
    assert (seen["ln1"] == 1).all() and (seen["ln2"] == 1).all()
    assert not (seen["final_norm"] == 1).all()     # dense_gqa's own rule
    dense = model.model_config(TINY_SPEC)
    assert model.arch_for(dense).__name__ == "bench.arch.dense_gqa"
    assert costs.token_linear_ops(cfg) == costs.token_linear_ops(dense)


def test_a_configuration_names_its_arch_module():
    assert model.arch(TINY_SPEC).__name__ == "bench.arch.dense_gqa"
    assert model.arch(model.load_config("qwen2-7b")).__name__ == \
        "bench.arch.dense_gqa"
    for name in ("model_config", "DRAW", "logical_layers", "linears",
                 "token_linear_ops", "head_ops", "attention_ops",
                 "kv_bytes_per_token", "decode_attention_bytes",
                 "weight_bytes"):
        assert hasattr(model.arch(TINY_SPEC), name), name

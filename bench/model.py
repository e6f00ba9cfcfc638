"""Configurations and seeded weights for the benchmark.

A configuration is a JSON file under ``bench/configs/`` holding the sizes as
run (published key names).  Its ``arch`` key names the module
``bench/arch/<arch>.py`` that knows its kind of model (``dense_gqa`` when
the key is absent): ``model_config`` turns the file into the program's
``ModelConfig`` through that module, and remembers which module built it.
``make_params`` draws every weight of the program's packed serving tree
from the run's seed in one jitted call on the device, directly in the
served types: every packed linear here (int4 nibbles and int8 bytes with
f32 scales and zero points, scaled 1/sqrt(fan_in) so that every linear
keeps its input's magnitude), every other leaf by its arch module's rule
for that leaf's name, one ``fold_in`` of the seed's key per leaf in tree
order.  ``embedding_store`` keeps the bf16 embedding table in a file inside
the checkout, drawn once per configuration from a fixed key, so a run
writes nothing to disk once the file exists.  ``logical_layers`` hands the
same weights to the reference as plain arrays, through the arch module and
``plain_linear``, with the packing undone by the benchmark's own code.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
STATE = BENCH / ".state"

# int4 codes are uniform on 0..15: mean 7.5, standard deviation sqrt(255/12)
Q4_STD = math.sqrt(255.0 / 12.0)
# int8 codes are uniform on -128..127: standard deviation sqrt(65535/12)
Q8_STD = math.sqrt(65535.0 / 12.0)
LANE = 128


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    with open(path) as f:
        return json.load(f)


DEFAULT_ARCH = "dense_gqa"
_BUILT_BY: dict = {}      # ModelConfig -> the arch module that built it


def arch(spec: dict):
    """The arch module a configuration file names."""
    return importlib.import_module(
        f"bench.arch.{spec.get('arch', DEFAULT_ARCH)}")


def model_config(spec: dict):
    """The program's ModelConfig for a configuration file, built by its
    arch module."""
    mod = arch(spec)
    cfg = mod.model_config(spec)
    if _BUILT_BY.setdefault(cfg, mod) is not mod:
        raise ValueError(f"{cfg.name}: the same ModelConfig is built by "
                         f"{_BUILT_BY[cfg].__name__} and {mod.__name__}")
    return cfg


def arch_for(cfg):
    """The arch module that built ``cfg`` through ``model_config``."""
    try:
        return _BUILT_BY[cfg]
    except KeyError:
        raise KeyError(f"{cfg.name}: not built by bench.model.model_config"
                       ) from None


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones above 2**32."""
    k = jax.random.fold_in(jax.random.PRNGKey(salt), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    for p in reversed(path):
        name = getattr(p, "key", None)
        if isinstance(name, str):
            return name
    raise ValueError(f"unnamed leaf at {path}")


def _packed(key, ab, col_valid_lo, col_valid_hi):
    """One PackedLinear drawn in its served layout."""
    from repro.runtime.plan import PackedLinear
    kd, ks, kz = jax.random.split(key, 3)
    lead = ab.data.shape[:-2]
    kp, cols = ab.data.shape[-2:]
    npad = ab.scale.shape[-1]
    row_ok = (jnp.arange(kp) < ab.k)[:, None]
    if ab.bits == 4:
        raw = jax.random.bits(kd, ab.data.shape, jnp.uint8)
        lo = jnp.where(row_ok & col_valid_lo, raw & 0x0F, 0)
        hi = jnp.where(row_ok & col_valid_hi, raw >> 4, 0)
        data = jax.lax.bitcast_convert_type(lo | (hi << 4), jnp.int8)
        std, mean = Q4_STD, 7.5
    else:
        raw = jax.lax.bitcast_convert_type(
            jax.random.bits(kd, ab.data.shape, jnp.uint8), jnp.int8)
        data = jnp.where(row_ok & (jnp.arange(cols) < ab.n)[None, :], raw, 0)
        std, mean = Q8_STD, -0.5
    col = jnp.arange(npad) < ab.n
    base = 1.0 / (std * math.sqrt(ab.k))
    scale = base * jax.random.uniform(ks, (*lead, 1, npad), jnp.float32,
                                      0.75, 1.25)
    zero = mean + jax.random.uniform(kz, (*lead, 1, npad), jnp.float32,
                                     -0.5, 0.5)
    return PackedLinear(data=data, scale=jnp.where(col, scale, 1.0),
                        zero=jnp.where(col, zero, 0.0),
                        bits=ab.bits, k=ab.k, n=ab.n)


def _lane_valid(n: int, cols: int):
    """Which value columns the low and high nibble of each packed column
    hold, in the kernels' layout: packed column g*128 + l carries value
    columns g*256 + l (low) and g*256 + 128 + l (high)."""
    j = np.arange(cols)
    g, l = j // LANE, j % LANE
    lo = g * 2 * LANE + l
    return jnp.asarray(lo < n)[None, :], jnp.asarray(lo + LANE < n)[None, :]


def _abstract_tree(cfg):
    from repro.models import transformer as T
    return T.init_params(cfg, mode="abstract", quantized=True, pack=True)


@functools.lru_cache(maxsize=None)
def _generator(cfg):
    from repro.runtime.plan import PackedLinear
    rules = arch_for(cfg).DRAW
    abstract = _abstract_tree(cfg)
    is_packed = lambda x: isinstance(x, PackedLinear)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract,
                                                         is_leaf=is_packed)

    def draw(key):
        out = []
        for i, (path, ab) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            if is_packed(ab):
                lo, hi = _lane_valid(ab.n, ab.data.shape[-1])
                out.append(_packed(k, ab, lo, hi))
                continue
            name = _leaf_name(path)
            if name not in rules:
                raise ValueError(f"no draw rule for parameter {name!r} in "
                                 f"{arch_for(cfg).__name__}")
            out.append(rules[name](k, ab))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)


def make_params(cfg, seed: int):
    """Every weight of the packed serving tree, drawn on the device in one
    jitted call from ``seed``."""
    return _generator(cfg)(seed_key(seed, salt=1))


def plain_linear(p) -> dict:
    """One PackedLinear as the reference takes it: its int4/int8 codes as
    stored and its f32 scale and zero rows."""
    return {"data": p.data, "scale": p.scale[..., 0, :],
            "zero": p.zero[..., 0, :]}


def logical_layers(cfg, params) -> dict:
    """The drawn tree as plain arrays for the reference, by the arch
    module that built ``cfg``; no packed linear is left in it."""
    from repro.runtime.plan import PackedLinear
    is_packed = lambda x: isinstance(x, PackedLinear)  # noqa: E731
    out = arch_for(cfg).logical_layers(cfg, params)
    assert not any(map(is_packed, jax.tree.leaves(out, is_leaf=is_packed)))
    return out


# --- the embedding table ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("rows", "d"))
def _draw_embedding(key, rows: int, d: int):
    return jax.random.normal(key, (rows, d), jnp.bfloat16)


def embedding_path(cfg) -> pathlib.Path:
    return (STATE / "flash" / cfg.name
            / f"embedding-{cfg.padded_vocab_size}x{cfg.d_model}-bf16-v1.bin")


def embedding_array(cfg) -> np.ndarray:
    """The bf16 embedding table, written on first use (atomically) and
    mapped read-only afterwards.  Unit-variance rows: every layer's input
    is normalised, so only the ratio to the layer outputs matters."""
    path = embedding_path(cfg)
    shape = (cfg.padded_vocab_size, cfg.d_model)
    if not path.exists():
        path.parent.mkdir(parents=True, exist_ok=True)
        table = np.asarray(_draw_embedding(seed_key(0, salt=2), *shape))
        tmp = path.with_name(f"{path.name}.tmp{os.getpid()}")
        table.tofile(tmp)
        os.replace(tmp, path)
        del table
    return np.memmap(path, dtype=jnp.bfloat16, mode="r", shape=shape)


def embedding_store(cfg):
    """The program's Flash-resident embedding store over the table file."""
    from repro.core import hybrid_storage as HS
    embedding_array(cfg)
    path = embedding_path(cfg)
    flash = HS.FlashStore(str(path.parent), HS.FlashSpec(simulate=False))
    name = path.name[:-len(".bin")]
    flash.open(name, (cfg.padded_vocab_size, cfg.d_model), jnp.bfloat16)
    return HS.EmbeddingStore(flash, name)

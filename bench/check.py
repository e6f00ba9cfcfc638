"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the greedy requests that were served, drawn from the seed and always
holding the one with most served tokens, runs through the plain float32
reference (``bench/reference/``) over its prompt and served tokens.  At
every served position the reference's logits give the gap by which the
served token lies below the reference's best token.  The number compared is
the widest such gap, in units of the standard deviation of that position's
reference logits, so that it does not depend on the logits' scale.

``compare(..., control=True)`` also puts the reference computed in a lower
precision (every linear's input quantized to 4 bits, against the int8 the
configuration states) in the program's place: at each position the token
it puts first is read in the float32 reference the same way.  The limit lies between
the program's readings and the control's.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import numpy as np

# served tokens the sample should reach; the requests with most tokens
# come first so that a long context is always in it
SAMPLE_TOKENS = 320
SAMPLE_MAX = 8


def sample(records, seed: int) -> list:
    """Greedy requests with served tokens: the longest, then others in an
    order drawn from the seed, until ``SAMPLE_TOKENS`` tokens are in."""
    pool = [r for r in records if r.greedy and len(r.tokens) > 0
            and r.error is None]
    if not pool:
        return []
    pool.sort(key=lambda r: (-len(r.tokens), r.index))
    rest = pool[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [pool[0]], len(pool[0].tokens)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _reference(spec: dict):
    return importlib.import_module(f"bench.reference.{spec['reference']}")


def gaps_of(ref: np.ndarray, toks: np.ndarray) -> np.ndarray:
    """Gap of each chosen token below the best one, in units of the
    standard deviation of that position's reference logits."""
    ref = ref.astype(np.float64)
    best = ref.max(axis=1)
    chosen = ref[np.arange(len(toks)), toks]
    return (best - chosen) / ref.std(axis=1)


def reference_logits(spec, weights, embed_rows, prompts, served,
                     act_bits=None) -> List[np.ndarray]:
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    starts = [len(p) - 1 for p in prompts]
    return _reference(spec).logits(weights, spec, embed_rows, seqs, starts,
                                   act_bits=act_bits)


def compare(spec, weights, embed_rows, prompts: Sequence, served: Sequence,
            control: bool = False):
    """The widest gap of a served token in the reference, and with
    ``control`` also the control's widest gap on the same positions."""
    ref = reference_logits(spec, weights, embed_rows, prompts, served)
    widest = float(max(gaps_of(r, np.asarray(s)).max()
                       for r, s in zip(ref, served)))
    if not control:
        return widest, None
    low = reference_logits(spec, weights, embed_rows, prompts, served,
                           act_bits=4)
    return widest, float(max(gaps_of(r, lo.argmax(axis=1)).max()
                             for r, lo in zip(ref, low)))

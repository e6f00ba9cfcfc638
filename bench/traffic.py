"""The one general traffic generator.

A traffic mix is a JSON file ``bench/traffic/<mix>.json`` of parameters;
a cell may add ``bench/cells/<cell>.json`` with what is its own (the
offered rate of an open loop, the limits of the comparison).  ``build``
turns them and the run's seed into the list of requests and the engine
settings.  The program receives only the generated requests.

Every seed gets the same work in another order.  An open loop has three
phases, the pre-roll, the window and the tail after it; in each, the
request count is fixed, the lengths are the stratified quantiles of their
distribution and the gaps between arrivals the stratified quantiles of the
exponential, and the seed permutes them within the phase and draws the
prompt tokens.  So the window holds the same requests, by size, for every
seed, and two seeds differ no more than two runs of one seed would.

A closed batch is ``requests`` requests all due when the schedule starts,
with the same stratified lengths and greedy flags permuted by the seed;
the run prefills them in set-up and measures while they decode.

Mix keys:
  arrival        "poisson" (open loop at the cell's ``rate_per_s``)
                 or "batch" (``requests`` due at once, at most ``slots``)
  engine         {"slots", "max_seq", "prefill_chunk"}
  prompt/output  {"dist": "lognormal", "median", "sigma", "min", "max"}
                 or {"dist": "uniform", "min", "max"}
  greedy_share   share of greedy requests (the rest sample)
  sample         {"temperature", "top_k"} of the sampled requests
  preroll_s      seconds of arrivals before the window opens (poisson)
  requests       requests in the batch (batch)
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
# open loop: arrivals keep coming this long after the window closes, while
# the run waits for the first token of every request due in the window
TAIL_S = 60.0


@dataclasses.dataclass
class Req:
    index: int
    due_s: float            # send time, seconds after the schedule starts
    prompt: np.ndarray      # token ids
    max_new: int
    greedy: bool


@dataclasses.dataclass
class Traffic:
    name: str
    arrival: str
    engine: dict
    requests: List[Req]
    preroll_s: float
    rate_per_s: float
    sample: dict


def load(mix: str, cell: str) -> tuple:
    with open(BENCH / "traffic" / f"{mix}.json") as f:
        spec = json.load(f)
    path = BENCH / "cells" / f"{cell}.json"
    own = {}
    if path.exists():
        with open(path) as f:
            own = json.load(f)
    return spec, own


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n lengths at the stratified quantiles (i + 0.5) / n, clipped."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _phase(spec: dict, rng, start: float, length: float, n: int):
    """n requests spread over [start, start + length): stratified gaps,
    lengths and greedy flags, each permuted by ``rng``."""
    if n <= 0:
        return []
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = rng.permutation(gaps * (length * n / (n + 1)) / gaps.sum())
    due = start + np.cumsum(gaps) - gaps[0] * 0.5
    plen = rng.permutation(quantiles(spec["prompt"], n))
    olen = rng.permutation(quantiles(spec["output"], n))
    greedy = rng.permutation(np.arange(n) < int(round(spec["greedy_share"]
                                                      * n)))
    return list(zip(due, plen, olen, greedy))


def build(spec: dict, own: dict, seed: int, seconds: float, vocab: int,
          name: str, rate_per_s: Optional[float] = None) -> Traffic:
    rng = np.random.default_rng(seed)
    arrival = spec["arrival"]
    if arrival == "batch":
        n, slots = int(spec["requests"]), spec["engine"]["slots"]
        if not 0 < n <= slots:
            raise ValueError(f"a batch of {n} requests for {slots} slots")
        rate, pre = 0.0, 0.0
        rows = _phase(spec, rng, 0.0, 0.0, n)      # every one due at 0
    elif arrival == "poisson":
        rate = float(rate_per_s if rate_per_s is not None
                     else own["rate_per_s"])
        pre = float(spec["preroll_s"])
        rows = []
        for start, length in ((0.0, pre), (pre, seconds),
                              (pre + seconds, TAIL_S)):
            rows += _phase(spec, rng, start, length,
                           int(round(rate * length)))
    else:
        raise ValueError(f"unknown arrival {arrival!r}")
    reqs = [Req(index=i, due_s=float(due),
                prompt=rng.integers(1, vocab, size=int(plen)),
                max_new=int(olen), greedy=bool(greedy))
            for i, (due, plen, olen, greedy) in enumerate(rows)]
    return Traffic(name=name, arrival=arrival, engine=dict(spec["engine"]),
                   requests=reqs, preroll_s=pre,
                   rate_per_s=rate, sample=dict(spec.get("sample", {})))

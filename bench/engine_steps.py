"""The serving loop's own step spans and counters in a traced window.

The program opens a profiler span ``engine.step`` around every
``EngineLoop.step``, tiled in order by one span per phase the step runs
(``engine.admit``, ``engine.prefill``, ``engine.sample``,
``engine.commit``, ``engine.decode``).  The step span's arguments are the
step number and the engine's step counters (``EngineStats.STEP_COUNTERS``)
as they stood when the step began.

This module reduces one traced window to what the ``engine step`` metrics
read (``reduce``):

* the window's device idle time, cut exactly (not labelled) by the
  phase spans: a gap that two phases share is split between them, and
  idle under no phase (the step's own time between phases, and time
  between steps) is ``other``; the six add up to the window's idle time;
* the steps that began in the window, which the idle time is divided by;
* the growth of each step counter from the first step that began in the
  window to the last (the steps in between).

A run's readers find the reduction in ``ctx["engine_steps"]`` when the
harness puts it there, else ``of`` loads the window from the trace a
traced run leaves in ``bench/.state/trace`` until its readers have run.
A program without these spans gives ``None``, and the readers then report
nothing.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import pathlib
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace as TR

TRACE_DIR = pathlib.Path(__file__).resolve().parent / ".state" / "trace"
PREFIX = "engine."
STEP = "engine.step"
PHASES = ("admit", "prefill", "sample", "commit", "decode")
WINDOW = "bench.window"


@dataclasses.dataclass
class Steps:
    idle_s: Dict[str, float]      # by phase, and "other"; sums to the idle
    steps: int                    # engine steps that began in the window
    counters: Dict[str, int]      # step counters' growth over those steps
    # the longest gaps: (seconds, {phase or "other": seconds under it})
    longest: List[Tuple[float, Dict[str, float]]] = dataclasses.field(
        default_factory=list)


def _union_ns(spans: List[TR.Ev], lo: int, hi: int) -> List[Tuple[int, int]]:
    return TR.union(TR.clip(spans, lo, hi))


def _overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            tot += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _split_ns(gaps: List[Tuple[int, int]],
              phases: Dict[str, List[Tuple[int, int]]]) -> Dict[str, int]:
    """Idle ns of ``gaps`` under each phase's spans, and under none."""
    out = {p: _overlap_ns(gaps, spans) for p, spans in phases.items()}
    out["other"] = sum(t - s for s, t in gaps) - sum(out.values())
    return out


def reduce(w: TR.Window, engine: List[TR.Ev],
           args: Dict[TR.Ev, dict], n_longest: int = 5) -> Optional[Steps]:
    """The window's idle time by engine phase, its steps and the growth of
    the step counters; None when the window holds no engine step."""
    begun = sorted((e for e in engine
                    if e.name == STEP and w.lo <= e.start < w.hi),
                   key=lambda e: e.start)
    if not begun:
        return None
    gaps = TR.gaps(w)
    by_phase = collections.defaultdict(list)
    for e in engine:
        by_phase[e.name[len(PREFIX):]].append(e)
    phases = {p: _union_ns(by_phase[p], w.lo, w.hi) for p in PHASES}
    idle = _split_ns(gaps, phases)
    longest = [((t - s) * 1e-9,
                {k: v * 1e-9 for k, v in _split_ns([(s, t)], phases).items()
                 if v})
               for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n_longest]]
    first, last = args.get(begun[0], {}), args.get(begun[-1], {})
    counters = {k: int(last[k]) - int(first[k]) for k in first
                if k in last and k != "step_num" and not k.startswith("_")}
    return Steps(idle_s={k: v * 1e-9 for k, v in idle.items()},
                 steps=len(begun), counters=counters, longest=longest)


def load(log_dir) -> Tuple[TR.Window, List[TR.Ev], Dict[TR.Ev, dict]]:
    """Device ops and programs, the ``bench.`` host spans (the window is
    the ``bench.window`` span when there is one, as the harness takes
    it), and the ``engine.`` host spans with their arguments, from the
    newest profile under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(str(log_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    ops, modules, host, engine, args = [], [], [], [], {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dst.append(TR.Ev(e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(TR.HOST_PREFIX):
                        s = int(e.start_ns)
                        host.append(TR.Ev(e.name, s,
                                          s + int(e.duration_ns)))
                    elif e.name.startswith(PREFIX):
                        s = int(e.start_ns)
                        ev = TR.Ev(e.name, s, s + int(e.duration_ns))
                        engine.append(ev)
                        if e.name == STEP:
                            args[ev] = dict(e.stats)
    span = [e for e in host if e.name == WINDOW]
    if span:
        lo, hi = span[0].start, span[0].end
    else:
        lo = min((e.start for e in host), default=0)
        hi = max((e.end for e in host), default=0)
    w = TR.Window(ops=ops, modules=modules, host=host, lo=lo, hi=hi)
    return w, engine, args


# the readers of one run share one parse of its profile: each reader is
# loaded as a module of its own, and they are handed nothing but ``ctx``
_CACHE: Dict[tuple, Optional[Steps]] = {}

# the engine's counters beside the probe's names for the same quantity
PROBE_NAMES = {"steps": "steps", "decode_steps": "decode_steps",
               "decode_rows": "decode_rows", "decode_ctx_keys": "decode_ctx",
               "prompts_completed": "prompts_done",
               "prefill_causal_keys": "prefill_keys"}


def of(ctx) -> Optional[Steps]:
    """The window's reduction: ``ctx["engine_steps"]`` if given, else from
    the traced run's profile (read once per profile, and logged)."""
    if "engine_steps" in ctx:
        return ctx["engine_steps"]
    files = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    key = (newest, os.path.getmtime(newest))
    if key not in _CACHE:
        _CACHE[key] = st = reduce(*load(TRACE_DIR))
        _log(st, ctx.get("counters") or {})
    return _CACHE[key]


def _log(st: Optional[Steps], probe: dict) -> None:
    if st is None:
        msg = "no engine.step span in the traced window"
    else:
        n, c = max(st.steps, 1), st.counters
        side = {k: (c.get(k), probe.get(p)) for k, p in PROBE_NAMES.items()}
        msg = (f"engine steps begun in the window {st.steps}; idle s by "
               f"engine phase {st.idle_s}; longest gaps by phase "
               f"{st.longest}; per step: h2d transfers "
               f"{c.get('h2d_transfers', 0) / n}, h2d bytes "
               f"{c.get('h2d_bytes', 0) / n}; engine counters over the "
               f"window's steps {c}; engine vs probe (its window) {side}")
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def per_step(st: Optional[Steps], counter: str) -> Optional[float]:
    """A step counter's growth per step over the window's steps."""
    if st is None or counter not in st.counters \
            or st.counters.get("steps", 0) <= 0:
        return None
    return st.counters[counter] / st.counters["steps"]


def step_idle_ms(ctx, phase: str) -> Optional[float]:
    st = of(ctx)
    if st is None or st.steps <= 0:
        return None
    return 1e3 * st.idle_s[phase] / st.steps

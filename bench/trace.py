"""Reduction of a profiler trace to the benchmark's device numbers.

Input: the events of one traced window.  Device ops and device programs
(the profiler's "XLA Ops" and "XLA Modules" lines of each device plane)
and the benchmark's own host spans (``TraceAnnotation`` names starting
with ``bench.``), all on the profiler's one clock.

* busy time: the union of the device op intervals inside the window;
* idle gaps: the complement, each labelled with the host span that covers
  most of it (the innermost on a tie), or "no host span";
* kernel calls: every Pallas call (a ``tpu_custom_call`` op) named by the
  kernel its HLO instruction came from, and by the kind of step program
  (decode or prefill) that ran it.  The names come from the compiled step
  programs' own HLO (``kernel_index``): its stack-frame tables say which
  source file each ``pallas_call`` was made in, which tells kernels apart
  although their bodies share one function name.  A program that calls the
  flash prefill kernel is a prefill program, one that calls the decode
  attention kernel otherwise is a decode program.  An op is matched by its
  instruction name and result shape; a device program's kind is the kind
  most of its matched ops belong to;
* program time: the executions of each kind of step program that lie
  inside the window, and their device time.

Ops on the "XLA Ops" line nest (a loop op holds its body's ops), so time
by op is exclusive time: an op's duration less that of the ops inside it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIX = "bench."
# source file of a Pallas call -> kernel name
KERNEL_FILES = {
    "w4a8_matmul.py": "w4a8_matmul",
    "quant_attention.py": "quant_decode_attention",
    "flash_prefill.py": "flash_prefill_attention",
    "rmsnorm.py": "rmsnorm",
    "grouped_matmul.py": "grouped_matmul",
}
_CC_KEY = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int            # ns
    end: int              # ns


@dataclasses.dataclass
class Window:
    ops: List[Ev]         # device ops
    modules: List[Ev]     # device programs
    host: List[Ev]        # benchmark host spans
    lo: int
    hi: int


# --- interval arithmetic ---------------------------------------------------

def clip(evs: Iterable[Ev], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(w: Window) -> int:
    return sum(t - s for s, t in union(clip(w.ops, w.lo, w.hi)))


def gaps(w: Window) -> List[Tuple[int, int]]:
    out, cur = [], w.lo
    for s, t in union(clip(w.ops, w.lo, w.hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if w.hi > cur:
        out.append((cur, w.hi))
    return out


def label(gap: Tuple[int, int], host: List[Ev]) -> str:
    best, key = "no host span", (0, 0)
    for e in host:
        ov = min(gap[1], e.end) - max(gap[0], e.start)
        if ov <= 0:
            continue
        k = (ov, -(e.end - e.start))
        if k > key:
            best, key = e.name, k
    return best


def idle_by_label(w: Window) -> Dict[str, float]:
    """Idle seconds of the window by the host span that covers each gap."""
    out: Dict[str, float] = collections.defaultdict(float)
    for g in gaps(w):
        out[label(g, w.host)] += (g[1] - g[0]) * 1e-9
    return dict(out)


def longest_gaps(w: Window, n: int = 10) -> List[Tuple[str, float]]:
    gs = sorted(gaps(w), key=lambda g: g[0] - g[1])[:n]
    return [(label(g, w.host), (g[1] - g[0]) * 1e-9) for g in gs]


def module_of(ops: List[Ev], modules: List[Ev]) -> List[Optional[Ev]]:
    """The device program whose interval holds each op's start."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        out.append(mods[i] if i >= 0 and mods[i].end >= op.start else None)
    return out


# --- names -------------------------------------------------------------------

def op_key(text: str) -> Optional[str]:
    """A Pallas call's instruction name and result shape, as both the
    compiled HLO and the trace's op name spell them."""
    text = text.strip()
    if _CC_KEY not in text:
        return None
    return text.split(" custom-call(")[0]


def _table(lines: List[str], header: str) -> Dict[int, str]:
    out, on = {}, False
    for ln in lines:
        if ln == header:
            on = True
            continue
        if on:
            m = re.match(r"^(\d+) (.*)$", ln)
            if not m:
                break
            out[int(m.group(1))] = m.group(2)
    return out


def program_kind(kernels: Iterable[str]) -> Optional[str]:
    """The kind of step program that calls these kernels."""
    kernels = set(kernels)
    if "flash_prefill_attention" in kernels:
        return "prefill"
    if "quant_decode_attention" in kernels:
        return "decode"
    return None


def kernel_index(hlo_text: str) -> Dict[str, set]:
    """{op key: {(kernel, kind)}} for every Pallas call of one compiled
    program, its kernel found from the source file in its stack frames and
    the program's kind from its kernels (``program_kind``); empty for a
    program of no kind.  Merge the indexes of several programs with
    ``merge``."""
    lines = hlo_text.splitlines()
    files = {i: v.strip('"') for i, v in _table(lines, "FileNames").items()}
    locs = {i: int(re.search(r"file_name_id=(\d+)", v).group(1))
            for i, v in _table(lines, "FileLocations").items()}
    frames = {}
    for i, v in _table(lines, "StackFrames").items():
        m = re.search(r"file_location_id=(\d+) parent_frame_id=(\d+)", v)
        frames[i] = (int(m.group(1)), int(m.group(2)))
    out = {}
    for ln in lines:
        key = op_key(ln)
        if key is None:
            continue
        m = re.search(r"stack_frame_id=(\d+)", ln)
        kernel, seen, fid = "pallas_call", set(), int(m.group(1)) if m else 0
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = frames[fid]
            base = os.path.basename(files.get(locs.get(loc, -1), ""))
            if base in KERNEL_FILES:
                kernel = KERNEL_FILES[base]
                break
            fid = parent
        out[key] = kernel
    kind = program_kind(out.values())
    if kind is None:
        return {}
    return {key: {(kernel, kind)} for key, kernel in out.items()}


def merge(index: Dict[str, set], more: Dict[str, set]) -> None:
    for k, v in more.items():
        index.setdefault(k, set()).update(v)


def exclusive(ops: List[Ev]) -> List[int]:
    """Each op's duration less that of the ops directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start,
                                                   -ops[i].end))
    excl = [e.end - e.start for e in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack and ops[i].end <= ops[stack[-1]].end:
            excl[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return excl


def module_kinds(w: Window, index: Dict[str, set],
                 mods: List[Optional[Ev]] = None) -> Dict[Ev, str]:
    """The kind of each device program: the kind most of its ops that the
    index names unambiguously belong to."""
    mods = module_of(w.ops, w.modules) if mods is None else mods
    votes: Dict[Ev, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for op, mod in zip(w.ops, mods):
        key = op_key(op.name)
        kinds = {k for _, k in index.get(key, ())} if key else set()
        if mod is not None and len(kinds) == 1:
            votes[mod][kinds.pop()] += 1
    return {m: v.most_common(1)[0][0] for m, v in votes.items()}


def inside(e: Ev, w: Window) -> bool:
    return w.lo <= e.start and e.end <= w.hi


def program_time(w: Window, index: Dict[str, set]) -> Dict[str, tuple]:
    """{kind: (executions, device seconds)} of the step programs of each
    kind that ran wholly inside the window."""
    out: Dict[str, list] = collections.defaultdict(lambda: [0, 0.0])
    for mod, kind in module_kinds(w, index).items():
        if inside(mod, w):
            out[kind][0] += 1
            out[kind][1] += (mod.end - mod.start) * 1e-9
    return {k: tuple(v) for k, v in out.items()}


def attribute(w: Window, index: Dict[str, set]):
    """[(op, kernel or None, kind or None)]: the kernel of each Pallas call
    the index names, and the kind of the program each op ran in."""
    mods = module_of(w.ops, w.modules)
    kinds = module_kinds(w, index, mods)
    out = []
    for op, mod in zip(w.ops, mods):
        kind = kinds.get(mod)
        key = op_key(op.name)
        kernels = {kn for kn, k in index.get(key, ()) if k == kind} \
            if key else set()
        out.append((op, kernels.pop() if len(kernels) == 1 else None, kind))
    return out


def short_name(op: Ev) -> str:
    """An op's instruction name without its number: 'fusion', 'copy'..."""
    m = re.match(r"%?([A-Za-z_\-]+?)(?:[.\d]*)\s*=", op.name.strip())
    return m.group(1) if m else op.name.split(" ")[0][:40]


def device_ops(w: Window, index: Dict[str, set],
               n: int = 10) -> List[Tuple[str, float]]:
    """Exclusive device time of the ops inside the window, grouped by
    kernel (Pallas calls) or by program and op name (everything else),
    the largest first."""
    tot: Dict[str, float] = collections.defaultdict(float)
    mods = module_of(w.ops, w.modules)
    for (op, kernel, kind), mod, ex in zip(attribute(w, index), mods,
                                           exclusive(w.ops)):
        if op.start < w.lo or op.end > w.hi:
            continue
        if kernel is not None:
            name = f"{kind}:{kernel}"
        else:
            prog = kind or (mod.name.split("(")[0] if mod else "no program")
            name = f"{prog}:{short_name(op)}"
        tot[name] += ex * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def kernel_calls(w: Window, index: Dict[str, set]):
    """[(kernel, kind, op text, seconds)] for every Pallas call that the
    index names, made by a program that ran wholly inside the window (the
    same executions ``program_time`` counts)."""
    mods = module_of(w.ops, w.modules)
    return [(kernel, kind, op.name, (op.end - op.start) * 1e-9)
            for (op, kernel, kind), mod in zip(attribute(w, index), mods)
            if kernel is not None and inside(mod or op, w)]


# --- loading -----------------------------------------------------------------

def load(log_dir: str, lo: int = None, hi: int = None) -> Window:
    """Read the newest ``.xplane.pb`` under ``log_dir``.  ``lo``/``hi``
    bound the window in the profile's clock; by default the first and last
    benchmark host span."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dst.append(Ev(e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = int(e.start_ns)
                        host.append(Ev(e.name, s, s + int(e.duration_ns)))
    if lo is None:
        lo = min((e.start for e in host), default=0)
    if hi is None:
        hi = max((e.end for e in host), default=0)
    return Window(ops=ops, modules=modules, host=host, lo=lo, hi=hi)

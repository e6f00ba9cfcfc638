#!/usr/bin/env python3
"""Chip benchmark of the serving path: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, with the cell's own rate and limits in
``bench/cells/<cell>.json``).  A run builds the model from the seed on the
device, warms up the engine's step programs, and drives the real serving
path: client threads -> ``EngineService.submit`` -> ``EngineLoop.step`` ->
the jitted chunk and bucketed decode programs on dispatch backend ``tpu``.
An open loop's window opens after its pre-roll; a closed batch's once every
request of the batch has its first token and the engine step that finished
the last prompt has ended, so nothing prefills inside it.  It measures for
``--seconds`` seconds, then checks the served tokens against the plain
reference (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics, taken on the client's
clock; ``--trace 1`` traces the window with the profiler and prints the
cell's per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The
last line of stdout is one JSON object.  With no TPU, with a kernel that
fell back to the reference path, or with a compilation inside the window,
the run exits non-zero and prints no result.

``--rate`` overrides an open loop's offered rate and ``--check 0`` skips
the reference comparison and prints no result, for finding the knee;
``--control 1`` puts the 4-bit control in the program's place in the
comparison (on the same prompts and served tokens), so a run reads the
control's number against the limit and comes out not correct.  None of
them is used by a measured run.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402
from typing import Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_CAP_S = 20.0        # a traced run traces at most this much window
FIRST_TOKEN_WAIT_S = 60.0  # a minute past the close for late first tokens
SETUP_LIMIT_S = 1200.0    # warm-up requests and a batch's prefill end by then
OCCUPANCY_EVERY_S = 5.0   # the gateway's queue and rows, read this often
SPAN = "bench."


class BenchError(Exception):
    """A run that must not print a result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Opts:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: str = ""
    mix: str = ""
    chips: int = 1
    spec: dict = None
    metrics: list = None
    rate: Optional[float] = None
    check: bool = True
    control: bool = False
    backend: str = "tpu"
    mix_spec: Optional[dict] = None     # a mix and cell file given inline
    own: Optional[dict] = None


def parse(argv=None) -> Opts:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--check", type=int, choices=(0, 1), default=1)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        raise BenchError("--seed must be a non-negative whole number")
    return Opts(workload=a.workload, seed=a.seed, seconds=a.seconds,
                trace=bool(a.trace), rate=a.rate, check=bool(a.check),
                control=bool(a.control))


def resolve(opts: Opts) -> Opts:
    """Fill the cell's configuration, mix and metrics from BENCHMARK.json."""
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"{path} not found")
    bench = json.loads(path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if opts.workload not in cells:
        raise BenchError(f"unknown workload {opts.workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[opts.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    opts.config, opts.mix, opts.chips = (cell["config"], cell["traffic"],
                                         int(cell["chips"]))
    with open(ROOT / conf["file"]) as f:
        opts.spec = json.load(f)
    group = bench["per_layer"] if opts.trace else bench["end_to_end"]
    opts.metrics = [m for m in group
                    if opts.workload in m.get("workloads", [opts.workload])]
    return opts


def program_path() -> None:
    src = ROOT / "src"
    if not (src / "repro" / "serving" / "engine.py").exists():
        raise BenchError(f"the program under test is not in {src}")
    for p in (str(ROOT), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)


def compile_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileWatch:
    """Counts JAX tracing, lowering and backend compilation events, with
    their times, through ``jax.monitoring``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.times = []
        self.names = []
        self._mu = threading.Lock()

        def listen(event, duration, **kw):
            if event in self.EVENTS:
                with self._mu:
                    self.times.append(time.perf_counter())
                    self.names.append(str(kw.get("fun_name", "?")))
        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, a: float, b: float) -> list:
        """Names of the functions compiled between two host times."""
        with self._mu:
            return [n for t, n in zip(self.times, self.names) if a <= t <= b]


class _Wrapped:
    """A jitted program of the loop, with a host span around each call;
    while the probe traces, the argument shapes of each distinct call are
    kept.  Everything else (``lower``, ``_cache_size``) passes through."""

    def __init__(self, fn, name, probe):
        self._fn, self._name, self._probe = fn, name, probe

    def __call__(self, *args, **kw):
        import jax
        if self._probe.tracing:
            self._probe.keep(self._fn, self._name, args, kw)
        with jax.profiler.TraceAnnotation(self._name):
            return self._fn(*args, **kw)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class Probe:
    """The benchmark's spans and counters around the engine, installed in a
    traced run only and set from outside the program.

    ``EngineLoop.step`` and ``Engine.embed`` get host spans (``bench.*``)
    on the profiler's clock, and so does every call of each jitted program
    the loop holds, whose argument shapes are kept for ``kernel_index``.
    After each step the probe reads the loop's public state: the rows that
    decoded (running requests that received a token in the step) with the
    context each attended over (``pool.row_pos``), the prompts the step
    completed, the live rows and the KV pages in use, and the engine's
    token and chunk counters."""

    def __init__(self, loop):
        import jax
        self.loop, self.eng = loop, loop.eng
        self.c = dict(steps=0, decode_steps=0, decode_rows=0, decode_ctx=0,
                      prompts_done=0, prefill_keys=0, live_rows=0,
                      pages_used=0, decode_tokens=0, prefill_tokens=0,
                      prefill_chunks=0)
        self._mu = threading.Lock()
        self.tracing = False
        self.seen = {}
        for name, fn in list(vars(loop).items()):
            if callable(fn) and hasattr(fn, "lower"):
                setattr(loop, name, _Wrapped(fn, SPAN + name.lstrip("_"),
                                             self))
        embed = self.eng.embed

        def embedded(*a, **kw):
            with jax.profiler.TraceAnnotation(SPAN + "embed"):
                return embed(*a, **kw)
        self.eng.embed = embedded
        step = loop.step

        def stepped():
            before = {r.uid: len(r.generated)
                      for r in loop.scheduler.running if r is not None}
            with jax.profiler.TraceAnnotation(SPAN + "step"):
                out = step()
            self._count(before)
            return out
        loop.step = stepped

    def _count(self, before: dict) -> None:
        loop, s = self.loop, self.eng.stats
        pos = loop.pool.row_pos
        rows = ctx = live = done = keys = 0
        for r in loop.scheduler.running:
            if r is None:
                continue
            live += 1
            n0 = before.get(r.uid, 0)
            if len(r.generated) > n0:     # a token this step, then decoded
                rows += 1
                ctx += int(pos[r.slot])
                if n0 == 0:               # its prompt completed this step
                    t = len(r.prompt_tokens)
                    done += 1
                    keys += t * (t + 1) // 2
        with self._mu:
            c = self.c
            c["steps"] += 1
            c["decode_steps"] += rows > 0
            c["decode_rows"] += rows
            c["decode_ctx"] += ctx
            c["prompts_done"] += done
            c["prefill_keys"] += keys
            c["live_rows"] += live
            c["pages_used"] += loop.geom.num_pages - loop.pool.free_pages
            c.update(decode_tokens=s.decode_tokens,
                     prefill_tokens=s.prefill_tokens,
                     prefill_chunks=s.prefill_chunks)

    def snapshot(self) -> dict:
        with self._mu:
            return dict(self.c)

    def keep(self, fn, name: str, args, kw) -> None:
        import jax
        key = (name, tuple((getattr(x, "shape", None),
                            str(getattr(x, "dtype", type(x))))
                           for x in jax.tree.leaves((args, kw))))
        if key not in self.seen:
            sds = lambda a: (jax.ShapeDtypeStruct(  # noqa: E731
                a.shape, a.dtype, sharding=a.sharding)
                if isinstance(a, jax.Array) else a)
            self.seen[key] = (fn, jax.tree.map(sds, (args, kw)))

    def kernel_index(self) -> dict:
        """Kernel names of every Pallas call in the programs called while
        tracing, from their compiled HLO."""
        from bench import trace as TR
        index = {}
        for fn, (args, kw) in self.seen.values():
            TR.merge(index, TR.kernel_index(
                fn.lower(*args, **kw).compile().as_text()))
        return index


class Occupancy:
    """The gateway's waiting queue, live rows and KV pages in use, read
    from the loop's public state every ``OCCUPANCY_EVERY_S`` seconds of
    the window.  Read without the engine lock: the engine thread takes it
    back as soon as a step ends, so a reader waiting for it can wait until
    the engine runs out of work."""

    def __init__(self, loop, t0: float):
        self.loop, self.next, self.rows = loop, t0, []

    def until(self, t: float) -> None:
        while True:
            now = time.perf_counter()
            if self.next <= min(now, t):
                sched = self.loop.scheduler
                self.rows.append((len(sched.waiting),
                                  sum(r is not None for r in sched.running),
                                  self.loop.geom.num_pages
                                  - self.loop.pool.free_pages))
                self.next += OCCUPANCY_EVERY_S
                continue
            if now >= t:
                return
            time.sleep(min(t, self.next) - now)

    def summary(self) -> dict:
        import numpy as np
        if not self.rows:
            return {}
        x = np.asarray(self.rows, np.float64)
        return {"waiting": [int(v) for v in x[:, 0]],
                "mean_live_rows": float(x[:, 1].mean()),
                "mean_kv_pages_in_use": float(x[:, 2].mean()),
                "kv_pages": int(self.loop.geom.num_pages)}


def _pct(xs, p) -> Optional[float]:
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else None


def end_to_end(records, t0: float, t1: float, setup_s: float) -> dict:
    """Client-side numbers of the window: TTFT of the requests due in it
    (from their due time), every gap between consecutive tokens received in
    it, and the tokens received in it per second."""
    due = [r for r in records if t0 <= r.due < t1]
    ttft = [(r.stamps[0] if r.stamps else t1 + FIRST_TOKEN_WAIT_S) - r.due
            for r in due]
    itl = [(b - a) * 1e3 for r in records
           for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b < t1]
    toks = sum(t0 <= s < t1 for r in records for s in r.stamps)
    return {"setup_s": setup_s, "ttft_p50_s": _pct(ttft, 50),
            "itl_p95_ms": _pct(itl, 95),
            "output_tok_per_s": toks / (t1 - t0),
            "_requests": len(due), "_gaps": len(itl),
            "_ttft_p90_s": _pct(ttft, 90), "_itl_p50_ms": _pct(itl, 50)}


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def mix_of(opts: Opts) -> tuple:
    from bench import traffic as TF
    if opts.mix_spec is not None:
        return opts.mix_spec, opts.own or {}
    return TF.load(opts.mix, opts.workload)


def limits_of(opts: Opts) -> dict:
    lim = mix_of(opts)[1].get("limits", {})
    if "served_logit_gap" not in lim:
        raise BenchError(f"bench/cells/{opts.workload}.json sets no "
                         f"served_logit_gap limit")
    return lim


def run(opts: Opts) -> dict:
    """Everything after the look for the chip."""
    import jax
    import numpy as np
    from bench import check as CK
    from bench import client as CL
    from bench import model as MD
    from bench import peaks as PK
    from bench import traffic as TF
    from repro.serving import engine as E
    from repro.serving import gateway as G
    from repro.serving import sampling as SM

    log(f"compile cache {compile_cache()}")
    watch = CompileWatch()
    limits = limits_of(opts) if opts.check else {}
    dev = jax.devices()[0]
    peaks = PK.peaks_for(dev.device_kind) if opts.backend == "tpu" else None
    cfg = MD.model_config(opts.spec)
    mix, own = mix_of(opts)
    tr = TF.build(mix, own, opts.seed, opts.seconds, cfg.vocab_size,
                  opts.mix, rate_per_s=opts.rate)
    eng_o = tr.engine

    params = MD.make_params(cfg, opts.seed)
    jax.block_until_ready(params)
    store = MD.embedding_store(cfg)
    eng = E.Engine(cfg, params, store, max_seq=eng_o["max_seq"],
                   backend=opts.backend)
    del params
    if eng.dispatch.backend != opts.backend:
        raise BenchError(f"dispatcher resolved {eng.dispatch.backend!r}")
    loop = E.EngineLoop(eng, max_slots=eng_o["slots"],
                        prefill_chunk=eng_o["prefill_chunk"])
    rep = loop.warmup()
    log(f"warmup {rep}")
    probe = Probe(loop) if opts.trace else None
    service = G.EngineService(loop, warmup=False).start()
    big = 1 << 30
    client = CL.Client(
        service, SM.SamplingParams(temperature=0.0, max_new_tokens=big),
        SM.SamplingParams(temperature=float(tr.sample.get("temperature", 1.0)),
                          top_k=int(tr.sample.get("top_k", 0)),
                          max_new_tokens=big))
    warm_traffic(client, rep["chunk_sizes"], eng_o["slots"], cfg.vocab_size,
                 opts.seed)
    t_start = time.perf_counter() + 0.2
    client.start(tr.requests, t_start)
    if tr.arrival == "batch":
        t0 = prefill_batch(client, eng.stats)
        log(f"batch of {len(tr.requests)} prefilled in {t0 - t_start} s")
    else:
        t0 = t_start + tr.preroll_s
        time.sleep(max(0.0, t0 - time.perf_counter()))
    setup_s = t0 - T_PROC
    chunks0 = eng.stats.prefill_chunks

    # --- the window -----------------------------------------------------
    t1 = t0 + opts.seconds
    occ = Occupancy(loop, t0)
    tdir = ROOT / "bench" / ".state" / "trace"
    traced = None
    if opts.trace:
        c0 = probe.snapshot()
        probe.tracing = True
        shutil.rmtree(tdir, ignore_errors=True)
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        po.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=po)
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN + "window"):
            occ.until(min(t1, ta + TRACE_CAP_S))
        c_tr = probe.snapshot()
        probe.tracing = False
        jax.profiler.stop_trace()
        traced = (c0, c_tr)
    occ.until(t1)
    in_window = watch.between(t0, t1)
    chunks = eng.stats.prefill_chunks - chunks0

    # --- after the window ------------------------------------------------
    # first tokens of the requests due in the window (a batch has none)
    end = t1 + FIRST_TOKEN_WAIT_S
    due = [r for r in client.records if t0 <= r.due < t1]
    while time.perf_counter() < end and not all(
            r.stamps or r.error for r in due):
        time.sleep(0.05)
    client.stop()
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    s = eng.stats
    log(f"generator lateness {client.lateness()}; sent "
        f"{sum(r.sent > 0 for r in client.records)}, succeeded "
        f"{sum(r.done for r in client.records)} (in the window "
        f"{sum(r.done and t0 <= r.stamps[-1] < t1 for r in client.records)}"
        f"), failed {sum(r.error is not None for r in client.records)}; "
        f"prefill chunks in the window {chunks}")
    log(f"occupancy in the window {occ.summary()}")
    log(f"compile events in the window {in_window}; recompiles_after_warmup "
        f"{s.recompiles_after_warmup}; spilled pages {s.spilled_pages} + "
        f"{s.cold_spilled_pages}; fallbacks {eng.dispatch.fallbacks}")
    if eng.dispatch.fallbacks:
        raise BenchError(f"kernels fell back: {eng.dispatch.fallbacks}")
    if in_window or s.recompiles_after_warmup:
        raise BenchError(f"compile events inside the window: {in_window}; "
                         f"{s.recompiles_after_warmup} step programs "
                         f"compiled after warmup")

    metrics, device_extra, breakdown = {}, {}, None
    e2e = end_to_end(client.records, t0, t1, setup_s)
    log(f"end to end {e2e}")
    if opts.trace:
        metrics, device_extra, breakdown = traced_metrics(
            opts, cfg, peaks, probe, traced, tdir)
    else:
        units = {m["name"]: m["unit"] for m in opts.metrics}
        for name, unit in units.items():
            if e2e.get(name) is None:
                raise BenchError(f"{name}: nothing to measure in the window")
            metrics[name] = {"value": e2e[name], "unit": unit}

    records = client.records
    attempted = records if tr.arrival == "batch" else [
        r for r in records if t0 <= r.due < t1]
    failed = sum(r.error is not None or not r.stamps for r in attempted)
    # the engine thread retakes the service's lock as soon as a step ends,
    # so close() could wait for the last running request to finish; the
    # stop flag set first ends the engine thread after its step in flight
    tc = time.perf_counter()
    service._stop = True
    service.close()
    log(f"engine closed in {time.perf_counter() - tc} s")
    client.join_readers()
    del service, loop, eng, probe, store
    gc.collect()

    if not opts.check:
        log("no comparison was made (--check 0): no result")
        return None
    picked = CK.sample(records, opts.seed)
    if not picked:
        raise BenchError("no greedy request was served")
    weights = MD.logical_layers(cfg, MD.make_params(cfg, opts.seed))
    table = MD.embedding_array(cfg)
    rows = lambda ids: np.asarray(table[ids], np.float32)  # noqa: E731
    widest, ctl = CK.compare(opts.spec, weights, rows,
                             [r.prompt for r in picked],
                             [r.tokens for r in picked], opts.control)
    if ctl is not None:
        log(f"program's served_logit_gap {widest!r}; the control (4-bit "
            f"activations) takes its place: {ctl!r}")
        widest = ctl
    lim = float(limits["served_logit_gap"])
    checks = {"served_logit_gap": {"value": widest, "limit": lim}}
    correct = widest <= lim
    log(f"checked {len(picked)} greedy requests, "
        f"{sum(len(r.tokens) for r in picked)} served tokens, longest "
        f"{len(picked[0].tokens)} after a prompt of "
        f"{len(picked[0].prompt)}")
    result = {
        "correct": bool(correct), "attempted": len(attempted),
        "failed": int(failed), "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(mem), **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def warm_traffic(client, chunk_sizes, slots: int, vocab: int,
                 seed: int) -> None:
    """Serve a few short requests through the gateway before the traffic
    starts, so that every host-side program of the serving path (greedy,
    sampled and mixed sampling, first tokens, finished rows) is compiled in
    set-up and never in the window: one greedy and one sampled prompt per
    prefill chunk size and one of two chunks, more requests than slots."""
    import numpy as np
    from bench import traffic as TF
    rng = np.random.default_rng(seed)
    lens = list(chunk_sizes) + [max(chunk_sizes) + 1]
    lens = (lens * (1 + (2 * slots) // (2 * len(lens))))[:max(len(lens),
                                                              slots + 1)]
    reqs = [TF.Req(index=-1 - i, due_s=0.0,
                   prompt=rng.integers(1, vocab, size=n), max_new=4,
                   greedy=bool(i % 2))
            for i, n in enumerate(x for n in lens for x in (n, n))]
    client.start(reqs, time.perf_counter())
    deadline = time.perf_counter() + SETUP_LIMIT_S
    while not all(r.done or r.error for r in client.records):
        if time.perf_counter() > deadline:
            raise BenchError("warm-up requests did not finish")
        time.sleep(0.02)
    bad = [r.error for r in client.records if r.error]
    if bad:
        raise BenchError(f"warm-up requests failed: {bad[:3]}")
    client.stop()
    client.join_readers()
    client.reset()


def prefill_batch(client, stats) -> float:
    """Wait until every request of a closed batch has its first token (or
    has failed), then for the engine step in flight to end: the engine
    counts a step (``EngineStats.steps``) as it begins, so the step that
    finished the last prompt has ended once the count passes the one read
    after that prompt's first token.  Returns the host time at which the
    window opens."""
    deadline = time.perf_counter() + SETUP_LIMIT_S

    def wait(until, what):
        while not until():
            if time.perf_counter() > deadline:
                raise BenchError(f"{what} within {SETUP_LIMIT_S} s")
            time.sleep(0.005)
    wait(lambda: all(r.stamps or r.error for r in client.records),
         "the batch was not prefilled")
    begun = stats.steps
    wait(lambda: stats.steps > begun or all(r.done or r.error for r in
                                            client.records),
         "the engine step after the batch's prefill did not begin")
    return time.perf_counter()


def traced_metrics(opts, cfg, peaks, probe, traced, tdir):
    from bench import costs
    from bench import trace as TR
    c0, c1 = traced
    index = probe.kernel_index()
    w = TR.load(str(tdir))
    span = [e for e in w.host if e.name == SPAN + "window"]
    if span:
        w.lo, w.hi = span[0].start, span[0].end
    window_s = (w.hi - w.lo) * 1e-9
    busy_s = TR.busy_ns(w) * 1e-9
    if busy_s <= 0:
        raise BenchError("the traced window holds no device operation")
    counters = {k: c1[k] - c0[k] for k in c1}
    ctx = dict(cfg=cfg, spec=opts.spec, peaks=peaks, costs=costs,
               counters=counters, busy_s=busy_s, window_s=window_s,
               calls=TR.kernel_calls(w, index),
               programs=TR.program_time(w, index))
    metrics = {}
    for m in opts.metrics:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {"device_ops": [[n, s] for n, s in TR.device_ops(w, index)],
                 "idle_gaps": [[n, s] for n, s in TR.longest_gaps(w)]}
    steps = max(counters["steps"], 1)
    log(f"trace: window {window_s} s, busy {busy_s} s, idle by host span "
        f"{TR.idle_by_label(w)}; device programs (count, seconds) by kind "
        f"{ctx['programs']}; counters {counters}; per step: live rows "
        f"{counters['live_rows'] / steps}, KV pages in use "
        f"{counters['pages_used'] / steps}")
    shutil.rmtree(tdir, ignore_errors=True)
    return metrics, {"busy_s": busy_s, "window_s": window_s}, breakdown


def main(argv=None) -> int:
    try:
        opts = resolve(parse(argv))
        program_path()
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
        if len(devs) < opts.chips:
            raise BenchError(f"the cell needs {opts.chips} chips; JAX found "
                             f"{len(devs)}")
        result = run(opts)
    except BenchError as e:
        log(f"FAILED: {e}")
        return 3
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The engine step metrics: the window's idle time cut by the program's
own phase spans, the step counters carried on its step spans, and those
counters against the probe's on the same run."""
import jax
import numpy as np
import pytest

from bench import engine_steps as ES
from bench import model
from bench import run as R
from bench import trace as TR
from bench.tests.conftest import TINY_MIX, TINY_SPEC
from repro.serving import engine as E
from repro.serving import sampling as SM
from repro.serving.scheduler import Request

Ev = TR.Ev
IDLE = ["step_idle_ms." + p for p in (*ES.PHASES, "other")]


def synthetic():
    """Two steps over a 100 ns window with device ops at 0-10, 30-40 and
    70-80.  The gap 10-30 falls under admit (10-12) and prefill (12-30);
    58-60 is the first step's own time after its decode, 95-100 the
    second's after its decode."""
    ops = [Ev("a", 0, 10), Ev("b", 30, 40), Ev("c", 70, 80)]
    s1, s2 = Ev("engine.step", 5, 60), Ev("engine.step", 60, 100)
    engine = [s1, Ev("engine.admit", 5, 12), Ev("engine.prefill", 12, 35),
              Ev("engine.sample", 35, 45), Ev("engine.commit", 45, 50),
              Ev("engine.decode", 50, 58),
              s2, Ev("engine.admit", 60, 62), Ev("engine.decode", 62, 95)]
    args = {s1: {"_r": 1, "step_num": 7, "steps": 7, "h2d_bytes": 1000,
                 "decode_rows": 10, "decode_bucket_rows": 16},
            s2: {"_r": 1, "step_num": 8, "steps": 8, "h2d_bytes": 5096,
                 "decode_rows": 13, "decode_bucket_rows": 20}}
    w = TR.Window(ops=ops, modules=[], host=[Ev("bench.window", 0, 100)],
                  lo=0, hi=100)
    return w, engine, args


def test_the_six_idle_readers_add_up_to_the_idle_time():
    w, engine, args = synthetic()
    st = ES.reduce(w, engine, args)
    assert st.steps == 2
    # exact in ns: a gap shared by two phases is split between them, not
    # given to the phase that covers most of it
    ns = {k: round(v * 1e9) for k, v in st.idle_s.items()}
    assert ns == {"admit": 2 + 2, "prefill": 18, "sample": 5, "commit": 5,
                  "decode": 8 + 8 + 15, "other": 2 + 5}
    # labelling would give the whole gap to the phase covering most of it
    phases = [e for e in engine if e.name != "engine.step"]
    assert TR.label((10, 30), phases) == "engine.prefill"
    busy_s, window_s = TR.busy_ns(w) * 1e-9, 100e-9
    ctx = {"engine_steps": st, "busy_s": busy_s, "window_s": window_s}
    per_step = [R.load_reader(m)(ctx) for m in IDLE]
    idle_share = R.load_reader("device_idle_share")(ctx)
    assert sum(per_step) * st.steps == pytest.approx(
        idle_share / 100 * window_s * 1e3, rel=1e-12)
    assert per_step[1] == pytest.approx(1e3 * 18e-9 / 2)
    # the longest gap, 40-70, split by phase
    gap, split = st.longest[0]
    assert round(gap * 1e9) == 30
    assert {k: round(v * 1e9) for k, v in split.items()} == {
        "sample": 5, "commit": 5, "decode": 16, "admit": 2, "other": 2}


def test_counter_readers_read_the_step_arguments():
    w, engine, args = synthetic()
    st = ES.reduce(w, engine, args)
    # the growth from the first step that began in the window to the last
    assert st.counters == {"steps": 1, "h2d_bytes": 4096, "decode_rows": 3,
                           "decode_bucket_rows": 4}
    ctx = {"engine_steps": st}
    assert R.load_reader("h2d_kb_per_step")(ctx) == pytest.approx(4.096)
    assert R.load_reader("decode_bucket_fill")(ctx) == pytest.approx(75.0)


def test_a_program_without_engine_spans_reports_nothing():
    w, _, _ = synthetic()
    assert ES.reduce(w, [], {}) is None
    ctx = {"engine_steps": None, "busy_s": 1.0, "window_s": 2.0}
    for m in (*IDLE, "h2d_kb_per_step", "decode_bucket_fill"):
        assert R.load_reader(m)(ctx) is None


def _tiny_loop():
    cfg = model.model_config(TINY_SPEC)
    eng = E.Engine(cfg, model.make_params(cfg, 7), model.embedding_store(cfg),
                   max_seq=TINY_MIX["engine"]["max_seq"], backend="reference")
    return E.EngineLoop(eng, max_slots=4, prefill_chunk=32)


def _submit(loop, seed):
    rng = np.random.default_rng(seed)
    lens, news = [9, 40, 70, 33, 12], [5, 9, 3, 7, 4]
    for i, (n, m) in enumerate(zip(lens, news)):
        loop.submit(Request(uid=100 * seed + i,
                            prompt_tokens=rng.integers(1, 500, n).tolist(),
                            max_new_tokens=m,
                            sampling=SM.SamplingParams(temperature=0.0)))


def test_engine_counters_equal_the_probes_and_the_trace_carries_them(
        tmp_path):
    loop = _tiny_loop()
    probe = R.Probe(loop)
    _submit(loop, 3)
    s = loop.eng.stats
    p0, e0 = probe.snapshot(), s.step_counters()
    before = []
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        while loop.has_work():
            before.append(s.step_counters())
            loop.step()
    jax.profiler.stop_trace()
    p = {k: v - p0[k] for k, v in probe.snapshot().items()}
    e = {k: v - e0[k] for k, v in s.step_counters().items()}
    for eng_name, probe_name in ES.PROBE_NAMES.items():
        assert e[eng_name] == p[probe_name], eng_name
    assert e["decode_rows"] == p["decode_tokens"]
    # the trace: the phases under their plain names, and the counters as
    # each step began on the step spans
    w, engine, args = ES.load(tmp_path)
    names = {ev.name for ev in engine}
    assert {"engine.step", "engine.admit", "engine.prefill", "engine.sample",
            "engine.commit", "engine.decode"} == names
    steps = sorted((ev for ev in engine if ev.name == "engine.step"),
                   key=lambda ev: ev.start)
    assert len(steps) == len(before)
    assert [{k: int(args[ev][k]) for k in e0} for ev in steps] == before
    st = ES.reduce(w, engine, args)
    assert st.steps == len(before)
    assert st.counters == {k: before[-1][k] - before[0][k] for k in e0}

"""The trace reduction: interval arithmetic, gap labels, nesting and
kernel attribution, on hand-made events and on a trace recorded on CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as TR

E = TR.Ev


def window(ops, modules=(), host=(), lo=0, hi=100):
    return TR.Window(ops=list(ops), modules=list(modules), host=list(host),
                     lo=lo, hi=hi)


def test_busy_is_the_union_clipped_to_the_window():
    ops = [E("a", -10, 10), E("b", 5, 20), E("c", 30, 40), E("d", 35, 38),
           E("e", 90, 120)]
    w = window(ops)
    assert TR.union(TR.clip(ops, 0, 100)) == [(0, 20), (30, 40), (90, 100)]
    assert TR.busy_ns(w) == 40
    assert TR.gaps(w) == [(20, 30), (40, 90)]
    assert TR.busy_ns(w) + sum(t - s for s, t in TR.gaps(w)) == 100


def test_gaps_are_labelled_by_the_host_span_covering_most():
    ops = [E("a", 0, 10), E("b", 50, 60)]
    host = [E("bench.step", 5, 100), E("bench.sample", 12, 45),
            E("bench.embed", 70, 80)]
    w = window(ops, host=host)
    # 10-50: the sample span covers 33 ns of it, the step 40: step wins
    assert TR.label((10, 50), host) == "bench.step"
    # a tie in cover goes to the innermost span
    assert TR.label((12, 45), host) == "bench.sample"
    assert TR.label((61, 69), [E("bench.embed", 70, 80)]) == "no host span"
    assert TR.longest_gaps(w, 1) == [("bench.step", 40e-9)]
    assert TR.idle_by_label(w) == pytest.approx({"bench.step": 80e-9})


def test_exclusive_time_of_nested_ops():
    ops = [E("%while.1 = ...", 0, 100), E("%a.1 = ...", 10, 30),
           E("%b.2 = ...", 40, 90), E("%c.3 = ...", 50, 60),
           E("%d.4 = ...", 120, 130)]
    assert TR.exclusive(ops) == [30, 20, 40, 10, 10]


CC = ('custom-call(%p), custom_call_target="tpu_custom_call", '
      'metadata={op_name="jit(f)/pallas_call" stack_frame_id={}}')
HLO = "\n".join([
    "HloModule jit__unknown", "", "FileNames",
    '1 "/x/src/repro/serving/engine.py"',
    '2 "/x/src/repro/kernels/w4a8_matmul.py"',
    '3 "/x/src/repro/kernels/quant_attention.py"', "",
    "FunctionNames", '1 "step"', '2 "w4a8_matmul"', "",
    "FileLocations",
    "1 {file_name_id=1 function_name_id=1 line=1 end_line=1}",
    "2 {file_name_id=2 function_name_id=2 line=2 end_line=2}",
    "3 {file_name_id=3 function_name_id=2 line=3 end_line=3}", "",
    "StackFrames",
    "1 {file_location_id=1 parent_frame_id=1}",
    "2 {file_location_id=2 parent_frame_id=1}",
    "3 {file_location_id=3 parent_frame_id=1}",
    "4 {file_location_id=1 parent_frame_id=3}", "",
    "ENTRY %main {",
    "  %closed_call.9 = f32[8,512]{1,0} " + CC.replace("{}", "2"),
    "  %closed_call.7 = f32[8,4,7,128]{3,2,1,0} " + CC.replace("{}", "4"),
    "}"])


def test_kernels_are_named_by_the_source_file_of_their_call():
    idx = TR.kernel_index(HLO)
    assert idx == {
        "%closed_call.9 = f32[8,512]{1,0}": {("w4a8_matmul", "decode")},
        "%closed_call.7 = f32[8,4,7,128]{3,2,1,0}":
            {("quant_decode_attention", "decode")}}
    # a program's kind follows from its kernels; one of no kind is left out
    assert TR.program_kind(["w4a8_matmul", "flash_prefill_attention",
                            "quant_decode_attention"]) == "prefill"
    assert TR.kernel_index(HLO.replace("quant_attention.py",
                                       "other.py")) == {}


def test_ops_are_attributed_to_kernel_and_program_kind():
    idx = TR.kernel_index(HLO)
    TR.merge(idx, {"%closed_call.9 = f32[8,512]{1,0}":
                   {("w4a8_matmul", "prefill")},
                   "%x.1 = f32[64,512]{1,0}": {("w4a8_matmul", "prefill")}})
    mm = ('%closed_call.9 = f32[8,512]{1,0} custom-call(s8[8,64] %a), '
          'custom_call_target="tpu_custom_call"')
    att = ('%closed_call.7 = f32[8,4,7,128]{3,2,1,0} custom-call(%q), '
           'custom_call_target="tpu_custom_call"')
    pre = ('%x.1 = f32[64,512]{1,0} custom-call(%q), '
           'custom_call_target="tpu_custom_call"')
    mods = [E("jit__unknown(1)", 0, 50), E("jit__unknown(2)", 60, 100)]
    ops = [E(mm, 1, 10), E(att, 12, 20), E("%fusion.1 = f32[8]", 21, 25),
           E(mm, 61, 70), E(pre, 71, 90)]
    w = window(ops, mods)
    got = [(k, kind) for _, k, kind in TR.attribute(w, idx)]
    # the first program holds only decode-named calls; the second's
    # unambiguous call says prefill, so the shared key is read as prefill
    assert got == [("w4a8_matmul", "decode"),
                   ("quant_decode_attention", "decode"), (None, "decode"),
                   ("w4a8_matmul", "prefill"), ("w4a8_matmul", "prefill")]
    calls = TR.kernel_calls(w, idx)
    assert [(c[0], c[1]) for c in calls] == [
        ("w4a8_matmul", "decode"), ("quant_decode_attention", "decode"),
        ("w4a8_matmul", "prefill"), ("w4a8_matmul", "prefill")]
    assert [c[3] for c in calls] == pytest.approx([9e-9, 8e-9, 9e-9, 19e-9])
    top = dict(TR.device_ops(w, idx))
    assert top["decode:w4a8_matmul"] == pytest.approx(9e-9)
    assert top["prefill:w4a8_matmul"] == pytest.approx(28e-9)
    assert top["decode:fusion"] == pytest.approx(4e-9)
    assert TR.program_time(w, idx) == {"decode": (1, pytest.approx(50e-9)),
                                       "prefill": (1, pytest.approx(40e-9))}


def test_only_programs_wholly_inside_the_window_count():
    """A program cut by either end of the window adds neither its
    execution, its time nor its kernel calls."""
    idx = TR.kernel_index(HLO)
    mm = ('%closed_call.9 = f32[8,512]{1,0} custom-call(s8[8,64] %a), '
          'custom_call_target="tpu_custom_call"')
    mods = [E("jit_d(1)", -20, 10), E("jit_d(2)", 20, 40),
            E("jit_d(3)", 50, 70), E("jit_d(4)", 90, 130)]
    ops = [E(mm, -15, 5), E(mm, 22, 30), E(mm, 55, 60), E(mm, 95, 99)]
    w = window(ops, mods)
    assert TR.program_time(w, idx) == {"decode": (2, pytest.approx(40e-9))}
    calls = TR.kernel_calls(w, idx)
    assert [c[3] for c in calls] == pytest.approx([8e-9, 5e-9])


def test_a_trace_recorded_on_cpu(tmp_path):
    """Host spans and the window come back from a real profiler file; on
    the CPU the XLA ops sit on host threads, so they stand in for the
    device line here."""
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    w = TR.load(str(tmp_path))
    names = sorted({e.name for e in w.host})
    assert names == ["bench.step", "bench.window"]
    span = [e for e in w.host if e.name == "bench.window"][0]
    assert (w.lo, w.hi) == (span.start, span.end)
    steps = [e for e in w.host if e.name == "bench.step"]
    assert len(steps) == 3 and all(w.lo <= s.start < s.end <= w.hi
                                   for s in steps)
    import glob
    import os
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    ops = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if dict(e.stats).get("hlo_module", "").startswith("jit_"):
                    ops.append(TR.Ev(e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
    assert ops
    w.ops = ops
    busy = TR.busy_ns(w)
    assert 0 < busy <= w.hi - w.lo
    assert busy + sum(t - s for s, t in TR.gaps(w)) == w.hi - w.lo
    # the work ran inside the steps, so no gap is longer than the window
    # minus the ops, and every op lies inside some step span
    assert all(any(s.start <= op.start and op.end <= s.end for s in steps)
               for op in ops)

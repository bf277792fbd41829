"""The served program's spans and scopes read from a trace, on a hand-made
trace whose answers are worked out below."""
import types

from bench.lib import manifest
from bench.lib import program_trace as pt
from bench.lib import trace
from bench.tests.test_bench_trace import _planes

MS = 1_000_000   # ns
LAYER = "jit(_fused_impl)/while/body/closed_call/while/body/"


def _span(name, start_ms, end_ms, **args):
    return pt.Span(name, start_ms * MS, end_ms * MS, args)


def _read():
    """(planes, spans, scope paths) of a window 0..100 ms: admit 0-30, step
    30-80, wait 80-100, and inside them the program's own spans."""
    spans = [
        _span("bench.window", 0, 100), _span("bench.admit", 0, 30),
        _span("serve.admit", 1, 29, rids="[1]", bucket=8),
        _span("serve.admit.prefill", 1, 10),
        _span("serve.admit.scatter", 10, 12),
        _span("serve.admit.first_token", 12, 28),
        _span("serve.admit.sync", 28, 29),
        _span("bench.step", 30, 80),
        _span("serve.decode", 31, 79, steps=1, active=2),
        _span("serve.decode.dispatch", 31, 33),
        _span("serve.decode.fetch", 33, 75),
        _span("serve.decode.settle", 75, 79),
        _span("bench.wait", 80, 100)]
    ops = [  # (HLO text, start ms, end ms, op_name path)
        ("%fusion.1 = bf16[8,16]{1,0} fusion(p)", 2, 9,
         "jit(admit_prefill)/dot_general"),
        ("%scatter.2 = bf16[2,2,8]{2,1,0} scatter(c)", 11, 13,
         "jit(slot_scatter)/scatter"),
        ("%reduce.3 = s32[2]{0} reduce(l)", 13, 14, "jit(argmax)/argmax"),
        ("%while.1 = (s32[], bf16[2]) while(t)", 34, 70, ""),
        ("%fusion.5 = bf16[8,128]{1,0} fusion(a)", 34, 44,
         LAYER + "attention/dot_general"),
        ("%copy.62 = bf16[2,8,64]{2,1,0} copy(x)", 44, 54, LAYER + "squeeze"),
        ("%fusion.6 = bf16[2,8,64]{2,1,0} fusion(k)", 54, 58,
         LAYER + "attention/kv_write/scatter"),
        ("%fusion.7 = bf16[8,256]{1,0} fusion(h)", 58, 66, LAYER + "mlp/mul"),
        ("%copy.63 = bf16[2,8,64]{2,1,0} copy(y)", 66, 70, "")]
    modules = [("jit_admit_prefill(1)", 2, 9), ("jit_slot_scatter(2)", 11, 13),
               ("jit_argmax(3)", 13, 14), ("jit__fused_impl(4)", 34, 70)]
    host = {"python3": [(s.name, s.start, s.end - s.start) for s in spans
                        if s.name.startswith("bench.")]}
    dev0 = {"XLA Ops": [(n, s * MS, (e - s) * MS) for n, s, e, _ in ops],
            "XLA Modules": [(n, s * MS, (e - s) * MS)
                            for n, s, e in modules]}
    return ({"/host:CPU": host, "/device:TPU:0": dev0}, spans,
            {n: p for n, _, _, p in ops})


def _close(a, b):
    return abs(a - b) < 1e-9


def test_idle_goes_to_the_innermost_span():
    planes, spans, paths = _read()
    got = dict(pt.breakdown(planes, spans, paths)["idle_gaps"])
    # device 0 busy 2-9, 11-14, 34-70: 46 ms; the rest of 100 is idle
    want = {"bench.admit": 2, "serve.admit.prefill": 2,
            "serve.admit.scatter": 1, "serve.admit.first_token": 14,
            "serve.admit.sync": 1, "bench.step": 2,
            "serve.decode.dispatch": 2, "serve.decode.fetch": 6,
            "serve.decode.settle": 4, "bench.wait": 20}
    assert set(got) == set(want)
    for k, v in want.items():
        assert _close(got[k], v * 1e-3), k
    assert _close(sum(got.values()), 0.054)
    # the benchmark's own reduction of the same trace: the same total, its
    # bench.* entries the sums of what lies inside them
    old = dict(trace.reduce(planes).idle_gaps)
    assert _close(sum(old.values()), sum(got.values()))
    assert _close(old["bench.admit"], sum(
        v for k, v in got.items() if k.startswith(("bench.admit",
                                                   "serve.admit"))))
    assert _close(old["bench.step"], sum(
        v for k, v in got.items() if k.startswith(("bench.step",
                                                   "serve.decode"))))


def test_decode_time_by_scope_and_the_admission_programs():
    planes, spans, paths = _read()
    t = pt.breakdown(planes, spans, paths)
    # only the decode program's operations, control flow left out
    assert {k: round(v * 1e3, 9) for k, v in t["scope_s"].items()} == {
        "unscoped": 14, "attention": 10, "mlp": 8, "kv_write": 4}
    assert _close(t["unscoped_share"], 14 / 36 * 100)
    ops = {(op, scope): s for op, scope, s in t["fused_ops"]}
    assert _close(ops[("copy.62 bf16[2,8,64]", "unscoped")], 0.010)
    assert _close(ops[("fusion.6 bf16[2,8,64]", "kv_write")], 0.004)
    assert t["decode_steps"] == 1
    # idle inside serve.decode: 31-34 and 70-79, over one step
    assert _close(t["step_gap_ms"], 12.0)
    assert _close(t["span_s"]["serve.decode.fetch"], 0.042)
    # the admission programs have names of their own in the benchmark's
    # reduction: the slot scatter ran 2 ms
    assert _close(trace.reduce(planes).module_s["jit_slot_scatter"], 0.002)


def test_slow_pass_names_the_span_that_held_it():
    planes, spans, paths = _read()
    got = pt.breakdown(planes, spans, paths,
                       slow=[(0.050, 0.030, 0.0, 0.050)])["slow"]
    start, dur, span, span_s, busy_s = got[0]
    assert (start, dur, span) == (0.030, 0.050, "serve.decode.fetch")
    assert _close(span_s, 0.042) and _close(busy_s, 0.036)


def test_a_program_without_spans_or_scopes():
    """The benchmark's first fixture, whose program has no spans and no
    scopes: idle keeps the harness's names and sums as there, and what
    needs the program's instrumentation reads None."""
    planes = _planes()
    spans = [pt.Span(n, s, s + d, {}) for n, s, d in
             planes["/host:CPU"]["python3"] if n.startswith("bench.")]
    t = pt.breakdown(planes, spans, {})
    old = dict(trace.reduce(planes).idle_gaps)
    assert {k: v for k, v in old.items() if v} == dict(t["idle_gaps"])
    assert t["step_gap_ms"] is None and t["unscoped_share"] is None
    assert t["span_s"] == {} and t["decode_steps"] == 0
    assert pt.breakdown(planes, [], {}) is None
    assert pt.breakdown({"/host:CPU": planes["/host:CPU"]}, spans, {}) \
        is None


def test_scope_of_a_path():
    assert pt.scope_of(LAYER + "attention/kv_write/scatter") == "kv_write"
    assert pt.scope_of(LAYER + "attention/dot_general") == "attention"
    assert pt.scope_of("jit(_fused_impl)/while/body/sample/argmax") \
        == "sample"
    assert pt.scope_of(LAYER + "squeeze") == "unscoped"
    assert pt.scope_of("") == "unscoped"


def _run(planes, admissions):
    return types.SimpleNamespace(
        trace=trace.reduce(planes) if planes else None,
        window=types.SimpleNamespace(admissions=admissions))


def test_scatter_ms_reads_the_scatter_program_per_round():
    """``scatter_ms.chat``: the slot scatter's device time over the
    window's admission rounds; None where the scatter has no name of its
    own (the benchmark's first fixture, whose admission programs are both
    ``jit__lambda``), where nothing was traced or nothing admitted."""
    read = manifest.reader("scatter_ms.chat")
    planes = _read()[0]
    assert _close(read(_run(planes, [(0.0, 0.03, [5])])), 2.0)
    assert _close(read(_run(planes, [(0.0, 0.03, [5])] * 4)), 0.5)
    assert read(_run(planes, [])) is None
    assert read(_run(_planes(), [(0.0, 0.03, [5])])) is None
    assert read(_run(None, [(0.0, 0.03, [5])])) is None


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 5000000000
             stats { metadata_id: 20 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.decode" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(f)" } }
  stat_metadata { key: 20 value { id: 20 name: "steps" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000
             stats { metadata_id: 12 int64_value: 9 } }
    events { metadata_id: 1 offset_ps: 4000000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 3000000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9 } }
  event_metadata { key: 1 value { id: 1 name: "%copy.62 = bf16[2]{0} copy(x)"
    stats { metadata_id: 10 str_value: "jit(_fused_impl)/while/body/squeeze" }
    stats { metadata_id: 11 ref_value: 13 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = bf16[8]{0} fusion(h)"
    stats { metadata_id: 10 str_value: "jit(_fused_impl)/while/body/mlp/mul" } } }
  event_metadata { key: 3 value { id: 3 name: "jit__fused_impl(5)" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "hlo_category" } }
  stat_metadata { key: 12 value { id: 12 name: "run_id" } }
  stat_metadata { key: 13 value { id: 13 name: "data formatting" } }
}
"""


def test_read_xplane_finds_spans_arguments_and_scope_paths(tmp_path):
    """The scope path sits in the operation's event metadata, which
    ``ProfileData`` does not show; the span's arguments are event stats."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    spans = pt.spans(str(path))
    assert [(s.name, s.start, s.end, s.args) for s in spans] == [
        ("bench.window", 0, 100 * MS, {}),
        ("serve.decode", 1 * MS, 6 * MS, {"steps": 4})]
    copy, fusion = "%copy.62 = bf16[2]{0} copy(x)", \
        "%fusion.7 = bf16[8]{0} fusion(h)"
    planes = trace.read_xplane(str(path))
    paths = pt.scope_paths(str(path), planes)
    assert paths == {copy: "jit(_fused_impl)/while/body/squeeze",
                     fusion: "jit(_fused_impl)/while/body/mlp/mul"}
    t = pt.breakdown(planes, spans, paths)
    assert {k: round(v * 1e3, 9) for k, v in t["scope_s"].items()} == {
        "unscoped": 2, "mlp": 1}
    meta = pt.metadata_stats(path.read_bytes(), "/device:TPU:0")
    assert meta[copy] == {"tf_op": "jit(_fused_impl)/while/body/squeeze",
                          "hlo_category": "data formatting"}

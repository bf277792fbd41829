"""The reduction from a profiler trace to device time, on a hand-made trace
whose answers are worked out below."""
from bench.lib import trace

MS = 1_000_000   # ns


def _planes():
    # window 0..100 ms; host spans: admit 0-30, step 30-80, wait 80-100
    host = {"python3": [
        ("bench.window", 0, 100 * MS), ("bench.admit", 0, 30 * MS),
        ("bench.step", 30 * MS, 50 * MS), ("bench.wait", 80 * MS, 20 * MS),
        ("PjitFunction(_fused_impl)", 31 * MS, 1 * MS)]}
    dev0 = {
        "XLA Ops": [
            ("%fusion.1 = bf16[8,128]{1,0} fusion(a)", 5 * MS, 20 * MS),
            ("%all-reduce.3 = bf16[8]{0} all-reduce(b)", 20 * MS, 10 * MS),
            ("%copy.2 = bf16[4]{0} copy(c)", 40 * MS, 30 * MS),
            ("%fusion.1 = bf16[8,128]{1,0} fusion(a)", 95 * MS, 10 * MS),
            ("%while.17 = (s32[], bf16[4]) while(e)", 40 * MS, 30 * MS)],
        "XLA Modules": [("jit__lambda(1)", 5 * MS, 25 * MS),
                        ("jit__fused_impl(7)", 40 * MS, 30 * MS)]}
    dev1 = {"XLA Ops": [("%fusion.9 = f32[2]{0} fusion(d)", 10 * MS,
                         40 * MS)]}
    return {"/host:CPU": host, "/device:TPU:0": dev0, "/device:TPU:1": dev1}


def test_reduce_busy_spans_and_gaps():
    t = trace.reduce(_planes())
    assert t.devices == 2
    assert abs(t.window_s - 0.100) < 1e-12
    # chip 0 busy: 5-30 (fusion and all-reduce overlap), 40-70, 95-100
    # chip 1 busy 10-50: 40 ms; the mean over chips is 50 ms
    assert abs(t.busy_s - 0.050) < 1e-12
    assert abs(t.span_busy_s["bench.admit"] - 0.025) < 1e-12
    assert abs(t.span_busy_s["bench.step"] - 0.030) < 1e-12
    assert abs(t.span_busy_s["bench.wait"] - 0.005) < 1e-12
    assert abs(t.module_s["jit__fused_impl"] - 0.030) < 1e-12
    assert abs(t.module_s["jit__lambda"] - 0.025) < 1e-12
    gaps = dict(t.idle_gaps)
    # chip 0 idle: 0-5 (admit), 30-40 and 70-80 (step), 80-95 (wait)
    assert abs(gaps["bench.admit"] - 0.005) < 1e-12
    assert abs(gaps["bench.step"] - 0.020) < 1e-12
    assert abs(gaps["bench.wait"] - 0.015) < 1e-12
    assert gaps["host"] == 0
    ops = dict(t.device_ops)
    # the op past the window's end is clipped to its 5 ms inside
    assert abs(ops["fusion.1 bf16[8,128]"] - 0.025) < 1e-12
    assert not [k for k in ops if k.startswith("while")]


def test_reduce_needs_a_window_and_a_device():
    planes = _planes()
    assert trace.reduce({"/host:CPU": planes["/host:CPU"]}) is None
    no_window = {k: v for k, v in planes.items()}
    no_window["/host:CPU"] = {"python3": [("bench.step", 0, 5)]}
    assert trace.reduce(no_window) is None


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.complement([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert trace.overlap([(0, 4), (6, 10)], [(3, 7)]) == 2
    assert trace.clip([(0, 4), (6, 10)], 2, 8) == [(2, 4), (6, 8)]
    assert trace.program_name("jit__fused_impl(123)") == "jit__fused_impl"
    assert trace.op_name("%copy.87 = bf16[10,8]{1,0:T(8,128)} copy(x)") \
        == "copy.87 bf16[10,8]"

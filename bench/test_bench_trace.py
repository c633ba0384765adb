"""The reduction from a profiler trace to busy time, idle gaps and
kernel time: on hand-made intervals, and on a small trace recorded on a
TPU v5e (``data/small_sweep.xplane.pb.gz``: two sweeps of a 12-tick
testbed grid, midas behind the cache, two scenarios x two seeds)."""

from pathlib import Path

from midasbench import tracecalc as tc

DATA = Path(__file__).resolve().parent / "data" / "small_sweep.xplane.pb.gz"


def _trace():
    op = tc.Op
    dev = [
        op("fusion.1", 10, 20, "jit__run_scan_sweep"),
        op("route_select", 15, 30, "jit__run_scan_sweep"),
        op("copy.2", 50, 60, "jit_other"),
    ]
    host = [
        tc.Span("bench/window", 0, 100),
        tc.Span("bench/sweep", 0, 70),
        tc.Span("sweep/host_slice", 30, 50),
        tc.Span("sweep/warmup", 70, 100),
    ]
    return tc.Trace({"/device:TPU:0": dev}, host)


def test_union_gaps_and_lengths():
    assert tc.merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert tc.length([(0, 3), (2, 4), (10, 11)]) == 5
    assert tc.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tc.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def test_busy_idle_and_kernel_time():
    tr = _trace()
    lo, hi = tc.window(tr)
    assert (lo, hi) == (0, 100)
    assert tc.busy_ns(tr, lo, hi) == 30  # [10, 30) and [50, 60)
    assert tc.busy_ns(
        tr, lo, hi, lambda o: "run_scan_sweep" in o.module
    ) == 20
    assert tc.op_time_ns(tr, lo, hi, lambda o: "route_select" in o.name) == (
        15, 1
    )
    assert tc.top_ops(tr, lo, hi)[0] == ["route_select", 15e-9]


def test_self_time_leaves_out_nested_ops():
    ops = [
        tc.Op("while.1", 0, 100, ""),
        tc.Op("fusion.2", 10, 30, ""),
        tc.Op("fusion.3", 12, 20, ""),
        tc.Op("fusion.4", 40, 50, ""),
        tc.Op("copy.5", 120, 125, ""),
    ]
    assert tc.self_times(ops) == [70, 12, 8, 10, 5]
    assert tc.op_name("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p)") == (
        "fusion.12"
    )


def test_idle_gaps_are_labelled_with_the_host_span_they_fell_in():
    tr = _trace()
    gaps = tc.idle_gaps(tr, 0, 100)
    assert gaps[0] == ["sweep/warmup", 40e-9]  # [60, 100)
    assert ["sweep/host_slice", 20e-9] in gaps  # [30, 50)
    assert ["bench/sweep", 10e-9] in gaps  # [0, 10)


def test_a_trace_recorded_on_the_chip():
    tr = tc.load(DATA)
    assert tr.devices, "no device plane"
    lo, hi = tc.window(tr)
    busy = tc.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    sweep = tc.busy_ns(tr, lo, hi, lambda o: "run_scan_sweep" in o.module)
    assert 0 < sweep <= busy
    kernel, calls = tc.op_time_ns(
        tr, lo, hi, lambda o: "route_select" in o.name
    )
    assert kernel > 0 and calls > 0
    assert {s.name for s in tr.host} >= {"bench/sweep", "sweep/execute"}
    assert len(tc.idle_gaps(tr, lo, hi)) == 10

"""The reduction from a trace to busy time, idle gaps, kernel time and
the breakdown, on hand-made events and on a trace recorded on the chip."""
import re
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on the path)
from bench.lib.trace import (Event, Trace, device_op, gaps, is_pallas,
                             op_family, union_ns)

KERNEL = ('{} = (s32[1,4096]) custom-call(f32[4096,784] %copy), '
          'custom_call_target="tpu_custom_call"')


def test_union_and_gaps_clip_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45), (100, 120)]
    assert union_ns(iv, (0, 200)) == 20 + 15 + 20
    assert union_ns(iv, (8, 35)) == 12 + 5
    assert gaps(iv, (0, 110)) == [(20, 30), (45, 100)]
    assert gaps([], (3, 7)) == [(3, 7)]
    assert union_ns([], (0, 5)) == 0.0


def _hand_trace():
    ops = [device_op(KERNEL.format("%pallas_call.3"), 100, 150, "0"),
           device_op("%fusion.12 = f32[50] fusion(f32[50,784] %c)",
                     150, 170, "0"),
           device_op(KERNEL.format("%cluster_sum_pallas.1"), 300, 340, "0"),
           device_op("%fusion.7 = f32[] fusion(f32[9] %x)", 330, 370, "0")]
    host = [Event("bench.window", 50, 450, "python"),
            Event("bench.fit", 60, 440, "python"),
            Event("PjitFunction(nested_round)", 200, 290, "python")]
    return Trace(ops, host)


def test_busy_kernels_and_breakdown_on_hand_made_events():
    t = _hand_trace()
    w = t.window("bench.window")
    assert w == (50, 450)
    assert t.busy_ns(w) == 70 + 70
    assert [e.name for e in t.device_ops] == [
        "pallas_call.3", "fusion.12", "cluster_sum_pallas.1", "fusion.7"]
    assert t.kernel_ns(w) == 50 + 40
    assert t.kernel_ns(w, re.compile(r"^cluster_sum_pallas")) == 40
    assert t.idle_gaps(w) == [(50, 100), (170, 300), (370, 450)]
    b = t.breakdown(w)
    name, secs = b["device_ops"][0]
    assert name == "fusion"
    assert secs == pytest.approx(60e-9)
    assert {n for n, _ in b["device_ops"]} == {
        "pallas_call (pallas)", "fusion", "cluster_sum_pallas (pallas)"}
    # the longest gap sits under the dispatch event, the others only
    # under the benchmark's fit span
    assert b["idle_gaps"][0][0] == "PjitFunction(nested_round)"
    assert b["idle_gaps"][0][1] == pytest.approx(130e-9)
    assert [g[0] for g in b["idle_gaps"][1:]] == ["bench.fit", "bench.fit"]
    assert op_family("custom-call.2.1") == "custom-call"


RECORDED = Path(__file__).with_name("data") / "tiny_trace.xplane.pb"


def test_recorded_chip_trace():
    """Three ``bench.predict`` spans, each one ``assign_top2_pallas``
    kernel (4,096 x 784 rows, 50 centroids) and a few small ops, 5 ms of
    sleep between them: recorded on a TPU v5 lite. The expected times
    were read off the trace's events by hand: each iteration's ops run
    back to back without overlap, so busy time is their sum."""
    t = Trace.load(RECORDED)
    w = t.window("bench.window")
    spans = t.spans("bench.predict")
    assert len(spans) == 3
    assert t.chips() == ["0"]
    ops = t.ops_in(w)
    assert [e.name for e in ops if is_pallas(e)] == [
        "assign_top2_pallas.1"] * 3
    for e in ops:
        assert w[0] <= e.start_ns and e.end_ns <= w[1]
    assert t.busy_ns(w) == pytest.approx(EXPECTED["busy_ns"])
    assert t.kernel_ns(w) == pytest.approx(EXPECTED["kernel_ns"])
    assert w[1] - w[0] == pytest.approx(EXPECTED["window_ns"])
    idle = sum(b - a for a, b in t.idle_gaps(w))
    assert idle + t.busy_ns(w) == pytest.approx(w[1] - w[0])
    # the sleeps between requests are the longest gaps
    gaps3 = t.breakdown(w)["idle_gaps"][:3]
    assert [g[0] for g in gaps3] == ["bench.window"] * 3
    assert all(g[1] > 5e-3 for g in gaps3)


EXPECTED = {"window_ns": 22_806_270.0,
            "busy_ns": 251_416.0,               # 3 x ~83,800
            "kernel_ns": 44_090.0 + 44_092.0 + 44_088.0}

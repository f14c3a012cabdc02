"""The trace reduction, on hand-made events and on a recorded chip trace
(`data/vgg11.cifar.b1.xplane.pb`: a 13 ms window of the harness driving
that cell on one TPU v5e)."""
import os
import random

import pytest

from bench import harness, trace

E = trace.Event
EVENT_MATMUL = harness.load_module(os.path.join(
    harness.BENCH, "metrics", "roofline.event_matmul.py"))
LIF = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                       "roofline.lif.py"))
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "vgg11.cifar.b1.xplane.pb")


def hand_trace():
    # One device, window [0, 10]: ops at [1, 3], [2, 4] (overlapping),
    # [6, 7] and [9, 12] (cut by the window's end).
    ops = {"/device:TPU:0": [
        E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1, 3),
        E("%_spike_matmul_csr_core.2 = f32[128,128]{1,0} custom-call(), "
          "custom_call_target=\"tpu_custom_call\"", 2, 4),
        E("%lif_occ.3 = (f32[4,8,128]{2,1,0}, s32[1]{0}) custom-call(), "
          "custom_call_target=\"tpu_custom_call\"", 6, 7),
        E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 9, 12)]}
    spans = [E("bench.device_put", 0, 0.8), E("bench.dispatch", 4.2, 5.9),
             E("bench.readback", 7, 8.5)]
    return trace.from_events(ops, spans, (0.0, 10.0))


def test_busy_union():
    tr = hand_trace()
    assert trace.merged(tr.ops["/device:TPU:0"], tr.window) == [
        (1, 4), (6, 7), (9, 10)]
    assert trace.busy_seconds(tr) == pytest.approx(5.0)
    assert trace.window_seconds(tr) == 10.0


def test_busy_union_averages_over_devices():
    tr = hand_trace()
    tr.ops["/device:TPU:1"] = [E("%fusion.2 = f32[8]{0} fusion()", 0, 10)]
    assert trace.busy_seconds(tr) == pytest.approx((5.0 + 10.0) / 2)


def test_family_attribution():
    tr = hand_trace()
    assert trace.family_seconds(tr, EVENT_MATMUL.PATTERNS) == 2.0
    assert trace.family_seconds(tr, LIF.PATTERNS) == 1.0
    assert trace.family_seconds(tr, [r"nothing"]) == 0.0
    assert trace.top_ops(tr) == [
        ["%fusion.1 f32[8]", 5.0],
        ["%_spike_matmul_csr_core.2 f32[128,128]", 2.0],
        ["%lif_occ.3 f32[4,8,128]", 1.0]]


def test_gap_attribution():
    # gaps [0, 1], [4, 6], [7, 9]; the first overlaps device_put, the
    # second dispatch, the third readback
    got = dict(trace.idle_gaps(hand_trace()))
    assert got == pytest.approx({"bench.device_put": 1.0,
                                 "bench.dispatch": 2.0,
                                 "bench.readback": 2.0})


def test_gap_without_span_is_host_other():
    tr = hand_trace()
    tr.spans = []
    assert trace.idle_gaps(tr) == [["host.other", pytest.approx(5.0)]]


def test_recorded_chip_trace():
    # 5 requests of vgg11.cifar.b1 on a TPU v5e, recorded by the harness
    tr = trace.load(RECORDED)
    busy, window = trace.busy_seconds(tr), trace.window_seconds(tr)
    assert 0 < busy < window
    assert trace.family_seconds(tr, EVENT_MATMUL.PATTERNS) > 0
    assert trace.family_seconds(tr, LIF.PATTERNS) > 0
    gaps = dict(trace.idle_gaps(tr))
    assert set(gaps) <= {"bench.device_put", "bench.dispatch",
                         "bench.readback", "host.other"}
    assert sum(gaps.values()) == pytest.approx(window - busy)


def test_gap_attribution_matches_every_span():
    # Many gaps among back-to-back spans, one of them long, as a latency
    # cell's window has: each gap goes to the span that overlaps it most.
    rng = random.Random(7)
    names = ("bench.device_put", "bench.dispatch", "bench.readback")
    ops, spans, t = [], [], 0.0
    for i in range(2000):
        length = 1.3 if i == 1000 else rng.uniform(1e-4, 3e-3)
        spans.append(E(names[i % 3], t, t + length))
        if rng.random() < 0.5:
            ops.append(E("%fusion.1 = f32[8]{0} fusion()",
                         t + 0.2 * length, t + 0.7 * length))
        t += length + rng.uniform(0, 1e-4)
    tr = trace.from_events({"/device:TPU:0": ops}, spans, (0.0, t))
    want: dict = {}
    edges = [0.0] + [x for iv in trace.merged(ops, tr.window)
                     for x in iv] + [t]
    for a, b in zip(edges[::2], edges[1::2]):
        overlaps = [(min(b, s.end) - max(a, s.start), s.name)
                    for s in spans]
        best = max(overlaps)
        name = best[1] if best[0] > 0 else "host.other"
        want[name] = want.get(name, 0.0) + (b - a)
    assert dict(trace.idle_gaps(tr)) == pytest.approx(want)

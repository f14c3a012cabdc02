"""What the readers of program runs and op_names take from a trace file
(`bench/rawtrace.py`), on hand-made events and on recorded chip traces of
the harness driving `vgg11.cifar.b1` on one TPU v5e:
`data/vgg11.cifar.b1.xplane.pb` (a 13 ms window, 5 requests, the program
before it had layer scopes) and `data/vgg11.cifar.b1.scoped.xplane.pb`
(a 15 ms window, 6 requests, with `.op_names.json`: the op_names that the
compiled program's HLO text gave the instructions it ran)."""
import json
import os
import shutil
import statistics
import types

import pytest

from bench import harness, rawtrace, trace

E = trace.Event
DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "vgg11.cifar.b1.xplane.pb")
SCOPED = os.path.join(DATA, "vgg11.cifar.b1.scoped")
READERS = ("busy_share.im2col", "host_ms.lat", "launch_ms.lat",
           "device_ms.lat", "return_ms.lat")
EVENT_MATMUL = harness.load_module(os.path.join(
    harness.BENCH, "metrics", "roofline.event_matmul.py"))
LIF = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                       "roofline.lif.py"))


def recorded(path=RECORDED):
    tr = trace.load(path)
    return tr, rawtrace.load(path, tr)


def test_recorded_chip_trace_reads_as_before():
    # The reduction that the accepted readers use keeps the clocks as
    # recorded: these are the values it gave before the runs were read.
    tr = trace.load(RECORDED)
    assert trace.busy_seconds(tr) == pytest.approx(0.0038852169999999894,
                                                   rel=1e-12)
    assert trace.window_seconds(tr) == pytest.approx(0.012984979, rel=1e-12)
    assert trace.family_seconds(tr, EVENT_MATMUL.PATTERNS) == pytest.approx(
        0.0012268570000000048, rel=1e-12)
    assert trace.family_seconds(tr, LIF.PATTERNS) == pytest.approx(
        0.0006687059999999981, rel=1e-12)
    assert trace.top_ops(tr, 3) == [
        ["%lif_occ.6 f32[4,1024,128]", pytest.approx(0.0004061340000000094)],
        ["%_spike_matmul_csr_core.15 f32[128,512]",
         pytest.approx(0.0002605060000000006)],
        ["%_spike_matmul_csr_core.14 f32[128,512]",
         pytest.approx(0.00026050299999999527)]]
    assert trace.idle_gaps(tr) == [
        ["bench.readback", pytest.approx(0.009087937999999997)],
        ["bench.device_put", pytest.approx(1.0672000000003234e-05)],
        ["bench.dispatch", pytest.approx(1.1500000000053134e-06)],
        ["host.other", pytest.approx(2.0000000058861822e-09)]]


def test_recorded_chip_trace_alignment():
    # The device stamps its runs early: before alignment a run starts
    # before the host has even called dispatch; the runtime's enqueue and
    # done events bracket each run 1.44-1.84 ms later than it is stamped.
    tr, raw = recorded()
    runs = raw.runs["/device:TPU:0"]
    reqs = rawtrace.requests(tr)
    assert len(runs) == len(reqs) == 5
    assert any(run.start < dispatch.start
               for run, (_, dispatch, _) in zip(runs, reqs))
    lo, hi = raw.offset_bounds
    assert 1.436e-3 < lo < raw.offset < hi < 1.84e-3
    assert raw.offset == pytest.approx((lo + hi) / 2)
    enqueued = raw.runtime[rawtrace.ENQUEUE_EVENT]
    done = raw.runtime[rawtrace.DONE_EVENT]
    for i, (run, (_, dispatch, readback)) in enumerate(zip(runs, reqs)):
        start, end = run.start + raw.offset, run.end + raw.offset
        assert dispatch.start <= start and end <= readback.end
        assert enqueued[i].end <= start and end <= done[i].start
    # without the runtime's events the spans alone bracket the runs
    loose = rawtrace.aligned(tr, raw.runs)
    assert loose.offset_bounds[0] < lo and hi < loose.offset_bounds[1]


def test_recorded_chip_trace_request_phases():
    tr, raw = recorded()
    phases = rawtrace.request_phases(tr, raw)
    assert len(phases) == 5
    runs = raw.runs["/device:TPU:0"]
    for p, (put, _, readback), run in zip(phases, rawtrace.requests(tr),
                                          runs):
        assert sum(p) == pytest.approx(readback.end - put.start, abs=1e-6)
        assert p[rawtrace.PHASES.index("device")] == pytest.approx(
            run.end - run.start)
        assert min(p) > 0
    for i, phase in enumerate(rawtrace.PHASES):
        assert rawtrace.phase_median(tr, raw, phase) == pytest.approx(
            sorted(p[i] for p in phases)[2])


def test_recorded_chip_trace_aligned_idle_gaps():
    tr, raw = recorded()
    gaps = dict(rawtrace.idle_gaps(tr, raw))
    assert set(gaps) <= {"bench.device_put", "bench.dispatch",
                         "bench.readback", "host.other"}
    lo, hi = tr.window
    busy = trace.merged(tr.ops["/device:TPU:0"],
                        (lo - raw.offset, hi - raw.offset))
    assert sum(gaps.values()) == pytest.approx(
        trace.window_seconds(tr) - sum(b - a for a, b in busy))
    # on the recorded clock the device's runs fall under device_put; on
    # the host's they fall under readback, so device_put idles longer
    assert gaps["bench.device_put"] > dict(
        trace.idle_gaps(tr))["bench.device_put"]


def test_load_refuses_another_window():
    tr = trace.load(RECORDED)
    other = trace.from_events(tr.ops, tr.spans,
                              (tr.window[0], tr.window[1] + 1e-9))
    assert rawtrace.load(RECORDED, other) is None


def aligned_trace(**runtime):
    # Two requests whose device runs, at [3, 6] and [14, 17] on the
    # host's clock, the device stamps 2 s early.
    spans = [E("bench.device_put", 0, 0.5), E("bench.dispatch", 0.5, 1),
             E("bench.readback", 1, 8), E("bench.device_put", 10, 10.5),
             E("bench.dispatch", 10.5, 11), E("bench.readback", 11, 19)]
    runs = {"/device:TPU:0": [E("jit_apply(1)", 1, 4),
                              E("jit_apply(1)", 12, 15)]}
    ops = {"/device:TPU:0": [E("%fusion.1 = f32[8]{0} fusion()", 1, 4),
                             E("%fusion.1 = f32[8]{0} fusion()", 12, 15)]}
    tr = trace.from_events(ops, spans, (0.0, 20.0))
    return tr, rawtrace.aligned(tr, runs, runtime)


def test_alignment_by_host_spans():
    # starts may move back 0.5 s (to the first dispatch's start), ends
    # forward 4 s (to either readback's end): the offset is the midpoint
    tr, raw = aligned_trace()
    assert raw.offset_bounds == pytest.approx((-0.5, 4.0))
    assert raw.offset == pytest.approx(1.75)
    assert rawtrace.request_phases(tr, raw) == [
        pytest.approx((1, 1.75, 3, 2.25)), pytest.approx((1, 2.75, 3, 2.25))]
    # aligned runs [2.75, 5.75], [13.75, 16.75]: every gap lies most
    # under a readback; busy time stays as recorded
    assert rawtrace.idle_gaps(tr, raw) == [
        ["bench.readback", pytest.approx(14.0)]]
    assert trace.busy_seconds(tr) == pytest.approx(6.0)


def test_alignment_tightened_by_runtime_events():
    # enqueue ends 1.5 and 1.2 s past the stamped starts, done starts 2.3
    # and 2.4 s past the stamped ends
    _, raw = aligned_trace(**{
        rawtrace.ENQUEUE_EVENT: [E(rawtrace.ENQUEUE_EVENT, 2, 2.5),
                                 E(rawtrace.ENQUEUE_EVENT, 12.5, 13.2)],
        rawtrace.DONE_EVENT: [E(rawtrace.DONE_EVENT, 6.3, 6.4),
                              E(rawtrace.DONE_EVENT, 17.4, 17.5)]})
    assert raw.offset_bounds == pytest.approx((1.5, 2.3))
    assert raw.offset == pytest.approx(1.9)


def test_alignment_refuses_what_cannot_be():
    # a done signal before the run could have ended
    with pytest.raises(ValueError, match="no clock offset"):
        aligned_trace(**{rawtrace.DONE_EVENT: [
            E(rawtrace.DONE_EVENT, 3, 3.1), E(rawtrace.DONE_EVENT, 15.5, 16)]})
    tr, raw = aligned_trace()
    with pytest.raises(ValueError, match="program runs"):
        rawtrace.aligned(tr, {"/device:TPU:0": raw.runs["/device:TPU:0"][:1]})
    short = trace.from_events(tr.ops, tr.spans[:-1], tr.window)
    with pytest.raises(ValueError, match="do not pair"):
        rawtrace.aligned(short, raw.runs)


def test_no_runs_no_phases():
    tr, _ = aligned_trace()
    raw = rawtrace.aligned(tr, {})
    assert raw.offset == 0.0 and raw.offset_bounds is None
    assert rawtrace.request_phases(tr, raw) == []
    assert rawtrace.phase_median(tr, raw, "host") is None


def test_recorded_scoped_trace_op_names_match_the_program_text():
    # the HLO the profiler keeps in the trace names every instruction
    # that ran as the compiled program's text does
    with open(SCOPED + ".xplane.pb", "rb") as f:
        modules = rawtrace.hlo_modules(f.read())
    assert list(modules) == ["jit_apply(4758747259268085394)"]
    names = rawtrace.op_names(rawtrace.instructions(
        modules["jit_apply(4758747259268085394)"]))
    with open(SCOPED + ".op_names.json") as f:
        want = json.load(f)
    assert {k: names.get(k) for k in want} == want
    tr, raw = recorded(SCOPED + ".xplane.pb")
    assert raw.names == names
    ran = {rawtrace.instruction(e.name) for e in trace._in_window(tr)}
    assert ran <= set(want)


def test_recorded_scoped_trace_layers():
    tr, raw = recorded(SCOPED + ".xplane.pb")
    layers = dict(rawtrace.layer_seconds(tr, raw.names, n=100))
    assert layers.pop(rawtrace.UNSCOPED) == 0.0
    assert set(layers) == {"encode", "head"} | {f"conv.{i}" for i in
                                                range(8)} | \
        {f"pool.{i}" for i in range(4)}
    device_s = sum(e.end - e.start for e in trace._in_window(tr))
    assert sum(layers.values()) == pytest.approx(device_s)
    assert sum(v for k, v in layers.items() if k.startswith("conv.")) \
        > 0.95 * device_s
    top = rawtrace.layer_seconds(tr, raw.names)
    assert len(top) == 13 and top[-1][0] == rawtrace.UNSCOPED
    assert [v for _, v in top[:-1]] == sorted(layers.values(),
                                              reverse=True)[:12]
    # without the map every operation is unscoped
    assert rawtrace.layer_seconds(tr, {}) == [
        [rawtrace.UNSCOPED, pytest.approx(device_s)]]


def harness_trace_dir(tmp, monkeypatch, name="bench-trace-x"):
    """A temporary directory holding the scoped recording where the
    harness writes its traces."""
    where = os.path.join(tmp, name, "plugins", "profile", "1")
    os.makedirs(where)
    shutil.copy(SCOPED + ".xplane.pb", os.path.join(where, "h.xplane.pb"))
    monkeypatch.setattr(rawtrace.tempfile, "tempdir", str(tmp))
    monkeypatch.setattr(rawtrace, "_CACHE", {})


def test_recorded_scoped_trace_readers(tmp_path, monkeypatch, capsys):
    harness_trace_dir(tmp_path, monkeypatch)
    # another run's trace beside it is passed over
    other = os.path.join(tmp_path, "bench-trace-y", "p", "h.xplane.pb")
    os.makedirs(os.path.dirname(other))
    shutil.copy(RECORDED, other)
    os.utime(other, (2e9, 2e9))
    tr, raw = recorded(SCOPED + ".xplane.pb")
    ctx = types.SimpleNamespace(trace=tr)
    read = {m: harness.load_module(os.path.join(
        harness.BENCH, "metrics", m + ".py")).read for m in READERS}
    im2col = rawtrace.scoped_seconds(tr, raw.names, "im2col")
    assert 0 < im2col < trace.busy_seconds(tr)
    assert read["busy_share.im2col"](ctx) == pytest.approx(
        100 * im2col / trace.busy_seconds(tr))
    phases = rawtrace.request_phases(tr, raw)
    assert len(phases) == 6
    for i, phase in enumerate(rawtrace.PHASES):
        assert read[f"{phase}_ms.lat"](ctx) == pytest.approx(
            1e3 * statistics.median(p[i] for p in phases))
    # the program's runs last about 0.8 ms on the chip
    assert 0.7 < read["device_ms.lat"](ctx) < 0.9
    err = capsys.readouterr().err
    assert err.count("[trace] clock offset") == 1
    assert "[trace] layers: [['conv." in err


def test_readers_without_a_trace_file(tmp_path, monkeypatch):
    monkeypatch.setattr(rawtrace.tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(rawtrace, "_CACHE", {})
    ctx = types.SimpleNamespace(trace=trace.load(RECORDED))
    for m in READERS:
        assert harness.load_module(os.path.join(
            harness.BENCH, "metrics", m + ".py")).read(ctx) is None


def test_im2col_share_of_an_unscoped_program(tmp_path, monkeypatch):
    # the program before the scopes: phases read, im2col does not
    monkeypatch.setattr(rawtrace.tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(rawtrace, "_CACHE", {})
    where = os.path.join(tmp_path, "bench-trace-z", "h.xplane.pb")
    os.makedirs(os.path.dirname(where))
    shutil.copy(RECORDED, where)
    ctx = types.SimpleNamespace(trace=trace.load(RECORDED))
    read = {m: harness.load_module(os.path.join(
        harness.BENCH, "metrics", m + ".py")).read for m in READERS}
    assert read["busy_share.im2col"](ctx) is None
    assert 0.7 < read["device_ms.lat"](ctx) < 0.9


def inst(name, op_name="", opcode="add", operands=(), calls=(),
         computation="main.6"):
    return rawtrace.Instruction(name, computation, opcode, op_name,
                                tuple(operands), tuple(calls))


def test_op_names_rules():
    econv = "jit(apply)/conv.0/econv/exp"
    kernel = "jit(apply)/conv.0/lif_scan_occ/jit(lif_occ)/lif_occ/pallas_call"
    insts = [
        inst("param_0", opcode="parameter", computation="fused.1"),
        inst("exp.2", econv, "exponential", ["param_0"], computation="fused.1"),
        inst("neg.3", econv, "negate", ["exp.2"], computation="fused.1"),
        inst("a", "reduce_sum", "parameter", computation="region_0.4"),
        inst("add.5", "reduce_sum", "add", ["a", "a"],
             computation="region_0.4"),
        inst("x.1", "x", "parameter"),
        inst("fusion.7", opcode="fusion", operands=["x.1"], calls=["fused.1"]),
        inst("copy.8", opcode="copy", operands=["fusion.7"]),
        inst("add.9", "reduce_window_sum", "add", ["copy.8", "copy.8"]),
        inst("lif_occ.10", kernel, "custom-call", ["add.9"])]
    names = rawtrace.op_names(insts)
    assert names["lif_occ.10"] == kernel
    # a fusion without an op_name: its fused instructions' most common
    assert names["fusion.7"] == econv
    # a name that is no path counts as none; without one an instruction
    # takes its first user's: the copy, the argument, the rewritten reduce
    assert names["add.9"] == kernel
    assert names["copy.8"] == kernel
    assert names["x.1"] == econv
    assert "add.5" not in names
    assert rawtrace.layer_of(names["copy.8"]) == "conv.0"
    assert rawtrace.layer_of("jit(apply)/gather") == rawtrace.UNSCOPED
    assert rawtrace.layer_of("jit(apply)/jit(floor_divide)/rem") == \
        rawtrace.UNSCOPED
    assert rawtrace.layer_of(None) == rawtrace.UNSCOPED
    assert rawtrace.instruction(
        "%lif_occ.10 = (f32[4,8]{1,0}, s32[1]{0}) custom-call()") == \
        "lif_occ.10"


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_hlo_proto_decoding():
    # a hand-serialized HloProto: a fusion calling a computation, with
    # packed operand and called-computation ids, and an unpacked operand
    fused = field(1, "fused.1") + field(5, 7) + field(2, (
        field(1, "exp.2") + field(2, "exponential") + field(35, 300)
        + field(7, field(1, "exp") + field(2, "jit(f)/s/exp"))))
    entry = field(1, "main.2") + field(5, 8) + field(2, (
        field(1, "x.1") + field(2, "parameter") + field(35, 400))) \
        + field(2, (field(1, "fusion.3") + field(2, "fusion") + field(35, 401)
                    + field(36, varint(400)) + field(38, varint(7)))) \
        + field(2, (field(1, "copy.4") + field(2, "copy") + field(35, 402)
                    + field(36, 401)))
    proto = field(1, field(1, "jit_f") + field(3, fused) + field(3, entry))
    assert rawtrace.instructions(proto) == [
        inst("exp.2", "jit(f)/s/exp", "exponential", computation="fused.1"),
        inst("x.1", opcode="parameter", computation="main.2"),
        inst("fusion.3", opcode="fusion", operands=["x.1"],
             calls=["fused.1"], computation="main.2"),
        inst("copy.4", opcode="copy", operands=["fusion.3"],
             computation="main.2")]
    # the same proto, as the profiler keeps it in its metadata plane
    stat_meta = field(1, 9) + field(2, field(1, 9) + field(2, "Hlo Proto"))
    event_meta = field(1, 5) + field(2, field(1, 5) + field(2, "jit_f(5)")
                                     + field(5, field(1, 9) + field(6, proto)))
    plane = field(1, 3) + field(2, "/host:metadata") + field(4, event_meta) \
        + field(5, stat_meta)
    other = field(1, 4) + field(2, "/device:TPU:0")
    xspace = field(1, other) + field(1, plane)
    assert rawtrace.hlo_modules(xspace) == {"jit_f(5)": proto}

"""What `bench/trace.py`'s reduction leaves out of a traced window, read
from the trace file itself by the per-layer metrics that need it.

- `clock_offset`: what puts the device's events on the host's clock. The
  device stamps its program runs (`XLA Modules`) about 1.5 ms early
  against the host spans on a v5e; one offset per trace puts every run
  inside its request's host bracket.
- `request_phases`: each request split, on the aligned clock, into host
  code, launch, the device's run and the return.
- `idle_gaps`: `trace.idle_gaps` on the aligned clock.
- `op_names`, `layer_seconds`, `scoped_seconds`: device time by the
  program's `named_scope`s, through the instruction -> op_name map of the
  optimized HLO that the profiler stores in the trace for each program.

A reader gets the reduced trace only (`ctx.trace`); the file lies in the
harness's trace directory (`bench-trace-*` under the temporary directory)
until the readers are done. `of(ctx)` finds the file whose `bench.window`
span is the reduced trace's, reads it once per window, and logs the
offset, the aligned idle gaps, the phases and the layers. Busy time,
families and top ops keep the clocks as recorded. Times are in seconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import statistics
import tempfile

from bench import trace

RUNS_LINE = "XLA Modules"
# One request's host spans, in the order the harness opens them.
REQUEST_SPANS = ("bench.device_put", "bench.dispatch", "bench.readback")
# The runtime's host events around each program run, where the profiler's
# host tracer records them: the run starts after the enqueue ends, and ends
# before the done signal starts.
ENQUEUE_EVENT = "tpu::System::Execute=>IssueSequencedEvent"
DONE_EVENT = "tpu::System::Execute=>Done"
PHASES = ("host", "launch", "device", "return")
UNSCOPED = "(unscoped)"
# Where the harness's traced run writes its trace (`tempfile.mkdtemp(
# prefix="bench-trace-")`, then the profiler's own directories).
TRACE_GLOB = os.path.join("bench-trace-*", "**", "*.xplane.pb")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


@dataclasses.dataclass
class Raw:
    runs: dict              # device plane name -> program runs, by start
    runtime: dict           # ENQUEUE_EVENT / DONE_EVENT -> [Event], by start
    names: dict             # instruction -> op_name (`op_names`)
    offset: float = 0.0     # added to a device time puts it on the host's
    offset_bounds: tuple | None = None   # (lo, hi): where it may lie


def aligned(tr: trace.Trace, runs: dict, runtime=None, names=None) -> Raw:
    """A Raw from events in hand (tests build them by hand), with its
    clock offset where `runs` holds program runs."""
    def by_start(events):
        return sorted(events, key=lambda e: e.start)
    raw = Raw({k: by_start(v) for k, v in runs.items() if v},
              {k: by_start(v) for k, v in (runtime or {}).items()},
              dict(names or {}))
    if raw.runs:
        lo, hi = clock_offset(tr, raw)
        raw.offset, raw.offset_bounds = (lo + hi) / 2, (lo, hi)
    return raw


def _event(ev) -> trace.Event:
    return trace.Event(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)


def load(path: str, tr: trace.Trace) -> Raw | None:
    """The program runs, runtime events and op_names of the trace at
    `path`, aligned against `tr`; None where `path` is not `tr`'s trace
    (its `bench.window` span differs)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    runs: dict = {}
    runtime: dict = {ENQUEUE_EVENT: [], DONE_EVENT: []}
    window = None
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            runs[plane.name] = [_event(ev) for line in plane.lines
                                if line.name == RUNS_LINE
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in runtime:
                        runtime[ev.name].append(_event(ev))
                    elif ev.name == trace.WINDOW_SPAN:
                        window = _event(ev)
    if window is None or (window.start, window.end) != tr.window:
        return None
    ran = {e.name for evs in runs.values() for e in evs}
    names: dict = {}
    with open(path, "rb") as f:
        modules = hlo_modules(f.read())
    for program, hlo in modules.items():
        if program in ran:
            names.update(op_names(instructions(hlo)))
    return aligned(tr, runs, runtime, names)


def find(tr: trace.Trace) -> Raw | None:
    """The Raw of `tr`'s trace file, the first among the harness's trace
    directories, newest first, that holds its window; None where none
    does."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_GLOB),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        raw = load(path, tr)
        if raw is not None:
            return raw
    return None


_CACHE: dict = {}


def of(ctx) -> Raw | None:
    """The Raw of the reader's traced window (`ctx.trace`), read once per
    window and logged; None, logged, where its trace file is not found."""
    tr = ctx.trace
    if tr.window not in _CACHE:
        from bench.harness import log
        _CACHE.clear()
        raw = _CACHE[tr.window] = find(tr)
        if raw is None:
            log("[trace] raw trace: no trace file holds this window")
        else:
            report(tr, raw, log)
    return _CACHE[tr.window]


def report(tr: trace.Trace, raw: Raw, log) -> None:
    """Log the offset, the aligned idle gaps, the phase medians and the
    layers of a window."""
    if raw.offset_bounds:
        lo, hi = raw.offset_bounds
        log(f"[trace] clock offset {1e3 * raw.offset:.4f} ms, interval "
            f"width {1e3 * (hi - lo):.4f} ms ({1e3 * lo:.4f} to "
            f"{1e3 * hi:.4f} ms)")
        log(f"[trace] idle gaps, aligned: {idle_gaps(tr, raw)}")
        log("[trace] phase medians, ms: " + str(
            {p: round(1e3 * phase_median(tr, raw, p), 4) for p in PHASES}))
    else:
        log("[trace] clock offset: no program runs in the trace")
    log(f"[trace] layers: {layer_seconds(tr, raw.names)}")


# ------------------------------------------------------------ clocks
def requests(tr: trace.Trace) -> list:
    """[(device_put, dispatch, readback) span] per request, in order; the
    harness reads answers back in the order it sends requests."""
    by_name = {n: [s for s in tr.spans if s.name == n]
               for n in REQUEST_SPANS}
    counts = {n: len(v) for n, v in by_name.items()}
    if len(set(counts.values())) != 1:
        raise ValueError(f"request spans do not pair up: {counts}")
    return list(zip(*by_name.values()))


def clock_offset(tr: trace.Trace, raw: Raw) -> tuple:
    """(lo, hi): the offsets which, added to every device time, put each
    program run inside its request's host bracket. The bracket is at
    least the request's dispatch span's start to its readback span's
    end; the runtime's enqueue and done events (`ENQUEUE_EVENT`,
    `DONE_EVENT`) narrow it wherever the trace holds one of each per
    run. The i-th run of each device is the i-th request's. Raises where
    the runs and requests do not pair up, or no offset fits them all."""
    reqs = requests(tr)
    lo, hi = -float("inf"), float("inf")
    enqueued = raw.runtime.get(ENQUEUE_EVENT) or []
    done = raw.runtime.get(DONE_EVENT) or []
    for device, runs in raw.runs.items():
        if len(runs) != len(reqs):
            raise ValueError(f"{len(runs)} program runs on {device} against "
                             f"{len(reqs)} requests")
        for i, (run, (_, dispatch, readback)) in enumerate(zip(runs, reqs)):
            lo = max(lo, dispatch.start - run.start)
            hi = min(hi, readback.end - run.end)
            if len(enqueued) == len(runs):
                lo = max(lo, enqueued[i].end - run.start)
            if len(done) == len(runs):
                hi = min(hi, done[i].start - run.end)
    if not lo <= hi:
        raise ValueError(f"no clock offset puts every program run inside "
                         f"its host bracket: the bracket needs at least "
                         f"{lo * 1e3:.4f} ms and at most {hi * 1e3:.4f} ms")
    return lo, hi


def request_phases(tr: trace.Trace, raw: Raw) -> list:
    """[(host, launch, device, return) seconds] per request, on the
    aligned clock: request start (its device_put span's) -> dispatch
    span's end -> run start -> run end -> readback span's end, so that
    the four sum to the request's latency. A run on several devices
    spans the first start to the last end. Empty without program runs."""
    if not raw.runs:
        return []
    runs = list(raw.runs.values())
    out = []
    for i, (put, dispatch, readback) in enumerate(requests(tr)):
        start = min(r[i].start for r in runs) + raw.offset
        end = max(r[i].end for r in runs) + raw.offset
        out.append((dispatch.end - put.start, start - dispatch.end,
                    end - start, readback.end - end))
    return out


def phase_median(tr: trace.Trace, raw: Raw, phase: str):
    """The median seconds of one of `PHASES` over the window's requests;
    None without program runs."""
    phases = request_phases(tr, raw)
    if not phases:
        return None
    return statistics.median(p[PHASES.index(phase)] for p in phases)



def phase_ms(ctx, phase: str):
    """A reader's value: `phase_median` of the reader's window in
    milliseconds; None where its trace file or program runs are missing."""
    raw = of(ctx)
    seconds = None if raw is None else phase_median(ctx.trace, raw, phase)
    return None if seconds is None else 1e3 * seconds

def idle_gaps(tr: trace.Trace, raw: Raw, n: int = 10) -> list:
    """`trace.idle_gaps` with the device's events on the host's clock:
    the host spans and the window are moved by the offset instead, which
    gives the same gaps."""
    def back(e):
        return trace.Event(e.name, e.start - raw.offset, e.end - raw.offset)
    lo, hi = tr.window
    moved = trace.Trace(tr.ops, [back(s) for s in tr.spans],
                        (lo - raw.offset, hi - raw.offset))
    return trace.idle_gaps(moved, n)


# ------------------------------------------------------------ layers
@dataclasses.dataclass(frozen=True)
class Instruction:
    name: str
    computation: str
    opcode: str
    op_name: str            # "" where the instruction has none
    operands: tuple         # instruction names
    calls: tuple            # computation names


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message's fields: an int for a
    varint, a memoryview for a length-delimited field (a string, bytes,
    a message or a packed list), raw bytes for a fixed-width one."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _ints(value) -> list:
    """A repeated integer field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_modules(xspace: bytes) -> dict:
    """{program name, as its runs are named: serialized HloProto} from a
    serialized XSpace (an `.xplane.pb`): the optimized HLO the profiler
    keeps in its metadata plane for every program it saw compiled or run.
    (XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5;
    map entries key 1, value 2; XEventMetadata.name 2, stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, bytes_value 6.)"""
    out = {}
    for field, plane in _fields(xspace):
        fields = list(_fields(plane)) if field == 1 else []
        if not any(f == 2 and bytes(v).decode() == METADATA_PLANE
                   for f, v in fields):
            continue
        stat_names = {}
        for f, entry in fields:
            meta = dict(_fields(entry)).get(2) if f == 5 else None
            if meta is not None:
                meta = dict(_fields(meta))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for f, entry in fields:
            meta = dict(_fields(entry)).get(2) if f == 4 else None
            if meta is None:
                continue
            name, hlo = "", None
            for g, v in _fields(meta):
                if g == 2:
                    name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                        hlo = bytes(stat[6])
            if hlo is not None:
                out[name] = hlo
    return out


def instructions(hlo_proto: bytes) -> list:
    """The instructions of a serialized HloProto, in its order.
    (HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.name 1, instructions 2, id 5;
    HloInstructionProto.name 1, opcode 2, metadata 7, id 35, operand_ids
    36, called_computation_ids 38; OpMetadata.op_name 2.)"""
    module = dict(_fields(hlo_proto)).get(1, b"")
    computations = []           # (name, id, [instruction fields])
    for field, comp in _fields(module):
        if field != 3:
            continue
        name, cid, insts = "", None, []
        for f, v in _fields(comp):
            if f == 1:
                name = bytes(v).decode()
            elif f == 2:
                insts.append(v)
            elif f == 5:
                cid = v
        computations.append((name, cid, insts))
    comp_names = {cid: name for name, cid, _ in computations}
    parsed = []                 # (computation, id, name, opcode, op_name,
    #                             operand ids, called computation ids)
    for comp, _, insts in computations:
        for inst in insts:
            name = opcode = op_name = ""
            iid, operands, calls = None, [], []
            for f, v in _fields(inst):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 2:
                    opcode = bytes(v).decode()
                elif f == 7:
                    op_name = bytes(dict(_fields(v)).get(2, b"")).decode()
                elif f == 35:
                    iid = v
                elif f == 36:
                    operands += _ints(v)
                elif f == 38:
                    calls += _ints(v)
            parsed.append((comp, iid, name, opcode, op_name, operands, calls))
    inst_names = {iid: name for _, iid, name, *_ in parsed}
    return [Instruction(name, comp, opcode, op_name,
                        tuple(inst_names[o] for o in operands
                              if o in inst_names),
                        tuple(comp_names[c] for c in calls
                              if c in comp_names))
            for comp, _, name, opcode, op_name, operands, calls in parsed]


def op_names(insts: list) -> dict:
    """{instruction name: op_name} for every instruction that has or
    takes one. An op_name is the traced function's path to the op; a name
    that is no path (an argument's, or a reducer body's that a rewritten
    reduce took) counts as none. The compiler drops the metadata of some
    instructions it makes, such as layout copies and rewritten
    convolutions: a fusion without an op_name takes the most common
    op_name among the instructions of the computation it calls, and an
    instruction still without one takes that of its first user that has
    or takes one, the op it was made for."""
    names: dict = {}
    users: dict = collections.defaultdict(list)
    members: dict = collections.defaultdict(list)
    fused: dict = {}                 # fusion -> the computation it calls
    for inst in insts:
        for operand in inst.operands:
            users[operand].append(inst.name)
        if "/" in inst.op_name:
            names[inst.name] = inst.op_name
            members[inst.computation].append(inst.op_name)
        elif inst.opcode == "fusion" and inst.calls:
            fused[inst.name] = inst.calls[0]
    for name, computation in fused.items():
        if members[computation]:
            names[name] = collections.Counter(
                members[computation]).most_common(1)[0][0]
    # Operands come before their users, so one pass from the end sees
    # every user resolved before the instruction it uses.
    for inst in reversed(insts):
        if inst.name not in names:
            for user in users[inst.name]:
                if user in names:
                    names[inst.name] = names[user]
                    break
    return names


def layer_of(op_name: str | None) -> str:
    """The top-level `named_scope` in an op_name: its first path below the
    outer jitted function's, where a path follows it; else UNSCOPED."""
    parts = (op_name or "").split("/")
    if parts[0].startswith("jit("):
        parts = parts[1:]
    if len(parts) < 2 or "(" in parts[0]:
        return UNSCOPED
    return parts[0]


def instruction(name: str) -> str:
    """The instruction's name (no `%`) in a device op's HLO text."""
    m = trace.OP_HEAD.match(name)
    return m.group(1)[1:] if m else name.split(" ", 1)[0].lstrip("%")


def scoped_seconds(tr: trace.Trace, names: dict, scope: str) -> float:
    """Device time, summed over devices, of the window's operations whose
    op_name (through `names`, from `op_names`) holds the path `scope`."""
    rx = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    hit: dict = {}
    total = 0.0
    for e in trace._in_window(tr):
        if e.name not in hit:
            hit[e.name] = bool(rx.search(names.get(instruction(e.name), "")))
        if hit[e.name]:
            total += e.end - e.start
    return total


def layer_seconds(tr: trace.Trace, names: dict, n: int = 12) -> list:
    """[[layer, seconds], ...]: device time of the window's operations,
    summed over devices, by top-level `named_scope` (`layer_of` their
    op_name through `names`): the `n` layers with most, then UNSCOPED."""
    layers: dict = {}
    by_layer: dict = {}
    for e in trace._in_window(tr):
        layer = layers.get(e.name)
        if layer is None:
            layer = layers[e.name] = layer_of(names.get(instruction(e.name)))
        by_layer[layer] = by_layer.get(layer, 0.0) + (e.end - e.start)
    unscoped = by_layer.pop(UNSCOPED, 0.0)
    top = sorted(by_layer.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top] + [[UNSCOPED, unscoped]]

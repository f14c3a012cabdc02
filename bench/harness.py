"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name from `BENCHMARK.json`:

- the configuration `bench/configs/<config>.json` (its sizes), with
  `<config>.py` (weights from the seed, the program's call, the work of
  one call) and `<config>.ref.py` (the plain reference) beside it;
- the traffic mix `bench/traffic/<traffic>.json`, read by `loadgen`,
  with its image source `bench/images/<source>.py`;
- each per-layer metric's reader `bench/metrics/<metric>.py`;
- the limits of the correctness check `bench/limits/<cell>.json`;
- the device's peaks in `bench/devices.json`, keyed by `device_kind`.

A run keeps at most `in_flight` requests of `batch` inputs outstanding,
sent as soon as a slot is free (a closed loop) or at the mix's fixed
rate (an open loop); each request is its pool inputs sent with
`device_put`, one call of the compiled program and the logits read back.
Host spans (`bench.*` TraceAnnotations) mark each step, so that a trace
can say what the host did while the device idled.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import BENCH, load_module, loadgen

ROOT = os.path.dirname(BENCH)
WARMUP_REQUESTS = 3          # per in-flight slot, before the window
REF_BLOCK_IMAGES = 128       # images per reference call
# A traced run's window, at most: on a v5e host the profiler takes about
# 6 s to write, and the reduction 1.4 s to read, each second of a latency
# cell's trace.
TRACED_SECONDS = 10.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                # the configuration file's contents
    model: object            # bench/configs/<config>.py
    ref: object              # bench/configs/<config>.ref.py
    mix: dict                # the traffic file's contents
    end_to_end: list         # this cell's end-to-end metric entries
    per_layer: list          # this cell's per-layer metric entries
    limits: dict | None      # bench/limits/<cell>.json, when it exists


def load_cell(name: str) -> Cell:
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = os.path.join(ROOT, config["file"])
    base = os.path.splitext(cfg_path)[0]
    # An end-to-end metric without `workloads` (`setup_s`) is every
    # cell's; each per-layer metric lists its cells.
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    limits_path = os.path.join(BENCH, "limits", f"{name}.json")
    return Cell(name, w["chips"], _json(cfg_path), load_module(base + ".py"),
                load_module(base + ".ref.py"),
                loadgen.load(w["traffic"]), e2e, per_layer,
                _json(limits_path) if os.path.exists(limits_path) else None)


def seed_key(seed: int):
    """A JAX key from any whole number, large ones included."""
    import jax
    state = np.random.SeedSequence(seed).generate_state(2).astype(np.uint32)
    return jax.random.wrap_key_data(state)


class CompileCounter:
    """Counts traces and compiles JAX reports, to show none happens in the
    window."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event in COMPILE_EVENTS:
            self.count += 1


@dataclasses.dataclass
class Window:
    seconds: float           # from the first request to the close
    end: float               # perf_counter at its close
    requests: list           # (pool index, t_hold, t_done) per request
    outputs: list            # host logits per request, in request order


class Runner:
    """The compiled program of one cell, driven from one seed at a time."""

    def __init__(self, cell: Cell, program=None):
        import jax
        self.cell = cell
        self.device = jax.devices()[0]
        make = program or cell.model.program
        self.fn = make(cell.cfg)
        self.compiled = None
        self.timings: dict = {}
        self.resolutions: dict = {}
        self._references: dict = {}

    @contextlib.contextmanager
    def _timed(self, key: str):
        t = time.perf_counter()
        yield
        self.timings[key] = self.timings.get(key, 0.0) \
            + time.perf_counter() - t

    def prepare(self, seed: int) -> None:
        """The weights (on the device, one jitted call) and the host pool."""
        import jax
        with self._timed("init_s"):
            self.params = jax.block_until_ready(jax.jit(
                functools.partial(self.cell.model.init, self.cell.cfg))(
                    seed_key(seed)))
        with self._timed("inputs_s"):
            self.pool = loadgen.make_pool(self.cell.mix, seed)

    def compile(self) -> None:
        import jax
        from repro.kernels import dispatch
        x = jax.ShapeDtypeStruct(self.pool.shape[1:], self.pool.dtype)
        with self._timed("trace_lower_s"):
            with jax.default_matmul_precision(
                    self.cell.cfg["matmul_precision"]), \
                    dispatch.watch_resolutions() as rec:
                lowered = jax.jit(self.fn).lower(self.params, x)
        with self._timed("compile_s"):
            self.compiled = lowered.compile()
        mem = self.compiled.memory_analysis()
        self.program_bytes = getattr(mem, "temp_size_in_bytes", 0) \
            + getattr(mem, "argument_size_in_bytes", 0)
        seen: dict = {}
        for r in rec:
            seen.setdefault(r["op"], set()).add(r["attribution"])
        self.resolutions = {op: sorted(a) for op, a in sorted(seen.items())}

    def warm_up(self) -> None:
        with self._timed("warmup_s"):
            self._loop(max_requests=WARMUP_REQUESTS * self.cell.mix[
                "in_flight"])

    def _loop(self, seconds: float = math.inf,
              max_requests: int | None = None) -> Window:
        import jax
        from jax.profiler import TraceAnnotation
        compiled, params, pool, device = (self.compiled, self.params,
                                          self.pool, self.device)
        in_flight = self.cell.mix["in_flight"]
        rate = self.cell.mix.get("rate_per_s")
        requests, outputs = [], []
        pending: collections.deque = collections.deque()

        def finish():
            idx, t_hold, out = pending.popleft()
            with TraceAnnotation("bench.readback"):
                host = np.asarray(out)
            requests.append((idx, t_hold, time.perf_counter()))
            outputs.append(host)

        i = 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while max_requests is None or i < max_requests:
            if rate:
                # Request i arrives at t0 + i / rate; answers that come in
                # meanwhile are read back at once.
                now = t0 + i / rate
                while time.perf_counter() < now:
                    if pending and pending[0][2].is_ready():
                        finish()
            else:
                now = time.perf_counter()
            if now >= t_end:
                break
            idx = i % len(pool)
            with TraceAnnotation("bench.device_put"):
                x = jax.device_put(pool[idx], device)
            with TraceAnnotation("bench.dispatch"):
                out = compiled(params, x)
            pending.append((idx, now, out))
            i += 1
            if len(pending) >= in_flight:
                finish()
        while pending:
            finish()
        # The window closes at the first answer at or after `seconds`, so
        # that it ends on completed work and a rate is not quantized to
        # whole calls; the call still in flight then has only just started.
        late = [t for _, _, t in requests if t >= t_end]
        close = min(late) if late else max((t for _, _, t in requests),
                                           default=t0)
        return Window(close - t0, close, requests, outputs)

    def drive(self, seconds: float) -> Window:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.window"):
            return self._loop(seconds)

    # ------------------------------------------------------ correctness
    def sample(self, window: Window, seed: int) -> np.ndarray:
        """Indices of the answered requests the check compares."""
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        n = len(window.requests)
        k = min(n, self.cell.mix["check_requests"])
        return np.sort(rng.choice(n, size=k, replace=False))

    def reference(self, pool_idx, precision: str, stats: bool = False):
        """The reference's logits (R, B, classes) for the pool entries
        `pool_idx`, in blocks of at most REF_BLOCK_IMAGES images."""
        import jax
        batch = self.cell.mix["batch"]
        per_block = max(1, REF_BLOCK_IMAGES // batch)
        fwd = self._references.get((precision, stats))
        if fwd is None:
            fwd = self._references[precision, stats] = jax.jit(
                functools.partial(self.cell.ref.forward, self.cell.cfg,
                                  precision=precision, stats=stats))
        logits, infos = [], []
        for i in range(0, len(pool_idx), per_block):
            got, info = fwd(self.params, self.pool[pool_idx[i:i + per_block]])
            logits.append(np.asarray(got))
            infos.append(jax.device_get(info))
        return np.concatenate(logits), infos



def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """`logit_gap`: the widest |got - want| over every finite answer, over
    the root-mean-square of the reference's logits. `mean_gap`: the mean
    |got - want| over the mean |want|. `bad_answers`: logits that are not
    finite numbers, or all of them where the shape is wrong.
    `class_mismatch`: the share of images whose top class differs. A
    cell's limits file says which of these it holds to a limit."""
    want = want.astype(np.float64).reshape(-1, want.shape[-1])
    got = got.astype(np.float64)
    if got.size != want.size:
        return {"logit_gap": 0.0, "mean_gap": 0.0, "bad_answers": want.size,
                "class_mismatch": 1.0}
    got = got.reshape(want.shape)
    finite = np.isfinite(got)
    diff = np.abs(np.where(finite, got, want) - want)
    rms = float(np.sqrt(np.mean(want ** 2))) or 1.0
    return {"logit_gap": float(diff.max() / rms),
            "mean_gap": float(diff.mean() / (np.mean(np.abs(want)) or 1.0)),
            "bad_answers": int((~finite).sum()),
            "class_mismatch": float(np.mean(
                got.argmax(-1) != want.argmax(-1)))}


def judge(readings: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) for the numbers that have a
    limit. A cell without limits is never correct."""
    if not limits:
        return False, {k: {"value": v, "limit": None}
                       for k, v in readings.items()}
    checks = {k: {"value": readings[k], "limit": lim["limit"]}
              for k, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# ------------------------------------------------------------ per layer
@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader gets."""
    trace: object            # trace.Trace of the window
    window_s: float
    images: int              # images completed in the traced window
    dense_flops_per_image: int
    peaks: dict              # devices.json entry of this chip
    calls: list              # (family, ops, bytes) of every kernel call


def peaks_for(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH, "devices.json"))
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/devices.json")
    return table[device_kind]


def kernel_calls(runner: Runner, window: Window, operands: bool) -> list:
    """(family, ops, bytes) of every LIF and, with `operands`, every event
    matmul call of the window's program calls."""
    from bench import work
    cell = runner.cell
    counts = collections.Counter(idx for idx, _, _ in window.requests)
    lif = [("lif", 0, work.lif_work(t, rows, k, maps=maps))
           for t, rows, k, maps in cell.model.lif_calls(cell.cfg,
                                                        cell.mix["batch"])]
    calls = lif * len(window.requests)
    if operands:
        used = np.array(sorted(counts))
        _, infos = runner.reference(used, cell.cfg["matmul_precision"],
                                    stats=True)
        per_idx: dict = collections.defaultdict(list)
        share: dict = collections.defaultdict(lambda: [0, 0, 0, 0])
        pos = 0
        for info in infos:
            layers = list(info["operands"].items())
            for r in range(len(layers[0][1]) if layers else 0):
                for name, layer in layers:
                    o = layer[r]
                    acc = share[name]
                    acc[0] += int(o["nnz"])
                    acc[1] += int(o["m"]) * int(o["k"])
                    acc[2] += int(o["occupied"])
                    acc[3] += int(o["tiles"])
                    per_idx[int(used[pos + r])].append(
                        ("event_matmul",) + work.event_matmul_work(
                            int(o["nnz"]), int(o["m"]), int(o["k"]),
                            int(o["n"]), operand_bits=int(o["bits"])))
            pos += len(layers[0][1]) if layers else 0
        log("[layers] event-matmul operands (density, occupied-tile share) "
            + str({k: (round(a / b, 4), round(c / d, 4))
                   for k, (a, b, c, d) in sorted(share.items())}))
        for idx, n in counts.items():
            calls += per_idx[idx] * n
    return calls


def layer_metrics(runner: Runner, window: Window, trace_dir: str,
                  peaks: dict) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced window."""
    from bench import trace
    cell = runner.cell
    tr = trace.load(trace.find(trace_dir))
    readers = {m["name"]: load_module(os.path.join(
        BENCH, "metrics", m["name"] + ".py")) for m in cell.per_layer}
    operands = any(getattr(r, "NEEDS_OPERANDS", False)
                   for r in readers.values())
    ctx = LayerContext(
        tr, trace.window_seconds(tr),
        sum(cell.mix["batch"] for _ in window.requests),
        cell.model.dense_flops_per_image(cell.cfg), peaks,
        kernel_calls(runner, window, operands))
    metrics = {}
    for m in cell.per_layer:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": trace.busy_seconds(tr), "window_s": ctx.window_s}
    breakdown = {"device_ops": trace.top_ops(tr), "idle_gaps":
                 trace.idle_gaps(tr)}
    return metrics, device, breakdown


# ---------------------------------------------------------------- a run
def end_to_end(cell: Cell, window: Window, setup_s: float) -> dict:
    done = [(t_hold, t_done) for _, t_hold, t_done in window.requests
            if t_done <= window.end]
    values = {"setup_s": setup_s,
              "images_per_s": len(done) * cell.mix["batch"] / window.seconds}
    if done:
        values["latency_p95_ms"] = 1e3 * float(np.percentile(
            [b - a for a, b in done], 95))
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def stalls(window: Window) -> str:
    """The median time between answers and the three longest, with when
    in the window each ended, to tell a pause from a slower run."""
    t0 = window.end - window.seconds
    done = sorted(t for _, _, t in window.requests)
    gaps = sorted(((b - a, b - t0) for a, b in zip(done, done[1:])),
                  reverse=True)
    if not gaps:
        return "answer gaps: none"
    median = float(np.median([g for g, _ in gaps]))
    longest = ", ".join(f"{1e3 * g:.3f} ms at {t:.3f} s" for g, t in gaps[:3])
    return f"answer gaps: median {1e3 * median:.3f} ms; longest {longest}"


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def run(name: str, seed: int, seconds: float, traced: bool,
        started: float, program=None) -> dict:
    """One run of cell `name`; `started` is the process's start on the
    perf_counter clock. Returns the result line's object."""
    import jax
    cell = load_cell(name)
    counter = CompileCounter()
    runner = Runner(cell, program)
    runner.prepare(seed)
    runner.compile()
    runner.warm_up()
    log(f"[setup] resolutions {runner.resolutions}; program arguments + "
        f"temporaries {runner.program_bytes} bytes")
    # Set-up leaves some 10^5 objects (traced programs, kernels); a full
    # collection over them takes about 0.1 s, and would land in the window.
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            # No Python call tracer: it slows the host loop and multiplies
            # the trace's events; the host spans are the harness's own.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles = counter.count
        t_first = time.perf_counter()
        try:
            window = runner.drive(min(seconds, TRACED_SECONDS) if traced
                                  else seconds)
        finally:
            if traced:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                log(f"[trace] written in {time.perf_counter() - t_stop:.3f}s")
        compiles = counter.count - compiles
        setup_s = t_first - started
        log(f"[setup] setup_s={setup_s:.3f} split "
            f"{ {k: round(v, 3) for k, v in runner.timings.items()} }")
        log(f"[window] requests={len(window.requests)} "
            f"seconds={window.seconds:.3f} compiles_in_window={compiles}")
        log(f"[window] {stalls(window)}")
        device = {"platform": runner.device.platform,
                  "kind": runner.device.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": memory_peak_bytes()}
        del runner.compiled
        t_ref = time.perf_counter()
        picked = runner.sample(window, seed)
        pool_idx = np.array([window.requests[i][0] for i in picked])
        want, infos = runner.reference(pool_idx,
                                       cell.cfg["matmul_precision"])
        readings = compare(np.stack([window.outputs[i] for i in picked]),
                           want)
        rates = {k: round(float(np.mean([i["rates"][k] for i in infos])), 4)
                 for k in infos[0]["rates"]}
        log(f"[check] reference over {len(picked)} requests in "
            f"{time.perf_counter() - t_ref:.3f}s; firing rates {rates}")
        result = {"correct": None, "attempted": len(window.requests),
                  "failed": 0, "metrics": {}, "device": device}
        if traced:
            t_trace = time.perf_counter()
            metrics, extra, breakdown = layer_metrics(
                runner, window, trace_dir, peaks_for(device["kind"]))
            log(f"[trace] read in {time.perf_counter() - t_trace:.3f}s")
            result["metrics"] = metrics
            result["device"].update(extra)
            result["breakdown"] = breakdown
        else:
            result["metrics"] = end_to_end(cell, window, setup_s)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[run] {time.perf_counter() - started:.3f}s since the process "
        f"started")
    correct, checks = judge(readings, cell.limits)
    log(f"[check] readings {readings}")
    for k, c in checks.items():
        log(f"[check] {k}: {c['value']!r} (limit {c['limit']!r})")
    result["correct"] = correct
    result["checks"] = checks
    return result

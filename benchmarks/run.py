"""Benchmark harness entry: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` prints
``name,us_per_call,derived`` CSV covering Fig. 2 / Fig. 7 / Fig. 8 /
Table I / Table II / Fig. 9 and the kernel-backend sweep.

Backend sweeps (speedups are measured, not asserted):

    # the registry sweep under two kernel routings, same CSV schema
    python -m benchmarks.run --only backends --backend ref --backend \\
        sdsa=pallas-interpret,ref

Each ``--backend`` value uses the EXSPIKE_BACKEND grammar (a backend name
for all ops, or comma-separated ``op=backend`` entries) and reruns the
selected suites with that routing; rows are prefixed ``<override>/``.
Every sweep leads with a ``resolved_backends`` row recording the backend
each op RESOLVES to under that override (post-fallback: an unknown or
unsupported request degrades to ``ref``), so sweep results are
attributable — the requested override alone is not trustworthy. The row
reflects resolution on each op's canonical example shapes; a suite whose
own shapes trip a per-call capability fallback additionally reports it
via RuntimeWarning and the backends suite's per-row ``default=`` field.
``--json PATH`` writes the same data structured: per sweep the requested
override, the resolved per-op map, and the CSV rows.
Only suites that route through the dispatch registry respond to the
override — ``backends`` (every registered pair) and the model-driven
suites whose spike collection runs registry ops; the paper-figure suites
that time fixed formulations against each other (fig2's tconv-vs-scatter
anchor, the cost-model tables) print identical numbers under any
override, by design.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import traceback


def _suites():
    from . import (e2e_event, fig2_econv_vs_tconv, fig7_apec, fig8_breakdown,
                   fig9_cpu, guard_overhead, hybrid_sweep, kernel_backends,
                   serve_bench, sparsity_sweep, table1_resources,
                   table2_throughput)
    return [
        ("fig2", fig2_econv_vs_tconv.run),
        ("fig7", fig7_apec.run),
        ("fig8", fig8_breakdown.run),
        ("table1", table1_resources.run),
        ("table2", table2_throughput.run),
        ("fig9", fig9_cpu.run),
        ("backends", kernel_backends.run),
        ("sparsity", sparsity_sweep.run),
        # uint32-packed CSR vs f32 CSR single ops + bytes-moved ledger
        ("sparsity_packed", sparsity_sweep.run_packed),
        # whole-network carried-occupancy (EventTensor) vs re-derive
        ("e2e_event", e2e_event.run),
        # whole-network packed pipeline vs f32 CSR + bytes-moved ledger
        ("e2e_packed", e2e_event.run_packed),
        # sharded-vs-single CSR columns (8-way mesh on this process's
        # devices; on a CPU host force them with XLA_FLAGS)
        ("sparsity_mesh", sparsity_sweep.run_mesh_rows),
        # density-adaptive hybrid dispatch vs the two static pins
        # (single-device model stacks + 8-way mesh rows)
        ("hybrid", hybrid_sweep.run),
        ("hybrid_mesh", hybrid_sweep.run_mesh),
        # EXSPIKE_GUARD audit/repair vs off (dense + packed payloads)
        ("guard", guard_overhead.run),
        # continuous-batching scheduler: trace-replay p50/p99 latency +
        # tokens/sec, spiking vs dense, single vs 2-replica pool
        ("serve", serve_bench.run),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of suite names to run (default: all)")
    ap.add_argument("--backend", action="append", default=None,
                    help="EXSPIKE_BACKEND override to sweep; repeatable. "
                         "Each value reruns the suites under that routing.")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON: per sweep the "
                         "requested override, the RESOLVED per-op backends "
                         "(post-fallback), and the rows.")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    suites = _suites()
    if args.only:
        wanted = {s.strip() for s in args.only.split(",")}
        unknown = wanted - {name for name, _ in suites}
        if unknown:
            raise SystemExit(f"unknown suites: {sorted(unknown)}")
        suites = [(n, f) for n, f in suites if n in wanted]

    from repro.kernels import dispatch

    @contextlib.contextmanager
    def _env_override(value):
        old = os.environ.get(dispatch.ENV_VAR)
        if value is not None:
            os.environ[dispatch.ENV_VAR] = value
        try:
            yield
        finally:
            if value is not None:
                if old is None:
                    os.environ.pop(dispatch.ENV_VAR, None)
                else:
                    os.environ[dispatch.ENV_VAR] = old

    sweeps = [(None, "")] if not args.backend \
        else [(ov, f"{ov}/") for ov in args.backend]

    print("name,us_per_call,derived")
    failures = 0
    report = []
    for override, prefix in sweeps:
        with _env_override(override):
            # The attributable identity of this sweep: what each op
            # actually resolves to under the override, post-fallback.
            resolved = dispatch.resolved_backends()
            print(prefix + "resolved_backends,0.0,"
                  + ";".join(f"{op}={be}" for op, be in resolved.items()),
                  flush=True)
            rows = []
            for name, fn in suites:
                try:
                    for row in fn():
                        rows.append(row)
                        print(prefix + row, flush=True)
                except Exception as e:
                    failures += 1
                    print(f"{prefix}{name}/ERROR,0.0,"
                          f"{type(e).__name__}:{e}", flush=True)
                    traceback.print_exc(file=sys.stderr)
            report.append({"requested": override, "resolved": resolved,
                           "rows": rows})
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"sweeps": report}, f, indent=2)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Density-adaptive hybrid dispatch vs the two static pins.

PR 3 committed the dense/event crossover per op; PR 5 made the occupancy
map flow to every consumer. This suite times what hybrid resolution buys:
the same whole-network forwards as the e2e suite (both model families'
event-hot stacks, carried `EventTensor` metadata) under THREE dispatch
modes — `dense` (predicated kernels pinned), `event` (csr family pinned),
and `hybrid` (per-call routing on the carried map via the calibrated cost
model). The claim the committed BENCH_PR6.json pins: hybrid is never
slower than the better static pin at any sparsity point, because it IS
the better pin at every point (plus a per-call resolution overhead orders
of magnitude below the kernels), picked from the map instead of by hand.

Rows:
  ``hybrid/<family>/<mode>/s<pct>``   stack-total CONSUME us — the sum
      over layers of the per-(layer, mode) reproducible-best sample,
      modes interleaved per layer (same drift/cache conditions; the
      mode-independent fire stage is excluded). Hybrid rows carry per-op
      route attribution (``routes=``) from `dispatch.watch_resolutions`
      and the jit recompile count across the whole sparsity sweep
      (``traces=``: bounded by the bucketed route set, NOT by occupancy
      values).
  ``hybrid/<family>/margin/s<pct>``   hybrid_vs_best = median PAIRED
      hybrid/winner ratio, judged against a self-measured
      ``noise_band`` (the deviation identical-program clone pairs show
      in the same rounds — see _margin), plus ``hybrid_is_winner_route`` attributing tie points
      to identical kernels rather than a lucky clock.
  ``hybrid-mesh/spike_matmul/<mode>/s<pct>``   8-way `event_op_sharded`
      rows with the report's attribution + per-shard ``occ_routes``.

``--json PATH`` writes the BENCH_PR6 schema: one sweep per mode with the
resolved per-op backends and all rows (single-device + mesh).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch, ops
from .common import (NOISE_BAND_FLOOR, csv_row, noise_band, not_slower,
                     paired_median_ratio, time_interleaved)
from .e2e_event import (FAMILIES, _consume, _forward, _produce_carried,
                        _stage_drive)
from .sparsity_sweep import SPARSITIES, clustered_spikes

_ = NOISE_BAND_FLOOR    # re-exported: the band floor rides every margin row

ITERS = 24   # min-of-N; interleaved samples, see common.time_interleaved
             # (the e2e suite's sample count — fewer rounds leave the
             # per-mode minimums of IDENTICAL programs a few % apart on a
             # cgroup-throttled host)
MESH_SHARDS = 8
M_MESH, K_MESH, N_MESH = 1024, 512, 256


def _pin_names() -> dict:
    """Platform-correct backend names for the two static pins."""
    tpu = jax.default_backend() == "tpu"
    return {"dense": "pallas" if tpu else "pallas-interpret",
            "event": "pallas-csr" if tpu else "pallas-csr-interpret"}


def _mode_scope(mode: str):
    """Dispatch context for one sweep mode (platform-correct pin names)."""
    if mode == "hybrid":
        return dispatch.use_hybrid()
    name = _pin_names()[mode]
    ctx = contextlib.ExitStack()
    for op in dispatch.HYBRID_OPS:
        ctx.enter_context(dispatch.use_backend(name, op=op))
    return ctx


def _time_trio(fns: dict, iters: int = ITERS,
               warmup: int = 2) -> tuple[dict, dict]:
    """Per-mode (min, all samples) via the shared interleaved rotating-
    order protocol (`common.time_interleaved` — one implementation for
    this sweep and the e2e pair timer)."""
    return time_interleaved(fns, iters=iters, warmup=warmup)


def _margin(samples: dict) -> tuple[float, float, str]:
    """(hybrid_vs_best, noise_band, winner).

    hybrid_vs_best: MEDIAN of per-round paired t_hybrid/t_winner ratios —
    within a round the modes run back-to-back, so host drift is
    common-mode and cancels; the median kills one-sided stall outliers
    (a min-of-ratios would credit hybrid whenever the WINNER caught the
    stall).

    noise_band: the largest deviation-from-1 the same statistic shows
    for the two IDENTICAL-program pairings in the same rounds — the
    ``dense2``/``event2`` clones against their pins. This is what "not
    slower" has to mean on this host: two separately-jitted executables
    of the IDENTICAL mesh HLO measure 1-2% apart in paired medians
    (instance layout, cgroup quota phase), so a hybrid margin within
    the band is indistinguishable from re-running the winner itself.
    One clone alone underestimates the band half the time (its own
    deviation can land BELOW 1). The margin rows pair the numbers with
    structural attribution (hybrid_is_winner_route / hybrid_picked_best
    / same_hlo) so tie points rest on program identity, not a lucky
    clock."""
    med = {m: sorted(v)[len(v) // 2] for m, v in samples.items()}
    winner = "dense" if med["dense"] <= med["event"] else "event"
    band = noise_band(samples, (("dense2", "dense"), ("event2", "event")))
    return paired_median_ratio(samples, "hybrid", winner), band, winner


# "Not slower" (common.not_slower) allows the measured identical-program
# noise band, floored at common.NOISE_BAND_FLOOR; `identical` is
# structural proof (hybrid_is_winner_route / same_hlo) that hybrid's
# program IS the winner's, which settles ties regardless of the clock.
_not_slower = not_slower


def run() -> list[str]:
    rows = []
    platform = jax.default_backend()
    for family, spec in FAMILIES.items():
        stages = [(n, kind, shape,
                   jax.random.normal(jax.random.PRNGKey(i + 1),
                                     wshape, jnp.float32) * 0.05)
                  for i, (n, kind, shape, wshape) in enumerate(spec)]

        # The timed consume ops run EAGER with concrete carried maps — the
        # serve-path regime the crossover was calibrated in, where the
        # event route gets its trimmed CSR grid (a traced map pays the
        # pow2 step cap instead and shifts the crossover). Hybrid's
        # measured resolution overhead is ~13us/call vs a plain pin,
        # noise at these stack totals. One jitted hybrid stack reused
        # across every sparsity point is the recompile-boundedness probe:
        # under tracing the route flip rides the compiled lax.cond on the
        # bucketed count, so its trace count stays 1 for the whole sweep.
        @jax.jit
        def _hybrid_stack(drives, stages=stages):
            with dispatch.use_hybrid():
                return _forward(drives, stages, True)

        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            drives = [
                _stage_drive(jax.random.fold_in(key, i), kind, shape,
                             sparsity)
                for i, (_, kind, shape, _w) in enumerate(stages)]

            def fwd(mode):
                with _mode_scope(mode):
                    return _forward(drives, stages, True)

            # parity guard: all modes (and the traced-route hybrid stack)
            # run the same math
            outs = {m: fwd(m) for m in ("dense", "event", "hybrid")}
            outs["hybrid-jit"] = _hybrid_stack(drives)
            for m in ("event", "hybrid", "hybrid-jit"):
                for a, b in zip(outs["dense"], outs[m]):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               atol=1e-4)
            # per-point route attribution: hybrid resolves on the
            # CONCRETE map, naming pick + bucket per call
            with dispatch.watch_resolutions() as recs:
                fwd("hybrid")
            picked = [r["attribution"].split("<-")[0] for r in recs
                      if r["op"] in dispatch.HYBRID_OPS]
            routes = ":".join(
                r["attribution"] for r in recs
                if r["op"] in dispatch.HYBRID_OPS)

            # Per-LAYER timing, per-mode minimums summed into the stack
            # total. Whole-stack samples (~70ms x 5 modes per round) span
            # several of this host's cgroup quota periods, so stack-level
            # drift is NOT common-mode and neither mins nor paired
            # medians converge (clones of the same eager path measured
            # up to 4% apart). Layer calls are 3-20ms — inside a quota
            # burst — and the per-(layer, mode) minimum is the
            # reproducible unthrottled cost (the e2e suite's protocol);
            # sums of minimums are stable. The fire stage is the same
            # compiled scan in every mode and is excluded: the routed
            # consume ops are all that differs. dense2/event2 re-run the
            # pins through the same eager path — their sum against the
            # winner's is the measured noise floor.
            modes = ("dense", "event", "hybrid", "dense2", "event2")
            ets = [jax.block_until_ready(_produce_carried(d))
                   for d in drives]
            sums = {m: 0.0 for m in modes}
            for (_n, kind, _shape, w), et in zip(stages, ets):
                def consume(m, kind=kind, et=et, w=w):
                    with _mode_scope(m.rstrip("2")):
                        return _consume(kind, et, w)
                layer_best, _ = _time_trio(
                    {m: (lambda m=m: consume(m)) for m in modes})
                for m in modes:
                    sums[m] += layer_best[m]
            best = sums
            winner = "dense" if sums["dense"] <= sums["event"] else "event"
            ratio = sums["hybrid"] / sums[winner]
            band = max(abs(sums["dense2"] / sums["dense"] - 1.0),
                       abs(sums["event2"] / sums["event"] - 1.0))
            # When hybrid resolves every layer to the winning pin's
            # backend, the two runs execute the SAME kernels — any
            # residual margin is resolution overhead (~13us/call) plus
            # timing noise, not a routing loss.
            same_route = int(all(p == _pin_names()[winner]
                                 for p in picked))
            pct = int(sparsity * 100)
            common = f"platform={platform};layers={len(stages)}"
            for mode in ("dense", "event"):
                rows.append(csv_row(f"hybrid/{family}/{mode}/s{pct}",
                                    best[mode] * 1e6, common))
            rows.append(csv_row(
                f"hybrid/{family}/hybrid/s{pct}", best["hybrid"] * 1e6,
                f"{common};routes={routes};"
                f"traces={_hybrid_stack._cache_size()}"))
            rows.append(csv_row(
                f"hybrid/{family}/margin/s{pct}", 0.0,
                f"hybrid_vs_best={ratio:.3f};noise_band={band:.3f};"
                f"not_slower={_not_slower(ratio, band, same_route)};"
                f"best_static={winner};"
                f"hybrid_is_winner_route={same_route};{common}"))
        rows.append(csv_row(
            f"hybrid/{family}/traces", 0.0,
            f"jit_traces_across_sweep={_hybrid_stack._cache_size()};"
            f"sparsity_points={len(SPARSITIES)};platform={platform}"))
    return rows


# --------------------------------------------------------------- 8-way mesh
def run_mesh(n_shards: int = MESH_SHARDS) -> list[str]:
    """Hybrid vs static pins through `event_op_sharded`: mesh-aware
    resolution on the carried map, per-shard route attribution in the
    report's ``occ_routes`` field."""
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding

    platform = jax.default_backend()
    if len(jax.devices()) < n_shards:
        raise RuntimeError(
            f"mesh sweep needs {n_shards} devices, have {len(jax.devices())}"
            " (on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards})")
    mesh = make_mesh((n_shards, 1), ("data", "model"))
    w = jax.random.normal(jax.random.PRNGKey(0), (K_MESH, N_MESH),
                          jnp.float32) * 0.05
    rows = []
    for sparsity in SPARSITIES:
        key = jax.random.PRNGKey(int(sparsity * 1000) + 7)
        s = clustered_spikes(key, M_MESH, K_MESH, sparsity)
        occ = jax.block_until_ready(ops.padded_occupancy(s))
        ref = np.asarray(s @ w)

        # One jitted sharded call per mode, the carried CONCRETE map
        # closed over (the serve convention): resolution runs at trace
        # time on the concrete map, so hybrid's pick — and, on the csr
        # route, the per-shard TRIMMED work lists — bake into the
        # compiled program as constants instead of re-deriving per call.
        # dense2/event2 are fresh jits of the SAME pin: the paired
        # clone-vs-pin ratio measures the executable-instance noise
        # floor the hybrid margin is judged against (see _margin).
        fns, reports = {}, {}
        for mode in ("dense", "event", "hybrid", "dense2", "event2"):
            with _mode_scope(mode.rstrip("2")):
                f = jax.jit(lambda s_, w_: sharding.event_op_sharded(
                    mesh, "spike_matmul", s_, w_, occupancy=occ))
                jax.block_until_ready(f(s, w))       # trace inside scope
                if not mode.endswith("2"):
                    _, reports[mode] = sharding.event_op_sharded(
                        mesh, "spike_matmul", s, w, occupancy=occ,
                        with_report=True)
            fns[mode] = f
        for m in ("dense", "event", "hybrid"):
            np.testing.assert_allclose(np.asarray(fns[m](s, w)), ref,
                                       atol=1e-4)
        best, samples = _time_trio({m: (lambda m=m: fns[m](s, w))
                                    for m in fns},
                                   iters=max(ITERS, 16))
        ratio, band, winner = _margin(samples)
        pct = int(sparsity * 100)
        for mode in ("dense", "event", "hybrid"):
            rep = reports[mode]
            occ_fields = rep["occupancy"].as_fields() \
                if rep["occupancy"] is not None else ""
            rows.append(csv_row(
                f"hybrid-mesh/spike_matmul/{mode}/s{pct}",
                best[mode] * 1e6,
                f"platform={platform};shards={n_shards};"
                f"resolved={rep['attribution']};{occ_fields}"))
        # hybrid_picked_best: hybrid resolved to the backend the faster
        # pin ran. same_hlo makes the tie structural: with a concrete
        # carried map the global pick compiles to the PIN'S OWN program
        # (trimmed csr stack or occupancy-gated dense), so when it is 1
        # any residual hybrid_vs_best is executable-instance noise, not
        # a routing cost.
        same_hlo = int(fns["hybrid"].lower(s, w).as_text()
                       == fns[winner].lower(s, w).as_text())
        rows.append(csv_row(
            f"hybrid-mesh/spike_matmul/margin/s{pct}", 0.0,
            f"hybrid_vs_best={ratio:.3f};noise_band={band:.3f};"
            f"not_slower={_not_slower(ratio, band, same_hlo)};"
            f"hybrid_picked_best="
            f"{int(reports[winner]['backend'] in reports['hybrid']['attribution'])};"
            f"same_hlo={same_hlo};"
            f"platform={platform};shards={n_shards}"))
    return rows


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="mesh rows only")
    ap.add_argument("--shards", type=int, default=MESH_SHARDS)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_PR6-schema JSON (single-device + "
                         "mesh rows, hybrid route attributions)")
    args = ap.parse_args()
    if args.mesh:
        print("\n".join(run_mesh(args.shards)))
        return
    rows = run()
    mesh_rows = run_mesh()
    print("\n".join(rows + mesh_rows))
    if args.json:
        with dispatch.use_hybrid():
            resolved = dispatch.resolved_backends()
        with open(args.json, "w") as f:
            json.dump({"sweeps": [{
                "requested": dispatch.HYBRID,
                "resolved": resolved,
                "rows": rows + mesh_rows,
            }]}, f, indent=2)


if __name__ == "__main__":
    main()

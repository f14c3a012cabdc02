"""Predicated vs event-compacted spike matmuls across the paper's sparsities.

Rows: ``sparsity/<op>/<pallas|pallas-csr>/s<pct>,us_per_call,...`` timing
the same op under the predicated dense-grid kernel (``pallas`` family) and
the scalar-prefetch CSR kernel (``pallas-csr`` family) at the paper's
measured sparsity levels (50/60/80/90/97%), plus one
``sparsity/<op>/crossover`` row reporting the first sparsity where the
compacted grid wins — the measured "when CSR beats predication" point the
kernel README cites.

Event layout: tile-skipping saves nothing on i.i.d. sparsity (a 128x128
tile at 97% uniform sparsity still holds ~490 events), and real spike maps
are not i.i.d. — events cluster in active regions (PAPER.md's irregular
sparsity; see `core.spikes.occupancy_fraction`). The generator therefore
draws *clustered* events: each (block_m x block_k) tile is live with
probability (1 - sparsity)/IN_TILE_DENSITY and live tiles fire at
IN_TILE_DENSITY, so overall sparsity matches the sweep level while tile
occupancy spans 1.0 -> ~0.06 across it. Each row's ``derived`` records the
realized occupancy fraction plus the cost model's FLOPs-saved and
DMA-saved fractions (`core.costmodel.tile_matmul_savings`) — the two
ledgers the backends differ on.

The suite times fixed formulations against each other, so (like fig2) its
numbers do not respond to ``--backend`` overrides, by design.

``--mesh`` (or the ``sparsity_mesh`` suite in benchmarks.run) adds the
sharded columns: the same CSR op at the same sparsity points, single
device vs row-sharded over an 8-way ('data') host mesh through
`runtime.sharding.event_op_sharded` — mesh-aware registry resolution,
per-shard `TileCSR` work lists (`core.spikes.shard_occupancy_to_csr`, no
global-occupancy gather), and per-shard occupancy columns
(`runtime.straggler.occupancy_imbalance`: ``occ_per_shard``/``occ_max``/
``occ_mean``/``occ_imbalance``) since event-load skew is what makes
sharded event execution straggle. Committed as BENCH_PR4.json.

``--pipelined`` adds the DMA-pipelining half of BENCH_PR10: the ``-pipe``
kernels (manual double-buffered weight-tile DMA, `kernels/README.md`
"DMA pipelining & load balance") paired against their serial CSR
baselines under the interleaved clone-pair protocol, with the modeled
prefetched-vs-stalled weight-byte split (`costmodel.dma_overlap_ledger`)
per row. ``--mesh --rebalance`` adds the load-balance half: static
row-contiguous vs occupancy-weighted shard splits on hotspot-clustered
maps at a taller M (M_MESH has one tile row per shard, so whole-tile-row
rebalancing has no freedom there — the `rebalanced=` column on the
ordinary mesh rows records exactly that). ``--pr10`` runs both halves
and writes the combined BENCH_PR10.json.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import costmodel
from repro.core.spikes import occupancy_fraction
from repro.kernels import ops
from .common import csv_row, noise_band, not_slower, time_fn, \
    time_interleaved

SPARSITIES = (0.50, 0.60, 0.80, 0.90, 0.97)
IN_TILE_DENSITY = 0.5
BLOCK = 128
# (M, K, N) for the matmul-form ops; positions grouped g=2 for APEC.
M, K, N = 512, 512, 256
APEC_G = 2


def clustered_spikes(key, m: int, k: int, sparsity: float,
                     block_m: int = BLOCK, block_k: int = BLOCK) -> jax.Array:
    """Binary (m, k) spikes at `sparsity` with tile-clustered events.

    Exactly max(1, round(live_frac * n_tiles)) tiles are live: an iid
    Bernoulli draw can zero out the whole map at the sparse end of the
    sweep, which would silently time the degenerate all-empty edge case
    instead of a representative sparse workload.
    """
    k_live, k_fire = jax.random.split(key)
    live_frac = min(1.0, (1.0 - sparsity) / IN_TILE_DENSITY)
    density = (1.0 - sparsity) / live_frac
    mt, kt = m // block_m, k // block_k
    n_live = max(1, round(live_frac * mt * kt))
    live = (jax.random.permutation(k_live, mt * kt) < n_live
            ).reshape(mt, 1, kt, 1)
    fire = jax.random.uniform(k_fire, (mt, block_m, kt, block_k)) < density
    return (live & fire).astype(jnp.float32).reshape(m, k)


def _savings_fields(s2: jax.Array, n: int) -> str:
    occ_map = ops.padded_occupancy(s2, BLOCK, BLOCK)
    occ_frac = float(occupancy_fraction(s2, BLOCK, BLOCK))
    pred = costmodel.tile_matmul_savings(occ_map, n, backend="pallas")
    csr = costmodel.tile_matmul_savings(occ_map, n, backend="pallas-csr")
    return (f"occupancy={occ_frac:.3f};"
            f"flops_saved={pred.flops_fraction_saved:.3f};"
            f"dma_saved_pallas={pred.dma_fraction_saved:.3f};"
            f"dma_saved_csr={csr.dma_fraction_saved:.3f}")


def _prepass_time(s: jax.Array, be: str) -> float:
    """Wall seconds of the standalone occupancy pre-pass the backend pays
    per call when no carried map is supplied: the dense `tile_occupancy`
    read (both kernel families) plus the eager CSR compaction (`pallas-csr`
    only). This is the share an EventTensor-carried forward deletes — the
    per-row `prepass_us`/`prepass_share` columns make visible how much of
    the 'CSR win' the pre-pass was eating."""
    from repro.core.spikes import build_csr

    if be.startswith("pallas-csr"):
        def fn(x):
            return build_csr(ops.padded_occupancy(x, BLOCK, BLOCK),
                             BLOCK, BLOCK)
    else:
        def fn(x):
            return ops.padded_occupancy(x, BLOCK, BLOCK)
    return time_fn(fn, s)


def run() -> list[str]:
    rows = []
    platform = jax.default_backend()
    crossover: dict[str, float | None] = {}
    variants = {
        "spike_matmul": {
            "pallas": jax.jit(ops.spike_matmul),
            # eager pre-pass (trimmed CSR grid) + jitted kernel core
            "pallas-csr": ops.spike_matmul_csr,
        },
        "apec_matmul": {
            "pallas": jax.jit(functools.partial(ops.apec_matmul, g=APEC_G)),
            "pallas-csr": functools.partial(ops.apec_matmul_csr, g=APEC_G),
        },
    }
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    for op, impls in variants.items():
        crossover[op] = None
        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            s = clustered_spikes(key, M, K, sparsity)
            stats = _savings_fields(s, N)
            t_by = {}
            for be, fn in impls.items():
                t_by[be] = time_fn(fn, s, w) * 1e6
                prepass = _prepass_time(s, be) * 1e6
                rows.append(csv_row(
                    f"sparsity/{op}/{be}/s{int(sparsity * 100)}", t_by[be],
                    f"platform={platform};prepass_us={prepass:.1f};"
                    f"prepass_share={prepass / max(t_by[be], 1e-9):.3f};"
                    f"{stats}"))
            if crossover[op] is None and t_by["pallas-csr"] < t_by["pallas"]:
                crossover[op] = sparsity
        rows.append(csv_row(
            f"sparsity/{op}/crossover", 0.0,
            f"csr_wins_from_sparsity="
            f"{'none' if crossover[op] is None else crossover[op]};"
            f"platform={platform}"))
    return rows


# ------------------------------------------------------- packed payload
def _bytes_fields(occ, n: int) -> str:
    """Absolute modeled HBM traffic of the two CSR payloads on this map
    (`costmodel.matmul_bytes_moved`): the event-payload stream responds
    32x to packing, the weight/output streams are route-invariant (same
    trimmed grid) and reported alongside."""
    mb = 1.0 / 2**20
    f32 = costmodel.matmul_bytes_moved(occ, n, backend="pallas-csr")
    pk = costmodel.matmul_bytes_moved(occ, n, backend="packed-csr")
    return (f"spike_mb_csr={f32.spike_hbm * mb:.3f};"
            f"spike_mb_packed={pk.spike_hbm * mb:.3f};"
            f"spike_reduction={f32.spike_hbm / pk.spike_hbm:.1f};"
            f"weight_mb={f32.weight_hbm * mb:.3f};"
            f"out_mb={f32.out_hbm * mb:.3f};"
            f"total_reduction={f32.total / pk.total:.2f}")


def run_packed() -> list[str]:
    """uint32-packed CSR vs f32 CSR, single ops at the sweep points.

    Rows ``sparsity/<op>/packed-csr/s<pct>`` time the packed kernel on
    pre-packed words (packing is the producer's job — fused into emission
    in the pipeline — so the consumer-side comparison starts from each
    route's canonical payload; both routes re-derive their occupancy +
    work list per call). Fields carry the paired packed-vs-csr ratio
    against the self-measured clone noise band (`common.time_interleaved`
    protocol) plus the absolute bytes-moved ledger.
    """
    import functools

    from repro.core.spikes import pack_spikes

    rows = []
    platform = jax.default_backend()
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    variants = {
        "spike_matmul": (ops.spike_matmul_csr,
                         functools.partial(ops.spike_matmul_packed,
                                           packed_k=K)),
        "apec_matmul": (functools.partial(ops.apec_matmul_csr, g=APEC_G),
                        functools.partial(ops.apec_matmul_packed, g=APEC_G,
                                          packed_k=K)),
    }
    for op, (csr_fn, packed_fn) in variants.items():
        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            s = clustered_spikes(key, M, K, sparsity)
            p = pack_spikes(s)
            ref = csr_fn(s, w)
            import numpy as np
            np.testing.assert_allclose(np.asarray(packed_fn(p, w)),
                                       np.asarray(ref), atol=1e-4)
            fns = {"csr": (lambda: csr_fn(s, w)),
                   "packed": (lambda: packed_fn(p, w)),
                   "csr2": (lambda: csr_fn(s, w)),
                   "packed2": (lambda: packed_fn(p, w))}
            best, samples = time_interleaved(fns, iters=24)
            ratio = best["packed"] / best["csr"]
            band = noise_band(samples, (("csr2", "csr"),
                                        ("packed2", "packed")))
            occ = ops.padded_occupancy(s, BLOCK, BLOCK)
            pct = int(sparsity * 100)
            rows.append(csv_row(
                f"sparsity/{op}/packed-csr/s{pct}", best["packed"] * 1e6,
                f"platform={platform};csr_us={best['csr'] * 1e6:.1f};"
                f"packed_vs_csr={ratio:.3f};noise_band={band:.3f};"
                f"not_slower={not_slower(ratio, band)};"
                f"{_bytes_fields(occ, N)};{_savings_fields(s, N)}"))
    return rows


# ------------------------------------------------------ pipelined kernels
def _dma_fields(occ, n: int, ledger_backend: str) -> str:
    """Modeled weight-stream DMA split for the serial-vs-pipe pair
    (`costmodel.dma_overlap_ledger`): total weight bytes the pipe variant
    fetches, how many land behind compute, how many stay exposed (one
    warm-up per N-tile iteration), and the serial baseline's all-exposed
    bytes for the same map."""
    mb = 1.0 / 2**20
    ser = costmodel.dma_overlap_ledger(occ, n, backend=ledger_backend)
    pipe = costmodel.dma_overlap_ledger(occ, n, backend=ledger_backend,
                                        pipelined=True)
    return (f"dma_w_mb={pipe.bytes_total * mb:.3f};"
            f"dma_prefetched_mb={pipe.bytes_prefetched * mb:.3f};"
            f"dma_stalled_mb={pipe.bytes_stalled * mb:.3f};"
            f"dma_stalled_serial_mb={ser.bytes_stalled * mb:.3f};"
            f"dma_overlap={pipe.overlap_fraction:.3f}")


def run_pipelined() -> list[str]:
    """Double-buffered weight-DMA (`-pipe`) kernels vs their serial CSR
    baselines at the sweep points.

    Rows ``sparsity/<op>/<family>-pipe/s<pct>`` time each registered
    pipelined matmul-form variant against the serial kernel it falls back
    to, under the paired interleaved clone protocol (`time_interleaved` /
    `noise_band` / `not_slower` — the same contract the packed rows use),
    after asserting forward parity at 1e-4. Fields carry the DMA-overlap
    ledger (`_dma_fields`): the weight bytes the pipe variant hides
    behind compute are the perf mechanism, so the modeled split rides
    next to the measured ratio.
    """
    import numpy as np

    from repro.core.spikes import pack_spikes

    rows = []
    platform = jax.default_backend()
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    for sparsity in SPARSITIES:
        key = jax.random.PRNGKey(int(sparsity * 1000))
        s = clustered_spikes(key, M, K, sparsity)
        p = pack_spikes(s)
        occ = ops.padded_occupancy(s, BLOCK, BLOCK)
        stats = _savings_fields(s, N)
        variants = (
            ("spike_matmul", "pallas-csr-pipe", "pallas-csr",
             lambda: ops.spike_matmul_csr(s, w),
             lambda: ops.spike_matmul_csr(s, w, pipeline=True)),
            ("spike_matmul", "packed-csr-pipe", "packed-csr",
             lambda: ops.spike_matmul_packed(p, w, packed_k=K),
             lambda: ops.spike_matmul_packed(p, w, packed_k=K,
                                             pipeline=True)),
            ("apec_matmul", "pallas-csr-pipe", "pallas-csr",
             lambda: ops.apec_matmul_csr(s, w, g=APEC_G),
             lambda: ops.apec_matmul_csr(s, w, g=APEC_G, pipeline=True)),
        )
        for op, pipe_name, ledger_be, ser_fn, pipe_fn in variants:
            np.testing.assert_allclose(np.asarray(pipe_fn()),
                                       np.asarray(ser_fn()), atol=1e-4)
            best, samples = time_interleaved(
                {"serial": ser_fn, "pipe": pipe_fn,
                 "serial2": ser_fn, "pipe2": pipe_fn}, iters=12)
            ratio = best["pipe"] / best["serial"]
            band = noise_band(samples, (("serial2", "serial"),
                                        ("pipe2", "pipe")))
            rows.append(csv_row(
                f"sparsity/{op}/{pipe_name}/s{int(sparsity * 100)}",
                best["pipe"] * 1e6,
                f"platform={platform};serial_us={best['serial'] * 1e6:.1f};"
                f"pipe_vs_serial={ratio:.3f};noise_band={band:.3f};"
                f"not_slower={not_slower(ratio, band)};"
                f"{_dma_fields(occ, N, ledger_be)};{stats}"))
    return rows


# ------------------------------------------------------------- mesh sweep
MESH_SHARDS = 8
# 128 rows per shard at 8 shards: every shard's tile grid divides cleanly,
# so the csr family passes its per-shard gate (the point of the sweep).
M_MESH = 1024
# Taller geometry for the rebalance rows: at M_MESH each shard owns ONE
# 128-row tile row, so whole-tile-row rebalancing has zero freedom; at
# M_REBAL each shard owns four and the occupancy-weighted split can move
# load (`core.spikes.rebalance_shard_plan`).
M_REBAL = 4096
REBAL_SPARSITIES = (0.90, 0.97)


def hotspot_spikes(key, m: int, k: int, sparsity: float,
                   block_m: int = BLOCK, block_k: int = BLOCK) -> jax.Array:
    """`clustered_spikes` live-tile count, but the live tiles form ONE
    contiguous row-major band at a key-dependent offset — the spatial
    hotspot (events concentrated in an active region) that motivates
    occupancy-weighted sharding: a static row-contiguous split lands the
    whole band on one or two shards, which the synchronous collective
    then waits for."""
    k_off, k_fire = jax.random.split(key)
    live_frac = min(1.0, (1.0 - sparsity) / IN_TILE_DENSITY)
    density = (1.0 - sparsity) / live_frac
    mt, kt = m // block_m, k // block_k
    n_live = max(1, round(live_frac * mt * kt))
    off = jax.random.randint(k_off, (), 0, mt * kt - n_live + 1)
    flat = jnp.arange(mt * kt)
    live = ((flat >= off) & (flat < off + n_live)).reshape(mt, 1, kt, 1)
    fire = jax.random.uniform(k_fire, (mt, block_m, kt, block_k)) < density
    return (live & fire).astype(jnp.float32).reshape(m, k)


def _shard_step_fields(occ_np, n_shards: int, plan=None) -> str:
    """Per-shard grid-step columns for the sharded CSR rows: every shard
    pads to ONE shared pow2 cap (`steps_cap` — what the synchronous grid
    actually runs), and `steps_per_shard` counts each shard's real steps
    (occupied tiles + one dummy per all-empty tile row) under the given
    split — the pre-padding work the cap is quantizing."""
    import numpy as np

    from repro.core.spikes import shard_occupancy_to_csr
    locals_ = shard_occupancy_to_csr(occ_np, n_shards,
                                     tiling=(BLOCK, BLOCK), plan=plan)
    steps = [int(np.asarray(c.valid).sum()) for c in locals_]
    return (f"steps_cap={int(locals_[0].n_steps)};"
            "steps_per_shard=" + ":".join(str(x) for x in steps))


def run_mesh(n_shards: int = MESH_SHARDS) -> list[str]:
    """Sharded vs single-device CSR at the same sparsity points.

    Both variants pin the CSR family; the sharded rows go through
    `event_op_sharded` (mesh-aware resolution + per-shard work lists) and
    carry the resolved attribution plus the per-shard occupancy columns.
    On one physical CPU the 8 host devices are threads, so sharded wall
    time mixes real thread parallelism with partitioning overhead — the
    columns that transfer to real meshes are the per-shard occupancy /
    imbalance ones.

    Grid formulation per row (the ``grid=`` field): spike_matmul shards
    consume eager per-shard trimmed work lists (`csr_stack`); apec has no
    CSR pass-through (its union pre-pass is built in-kernel), so its
    sharded variant traces the pre-pass and runs the dense-capped clamped
    grid while its single row runs the eager trimmed grid — an asymmetry
    the field makes explicit rather than hides.
    """
    import numpy as np

    from repro.core.spikes import rebalance_shard_plan
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding

    platform = jax.default_backend()
    if len(jax.devices()) < n_shards:
        raise RuntimeError(
            f"mesh sweep needs {n_shards} devices, have {len(jax.devices())}"
            " (on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards})")
    mesh = make_mesh((n_shards, 1), ("data", "model"))
    csr = "pallas-csr" if platform == "tpu" else "pallas-csr-interpret"
    rows = []
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    for op, single_fn, kwargs in (
            ("spike_matmul", ops.spike_matmul_csr, {}),
            ("apec_matmul",
             functools.partial(ops.apec_matmul_csr, g=APEC_G),
             {"g": APEC_G})):
        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            s = clustered_spikes(key, M_MESH, K, sparsity)
            stats = _savings_fields(s, N)
            with dispatch.use_backend(csr, op=op):
                t_single = time_fn(single_fn, s, w) * 1e6
                if op == "spike_matmul":
                    # carried concrete map -> per-shard trimmed work
                    # lists inside event_op_sharded (one shared pow2 cap,
                    # no global-map gather), occupancy-weighted when the
                    # plan can move load — at M_MESH's one tile row per
                    # shard it cannot, which `rebalanced=` records.
                    occ_np = np.asarray(
                        ops.padded_occupancy(s, BLOCK, BLOCK))
                    plan = rebalance_shard_plan(occ_np, n_shards)
                    if plan.identity or not plan.improves:
                        plan = None
                    sharded = jax.jit(functools.partial(
                        sharding.event_op_sharded, mesh, op,
                        occupancy=occ_np))
                    grid = "trimmed"
                    extra = (f"rebalanced={int(plan is not None)};"
                             f"{_shard_step_fields(occ_np, n_shards, plan)}")
                    _, rep = sharding.event_op_sharded(
                        mesh, op, s, w, with_report=True,
                        occupancy=occ_np, **kwargs)
                else:
                    sharded = jax.jit(functools.partial(
                        sharding.event_op_sharded, mesh, op, **kwargs))
                    grid = "dense-capped"    # traced in-shard pre-pass
                    # every shard runs the same clamped dense-capped
                    # union grid — the step columns say so explicitly
                    cap = (M_MESH // n_shards // BLOCK) * (K // BLOCK)
                    extra = (f"rebalanced=0;steps_cap={cap};"
                             "steps_per_shard="
                             + ":".join([str(cap)] * n_shards))
                    _, rep = sharding.event_op_sharded(
                        mesh, op, s, w, with_report=True, **kwargs)
                t_shard = time_fn(sharded, s, w) * 1e6
            pct = int(sparsity * 100)
            rows.append(csv_row(
                f"sparsity/mesh/{op}/single/s{pct}", t_single,
                f"platform={platform};shards=1;backend={csr};"
                f"grid=trimmed;{stats}"))
            rows.append(csv_row(
                f"sparsity/mesh/{op}/sharded/s{pct}", t_shard,
                f"platform={platform};shards={n_shards};"
                f"backend={rep['backend']};resolved={rep['attribution']};"
                f"grid={grid};{extra};{rep['occupancy'].as_fields()};"
                f"{stats}"))
    return rows


def run_mesh_rebalance(n_shards: int = MESH_SHARDS) -> list[str]:
    """Static row-contiguous vs occupancy-weighted shard split on hotspot
    maps — the load-balance half of BENCH_PR10.

    Rows ``sparsity/mesh/rebalance/spike_matmul/{static,rebalanced}/s<pct>``
    run the SAME carried map through `event_op_sharded` with rebalancing
    off and on, at `M_REBAL` (four tile rows per shard — room to move)
    on `hotspot_spikes` maps (one contiguous active band — the split a
    static partition concentrates on few shards). Forward outputs are
    asserted equal at 1e-5 (the plan only permutes who computes which
    tile rows), and the rebalanced row carries the pre/post imbalance
    pair (`occ_pre_*` columns from `OccupancyImbalance.as_fields`) plus
    the per-shard step columns under both splits.
    """
    import numpy as np

    from repro.core.spikes import rebalance_shard_plan
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding

    platform = jax.default_backend()
    if len(jax.devices()) < n_shards:
        raise RuntimeError(
            f"rebalance sweep needs {n_shards} devices, have "
            f"{len(jax.devices())} (on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards})")
    mesh = make_mesh((n_shards, 1), ("data", "model"))
    csr = "pallas-csr" if platform == "tpu" else "pallas-csr-interpret"
    rows = []
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    for sparsity in REBAL_SPARSITIES:
        key = jax.random.PRNGKey(int(sparsity * 1000))
        s = hotspot_spikes(key, M_REBAL, K, sparsity)
        occ_np = np.asarray(ops.padded_occupancy(s, BLOCK, BLOCK))
        plan = rebalance_shard_plan(occ_np, n_shards)
        if plan.identity or not plan.improves:
            plan = None
        with dispatch.use_backend(csr, op="spike_matmul"):
            out_st, rep_st = sharding.event_op_sharded(
                mesh, "spike_matmul", s, w, occupancy=occ_np,
                rebalance=False, with_report=True)
            out_rb, rep_rb = sharding.event_op_sharded(
                mesh, "spike_matmul", s, w, occupancy=occ_np,
                with_report=True)
            np.testing.assert_allclose(np.asarray(out_rb),
                                       np.asarray(out_st), atol=1e-5)
            t_st = time_fn(jax.jit(functools.partial(
                sharding.event_op_sharded, mesh, "spike_matmul",
                occupancy=occ_np, rebalance=False)), s, w) * 1e6
            t_rb = time_fn(jax.jit(functools.partial(
                sharding.event_op_sharded, mesh, "spike_matmul",
                occupancy=occ_np)), s, w) * 1e6
        pct = int(sparsity * 100)
        imb_st = rep_st["occupancy"].imbalance
        imb_rb = rep_rb["occupancy"].imbalance
        rows.append(csv_row(
            f"sparsity/mesh/rebalance/spike_matmul/static/s{pct}", t_st,
            f"platform={platform};shards={n_shards};"
            f"backend={rep_st['backend']};generator=hotspot;rows={M_REBAL};"
            f"rebalanced=0;{_shard_step_fields(occ_np, n_shards)};"
            f"{rep_st['occupancy'].as_fields()}"))
        rows.append(csv_row(
            f"sparsity/mesh/rebalance/spike_matmul/rebalanced/s{pct}", t_rb,
            f"platform={platform};shards={n_shards};"
            f"backend={rep_rb['backend']};generator=hotspot;rows={M_REBAL};"
            f"rebalanced={int(plan is not None)};parity_vs_static=1e-5;"
            f"imbalance_vs_static={imb_rb / imb_st:.3f};"
            f"{_shard_step_fields(occ_np, n_shards, plan)};"
            f"{rep_rb['occupancy'].as_fields()}"))
    return rows


def run_mesh_rows() -> list[str]:
    """Suite entry for benchmarks.run: the mesh rows on this process's
    own devices (on a CPU host, force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    return run_mesh()


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="sharded-vs-single CSR columns on an "
                         f"{MESH_SHARDS}-way host mesh")
    ap.add_argument("--shards", type=int, default=MESH_SHARDS)
    ap.add_argument("--pipelined", action="store_true",
                    help="paired pipelined-vs-serial CSR rows with the "
                         "DMA-overlap ledger (single device)")
    ap.add_argument("--rebalance", action="store_true",
                    help="(with --mesh) static-vs-rebalanced shard-split "
                         f"rows on hotspot maps at M={M_REBAL}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="(with --mesh) also write BENCH_PR4-schema JSON: "
                         "mesh shape, mesh-aware resolved backends "
                         "(attribution), and the rows")
    ap.add_argument("--pr10", default=None, metavar="PATH",
                    help="write BENCH_PR10 JSON: pipelined paired rows "
                         "plus mesh + rebalance rows")
    args = ap.parse_args()
    if args.pr10:
        rows = (run_pipelined() + run_mesh(args.shards)
                + run_mesh_rebalance(args.shards))
        print("\n".join(rows))
        with open(args.pr10, "w") as f:
            json.dump({"mesh": {"shards": args.shards,
                                "axes": ["data", "model"],
                                "platform": jax.default_backend()},
                       "pipelined_geometry": {"M": M, "K": K, "N": N,
                                              "apec_g": APEC_G},
                       "rebalance_geometry": {"M": M_REBAL, "K": K,
                                              "generator": "hotspot",
                                              "sparsities":
                                              list(REBAL_SPARSITIES)},
                       "bench_rows_per_shard": M_MESH // args.shards,
                       "rows": rows}, f, indent=2)
        return
    if args.pipelined:
        print("\n".join(run_pipelined()))
        return
    if not args.mesh:
        print("\n".join(run()))
        return
    rows = run_mesh(args.shards)
    if args.rebalance:
        rows += run_mesh_rebalance(args.shards)
    print("\n".join(rows))
    if args.json:
        from repro.kernels import dispatch
        csr = ("pallas-csr" if jax.default_backend() == "tpu"
               else "pallas-csr-interpret")
        # Two resolution snapshots: the canonical example shapes are too
        # small to fill per-shard 128-row tiles, so their attribution
        # shows the degrade chain ("pallas<-pallas-csr"); the bench
        # shapes (M_MESH rows) divide cleanly, so the csr family holds —
        # per-row `resolved=` fields record it. Committing both pins the
        # two sides of the mesh gate.
        with dispatch.use_backend(csr, op="spike_matmul"), \
                dispatch.use_backend(csr, op="apec_matmul"), \
                dispatch.use_backend(csr, op="econv"):
            resolved_small = dispatch.resolved_backends(mesh=args.shards)
        with open(args.json, "w") as f:
            json.dump({"mesh": {"shards": args.shards,
                                "axes": ["data", "model"],
                                "platform": jax.default_backend()},
                       "requested_csr_family": csr,
                       "bench_rows_per_shard": M_MESH // args.shards,
                       "resolved_mesh_aware_example_shapes": resolved_small,
                       "rows": rows}, f, indent=2)


if __name__ == "__main__":
    main()

"""Whole-network forward: carried occupancy (EventTensor) vs re-derive.

The PR 3/4 sweeps timed single ops; this suite times the thing the
full-event pipeline actually changes — a whole multi-layer forward where
every spiking layer's metadata either (a) is re-derived by each consumer
from the dense activation it was just handed (`rederive`: the pre-PR 5
model behavior) or (b) flows from the producer as an `EventTensor`
(`carried`: the fused LIF emits the map, convs propagate it through
im2col on tile granularity, matmuls consume it directly).

Layer stacks mirror the two model families' event-hot shapes (the paper's
SCNN convs and the SpikingFormer SPS + FFN); each layer's drive is
clustered-event spikes pinned at the sweep sparsity (the
`sparsity_sweep.clustered_spikes` generator — LIF with v_th=1 fires a
{0,1}*v_th drive back out exactly, so per-layer sparsity is controlled at
the PR 3 points instead of drifting with untrained weights). Both
variants run the same kernels (`pallas-csr` family) on identical spike
values — the measured delta is purely the metadata plumbing: the
consumer-side dense `tile_occupancy` passes (kh*kw-fold on im2col
patches) the carried route deletes, minus the producer-side emission it
adds.

Rows: ``e2e_event/<family>/<carried|rederive>/s<pct>`` with the network
total, per-layer pre-pass share columns (``prepass_share_<layer>``: the
fraction of the re-derive total each layer's standalone pre-pass eats,
measured on that layer's actual consumer operand), and a
``e2e_event/<family>/speedup/s<pct>`` row (rederive/carried). Committed
as BENCH_PR5.json by CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costmodel
from repro.core.events import EventTensor
from repro.core.lif import LIFConfig
from repro.core.spikes import build_csr
from repro.kernels import dispatch, ops
from repro.models.layers import lif_fire_events
from .common import (csv_row, noise_band, not_slower, time_fn,
                     time_interleaved, time_pair)
from .sparsity_sweep import SPARSITIES, clustered_spikes

LIF = LIFConfig()        # v_th=1.0: a {0,1} drive fires itself back out

# (name, kind, drive shape (T, B, ...), weight shape). Conv layers are the
# event-hot part of both families: their re-derive pre-pass reads the
# kh*kw-times-larger im2col patch tensor (K = 9*C at 3x3).
FAMILIES = {
    "cnn": (           # VGG event-hot tail (8x8x128 convs) + EAFC-style
                       # fused fc head, T=2
        ("conv1", "conv", (2, 2, 8, 8, 128), (3, 3, 128, 128)),
        ("conv2", "conv", (2, 2, 8, 8, 128), (3, 3, 128, 128)),
        ("conv3", "conv", (2, 2, 8, 8, 128), (3, 3, 128, 128)),
        ("fc_head", "matmul", (2, 2, 64, 512), (512, 128)),
    ),
    "spikingformer": (                        # SPS tail + encoder FFN, T=4
        ("sps_conv", "conv", (4, 2, 8, 8, 128), (3, 3, 128, 128)),
        ("fc1", "matmul", (4, 2, 64, 512), (512, 128)),
        ("fc2", "matmul", (4, 2, 64, 512), (512, 128)),
    ),
}
ITERS = 24   # CPU wall-clock needs more samples than the op sweeps


def _time_min(fn, *args, iters=ITERS, warmup=2):
    """Best-of-N wall seconds (stable for the small pre-pass probes)."""
    mins, _ = time_interleaved({"fn": fn}, *args, iters=iters, warmup=warmup)
    return mins["fn"]


def _time_pair(fn_a, fn_b, *args, iters=ITERS, warmup=2):
    """Paired interleaved min-of-N via the shared protocol
    (`common.time_pair` — one implementation for this sweep and the
    hybrid trio timer). Returns (min_a, min_b, min_b/min_a)."""
    return time_pair(fn_a, fn_b, *args, iters=iters, warmup=warmup)


def _stage_drive(key, kind, shape, sparsity):
    t = shape[0]
    k = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    pattern = clustered_spikes(key, rows, k, sparsity, block_m=128,
                               block_k=min(128, k))
    return (pattern * LIF.v_th).reshape(shape)


def _consume(kind, s, w):
    """The layer's event op on spikes-or-EventTensor (csr family pinned
    by the caller): conv folds (T, B) into the batch like models/cnn."""
    if kind == "conv":
        from repro.core.econv import econv
        flat = s.reshape((-1,) + s.shape[2:])
        return econv(flat, w)
    return dispatch.spike_matmul(s, w)


# Jitted producers (one compile per drive shape): the fire stage is the
# same compiled scan in both variants — `carried` additionally emits the
# map inside the same jit, `rederive` leaves the consumer to re-derive it
# eagerly from the dense spikes (the serve-path calling convention, where
# concrete maps buy the trimmed eager CSR grid).
@jax.jit
def _produce_carried(drive):
    return lif_fire_events(drive, LIF)


@jax.jit
def _produce_dense(drive):
    return dispatch.lif_scan(drive)


@jax.jit
def _produce_packed(drive):
    # uint32 words as the canonical payload: packing fused into the same
    # emission pass that popcounts the occupancy map (no f32 spike tensor
    # leaves the fire stage)
    return lif_fire_events(drive, LIF, packed=True)


def _forward(drives, stages, carried: bool):
    outs = []
    for (name, kind, _, w), drive in zip(stages, drives):
        s = _produce_carried(drive) if carried else _produce_dense(drive)
        outs.append(_consume(kind, s, w))
    return outs


def _forward_packed(drives, stages):
    """The packed pipeline: every fire stage emits a packed-only
    EventTensor and every consumer unpacks VMEM-resident in-kernel."""
    outs = []
    for (name, kind, _, w), drive in zip(stages, drives):
        outs.append(_consume(kind, _produce_packed(drive), w))
    return outs


def _layer_prepass_seconds(kind, drive, w):
    """What the re-derive route pays per call for THIS layer: the dense
    `tile_occupancy` read of the consumer operand (im2col patches for
    convs) plus the eager CSR compaction."""
    s = _produce_dense(drive)
    if kind == "conv":
        flat = s.reshape((-1,) + s.shape[2:])
        kh, kw = w.shape[:2]
        operand = jax.lax.conv_general_dilated_patches(
            flat, (kh, kw), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        operand = operand.reshape(-1, operand.shape[-1])
    else:
        operand = s.reshape(-1, s.shape[-1])

    def prepass(x):
        return build_csr(ops.padded_occupancy(x), 128, 128)

    return _time_min(prepass, operand)


def run() -> list[str]:
    rows = []
    platform = jax.default_backend()
    csr = "pallas-csr" if platform == "tpu" else "pallas-csr-interpret"
    for family, spec in FAMILIES.items():
        stages = [(n, kind, shape,
                   jax.random.normal(jax.random.PRNGKey(i + 1),
                                     wshape, jnp.float32) * 0.05)
                  for i, (n, kind, shape, wshape) in enumerate(spec)]
        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            drives = [
                _stage_drive(jax.random.fold_in(key, i), kind, shape,
                             sparsity)
                for i, (_, kind, shape, _w) in enumerate(stages)]
            with dispatch.use_backend(csr, op="spike_matmul"), \
                    dispatch.use_backend(csr, op="econv"):
                # value parity guard: same spikes, same kernels — the two
                # routes must agree before their timings mean anything
                for oc, od in zip(_forward(drives, stages, True),
                                  _forward(drives, stages, False)):
                    np.testing.assert_allclose(np.asarray(oc),
                                               np.asarray(od), atol=1e-4)
                # Per-layer paired timing, summed to the network total:
                # each layer's two routes are measured interleaved under
                # identical cache/scheduler conditions (a monolithic
                # whole-pipeline call lets allocator/cache interactions
                # between unrelated layers leak into the few-ms metadata
                # delta being measured).
                t_carried = t_rederive = 0.0
                fields = []
                for stage, d in zip(stages, drives):
                    a, b, _ = _time_pair(
                        lambda dd, st=stage: _forward([dd], [st], True),
                        lambda dd, st=stage: _forward([dd], [st], False), d)
                    t_carried += a * 1e6
                    t_rederive += b * 1e6
                    name, kind, _, w = stage
                    pre = _layer_prepass_seconds(kind, d, w) * 1e6
                    fields.append((name, a * 1e6, b * 1e6, pre))
                shares = ";".join(
                    f"prepass_share_{name}="
                    f"{pre / max(t_rederive, 1e-9):.3f}"
                    for name, _, _, pre in fields)
                layer_cols = ";".join(
                    f"us_{name}={ca:.0f}/{re:.0f}"
                    for name, ca, re, _ in fields)
            pct = int(sparsity * 100)
            common = f"platform={platform};backend={csr};layers={len(stages)}"
            rows.append(csv_row(f"e2e_event/{family}/carried/s{pct}",
                                t_carried, f"{common};occupancy=carried"))
            rows.append(csv_row(f"e2e_event/{family}/rederive/s{pct}",
                                t_rederive,
                                f"{common};occupancy=rederived;{shares};"
                                f"{layer_cols}"))
            rows.append(csv_row(
                f"e2e_event/{family}/speedup/s{pct}", 0.0,
                f"carried_speedup="
                f"{t_rederive / max(t_carried, 1e-9):.3f};{common}"))
    return rows


# ----------------------------------------------- packed payload (PR 7)
def _consumer_operand(kind, s_dense, w):
    """The (R, K) matrix the layer's matmul-form kernel actually tiles:
    im2col patches for convs (K = kh*kw*C), the flattened spikes for
    matmuls — the operand whose occupancy map prices the bytes ledger."""
    if kind == "conv":
        flat = s_dense.reshape((-1,) + s_dense.shape[2:])
        kh, kw = w.shape[:2]
        operand = jax.lax.conv_general_dilated_patches(
            flat, (kh, kw), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return operand.reshape(-1, operand.shape[-1]), w.shape[-1]
    return s_dense.reshape(-1, s_dense.shape[-1]), w.shape[-1]


def _stack_bytes(stages, drives):
    """Modeled HBM bytes over the stack, f32-csr vs packed-csr: emission
    writes (`costmodel.spike_payload_bytes`) + consumer spike-tile reads
    (`costmodel.matmul_bytes_moved`), with the payload-invariant weight/
    output traffic kept separate — both routes run the SAME trimmed grid,
    so only the event-payload stream responds to packing."""
    spike = {"f32": 0.0, "packed": 0.0}
    weight = out = 0.0
    for (name, kind, shape, w), drive in zip(stages, drives):
        s = _produce_dense(drive)
        operand, n = _consumer_operand(kind, s, w)
        occ = np.asarray(ops.padded_occupancy(operand, 128, 128))
        rows_emit = int(np.prod(shape[:-1]))
        for payload, backend in (("f32", "pallas-csr"),
                                 ("packed", "packed-csr")):
            bm = costmodel.matmul_bytes_moved(occ, n, backend=backend)
            spike[payload] += bm.spike_hbm + costmodel.spike_payload_bytes(
                rows_emit, shape[-1],
                "dense" if payload == "f32" else "packed")
        weight += bm.weight_hbm
        out += bm.out_hbm
    return spike, weight, out


def run_packed() -> list[str]:
    """Packed uint32 pipeline vs the f32 CSR pipeline, same stacks.

    Rows per (family, sparsity):
      ``e2e_event/<family>/f32csr/s<pct>``   stack produce+consume us,
          dense f32 spikes through the pallas-csr family.
      ``e2e_event/<family>/packed/s<pct>``   same stack with packed
          emission and packed-csr consumers; ``routes=`` asserts every
          consume resolved to the packed family (no silent densify).
      ``e2e_event/<family>/packed_margin/s<pct>``  paired ratio vs the
          self-measured clone noise band (the hybrid suite's protocol).
      ``e2e_event/<family>/bytes/s<pct>``    the modeled bytes-moved
          ledger: event-payload HBM traffic (emission writes + spike-tile
          reads) per payload, reduction, and the payload-invariant
          weight/output traffic alongside. Committed as BENCH_PR7.json.
    """
    rows = []
    platform = jax.default_backend()
    tpu = platform == "tpu"
    csr = "pallas-csr" if tpu else "pallas-csr-interpret"
    pcsr = "packed-csr-interpret"   # the packed family is CPU-only

    def f32_scope():
        import contextlib
        ctx = contextlib.ExitStack()
        for op in ("spike_matmul", "econv"):
            ctx.enter_context(dispatch.use_backend(csr, op=op))
        return ctx

    def packed_scope():
        import contextlib
        ctx = contextlib.ExitStack()
        for op in ("spike_matmul", "econv"):
            ctx.enter_context(dispatch.use_backend(pcsr, op=op))
        return ctx

    for family, spec in FAMILIES.items():
        stages = [(n, kind, shape,
                   jax.random.normal(jax.random.PRNGKey(i + 1),
                                     wshape, jnp.float32) * 0.05)
                  for i, (n, kind, shape, wshape) in enumerate(spec)]
        for sparsity in SPARSITIES:
            key = jax.random.PRNGKey(int(sparsity * 1000))
            drives = [
                _stage_drive(jax.random.fold_in(key, i), kind, shape,
                             sparsity)
                for i, (_, kind, shape, _w) in enumerate(stages)]
            # parity guard: the packed route must match the f32 oracle
            # before its timings mean anything, and every consume must
            # ATTRIBUTE to the packed family (never a silent densify)
            with f32_scope():
                ref = _forward(drives, stages, True)
            with dispatch.watch_resolutions() as recs, packed_scope():
                outs = _forward_packed(drives, stages)
            for a, b in zip(ref, outs):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-4)
            picked = [r["attribution"].split("<-")[0] for r in recs
                      if r["op"] in ("spike_matmul", "econv")]
            assert picked and all(p == pcsr for p in picked), \
                f"packed consume leaked off the packed family: {picked}"
            routes = ":".join(sorted(set(picked)))

            # per-layer paired timing (the hybrid suite's protocol):
            # modes interleaved per layer, per-(layer, mode) minimums
            # summed; f32b/packedb re-run the same pins — their sums
            # against the originals are the measured noise floor.
            modes = ("f32", "packed", "f32b", "packedb")
            sums = {m: 0.0 for m in modes}
            for stage, d in zip(stages, drives):
                def one(m, st=stage, dd=d):
                    if m.startswith("f32"):
                        with f32_scope():
                            return _forward([dd], [st], True)
                    with packed_scope():
                        return _forward_packed([dd], [st])
                layer_best, _ = time_interleaved(
                    {m: (lambda m=m: one(m)) for m in modes}, iters=ITERS)
                for m in modes:
                    sums[m] += layer_best[m]
            ratio = sums["packed"] / sums["f32"]
            band = max(abs(sums["f32b"] / sums["f32"] - 1.0),
                       abs(sums["packedb"] / sums["packed"] - 1.0))

            spike, weight_b, out_b = _stack_bytes(stages, drives)
            mb = 1.0 / 2**20
            pct = int(sparsity * 100)
            common = f"platform={platform};layers={len(stages)}"
            rows.append(csv_row(f"e2e_event/{family}/f32csr/s{pct}",
                                sums["f32"] * 1e6,
                                f"{common};backend={csr}"))
            rows.append(csv_row(f"e2e_event/{family}/packed/s{pct}",
                                sums["packed"] * 1e6,
                                f"{common};backend={pcsr};routes={routes}"))
            rows.append(csv_row(
                f"e2e_event/{family}/packed_margin/s{pct}", 0.0,
                f"packed_vs_f32={ratio:.3f};noise_band={band:.3f};"
                f"not_slower={not_slower(ratio, band)};{common}"))
            rows.append(csv_row(
                f"e2e_event/{family}/bytes/s{pct}", 0.0,
                f"spike_mb_f32={spike['f32'] * mb:.3f};"
                f"spike_mb_packed={spike['packed'] * mb:.3f};"
                f"bytes_reduction={spike['f32'] / spike['packed']:.1f};"
                f"weight_mb={weight_b * mb:.3f};out_mb={out_b * mb:.3f};"
                f"total_mb_f32={(spike['f32'] + weight_b + out_b) * mb:.3f};"
                f"total_mb_packed="
                f"{(spike['packed'] + weight_b + out_b) * mb:.3f};"
                f"total_reduction="
                f"{(spike['f32'] + weight_b + out_b) / (spike['packed'] + weight_b + out_b):.2f};"
                f"{common}"))
    return rows


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--packed", action="store_true",
                    help="packed-payload rows (e2e packed pipeline + "
                         "single-op packed sparsity sweep) instead of the "
                         "carried-vs-rederive suite")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="(with --packed) write BENCH_PR7-schema JSON: "
                         "packed-route resolution + the bytes-moved rows")
    args = ap.parse_args()
    if not args.packed:
        print("\n".join(run()))
        return
    if not dispatch.packed_kernels_available():
        raise SystemExit(f"--packed: no packed-csr kernels on "
                         f"{jax.default_backend()}")
    from .sparsity_sweep import run_packed as run_packed_ops
    rows = run_packed_ops() + run_packed()
    print("\n".join(rows))
    if args.json:
        pcsr = "packed-csr-interpret"
        with dispatch.use_backend(pcsr, op="spike_matmul"), \
                dispatch.use_backend(pcsr, op="apec_matmul"), \
                dispatch.use_backend(pcsr, op="econv"):
            resolved = dispatch.resolved_backends()
        with open(args.json, "w") as f:
            json.dump({"sweeps": [{
                "requested": pcsr,
                "resolved": resolved,
                "rows": rows,
            }]}, f, indent=2)


if __name__ == "__main__":
    main()

"""Jit'd public wrappers around the Pallas kernels.

These handle padding to block multiples, dtype plumbing, head/batch axis
flattening, and CPU-interpret fallback, so model code can call them on
arbitrary shapes. Each wrapper is shape-polymorphic under jit and safe to
use inside pjit/shard_map (pure, no host callbacks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.events import EventTensor
from repro.core.spikes import (PACK, TileCSR, build_csr, pack_spikes,
                               pack_spikes_padded, packed_tile_occupancy,
                               packed_width, tile_occupancy, unpack_spikes)
from .im2col import im2col_pallas
from .lif_scan import (lif_scan_occ_packed_pallas, lif_scan_occ_pallas_sg,
                       lif_scan_pallas_sg)
from .sdsa_kernel import (sdsa_causal_status_pallas, sdsa_packed,
                          sdsa_status_pallas)
from .spike_matmul import (apec_matmul_csr_pallas,
                           apec_matmul_packed_csr_pallas,
                           spike_matmul_csr_pallas,
                           spike_matmul_packed_csr_pallas,
                           spike_matmul_pallas)


def _pad_to(x: jax.Array, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("decay", "v_th", "soft_reset",
                                              "surrogate_alpha"))
def lif(x: jax.Array, decay: float = 0.5, v_th: float = 1.0,
        soft_reset: bool = True, surrogate_alpha: float = 2.0) -> jax.Array:
    """Fused LIF over leading time axis, any trailing shape.

    Differentiable: routes through `lif_scan_pallas_sg`, whose backward is
    the reversed-scan Pallas kernel with the ATan surrogate. Padding /
    reshape around the kernel are native jax ops, so `jax.grad` composes.
    """
    t = x.shape[0]
    rest = x.shape[1:]
    flat = x.reshape(t, -1)
    # Fold into (T, M, N) with N a lane multiple.
    n = 128
    flat, orig = _pad_to(flat, 1, n * 8)
    m = flat.shape[1] // n
    out = lif_scan_pallas_sg(flat.reshape(t, m, n), decay, v_th, soft_reset,
                             surrogate_alpha)
    return out.reshape(t, -1)[:, :orig].reshape((t,) + rest)


@functools.partial(jax.jit, static_argnames=("decay", "v_th", "soft_reset",
                                              "surrogate_alpha", "packed"))
def lif_occ(x: jax.Array, decay: float = 0.5, v_th: float = 1.0,
            soft_reset: bool = True, surrogate_alpha: float = 2.0,
            packed: bool = False):
    """Fused LIF that also emits the (128, 128)-tiled occupancy map of its
    own spike output — the full-event producer.

    x: (T, ..., K) drive -> (spikes (T, ..., K),
    occupancy (ceil(T*R/128), ceil(K/128)) int32,
    chunks (ceil(T*R/128)*16, ceil(K/128)) int32) where R = prod of the
    middle axes. `occupancy` is exactly `padded_occupancy(spikes)` —
    valid for every matmul-form consumer that flattens lead axes into
    rows; `chunks` is the kernel's native per-(8-row, 128-lane) popcount
    map (what window propagation dilates at fine granularity). Both come
    from the scan kernel's in-VMEM popcounts plus a reduction over the
    tiny count map, never a dense re-read of the spikes. Requires
    R % 8 == 0 (the kernel's row-chunk size; the dispatch `supports`
    gate falls back to ref otherwise).

    ``packed=True`` switches to the FORWARD-ONLY fused pack emission:
    the first return value is the uint32 word tensor
    (T, ..., ceil(K/32)) instead of dense spikes — packed in-VMEM by the
    same kernel pass that fires, with the counts taken from the words'
    popcounts, so no f32 spike tensor ever reaches HBM. The K padding to
    the lane tile never fires (zero drive keeps v below threshold), so
    slicing the word axis to `packed_width(K)` leaves the exact words
    `pack_spikes_padded` would produce, tail bits zero.
    """
    t = x.shape[0]
    k = x.shape[-1]
    mid = x.shape[1:-1]
    r = 1
    for d in mid:
        r *= d
    if r % 8:
        raise ValueError(f"middle axes {mid} (R={r}) must divide by 8")
    xr = x.reshape(t, r, k)
    xr, k_orig = _pad_to(xr, 2, 128)   # zero drive never fires: counts 0
    if packed:
        p, cnt = lif_scan_occ_packed_pallas(xr, decay=decay, v_th=v_th,
                                            soft_reset=soft_reset)
        pw = packed_width(k_orig)
        payload = p[..., :pw].reshape(x.shape[:-1] + (pw,))
    else:
        s, cnt = lif_scan_occ_pallas_sg(xr, decay, v_th, soft_reset,
                                        surrogate_alpha)
        payload = s[..., :k_orig].reshape(x.shape)
    # (T, R/8, KT) per-chunk counts -> (ceil(T*R/128), KT) matmul tiles:
    # flattened row chunk (t, a) sits at index t*(R/8)+a, so groups of 16
    # consecutive chunks are exactly the 128-row tiles (zero-padded tail
    # chunks match the consumers' zero-padded rows).
    kt = cnt.shape[-1]
    cnt2 = cnt.reshape(t * (r // 8), kt)
    cnt2, _ = _pad_to(cnt2, 0, 16)
    occ = jnp.sum(cnt2.reshape(-1, 16, kt), axis=1)
    return (payload, jax.lax.stop_gradient(occ),
            jax.lax.stop_gradient(cnt2))


@jax.jit
def sdsa_or(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Paper-faithful OR-form SDSA on dense binary tensors of shape
    (..., N, d); internally bit-packed and run through the Pallas kernels.
    """
    lead = q.shape[:-2]
    n, d = q.shape[-2:]
    dt = q.dtype

    def prep(x):
        x = x.reshape(-1, n, d)
        x, _ = _pad_to(x, 2, PACK)
        return pack_spikes(x, axis=-1)

    qp, kp, vp = prep(q), prep(k), prep(v)
    # Pad N to a block_n multiple (the kernel grid divides N exactly);
    # zero K/V rows are OR no-ops, zero Q rows are sliced off below.
    block_n = min(256, n + (-n) % 8)
    qp, n_orig = _pad_to(qp, 1, block_n)
    kp, _ = _pad_to(kp, 1, block_n)
    vp, _ = _pad_to(vp, 1, block_n)
    out_p = sdsa_packed(qp, kp, vp, block_n=block_n)
    out = unpack_spikes(out_p, axis=-1, dtype=dt)[:, :n_orig, :d]
    return out.reshape(lead + (n, d))


@jax.jit
def causal_sdsa_or(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal (LM) OR-form SDSA on dense binary tensors.

    q, k, v: (T, ..., N, d) with T the micro-timestep axis and N the token
    axis. status[i] = OR over micro-steps and tokens j <= i of K AND V;
    out[t, i] = Q[t, i] AND status[i]. Internally bit-packed: the kv mask
    is OR-folded over T elementwise, the prefix-OR over tokens runs in the
    Pallas causal-status kernel, and the Q AND is a packed vector op.
    """
    t = q.shape[0]
    lead = q.shape[1:-2]
    n, d = q.shape[-2:]
    dt = q.dtype

    def prep(x):
        x = x.reshape(t, -1, n, d)
        x, _ = _pad_to(x, 3, PACK)
        return pack_spikes(x, axis=-1)

    qp, kp, vp = prep(q), prep(k), prep(v)
    # kv mask per micro-step, then OR over T (elementwise on packed words).
    kv = jax.lax.reduce(kp & vp, jnp.uint32(0), jax.lax.bitwise_or, (0,))
    # Token-axis padding must reach a block_n multiple (the kernel grid
    # divides N exactly); trailing zero rows are prefix-OR no-ops and the
    # padded outputs are sliced off.
    block_n = min(256, n + (-n) % 8)
    kv, n_orig = _pad_to(kv, 1, block_n)
    status = sdsa_causal_status_pallas(kv, block_n=block_n)
    out_p = qp & status[None, :, :n_orig, :]
    out = unpack_spikes(out_p, axis=-1, dtype=dt)[..., :d]
    return out.reshape((t,) + lead + (n, d))


@jax.jit
def sdsa_status(k: jax.Array, v: jax.Array) -> jax.Array:
    """Status vector only (decode prefill path). (..., N, d) -> (..., d)."""
    lead = k.shape[:-2]
    n, d = k.shape[-2:]

    block_n = min(256, n + (-n) % 8)

    def prep(x):
        x = x.reshape(-1, n, d)
        x, _ = _pad_to(x, 2, PACK)
        x, _ = _pad_to(x, 1, block_n)
        return pack_spikes(x, axis=-1)

    kp, vp = prep(k), prep(v)
    st = sdsa_status_pallas(kp, vp, block_n=block_n)
    return unpack_spikes(st, axis=-1, dtype=k.dtype)[:, :d].reshape(lead + (d,))


@functools.partial(jax.jit, static_argnames=("g",))
def apec_decompose(s: jax.Array, g: int = 2):
    """Dense binary (P, C) spikes -> (overlap (P/g, C), residual (P, C))
    via the packed bitwise kernel. P must divide by g."""
    from .apec_kernel import apec_decompose_packed
    p, c = s.shape
    sp, _ = _pad_to(s, 1, PACK)
    packed = pack_spikes(sp, axis=-1)
    packed, p_orig = _pad_to(packed, 0, g * 8)
    ov_p, res_p = apec_decompose_packed(packed, g,
                                        block_n=min(128, packed.shape[1]))
    ov = unpack_spikes(ov_p, axis=-1, dtype=s.dtype)[: p_orig // g, :c]
    res = unpack_spikes(res_p, axis=-1, dtype=s.dtype)[:p_orig, :c]
    return ov, res


def _pad_operands(s2, w, block_m, block_n, block_k):
    """Pad a flattened (R, K) spike matrix and (K, N) weights to block
    multiples — padding adds zeros, so it can never mark a tile occupied."""
    s2, m_orig = _pad_to(s2, 0, block_m)
    s2, _ = _pad_to(s2, 1, block_k)
    w2, _ = _pad_to(w, 0, block_k)
    w2, n_orig = _pad_to(w2, 1, block_n)
    return s2, w2, m_orig, n_orig


def padded_occupancy(s: jax.Array, block_m: int = 128,
                     block_k: int = 128) -> jax.Array:
    """The occupancy pre-pass exactly as `spike_matmul` computes it: lead
    axes flattened into rows, then padded-tiling per-tile event counts.
    Callers running several matmuls over the *same* spike tensor (e.g. one
    encoding against several weight matrices, or stat collection alongside
    the matmul) run this once and pass the result through
    `spike_matmul(..., occupancy=)` or `occupancy_to_csr` ->
    `spike_matmul_csr(..., csr=)`. The kernels validate the map's shape
    against their tiling — a map for another tiling would silently gate
    the wrong tiles.
    """
    k = s.shape[-1]
    s2 = s.reshape(-1, k)
    s2, _ = _pad_to(s2, 0, block_m)
    s2, _ = _pad_to(s2, 1, block_k)
    return tile_occupancy(s2, block_m, block_k)


def _carried_occupancy(s, occupancy, block_m: int, block_k: int,
                       want_csr: bool = False):
    """Unpack an EventTensor operand into (dense spikes, validated carried
    occupancy, cached TileCSR). Explicit `occupancy=` wins over the
    carried map; a map built for another tiling raises (loudly) inside
    `EventTensor.occupancy_for`."""
    if isinstance(s, EventTensor):
        csr = None
        if occupancy is None:
            occupancy = s.occupancy_for(block_m, block_k)
            if want_csr and occupancy is not None:
                csr = s.csr(block_m, block_k)
        return s.spikes, occupancy, csr
    return s, occupancy, None


def _group_occupancy(occ, g: int, rows: int, block_m: int = 128):
    """Conservative overlap-operand map derived from the carried map of
    the undecomposed spikes: the overlap tile at row-tile i unions group
    members living in s row-tiles [g*i, g*i+g) (AND-of-group is a subset
    of each member, so a zero s-tile group guarantees a zero overlap
    tile). Only derivable when the row tiling regroups exactly
    (rows % (block_m*g) == 0); otherwise None (caller re-derives)."""
    if occ is None or rows % (block_m * g):
        return None
    mt = occ.shape[0]
    return jnp.sum(occ.reshape(mt // g, g, occ.shape[1]), axis=1)


@functools.partial(jax.jit, static_argnames=("g",))
def _apec_matmul_jit(w, g, ov, res, occ_res, occ_ov):
    wf = w.astype(jnp.float32)
    psum_ov = spike_matmul(ov, wf, occupancy=occ_ov)   # (R/g, F) cached sums
    psum_res = spike_matmul(res, wf, occupancy=occ_res)  # (R, F) residuals
    return psum_res + jnp.repeat(psum_ov, g, axis=0)   # reuse across members


def apec_matmul(s, w: jax.Array, g: int = 2, *, decomposed=None,
                occ_res: jax.Array | None = None,
                occ_ov: jax.Array | None = None,
                occupancy: jax.Array | None = None) -> jax.Array:
    """APEC matmul on the packed kernels: bitwise overlap/residual
    decomposition, then two occupancy-skipping matmuls with the overlap
    partial sums reused across each group's members.

    s: (..., P, C) binary (or an `EventTensor`) with P % g == 0;
    w: (C, F) -> (..., P, F). Leading axes are flattened into the
    position axis — safe because each row contributes whole groups when P
    divides by g.

    Callers that already decomposed pass ``decomposed=(residual,
    overlap)`` (flattened (R, C) / (R/g, C)) plus their per-operand maps
    ``occ_res`` / ``occ_ov`` — aligning this path with the CSR kernel's
    single-pre-pass behavior instead of paying two fresh dense passes
    here. A carried ``occupancy`` (of the undecomposed s) gates both
    matmuls conservatively: residual tiles are a subset of s tiles, and
    the overlap map folds g s-row-tiles (`_group_occupancy`).
    """
    s, occupancy, _ = _carried_occupancy(s, occupancy, 128, 128)
    lead = s.shape[:-2]
    p, c = s.shape[-2:]
    if p % g:
        raise ValueError(f"positions {p} not divisible by group {g}")
    s2 = s.reshape(-1, c)
    if decomposed is None:
        ov, res = apec_decompose(s2, g)              # packed bitwise kernel
    else:
        res, ov = decomposed
    if occupancy is not None and occ_res is None:
        occ_res = occupancy                          # res tiles ⊆ s tiles
        if occ_ov is None:
            occ_ov = _group_occupancy(occupancy, g, s2.shape[0])
    out = _apec_matmul_jit(w, g, ov, res, occ_res, occ_ov)
    return out.reshape(lead + (p, w.shape[-1])).astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def spike_matmul(s, w: jax.Array, block_m: int = 128,
                 block_n: int = 128, block_k: int = 128,
                 occupancy: jax.Array | None = None) -> jax.Array:
    """Occupancy-skipping spike matmul for (..., M, K) x (K, N).

    `s` may be an `EventTensor` — its carried map replaces the pre-pass.
    `occupancy`: optional precomputed per-tile event counts from
    `padded_occupancy(s, block_m, block_k)` (or the fused LIF emission) —
    callers that already hold the map skip recomputing it here. A map for
    the wrong tiling/tile grid is rejected, never silently consumed.

    This is the PREDICATED-DENSE route of the hybrid pair: the grid walks
    every tile and the map gates compute per step. Density-adaptive
    dispatch (`kernels.dispatch.use_hybrid`) picks between this and the
    event-compacted `spike_matmul_csr` per call from the carried map's
    occupied-tile count — direct callers pick a route statically instead.
    """
    s, occupancy, _ = _carried_occupancy(s, occupancy, block_m, block_k)
    lead = s.shape[:-2]
    m, k = s.shape[-2:]
    n = w.shape[-1]
    s2 = s.reshape(-1, k) if lead else s.reshape(m, k)
    s2, w2, m_orig, n_orig = _pad_operands(s2, w, block_m, block_n, block_k)
    if occupancy is None:
        occupancy = tile_occupancy(s2, block_m, block_k)
    out = spike_matmul_pallas(s2, w2, occupancy, block_m=block_m,
                              block_n=block_n, block_k=block_k)
    out = out[:m_orig, :n_orig]
    return out.reshape(lead + (m, n)) if lead else out


# ------------------------------------------------- event-compacted (CSR)
# The pow2-bucketed CSR builder lives in core.spikes.build_csr (shared
# with the per-shard pre-pass and EventTensor.csr).
_build_csr = build_csr


def _check_map(occupancy, s2, block_m, block_k):
    if occupancy.shape != (s2.shape[0] // block_m, s2.shape[1] // block_k):
        raise ValueError(
            f"occupancy map {occupancy.shape} does not match the padded "
            f"({s2.shape[0] // block_m}, {s2.shape[1] // block_k}) tile "
            f"grid — built for a different flattening or tiling")


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                              "block_k", "pipeline"))
def _spike_matmul_csr_core(s2, w2, csr, *, block_m, block_n, block_k,
                           pipeline=False):
    return spike_matmul_csr_pallas(s2, w2, csr, block_m=block_m,
                                   block_n=block_n, block_k=block_k,
                                   pipeline=pipeline)


def spike_matmul_csr(s, w: jax.Array,
                     csr: TileCSR | None = None, *, block_m: int = 128,
                     block_n: int = 128, block_k: int = 128,
                     occupancy: jax.Array | None = None,
                     pipeline: bool = False) -> jax.Array:
    """Event-compacted spike matmul for (..., M, K) x (K, N).

    The CSR pre-pass (occupancy -> `TileCSR` work list) runs *outside* the
    jitted kernel call: with concrete inputs (serve/benchmark paths) the
    compaction trims the grid to occupied tiles only, so empty tiles cost
    zero grid steps; under jit tracing the step count is the dense bound
    but clamped padding steps still cost zero tile DMA and zero FLOPs.
    `s` may be an `EventTensor` (carried map + cached work list).
    `csr`: optional precomputed `TileCSR` for this padded tiling — the
    layer-level pass-through. `occupancy`: optional precomputed map for
    callers holding occupancy but no work list yet — the compaction runs
    on the tiny map; the dense `tile_occupancy` pass is skipped.

    This is the EVENT route of the hybrid pair (see `spike_matmul`): it
    wins when few tiles are occupied (the compacted grid skips empty
    steps outright) and loses to predicated-dense near-full occupancy
    (per-step compaction overhead with nothing left to skip) — the
    calibrated crossover lives in `core.costmodel`.
    """
    if csr is None:
        s, occupancy, csr = _carried_occupancy(s, occupancy, block_m,
                                               block_k, want_csr=True)
    else:
        s, occupancy, _ = _carried_occupancy(s, occupancy, block_m, block_k)
    lead = s.shape[:-2]
    m, k = s.shape[-2:]
    n = w.shape[-1]
    s2 = s.reshape(-1, k) if lead else s.reshape(m, k)
    s2, w2, m_orig, n_orig = _pad_operands(s2, w, block_m, block_n, block_k)
    if csr is None:
        if occupancy is None:
            occupancy = tile_occupancy(s2, block_m, block_k)
        else:
            _check_map(occupancy, s2, block_m, block_k)
        csr = _build_csr(occupancy, block_m, block_k)
    # The jit core can't see the static tags — validate before entering.
    csr.check_compatible(block_m, block_k,
                         s2.shape[0] // block_m, s2.shape[1] // block_k)
    out = _spike_matmul_csr_core(s2, w2, csr, block_m=block_m,
                                 block_n=block_n, block_k=block_k,
                                 pipeline=pipeline)
    out = out[:m_orig, :n_orig]
    return out.reshape(lead + (m, n)) if lead else out


@functools.partial(jax.jit,
                   static_argnames=("g", "block_m", "block_n", "block_k",
                                    "pipeline"))
def _apec_matmul_csr_core(res2, ov2, w2, csr, occ_res, occ_ov, *, g,
                          block_m, block_n, block_k, pipeline=False):
    return apec_matmul_csr_pallas(res2, ov2, w2, g, csr, occ_res, occ_ov,
                                  block_m=block_m, block_n=block_n,
                                  block_k=block_k, pipeline=pipeline)


def apec_matmul_csr(s, w: jax.Array, g: int = 2, *,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128,
                    occupancy: jax.Array | None = None,
                    pipeline: bool = False) -> jax.Array:
    """APEC matmul fused into one event-compacted kernel pass.

    Overlap/residual decomposition (packed bitwise kernel), then a single
    CSR-grid kernel computes both matmuls — each weight k-tile is DMA'd
    once and feeds the residual AND overlap dots — and accumulates the
    overlap partial sum directly into its group's g residual output rows
    in the epilogue. The union CSR pre-pass runs once and is shared
    between the two operands (no per-matmul occupancy recompute, no
    `jnp.repeat` combine pass).

    `s` may be an `EventTensor`, and `occupancy` a precomputed map of the
    UNDECOMPOSED spikes: an s-tile holds events iff its residual or
    (broadcast) overlap tile does, so the carried map IS the union gate —
    the work list compacts from it directly and both in-kernel dots are
    gated conservatively on it (an exclusive-operand step runs one empty
    dot instead of paying two dense pre-passes on the decomposed pair).
    """
    s, occupancy, _ = _carried_occupancy(s, occupancy, block_m, block_k)
    lead = s.shape[:-2]
    p, c = s.shape[-2:]
    if p % g:
        raise ValueError(f"positions {p} not divisible by group {g}")
    if block_m % g:
        raise ValueError(f"block_m {block_m} not divisible by group {g}")
    s2 = s.reshape(-1, c)
    ov, res = apec_decompose(s2, g)                  # packed bitwise kernel
    res2, w2, p_orig, n_orig = _pad_operands(
        res, w.astype(jnp.float32), block_m, block_n, block_k)
    ov2, _ = _pad_to(ov, 0, block_m // g)            # rows stay group-aligned
    ov2, _ = _pad_to(ov2, 1, block_k)
    # One union pre-pass serves both operands: a k-tile enters the work
    # list when either the residual or the overlap tile holds events, and
    # per-step counts gate each dot separately in-kernel. A carried map
    # replaces the pre-pass outright (union == s-tile occupancy).
    if occupancy is not None:
        _check_map(occupancy, res2, block_m, block_k)
        csr = _build_csr(occupancy, block_m, block_k)
        steps = (csr.tile_m_idx, csr.tile_k_idx)
        gate = (occupancy[steps] * csr.valid).astype(jnp.int32)
        occ_res_steps = occ_ov_steps = gate
    else:
        occ_res = tile_occupancy(res2, block_m, block_k)
        occ_ov = tile_occupancy(ov2, block_m // g, block_k)
        csr = _build_csr(occ_res + occ_ov, block_m, block_k)
        steps = (csr.tile_m_idx, csr.tile_k_idx)
        occ_res_steps = (occ_res[steps] * csr.valid).astype(jnp.int32)
        occ_ov_steps = (occ_ov[steps] * csr.valid).astype(jnp.int32)
    out = _apec_matmul_csr_core(res2, ov2, w2, csr, occ_res_steps,
                                occ_ov_steps, g=g, block_m=block_m,
                                block_n=block_n, block_k=block_k,
                                pipeline=pipeline)
    out = out[:p_orig, :n_orig]
    return out.reshape(lead + (p, w.shape[-1])).astype(w.dtype)


# -------------------------------------------------- packed-payload (PR 7)
# The packed wrappers are the `packed-csr` backend family's entry points.
# They accept EITHER a dense binary operand (packed_k=None — packed
# internally, which is how the registry-enumerated parity harness covers
# them with its dense f32 example inputs) OR pre-packed uint32 words with
# `packed_k=` the logical channel count (how dispatch threads a packed
# EventTensor's payload). Forward-only: gradients come from the dispatch
# layer's ref-replay / `_matmul_bwd` contract, which unpacks first —
# cotangents flow through the unpacked values, never through the words.


def _packed_rows(s, packed_k, occupancy, block_m, block_k):
    """Normalize the spike operand to flattened (R, KW) uint32 words.

    Returns (words, logical_k, lead_shape, logical_rows, occupancy). The
    dense entry stops gradients before packing (pack is forward-only
    aux); pre-packed words are validated against `packed_width(packed_k)`
    so a wrong-width payload is rejected loudly, never reinterpreted.
    """
    if isinstance(s, EventTensor):
        if occupancy is None:
            occupancy = s.occupancy_for(block_m, block_k)
        if s.is_packed:
            packed_k, s = s.feature_size, s.packed
        else:
            packed_k, s = None, s.spikes
    lead = s.shape[:-2]
    m = s.shape[-2]
    if packed_k is None:
        k = s.shape[-1]
        p2 = pack_spikes_padded(jax.lax.stop_gradient(s).reshape(-1, k))
        return p2, k, lead, m, occupancy
    kw = s.shape[-1]
    if kw != packed_width(packed_k):
        raise ValueError(
            f"packed operand {s.shape} carries {kw} words which does not "
            f"cover packed_k={packed_k} (want {packed_width(packed_k)})")
    return s.reshape(-1, kw), int(packed_k), lead, m, occupancy


def _pad_packed_operands(p2, w, packed_k, block_m, block_n, block_k):
    """Pad (R, KW) words and (K, N) weights to the packed tile grid.

    Zero words never mark a tile occupied; weight rows pad to KW*32 so
    the in-kernel unpack's phantom channels (always-zero bits) multiply
    zero weights.
    """
    if w.shape[0] != packed_k:
        raise ValueError(
            f"weights have {w.shape[0]} rows, packed operand covers "
            f"packed_k={packed_k} channels")
    bkw = block_k // PACK
    p2, m_orig = _pad_to(p2, 0, block_m)
    p2, _ = _pad_to(p2, 1, bkw)
    w2, _ = _pad_to(w, 0, p2.shape[1] * PACK)
    w2, n_orig = _pad_to(w2, 1, block_n)
    return p2, w2, m_orig, n_orig


def _check_packed_map(occupancy, p2, block_m, bkw):
    if occupancy.shape != (p2.shape[0] // block_m, p2.shape[1] // bkw):
        raise ValueError(
            f"occupancy map {occupancy.shape} does not match the padded "
            f"({p2.shape[0] // block_m}, {p2.shape[1] // bkw}) packed tile "
            f"grid — built for a different flattening or tiling")


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                              "block_k", "pipeline"))
def _spike_matmul_packed_core(p2, w2, csr, *, block_m, block_n, block_k,
                              pipeline=False):
    return spike_matmul_packed_csr_pallas(p2, w2, csr, block_m=block_m,
                                          block_n=block_n, block_k=block_k,
                                          pipeline=pipeline)


def spike_matmul_packed(s, w: jax.Array, *, packed_k: int | None = None,
                        csr: TileCSR | None = None,
                        occupancy: jax.Array | None = None,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128,
                        pipeline: bool = False) -> jax.Array:
    """Event-compacted spike matmul on the uint32-packed payload.

    `s`: packed words (..., M, ceil(K/32)) with ``packed_k=K``, a packed
    `EventTensor`, or a dense binary (..., M, K) operand (packed here).
    Same CSR grid and work list as `spike_matmul_csr` — the tile indices
    are payload-agnostic — but the spike-side HBM read is 1/32 the f32
    route's, and each occupied tile unpacks VMEM-resident in-kernel.
    A carried/explicit `occupancy` map skips the popcount pre-pass (its
    (rows/128, ceil(K/128)) grid matches the packed word tiling exactly).
    """
    p2, packed_k, lead, m, occupancy = _packed_rows(
        s, packed_k, occupancy, block_m, block_k)
    n = w.shape[-1]
    p2, w2, m_orig, n_orig = _pad_packed_operands(
        p2, w, packed_k, block_m, block_n, block_k)
    bkw = block_k // PACK
    if csr is None:
        if occupancy is None:
            occupancy = packed_tile_occupancy(p2, block_m, block_k)
        else:
            _check_packed_map(occupancy, p2, block_m, bkw)
        csr = _build_csr(occupancy, block_m, block_k)
    csr.check_compatible(block_m, block_k,
                         p2.shape[0] // block_m, p2.shape[1] // bkw)
    out = _spike_matmul_packed_core(p2, w2, csr, block_m=block_m,
                                    block_n=block_n, block_k=block_k,
                                    pipeline=pipeline)
    out = out[:m_orig, :n_orig]
    return out.reshape(lead + (m, n)) if lead else out


@functools.partial(jax.jit, static_argnames=("g", "block_m", "block_n"))
def _apec_decompose_packed_jit(p2, *, g, block_m, block_n):
    from .apec_kernel import apec_decompose_packed
    return apec_decompose_packed(p2, g, block_m=block_m, block_n=block_n)


@functools.partial(jax.jit,
                   static_argnames=("g", "block_m", "block_n", "block_k"))
def _apec_matmul_packed_core(res2, ov2, w2, csr, occ_res, occ_ov, *, g,
                             block_m, block_n, block_k):
    return apec_matmul_packed_csr_pallas(res2, ov2, w2, g, csr, occ_res,
                                         occ_ov, block_m=block_m,
                                         block_n=block_n, block_k=block_k)


def apec_matmul_packed(s, w: jax.Array, g: int = 2, *,
                       packed_k: int | None = None,
                       occupancy: jax.Array | None = None,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 128) -> jax.Array:
    """Fused APEC matmul staying in the packed domain end to end.

    The overlap/residual decomposition is already bitwise on uint32 words
    (`apec_decompose_packed`), so a packed operand never round-trips
    through f32: decompose packed -> popcount maps from the words ->
    union-CSR kernel unpacking each occupied residual/overlap tile
    in-VMEM. Contracts (union gate, carried-map semantics) mirror
    `apec_matmul_csr`.
    """
    from .apec_kernel import apec_decompose_packed
    p2, packed_k, lead, p_pos, occupancy = _packed_rows(
        s, packed_k, occupancy, block_m, block_k)
    if p2.shape[0] % g:
        raise ValueError(f"positions {p2.shape[0]} not divisible by "
                         f"group {g}")
    if block_m % g:
        raise ValueError(f"block_m {block_m} not divisible by group {g}")
    wf = w.astype(jnp.float32)
    p2, w2, p_orig, n_orig = _pad_packed_operands(
        p2, wf, packed_k, block_m, block_n, block_k)
    kw = p2.shape[1]
    bkw = block_k // PACK
    bn_dec = min(128, kw)
    if kw % bn_dec:
        bn_dec = bkw                      # bkw always divides the padding
    # Largest tileable row block: the decompose grid shrinks accordingly,
    # which is what keeps the per-step interpret overhead off the CPU
    # wall clock (rows are padded to block_m, and g divides block_m, so
    # the fallback chain always terminates). The jit wrapper caches the
    # pallas trace — an eager interpret-mode pallas_call re-traces every
    # call, which would put ~100ms of pure tracing on each APEC call.
    bm_dec = next(b for b in (128, 64, 32, 16, 8)
                  if p2.shape[0] % (g * b) == 0)
    ov_p, res_p = _apec_decompose_packed_jit(p2, g=g, block_m=bm_dec,
                                             block_n=bn_dec)
    if occupancy is not None:
        _check_packed_map(occupancy, p2, block_m, bkw)
        csr = _build_csr(occupancy, block_m, block_k)
        steps = (csr.tile_m_idx, csr.tile_k_idx)
        gate = (occupancy[steps] * csr.valid).astype(jnp.int32)
        occ_res_steps = occ_ov_steps = gate
    else:
        occ_res = packed_tile_occupancy(res_p, block_m, block_k)
        occ_ov = packed_tile_occupancy(ov_p, block_m // g, block_k)
        csr = _build_csr(occ_res + occ_ov, block_m, block_k)
        steps = (csr.tile_m_idx, csr.tile_k_idx)
        occ_res_steps = (occ_res[steps] * csr.valid).astype(jnp.int32)
        occ_ov_steps = (occ_ov[steps] * csr.valid).astype(jnp.int32)
    out = _apec_matmul_packed_core(res_p, ov_p, w2, csr, occ_res_steps,
                                   occ_ov_steps, g=g, block_m=block_m,
                                   block_n=block_n, block_k=block_k)
    out = out[:p_orig, :n_orig]
    return out.reshape(lead + (p_pos, w.shape[-1])).astype(w.dtype)


def _conv_pads(size: int, k: int, stride: int, padding):
    """(out_size, pad_lo, pad_hi) matching lax's conventions: "SAME",
    "VALID", or an explicit (lo, hi) pair for this dimension."""
    if padding == "SAME":
        out = -(-size // stride)
        total = max((out - 1) * stride + k - size, 0)
        return out, total // 2, total - total // 2
    lo, hi = (0, 0) if padding == "VALID" else padding
    return (size + lo + hi - k) // stride + 1, lo, hi


def im2col_rows(x: jax.Array, kh: int, kw: int, stride: int, padding,
                lanes: int = 1):
    """Patch rows of an NHWC map, by copies only: `x` zero-padded as lax
    pads a conv ("SAME", "VALID" or ((lo, hi), (lo, hi))), then kh*kw
    static strided windows concatenated on the last axis (the `im2col`
    kernel), so the features run in (kh, kw, C) order and HWIO weights
    match them by a plain reshape. `lanes` > 1 rounds K up to its
    multiple with zeros, in the same concatenation.

    Returns ((N*Ho*Wo, K) rows, (Ho, Wo))."""
    _, h, w, c = x.shape
    pad_h, pad_w = (padding, padding) if isinstance(padding, str) \
        else padding
    ho, pt, pb = _conv_pads(h, kh, stride, pad_h)
    wo, pl_, pr = _conv_pads(w, kw, stride, pad_w)
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    k = kh * kw * c
    rows = im2col_pallas(xp, kh, kw, stride, (ho, wo), k + (-k % lanes))
    return rows, (ho, wo)


def econv_packed(s, w: jax.Array, *, stride: int = 1,
                 padding: str = "SAME", packed_k: int | None = None,
                 occupancy: jax.Array | None = None) -> jax.Array:
    """Event conv with the payload packed end to end.

    im2col runs in the WORD domain: channels are the packed axis, so a
    spatial window of the word array IS the packed patch — kh*kw strided
    shifted slices of the zero-padded words concatenate into
    (N*Ho*Wo, kh*kw*ciw) patch rows with feature order (kh, kw,
    ci-words), and the weights are relaid to match: ci zero-padded to
    ciw*32 (the phantom channels multiply zero weights), transposed to
    (kh, kw, ci_pad, co). The packed CSR matmul consumes the patch words
    directly.

    A carried `occupancy` (the conv_patch_occupancy map of the DENSE
    patch matrix) is honored only when ci % 32 == 0 — then the packed
    patch k-tiling coincides with the dense one (the map is row-granular
    across k-tiles, so feature order doesn't matter); otherwise the word
    popcount pre-pass re-derives the map (32x cheaper than a dense scan).
    """
    if isinstance(s, EventTensor):
        if s.is_packed:
            packed_k, s = s.feature_size, s.packed
        else:
            s = s.spikes
    if packed_k is None:
        ci = s.shape[-1]
        p = pack_spikes_padded(jax.lax.stop_gradient(s))
    else:
        ci = int(packed_k)
        p = s
        if p.shape[-1] != packed_width(ci):
            raise ValueError(
                f"packed conv input {p.shape} carries {p.shape[-1]} words "
                f"which does not cover packed_k={ci}")
    kh, kw_, ci_w, co = w.shape
    if ci_w != ci:
        raise ValueError(f"weights expect {ci_w} input channels, packed "
                         f"operand covers {ci}")
    n, ciw = p.shape[0], p.shape[-1]
    rows, (ho, wo) = im2col_rows(p, kh, kw_, stride, padding)
    k_eff = kh * kw_ * ciw * PACK
    ci_pad = ciw * PACK
    w2 = jnp.pad(w, ((0, 0), (0, 0), (0, ci_pad - ci), (0, 0)))
    w2 = w2.reshape(kh * kw_ * ci_pad, co)
    if occupancy is not None and ci % PACK:
        occupancy = None               # dense-patch tiling doesn't align
    out = spike_matmul_packed(rows, w2, packed_k=k_eff,
                              occupancy=occupancy)
    return out.reshape(n, ho, wo, co)

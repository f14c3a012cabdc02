"""Event-driven (occupancy-skipping) spike matmuls — Pallas TPU kernels.

The EPE Core computes only while the AER FIFO is non-empty: no events, no
work. Per-event scatter is hostile to the MXU, so the TPU-native event
granularity is the VMEM tile. Two realizations live here:

* **Predicated** (`spike_matmul_pallas`): a dense (M/bm, N/bn, K/bk) grid
  where a precomputed occupancy map gates the MXU dot with `pl.when`.
  Empty tiles save FLOPs, but every grid step still runs and every weight
  tile still streams HBM->VMEM — the wasted read the CSR form removes.

* **Event-compacted** (`spike_matmul_csr_pallas`, `apec_matmul_csr_pallas`):
  the occupancy map is drained into a CSR-of-tiles work list
  (`core.spikes.TileCSR`) and the grid — via
  `pltpu.PrefetchScalarGridSpec` — runs over occupied tiles only. The
  scalar-prefetched tile indices feed the block index maps, so empty
  tiles cost zero grid steps (concrete pre-pass) and zero tile DMA (the
  traced pre-pass clamps padding steps onto already-resident tiles).
  This is the TPU analogue of the AER FIFO draining to empty. The APEC
  variant additionally fuses the overlap/residual combine: one pass over
  the weight tiles accumulates both matmuls, and the epilogue broadcasts
  each group's overlap partial sum into its g residual output rows
  in-kernel — no `jnp.repeat` full-tensor pass afterwards.

Under the paper's measured sparsities (60-97%) K-tiles of a spike matrix
empty out only for spatially clustered events (which real feature maps
have); the practical win tracks `core.spikes.occupancy_fraction`, which
the cost model (`core.costmodel.tile_matmul_savings`) and benchmarks
report alongside.

APEC composes with both kernels: `apec_matmul` rewrites grouped positions
as [overlap, residual...] rows, so residual tiles are strictly sparser and
skip more often (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spikes import (PACK, TileCSR, occupancy_to_csr,
                               packed_tile_occupancy, tile_occupancy)


def _spike_matmul_kernel(occ_ref, s_ref, w_ref, out_ref, acc_ref, *,
                         k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[pl.program_id(0) * k_steps + kk] > 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(
            s_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spike_matmul_pallas(
    s: jax.Array,
    w: jax.Array,
    occupancy: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Occupancy-skipping matmul. s: (M, K) binary; w: (K, N) -> (M, N).

    `occupancy`: (M/bm, K/bk) int32 per-tile event counts (from
    `core.spikes.tile_occupancy`); computed here if not supplied.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = s.shape
    k2, n = w.shape
    assert k == k2, (s.shape, w.shape)
    if m % block_m or k % block_k or n % block_n:
        raise ValueError(
            f"(M,K,N)=({m},{k},{n}) must tile by ({block_m},{block_k},{block_n})")
    if occupancy is None:
        occupancy = tile_occupancy(s, block_m, block_k)
    if occupancy.shape != (m // block_m, k // block_k):
        # A map built for another tiling would silently gate the wrong
        # tiles (Pallas clamps out-of-range block indices) — refuse it.
        raise ValueError(
            f"occupancy shape {occupancy.shape} does not match tiling "
            f"({m // block_m}, {k // block_k})")
    occupancy = occupancy.astype(jnp.int32)

    k_steps = k // block_k
    kernel = functools.partial(_spike_matmul_kernel, k_steps=k_steps)
    # The map rides in SMEM as a scalar-prefetch operand (flat, row-major
    # (M/bm, K/bk)): a per-step (1, 1) SMEM block breaks Mosaic's (8, 128)
    # block rule.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk, occ: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk, occ: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, occ: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret,
        name="_spike_matmul_predicated",
    )(occupancy.reshape(-1), s, w)


# ---------------------------------------------------------------- CSR grid
def _weight_prefetch(gate, kidx_ref, w_hbm, wbuf, sem, *,
                     block_k: int, block_n: int):
    """Double-buffered weight-tile motion for the CSR grids (the spikehard
    `dma_controller`/`dma_buffer` pattern): while step t's dot runs out of
    rotation slot t%2, the HBM->VMEM copy for step t+1's tile streams into
    slot (t+1)%2, so an occupied step's MXU work hides the next weight
    fetch instead of stalling on its own.

    `gate(u)` must be True exactly when step u performs a dot: every
    `start()` here is paired with exactly one `wait()` (returned closure)
    under the same gate, and dummy / clamp-padding steps (occ=0) issue no
    DMA at all — the serial kernels' "empty tiles cost zero weight DMA"
    contract survives the rewrite. Only the warm-up copy at t==0 is
    exposed; the cost model's `dma_overlap_ledger` counts exactly that.
    """
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    j = pl.program_id(0)

    def copy(slot, step):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(kidx_ref[step] * block_k, block_k),
                     pl.ds(j * block_n, block_n)],
            wbuf.at[slot], sem.at[slot])

    @pl.when((t == 0) & gate(0))
    def _warmup():
        copy(0, 0).start()

    nxt = jnp.minimum(t + 1, n_t - 1)

    @pl.when((t + 1 < n_t) & gate(nxt))
    def _lookahead():
        copy((t + 1) % 2, nxt).start()

    def wait_resident():
        copy(t % 2, t).wait()
    return wait_resident


def _spike_matmul_csr_kernel(row_ref, kidx_ref, occ_ref,
                             s_ref, w_ref, out_ref, acc_ref):
    """One grid step per occupied (m-tile, k-tile); j (N-tile) is the outer
    grid axis so steps of one output row are consecutive. The accumulator
    resets on row change and flushes on the last step of each row; dummy /
    padding steps (occ=0) contribute nothing but keep empty rows written
    and clamped indices DMA-free (see core.spikes.TileCSR)."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[t] > 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(
            s_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _spike_matmul_csr_pipe_kernel(row_ref, kidx_ref, occ_ref,
                                  s_ref, w_hbm, out_ref,
                                  acc_ref, wbuf, sem, *,
                                  block_k: int, block_n: int):
    """Pipelined twin of `_spike_matmul_csr_kernel`: the weight operand
    stays an HBM ref and occupied steps read their tile from the 2-deep
    VMEM rotation that `_weight_prefetch` keeps one step ahead. Init /
    accumulate / flush row logic is identical to the serial kernel."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]
    wait_resident = _weight_prefetch(
        lambda u: occ_ref[u] > 0, kidx_ref, w_hbm, wbuf, sem,
        block_k=block_k, block_n=block_n)

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[t] > 0)
    def _accumulate():
        wait_resident()
        acc_ref[...] += jnp.dot(
            s_ref[...], wbuf[t % 2], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spike_matmul_csr_pallas(
    s: jax.Array,
    w: jax.Array,
    csr: TileCSR | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    pipeline: bool = False,
) -> jax.Array:
    """Event-compacted matmul: grid over occupied tiles only.

    s: (M, K) binary; w: (K, N) -> (M, N). `csr`: a precomputed
    `core.spikes.TileCSR` for this (block_m, block_k) tiling (built here
    if not supplied — suppliers get the pre-pass cost once per layer).
    `pipeline=True` switches to the double-buffered weight-DMA kernel
    (see `_weight_prefetch`); same math, same work list, same outputs.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = s.shape
    k2, n = w.shape
    assert k == k2, (s.shape, w.shape)
    if m % block_m or k % block_k or n % block_n:
        raise ValueError(
            f"(M,K,N)=({m},{k},{n}) must tile by ({block_m},{block_k},{block_n})")
    if csr is None:
        csr = occupancy_to_csr(tile_occupancy(s, block_m, block_k),
                               tiling=(block_m, block_k))
    csr.check_compatible(block_m, block_k, m // block_m, k // block_k)
    if csr.n_rows != m // block_m:
        raise ValueError(
            f"csr has {csr.n_rows} m-tile rows, input needs {m // block_m}")

    if pipeline:
        kernel = functools.partial(_spike_matmul_csr_pipe_kernel,
                                   block_k=block_k, block_n=block_n)
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((2, block_k, block_n), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = _spike_matmul_csr_kernel
        w_spec = pl.BlockSpec((block_k, block_n),
                              lambda j, t, row, kidx, occ: (kidx[t], j))
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // block_n, csr.n_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda j, t, row, kidx, occ: (row[t], kidx[t])),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, t, row, kidx, occ: (row[t], j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret,
        name="_spike_matmul_csr_core",
    )(csr.tile_m_idx, csr.tile_k_idx, csr.occ, s, w)


# ------------------------------------------------------- packed CSR grid
# The `packed-csr` family: the spike operand arrives as uint32 words
# (32 lanes per word — 1/32 the HBM read of the f32 operand) and each
# occupied tile is unpacked VMEM-RESIDENT, inside the grid step that
# already holds it for the dot: a broadcast-compare against the 32 bit
# masks, never an HBM round-trip through f32. Weight traffic, grid
# compaction, accumulate/flush logic are identical to the f32 CSR kernels
# above — only the spike-side DMA shrinks.
def _unpack_tile(words, block_k: int):
    """(bm, bk/32) uint32 -> (bm, bk) f32 {0,1}: broadcast-compare each
    word against the 32 single-bit masks (little-endian lane order,
    matching `core.spikes.pack_spikes`)."""
    bm = words.shape[0]
    masks = jnp.uint32(1) << jnp.arange(PACK, dtype=jnp.uint32)
    bits = (words[:, :, None] & masks[None, None, :]) != 0
    return bits.reshape(bm, block_k).astype(jnp.float32)


def _spike_matmul_packed_csr_kernel(row_ref, kidx_ref, occ_ref,
                                    p_ref, w_ref, out_ref, acc_ref, *,
                                    block_k: int):
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[t] > 0)
    def _accumulate():
        s_tile = _unpack_tile(p_ref[...], block_k)
        acc_ref[...] += jnp.dot(
            s_tile, w_ref[...], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _spike_matmul_packed_csr_pipe_kernel(row_ref, kidx_ref, occ_ref,
                                         p_ref, w_hbm, out_ref,
                                         acc_ref, wbuf, sem, *,
                                         block_k: int, block_n: int):
    """Pipelined twin of `_spike_matmul_packed_csr_kernel`: the uint32
    word tile unpacks in-VMEM while the next step's weight tile streams
    into the other rotation slot — the two sides of the dot overlap."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]
    wait_resident = _weight_prefetch(
        lambda u: occ_ref[u] > 0, kidx_ref, w_hbm, wbuf, sem,
        block_k=block_k, block_n=block_n)

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occ_ref[t] > 0)
    def _accumulate():
        wait_resident()
        s_tile = _unpack_tile(p_ref[...], block_k)
        acc_ref[...] += jnp.dot(
            s_tile, wbuf[t % 2], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spike_matmul_packed_csr_pallas(
    p: jax.Array,
    w: jax.Array,
    csr: TileCSR | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    pipeline: bool = False,
) -> jax.Array:
    """Event-compacted matmul on a PACKED spike operand.

    p: (M, K/32) uint32 words of a binary (M, K) matrix; w: (K, N) ->
    (M, N). The packed operand's k-tile blocks are (block_m, block_k/32)
    words addressed by the same scalar-prefetched tile indices as the f32
    kernel — the work list is payload-agnostic. `csr` built here from the
    words' popcounts if not supplied (32x cheaper than the dense pre-pass,
    same counts exactly).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, kw = p.shape
    k2, n = w.shape
    if block_k % PACK:
        raise ValueError(f"block_k {block_k} not a multiple of {PACK}")
    bkw = block_k // PACK
    if kw * PACK != k2:
        raise ValueError(
            f"packed operand ({m},{kw}) words does not cover w rows {k2} "
            f"(want {k2 // PACK} words — pad both to the tile boundary)")
    if m % block_m or kw % bkw or n % block_n:
        raise ValueError(
            f"(M,KW,N)=({m},{kw},{n}) must tile by ({block_m},{bkw},{block_n})")
    if csr is None:
        csr = occupancy_to_csr(packed_tile_occupancy(p, block_m, block_k),
                               tiling=(block_m, block_k))
    csr.check_compatible(block_m, block_k, m // block_m, kw // bkw)
    if csr.n_rows != m // block_m:
        raise ValueError(
            f"csr has {csr.n_rows} m-tile rows, input needs {m // block_m}")

    if pipeline:
        kernel = functools.partial(_spike_matmul_packed_csr_pipe_kernel,
                                   block_k=block_k, block_n=block_n)
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((2, block_k, block_n), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_spike_matmul_packed_csr_kernel,
                                   block_k=block_k)
        w_spec = pl.BlockSpec((block_k, block_n),
                              lambda j, t, row, kidx, occ: (kidx[t], j))
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // block_n, csr.n_steps),
        in_specs=[
            pl.BlockSpec((block_m, bkw),
                         lambda j, t, row, kidx, occ: (row[t], kidx[t])),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, t, row, kidx, occ: (row[t], j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret,
        name="_spike_matmul_packed_csr",
    )(csr.tile_m_idx, csr.tile_k_idx, csr.occ, p, w)


def _apec_matmul_packed_csr_kernel(row_ref, kidx_ref, occ_res_ref,
                                   occ_ov_ref, res_ref, ov_ref, w_ref,
                                   out_ref, acc_ref, acc_ov_ref, *, g: int,
                                   block_k: int):
    """Packed twin of `_apec_matmul_csr_kernel`: both spike operands
    (residual and overlap) arrive as uint32 words and unpack in-VMEM per
    occupied step; weight DMA, union gating, and the fused group-broadcast
    epilogue are unchanged."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ov_ref[...] = jnp.zeros_like(acc_ov_ref)

    @pl.when(occ_res_ref[t] > 0)
    def _acc_res():
        acc_ref[...] += jnp.dot(
            _unpack_tile(res_ref[...], block_k), w_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(occ_ov_ref[t] > 0)
    def _acc_ov():
        acc_ov_ref[...] += jnp.dot(
            _unpack_tile(ov_ref[...], block_k), w_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        bmg, bn = acc_ov_ref.shape
        ov_rep = jnp.broadcast_to(acc_ov_ref[...][:, None, :],
                                  (bmg, g, bn)).reshape(bmg * g, bn)
        out_ref[...] = (acc_ref[...] + ov_rep).astype(out_ref.dtype)


def apec_matmul_packed_csr_pallas(
    res: jax.Array,
    ov: jax.Array,
    w: jax.Array,
    g: int,
    csr: TileCSR,
    occ_res: jax.Array,
    occ_ov: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused APEC matmul over the event-compacted grid, packed operands.

    res: (M, K/32) uint32 residual words; ov: (M/g, K/32) uint32 overlap
    words; w: (K, N). Same union-CSR / per-step gating contract as
    `apec_matmul_csr_pallas` — see there.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, kw = res.shape
    mg, kwg = ov.shape
    k2, n = w.shape
    if block_k % PACK:
        raise ValueError(f"block_k {block_k} not a multiple of {PACK}")
    bkw = block_k // PACK
    assert kw == kwg and kw * PACK == k2 and mg * g == m, \
        (res.shape, ov.shape, w.shape, g)
    if block_m % g:
        raise ValueError(f"block_m {block_m} not divisible by group {g}")
    if m % block_m or kw % bkw or n % block_n:
        raise ValueError(
            f"(M,KW,N)=({m},{kw},{n}) must tile by ({block_m},{bkw},{block_n})")

    kernel = functools.partial(_apec_matmul_packed_csr_kernel, g=g,
                               block_k=block_k)
    w_spec = pl.BlockSpec((block_k, block_n),
                          lambda j, t, row, kidx, o1, o2: (kidx[t], j))
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
               pltpu.VMEM((block_m // g, block_n), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // block_n, csr.n_steps),
        in_specs=[
            pl.BlockSpec((block_m, bkw),
                         lambda j, t, row, kidx, o1, o2: (row[t], kidx[t])),
            pl.BlockSpec((block_m // g, bkw),
                         lambda j, t, row, kidx, o1, o2: (row[t], kidx[t])),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, t, row, kidx, o1, o2: (row[t], j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret,
        name="apec_matmul_packed_csr",
    )(csr.tile_m_idx, csr.tile_k_idx, occ_res, occ_ov, res, ov, w)


def _apec_matmul_csr_kernel(row_ref, kidx_ref, occ_res_ref, occ_ov_ref,
                            res_ref, ov_ref, w_ref, out_ref,
                            acc_ref, acc_ov_ref, *, g: int):
    """Fused APEC epilogue: the residual and overlap matmuls share one
    pass over the weight tiles (one DMA serves both dots), and the flush
    broadcasts each group's overlap partial sum into its g member rows
    in-kernel — the `psum_res + jnp.repeat(psum_ov, g)` full-tensor pass
    is gone from the `pallas-csr` path."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ov_ref[...] = jnp.zeros_like(acc_ov_ref)

    @pl.when(occ_res_ref[t] > 0)
    def _acc_res():
        acc_ref[...] += jnp.dot(
            res_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(occ_ov_ref[t] > 0)
    def _acc_ov():
        acc_ov_ref[...] += jnp.dot(
            ov_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        bmg, bn = acc_ov_ref.shape
        ov_rep = jnp.broadcast_to(acc_ov_ref[...][:, None, :],
                                  (bmg, g, bn)).reshape(bmg * g, bn)
        out_ref[...] = (acc_ref[...] + ov_rep).astype(out_ref.dtype)


def _apec_matmul_csr_pipe_kernel(row_ref, kidx_ref, occ_res_ref, occ_ov_ref,
                                 res_ref, ov_ref, w_hbm, out_ref,
                                 acc_ref, acc_ov_ref, wbuf, sem, *, g: int,
                                 block_k: int, block_n: int):
    """Pipelined twin of `_apec_matmul_csr_kernel`: the shared weight tile
    is prefetched one union step ahead (DMA gate = either operand live),
    and both dots read it from the same rotation slot."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    row = row_ref[t]

    def gate(u):
        return (occ_res_ref[u] > 0) | (occ_ov_ref[u] > 0)

    wait_resident = _weight_prefetch(gate, kidx_ref, w_hbm, wbuf, sem,
                                     block_k=block_k, block_n=block_n)

    @pl.when((t == 0) | (row != row_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ov_ref[...] = jnp.zeros_like(acc_ov_ref)

    @pl.when(gate(t))
    def _land():
        wait_resident()

    @pl.when(occ_res_ref[t] > 0)
    def _acc_res():
        acc_ref[...] += jnp.dot(
            res_ref[...], wbuf[t % 2], preferred_element_type=jnp.float32)

    @pl.when(occ_ov_ref[t] > 0)
    def _acc_ov():
        acc_ov_ref[...] += jnp.dot(
            ov_ref[...], wbuf[t % 2], preferred_element_type=jnp.float32)

    @pl.when((t == n_t - 1) | (row_ref[jnp.minimum(t + 1, n_t - 1)] != row))
    def _flush():
        bmg, bn = acc_ov_ref.shape
        ov_rep = jnp.broadcast_to(acc_ov_ref[...][:, None, :],
                                  (bmg, g, bn)).reshape(bmg * g, bn)
        out_ref[...] = (acc_ref[...] + ov_rep).astype(out_ref.dtype)


def apec_matmul_csr_pallas(
    res: jax.Array,
    ov: jax.Array,
    w: jax.Array,
    g: int,
    csr: TileCSR,
    occ_res: jax.Array,
    occ_ov: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    pipeline: bool = False,
) -> jax.Array:
    """Fused APEC matmul over the event-compacted grid.

    res: (M, K) residual spikes (M = padded positions, group members
    adjacent); ov: (M/g, K) overlap spikes; w: (K, N). Output (M, N) =
    res @ w + repeat(ov @ w, g) — computed in one kernel. `csr` must be
    built from the *union* occupancy (a k-tile is visited when either
    operand's tile holds events) and `occ_res`/`occ_ov` are the per-step
    counts of each operand (0 on the other operand's exclusive steps and
    on dummy/padding steps) — see `ops.apec_matmul_csr` for the pre-pass.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = res.shape
    mg, kg = ov.shape
    k2, n = w.shape
    assert k == k2 == kg and mg * g == m, (res.shape, ov.shape, w.shape, g)
    if block_m % g:
        raise ValueError(f"block_m {block_m} not divisible by group {g}")
    if m % block_m or k % block_k or n % block_n:
        raise ValueError(
            f"(M,K,N)=({m},{k},{n}) must tile by ({block_m},{block_k},{block_n})")

    if pipeline:
        kernel = functools.partial(_apec_matmul_csr_pipe_kernel, g=g,
                                   block_k=block_k, block_n=block_n)
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((block_m // g, block_n), jnp.float32),
                   pltpu.VMEM((2, block_k, block_n), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_apec_matmul_csr_kernel, g=g)
        w_spec = pl.BlockSpec((block_k, block_n),
                              lambda j, t, row, kidx, o1, o2: (kidx[t], j))
        scratch = [pltpu.VMEM((block_m, block_n), jnp.float32),
                   pltpu.VMEM((block_m // g, block_n), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // block_n, csr.n_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda j, t, row, kidx, o1, o2: (row[t], kidx[t])),
            pl.BlockSpec((block_m // g, block_k),
                         lambda j, t, row, kidx, o1, o2: (row[t], kidx[t])),
            w_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, t, row, kidx, o1, o2: (row[t], j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        interpret=interpret,
        name="apec_matmul_csr",
    )(csr.tile_m_idx, csr.tile_k_idx, occ_res, occ_ov, res, ov, w)

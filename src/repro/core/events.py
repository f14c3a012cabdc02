"""AER (address-event representation) utilities — the Sparse Core analog.

The Sparse Core (Sec. III, Fig. 4) fetches spike words, extracts one valid
event position per cycle via a lowest-set-bit priority encoder + LUT, and
pushes (position) entries into the AER FIFO that triggers the EPE Core.

On TPU we keep two views of the same information:
  * a dense binary tensor (what the MXU paths consume), and
  * packed words + per-tile occupancy (what the Pallas kernels consume).
This module provides the reference event-filter semantics (for tests and
for the cycle cost model, which needs exact per-word event counts), plus
sparsity instrumentation used throughout the benchmarks.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spikes import (build_csr, pack_spikes, pack_spikes_padded,
                     packed_width, popcount, tile_occupancy, unpack_spikes)


class EventStream(NamedTuple):
    """Padded AER stream: linear addresses + validity mask."""
    addr: jax.Array   # (max_events,) int32 linear index into the flat map
    valid: jax.Array  # (max_events,) bool


def fast_event_filter(word: jax.Array, width: int = 32) -> jax.Array:
    """Reference of the hardware fast event filter on one packed word.

    Emits the bit positions of set bits in ascending order (lowest active
    bit first — the one-hot + LUT scheme), padded with -1. Static output
    length = `width`.
    """
    positions = jnp.arange(width, dtype=jnp.int32)
    set_mask = ((word >> positions.astype(jnp.uint32)) & jnp.uint32(1)).astype(bool)
    order = jnp.argsort(~set_mask, stable=True)      # set bits first, ascending
    sorted_pos = jnp.where(jnp.sort(~set_mask) == 0, positions[order], -1)
    return sorted_pos.astype(jnp.int32)


def to_event_stream(s: jax.Array, max_events: int) -> EventStream:
    """Flatten a binary tensor into a padded AER stream (raster order)."""
    flat = s.reshape(-1)
    (lin,) = jnp.nonzero(flat, size=max_events, fill_value=-1)
    return EventStream(addr=lin.astype(jnp.int32), valid=lin >= 0)


def events_per_position(s: jax.Array) -> jax.Array:
    """(..., P, C) -> (..., P) active-channel counts per spatial position
    ("spike events ... collected at the same spatial location", Alg. 1 l.9).
    """
    return jnp.sum(s.astype(jnp.int32), axis=-1)


def word_event_counts(s: jax.Array, axis: int = -1) -> jax.Array:
    """Popcount per packed 32-channel word (Spike SRAM word granularity)."""
    return popcount(pack_spikes(s, axis=axis))


# ======================================================================
# EventTensor — the full-event inter-layer carrier
# ======================================================================
# The paper's architecture keeps event *metadata* flowing alongside the
# spikes: the AER FIFO is filled by the producer (the fire stage), never
# re-derived by scanning the dense activation. `EventTensor` is that
# contract on TPU: binary spikes plus the per-tile occupancy map the fused
# LIF kernel emitted while writing them (and, lazily, the map's `TileCSR`
# compaction), registered as a pytree so it flows through jit/shard_map
# between layers. Consumers (`kernels.ops` / the dispatch entry points)
# take it in place of a dense spike tensor and skip their own occupancy
# pre-pass.
#
# Occupancy contract
# ------------------
# `occupancy[i, j]` covers tile (i, j) of the zero-padded
# (rows, K) = (prod(shape[:-1]), shape[-1]) flattening of `spikes` under
# `tiling` — exactly what `kernels.ops.padded_occupancy` computes and what
# every matmul-form consumer tiles by. Counts are UPPER BOUNDS with an
# exact zero set: occupancy[i, j] == 0 guarantees the tile holds no
# events (consumers only branch on > 0), while propagated maps
# (`window_occupancy`) may over-count. A map built for a different tiling
# or tile grid is rejected loudly (`occupancy_for` raises) — silently
# gating the wrong tiles would corrupt outputs.
#
# `chunks` is the same information at the producer's native granularity —
# per (CHUNK=8-row, tile_k-lane) block counts, shape (MT*16, KT), the raw
# per-chunk popcounts the fused LIF kernel emits before they are
# aggregated 16:1 into `occupancy`. It exists so window PROPAGATION
# (im2col, pooling) can dilate at 8-row resolution instead of 128-row
# tiles: a tile-granular dilation marks ~3x the occupied tiles and hands
# the compacted kernel back the grid steps the carried route just saved.
# Consumers never read `chunks`; only propagation does.
#
# When a carried map survives a transform, and when it must be dropped
# ----------------------------------------------------------------------
# * reshapes that PRESERVE the trailing (channel/feature) axis — merging
#   or splitting lead axes, e.g. (T,B,H,W,C)->(T*B,H,W,C) or
#   (T,B,8,8,D)->(T,B,64,D) — keep rows and K intact: the map survives
#   (`EventTensor.reshape` carries it).
# * reshapes that change the trailing axis (head splits, flatten-to-1D),
#   slicing, padding, or any transform that moves events to new
#   addresses: the map is DROPPED (occupancy=None) — consumers re-derive
#   or run dense. `EventTensor.reshape` applies this rule automatically.
# * local window transforms with raster-monotone address maps (conv
#   im2col patches, pooling, strided patch extraction): the map is
#   *propagated* on tile granularity (`window_occupancy`) — a
#   conservative interval dilation on the tiny (MT,) tile map, never a
#   re-scan of the spike tensor.
# * non-spike transforms (matmul outputs, membrane sums): the result is
#   not binary — it is not an EventTensor at all until the next fire
#   stage re-emits one.
#
# Packed payload (PR 7)
# ---------------------
# `packed` optionally replaces `spikes` as the canonical payload: uint32
# words along the channel axis (bit i of word w = channel w*32+i, the
# `spikes.pack_spikes` little-endian layout, zero-padded to whole words),
# shape = spikes.shape[:-1] + (ceil(K/32),). In packed-only mode
# (spikes=None) the logical shape/dtype live in the `feature_size` /
# `spike_dtype` static aux, and NOTHING densifies silently: `.dense()` is
# the one explicit unpack point (what `as_spikes` calls for the ops with
# no packed backend), dispatch routes packed calls only to backends
# declaring `payload="packed"`, and the fallback chain unpacks via an
# attributed shim. Pack survival mirrors the occupancy rules: last-axis-
# preserving reshapes keep the words (rows regroup, bits don't move);
# last-axis-changing reshapes RAISE on a packed-only tensor (call
# `.dense()` first — the loud spelling of the densify); spatial max-pool
# pools words bitwise-OR (the per-bit max of binary lanes), so the packed
# payload survives pooling with the maps. The packed payload is
# forward-only: it is integer-typed aux under autodiff (float0
# cotangent); training paths carry dense spikes.


CHUNK = 8    # fine-map row granularity: the LIF kernel's block_m


@jax.tree_util.register_pytree_node_class
class EventTensor:
    """Binary spikes + producer-emitted per-tile occupancy (see module
    notes for the contract). `occupancy=None` is a valid degenerate state
    (metadata lost to a transform); consumers then re-derive. `chunks` is
    the optional fine (8-row) map used only by window propagation."""

    __slots__ = ("spikes", "occupancy", "tiling", "chunks", "packed",
                 "feature_size", "spike_dtype", "_csr_cache")

    def __init__(self, spikes: Optional[jax.Array],
                 occupancy: Optional[jax.Array],
                 tiling: Tuple[int, int] = (128, 128),
                 chunks: Optional[jax.Array] = None,
                 packed: Optional[jax.Array] = None,
                 feature_size: Optional[int] = None,
                 spike_dtype=None):
        self.spikes = spikes
        self.occupancy = occupancy
        self.tiling = tuple(tiling)
        self.chunks = chunks
        self.packed = packed
        self._csr_cache = None
        if spikes is None and packed is None:
            raise ValueError("EventTensor needs a payload: spikes, packed, "
                             "or both")
        if spikes is not None and hasattr(spikes, "shape"):
            feature_size = spikes.shape[-1]
            spike_dtype = spikes.dtype
        elif feature_size is None:
            raise ValueError(
                "packed-only EventTensor needs feature_size= (the logical "
                "channel count; the word axis alone is ambiguous)")
        self.feature_size = feature_size
        self.spike_dtype = jnp.dtype(spike_dtype or jnp.float32)
        if packed is not None and hasattr(packed, "shape"):
            if packed.dtype != jnp.uint32:
                raise ValueError(
                    f"EventTensor packed payload must be uint32 words, got "
                    f"{packed.dtype}")
            want_w = packed_width(self.feature_size)
            if packed.shape[-1] != want_w:
                raise ValueError(
                    f"EventTensor packed width {packed.shape[-1]} words "
                    f"does not cover feature_size {self.feature_size} "
                    f"(want {want_w})")
            if spikes is not None and hasattr(spikes, "shape") \
                    and tuple(packed.shape[:-1]) != tuple(spikes.shape[:-1]):
                raise ValueError(
                    f"EventTensor packed lead shape "
                    f"{tuple(packed.shape[:-1])} does not match spikes "
                    f"{tuple(spikes.shape[:-1])}")
        if occupancy is not None and hasattr(occupancy, "shape") \
                and self._has_shapes():
            want = self.expected_map_shape(*self.tiling)
            if tuple(occupancy.shape) != want:
                raise ValueError(
                    f"EventTensor occupancy shape {tuple(occupancy.shape)} "
                    f"does not cover spikes {tuple(self.shape)} under "
                    f"tiling {self.tiling} (expected {want})")
            if chunks is not None and tuple(chunks.shape) != (
                    want[0] * (self.tiling[0] // CHUNK), want[1]):
                raise ValueError(
                    f"EventTensor chunk map {tuple(chunks.shape)} does not "
                    f"refine occupancy {want} at {CHUNK}-row granularity")

    def _has_shapes(self) -> bool:
        payload = self.spikes if self.spikes is not None else self.packed
        return hasattr(payload, "shape")

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return ((self.spikes, self.occupancy, self.chunks, self.packed),
                (self.tiling, self.feature_size, self.spike_dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        spikes, occupancy, chunks, packed = children
        obj = object.__new__(cls)
        obj.spikes = spikes
        obj.occupancy = occupancy
        obj.tiling = aux[0]
        obj.chunks = chunks
        obj.packed = packed
        obj.feature_size = aux[1]
        obj.spike_dtype = aux[2]
        obj._csr_cache = None
        return obj

    # ------------------------------------------------------- array facade
    @property
    def shape(self):
        if self.spikes is not None:
            return self.spikes.shape
        return self.packed.shape[:-1] + (self.feature_size,)

    @property
    def dtype(self):
        return self.spikes.dtype if self.spikes is not None \
            else self.spike_dtype

    @property
    def ndim(self):
        return self.spikes.ndim if self.spikes is not None \
            else self.packed.ndim

    @property
    def is_packed(self) -> bool:
        """True when the canonical payload is the uint32 words (no dense
        spikes carried — the no-f32-between-layers mode)."""
        return self.spikes is None

    @property
    def rows(self) -> int:
        return int(np.prod(self.shape[:-1]))

    def expected_map_shape(self, tile_m: int, tile_k: int) -> Tuple[int, int]:
        k = self.shape[-1]
        return (-(-self.rows // tile_m), -(-k // tile_k))

    def __repr__(self):
        occ = None if self.occupancy is None else tuple(self.occupancy.shape)
        payload = "packed" if self.is_packed else "spikes"
        return (f"EventTensor({payload}={tuple(self.shape)}, occupancy={occ}, "
                f"tiling={self.tiling})")

    # ------------------------------------------------------------- carrier
    @classmethod
    def from_spikes(cls, spikes: jax.Array,
                    tiling: Tuple[int, int] = (128, 128),
                    pack: bool = False) -> "EventTensor":
        """Re-derive the map from dense spikes (ONE standalone pre-pass,
        at chunk granularity; the tile map is its 16:1 aggregation) — the
        entry point for producers without fused emission. Prefer the
        fused `lif_scan_occ` dispatch op, which emits the maps for free.
        `pack=True` additionally packs the spikes to uint32 words and
        makes THEM the canonical payload (packed-only tensor, dense view
        dropped) — the eager-side mirror of `lif_fire_events(packed=True)`.
        """
        tm, tk = tiling
        k = spikes.shape[-1]
        s2 = spikes.reshape(-1, k)
        s2 = jnp.pad(s2, (((0, (-s2.shape[0]) % tm), (0, (-k) % tk))))
        chunks = tile_occupancy(s2, CHUNK, tk)
        per = tm // CHUNK
        occ = jnp.sum(chunks.reshape(-1, per, chunks.shape[1]), axis=1)
        if pack:
            words = jax.lax.stop_gradient(
                pack_spikes_padded(spikes, axis=-1))
            return cls(None, jax.lax.stop_gradient(occ), tiling,
                       jax.lax.stop_gradient(chunks), packed=words,
                       feature_size=k, spike_dtype=spikes.dtype)
        return cls(spikes, jax.lax.stop_gradient(occ), tiling,
                   jax.lax.stop_gradient(chunks))

    def dense(self) -> jax.Array:
        """The dense spike view — THE explicit densify point for a
        packed-only tensor (unpack words, slice the logical channels,
        cast to the recorded spike dtype). Never called implicitly by
        dispatch routing; ops with no packed backend reach it through
        `as_spikes`."""
        if self.spikes is not None:
            return self.spikes
        out = unpack_spikes(self.packed, axis=-1, dtype=self.spike_dtype)
        return out[..., :self.feature_size]

    def occupancy_for(self, tile_m: int, tile_k: int) -> Optional[jax.Array]:
        """The carried map, validated for a consumer tiling — None when no
        map is carried, ValueError (loud, never silent) when the carried
        map was built for a different tiling or tile grid."""
        if self.occupancy is None:
            return None
        if (tile_m, tile_k) != self.tiling:
            raise ValueError(
                f"EventTensor occupancy built for tiling {self.tiling} "
                f"used with tiling {(tile_m, tile_k)}; drop to .spikes or "
                f"rebuild with from_spikes")
        want = self.expected_map_shape(tile_m, tile_k)
        if tuple(self.occupancy.shape) != want:
            raise ValueError(
                f"EventTensor occupancy shape "
                f"{tuple(self.occupancy.shape)} does not match tile grid "
                f"{want} for spikes {tuple(self.shape)}")
        return self.occupancy

    def csr(self, tile_m: int = 128, tile_k: int = 128):
        """Lazily build (and cache per instance/trace) the `TileCSR`
        compaction of the carried map; None when no map is carried."""
        occ = self.occupancy_for(tile_m, tile_k)
        if occ is None:
            return None
        if self._csr_cache is None:
            self._csr_cache = build_csr(occ, tile_m, tile_k)
        return self._csr_cache

    def reshape(self, *shape) -> "EventTensor":
        """Reshape the spikes; the carried maps survive iff the trailing
        axis is preserved (rows regroup, addresses don't move — see the
        module contract), else they are dropped. A packed payload follows
        the same rule — and on a packed-ONLY tensor a trailing-axis change
        RAISES instead of silently unpacking (call `.dense()` first)."""
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(int(d) for d in shape)
        k = self.shape[-1]
        neg = [i for i, d in enumerate(shape) if d < 0]
        if neg:
            known = int(np.prod([d for d in shape if d >= 0]))
            shape = tuple(int(np.prod(self.shape)) // max(known, 1)
                          if d < 0 else d for d in shape)
        keep = bool(shape) and shape[-1] == k
        if self.spikes is None and not keep:
            raise ValueError(
                f"reshape to {shape} changes the packed trailing axis "
                f"({k}); a packed-only EventTensor cannot re-bucket bits "
                f"— call .dense() (the explicit unpack) first")
        spikes = None if self.spikes is None else self.spikes.reshape(shape)
        packed = self.packed
        if packed is not None:
            packed = packed.reshape(shape[:-1] + (packed.shape[-1],)) \
                if keep else None
        return EventTensor(spikes, self.occupancy if keep else None,
                           self.tiling, self.chunks if keep else None,
                           packed=packed, feature_size=k,
                           spike_dtype=self.spike_dtype)

    def astype(self, dtype) -> "EventTensor":
        """Cast the dense view's dtype. On a packed-only tensor the words
        are dtype-free — only the recorded unpack dtype changes."""
        spikes = None if self.spikes is None else self.spikes.astype(dtype)
        return EventTensor(spikes, self.occupancy, self.tiling, self.chunks,
                           packed=self.packed,
                           feature_size=self.feature_size,
                           spike_dtype=dtype)


def as_spikes(x):
    """Dense view of an array-or-EventTensor operand (for a packed-only
    tensor this is the explicit `.dense()` unpack — the documented
    densify point for ops without a packed backend)."""
    return x.dense() if isinstance(x, EventTensor) else x


# ----------------------------------------------- occupancy propagation
def window_occupancy(et: EventTensor, window: Tuple[int, int], stride: int,
                     out_hw: Tuple[int, int], out_k: int,
                     padding: str = "SAME"):
    """Propagate a carried map through a raster-monotone spatial window
    transform (im2col patch extraction, pooling) WITHOUT touching the
    dense tensor.

    `et.spikes` is (N, H, W, C)-shaped (any lead axes folded into N);
    the transform maps output position (n, y, x) onto the input window
    anchored at n*H*W + y*stride*W + x*stride with spatial extent
    `window`. Each output row block's event bound is the interval sum of
    the input CHUNK counts its windows can reach (8-row granularity — the
    fused LIF emission's native resolution, via `et.chunks`, falling back
    to the 128-row tile map when only that is carried): one cumsum over
    the tiny map, two gathers. Counts over-approximate, but a zero is
    exact: if no input chunk in reach holds events, every output row in
    the block is all-zero. A row block's bound is the same in each of its
    KT_out k-tiles: it bounds whole output rows, so the order of the
    features within a row (im2col's (kh, kw, C) in `ops.im2col_rows`)
    does not change which tiles it marks empty. Returns (tile map
    (MT_out, KT_out), chunk map (MT_out*16, KT_out)) or (None, None).
    """
    occ = et.occupancy_for(*et.tiling)
    if occ is None or et.ndim < 4:
        return None, None
    kh, kw = window
    h, w_, _ = et.shape[-3:]
    n = int(np.prod(et.shape[:-3]))
    ho, wo = out_hw
    tm, tk = et.tiling
    per = tm // CHUNK
    out_rows = n * ho * wo
    mt_out = -(-out_rows // tm)
    kt_out = -(-out_k // tk)
    # Input counts at chunk granularity (prefer the carried fine map; a
    # coarse-only carrier spreads each tile's count over its 16 chunks —
    # still conservative, just a wider dilation).
    fine = et.chunks if et.chunks is not None else occ
    xp = jnp if isinstance(fine, jax.core.Tracer) else np
    fine = xp.asarray(fine)
    if et.chunks is not None:
        cnt8 = xp.sum(fine, axis=1)                        # (MT_in*16,)
    else:
        cnt8 = xp.repeat(xp.sum(fine, axis=1), per)
    in_chunks = cnt8.shape[0]
    # The window of output position (n, y, x) covers input rows
    # [y*stride - pad_top, y*stride - pad_top + kh - 1] (likewise cols),
    # so the raster reach around the anchor a = (y*stride)*w_ + x*stride
    # is ASYMMETRIC: back by exactly the leading padding, forward by the
    # rest of the window. XLA's SAME convention puts floor(pad/2) first;
    # VALID pads nothing, so windows only extend forward. The previous
    # symmetric (k-1) bound marked up to k-1 rows of out-of-image chunks
    # occupied behind every straddling window — on stride > 1 pooling and
    # non-divisible H/W that handed the compacted kernel back the very
    # boundary tiles the carried map had excluded.
    if padding == "SAME":
        pad_top = max((ho - 1) * stride + kh - h, 0) // 2
        pad_left = max((wo - 1) * stride + kw - w_, 0) // 2
    else:                                    # VALID: window starts at anchor
        pad_top = pad_left = 0
    back_halo = pad_top * w_ + pad_left
    fwd_halo = (kh - 1 - pad_top) * w_ + (kw - 1 - pad_left)
    # Anchor interval per output chunk: anchors are monotone in raster
    # order, so chunk c's reach is [anchor(first row)-halo,
    # anchor(last row)+halo], clamped to the owning image (windows never
    # cross image boundaries — unclamped intervals would bleed a
    # neighbor image's events into this one's boundary tiles).
    # Concrete maps take the numpy path (chosen above): they are a few
    # hundred entries, and ~20 eager jnp dispatches would cost more than
    # the dense pre-pass this propagation replaces.
    out_chunks = mt_out * per
    q_lo = CHUNK * xp.arange(out_chunks)
    q_hi = xp.minimum(q_lo + CHUNK - 1, out_rows - 1)
    q_lo = xp.minimum(q_lo, out_rows - 1)    # zero-pad tail chunks below

    def reach(q, sign):
        n_i, rem = q // (ho * wo), q % (ho * wo)
        y, x = rem // wo, rem % wo
        a = n_i * (h * w_) + (y * stride) * w_ + x * stride
        if sign < 0:
            return xp.maximum(a - back_halo, n_i * (h * w_))
        return xp.minimum(a + fwd_halo, (n_i + 1) * (h * w_) - 1)

    csum = xp.concatenate(
        [xp.zeros((1,), cnt8.dtype), xp.cumsum(cnt8)])
    lo = xp.clip(reach(q_lo, -1) // CHUNK, 0, in_chunks)
    hi = xp.clip(reach(q_hi, +1) // CHUNK + 1, 0, in_chunks)
    live = (CHUNK * xp.arange(out_chunks)) < out_rows
    bound = ((csum[hi] - csum[lo]) * live).astype(xp.int32)
    chunks_out = xp.broadcast_to(bound[:, None], (out_chunks, kt_out))
    occ_out = xp.sum(chunks_out.reshape(mt_out, per, kt_out), axis=1)
    if xp is np:
        return jnp.asarray(occ_out), jnp.asarray(chunks_out)
    return occ_out, chunks_out


def conv_patch_occupancy(et: EventTensor, w_shape: Tuple[int, ...],
                         stride: int, padding: str) -> Optional[jax.Array]:
    """Carried map for the im2col patch matrix of a conv over `et`
    ((N,H,W,C) spikes, HWIO weights): rows = output positions, K =
    kh*kw*C in any feature order (see `window_occupancy`). None when no
    map is carried or the geometry is unsupported (the consumer then
    re-derives)."""
    if et.occupancy is None or et.ndim < 4:
        return None
    kh, kw, ci, co = w_shape
    h, w_ = et.shape[-3:-1]
    if padding == "SAME":
        ho, wo = -(-h // stride), -(-w_ // stride)
    elif padding == "VALID":
        ho, wo = (h - kh) // stride + 1, (w_ - kw) // stride + 1
    else:
        return None
    if ho <= 0 or wo <= 0:
        return None
    occ, _ = window_occupancy(et, (kh, kw), stride, (ho, wo), ci * kh * kw,
                              padding)
    return occ


def max_pool_events(et, pool: int):
    """Spatial max-pool of (..., H, W, C) spikes with the carried maps
    propagated (chunk-granular window dilation) instead of dropped.
    Accepts a dense array too (returns a dense array). A packed-only
    tensor pools its uint32 words bitwise-OR — per bit, OR of binary
    lanes IS the max — so the payload stays packed through pooling."""
    if isinstance(et, EventTensor) and et.is_packed:
        p = et.packed
        window = (1,) * (p.ndim - 3) + (pool, pool, 1)
        pooled_p = jax.lax.reduce_window(
            p, jnp.uint32(0), jax.lax.bitwise_or, window, window, "VALID")
        h, w_, _ = et.shape[-3:]
        occ = chunks = None
        if et.occupancy is not None and et.ndim >= 4:
            occ, chunks = window_occupancy(et, (pool, pool), pool,
                                           (h // pool, w_ // pool),
                                           et.feature_size, padding="VALID")
        return EventTensor(None, occ, et.tiling, chunks, packed=pooled_p,
                           feature_size=et.feature_size,
                           spike_dtype=et.spike_dtype)
    s = as_spikes(et)
    window = (1,) * (s.ndim - 3) + (pool, pool, 1)
    pooled = jax.lax.reduce_window(s, -jnp.inf, jax.lax.max, window, window,
                                   "VALID")
    if not isinstance(et, EventTensor) or et.occupancy is None \
            or et.ndim < 4:
        if isinstance(et, EventTensor):
            return EventTensor(pooled, None, et.tiling)
        return pooled
    h, w_, c = s.shape[-3:]
    occ, chunks = window_occupancy(et, (pool, pool), pool,
                                   (h // pool, w_ // pool), c,
                                   padding="VALID")
    return EventTensor(pooled, occ, et.tiling, chunks)


def layer_sparsity_report(name: str, s: jax.Array) -> dict:
    """Instrumentation record used by the Fig. 2 / Fig. 7 benchmarks."""
    total = float(jnp.asarray(s.size))
    active = float(jnp.sum(s.astype(jnp.float32)))
    return {
        "layer": name,
        "total_sites": total,
        "events": active,
        "sparsity": 1.0 - active / max(total, 1.0),
    }

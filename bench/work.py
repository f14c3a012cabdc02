"""Operation and byte counts that the MFU and roofline metrics divide by.

Counted from shapes and from the spike counts of the reference, never from
the program: what a kernel must do is fixed by the data, whatever
implements it.

- Dense-equivalent FLOPs (`conv_flops`, `linear_flops`): 2 x the
  multiply-accumulates of a convolution or a linear layer as if every
  input were non-zero. A configuration sums them over its layers for one
  image at all its time steps; `mfu` divides by them.
- Event matmul (`event_matmul_work`): the operations are synaptic ones,
  2 x the non-zeros of the spike operand as the kernel receives it x N.
  APEC (adjacent-position event compression), which is not on the path of
  either configuration today, would accumulate less than this count; a
  benchmark change has to revisit the count if APEC enters a timed path.
  The bytes are the operand at its bits per element (1 for spikes, the
  coding width for a direct-coded image), the (K, N) weights and the
  (M, N) output at their dtypes.
- LIF (`lif_work`): bytes only. The drive read once in float32, the
  spikes written at 1 bit per element and the tile and row-chunk
  occupancy maps that the fused emission writes beside them.
"""
from __future__ import annotations

import math

TILE = 128          # the occupancy map's tile, rows and lanes
CHUNK_ROWS = 8      # rows of one chunk of the finer occupancy map


def conv_flops(h: int, w: int, ci: int, co: int, k: int = 3) -> int:
    """Dense FLOPs of a stride-1 SAME k x k convolution over an h x w map."""
    return 2 * h * w * k * k * ci * co


def linear_flops(m: int, k: int, n: int) -> int:
    """Dense FLOPs of an (m, k) @ (k, n) product."""
    return 2 * m * k * n


def event_matmul_work(nnz: int, m: int, k: int, n: int, *,
                      operand_bits: int = 1, weight_bytes: int = 4,
                      out_bytes: int = 4) -> tuple[int, int]:
    """(operations, bytes) of one (m, k) @ (k, n) event matmul whose
    operand holds `nnz` non-zeros."""
    ops = 2 * nnz * n
    nbytes = (math.ceil(m * k * operand_bits / 8) + weight_bytes * k * n
              + out_bytes * m * n)
    return ops, nbytes


def lif_work(t: int, rows: int, k: int, *, maps: bool) -> int:
    """Bytes of one LIF call over a (t, rows, k) drive; `maps` adds the
    (128, 128) tile map and the (8, 128) chunk map, int32, over the
    (t * rows, k) spike matrix."""
    n = t * rows * k
    nbytes = 4 * n + math.ceil(n / 8)
    if maps:
        row_tiles = math.ceil(t * rows / TILE)
        lanes = math.ceil(k / TILE)
        nbytes += 4 * row_tiles * lanes * (1 + TILE // CHUNK_ROWS)
    return nbytes


def least_seconds(ops: float, nbytes: float, peak_flops: float,
                  peak_bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peak_flops, nbytes / peak_bytes_per_s)

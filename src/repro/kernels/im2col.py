"""im2col for the event convolutions — Pallas TPU kernel.

A convolution's MXU form multiplies a patch matrix, one row per output
position, by the HWIO weights reshaped to (kh*kw*Ci, Co). Each row is a
copy of the input: the kh*kw windows of the zero-padded NHWC map at that
position, concatenated in (kh, kw, Ci) order. This kernel takes static
strided window slices of a block of whole images in VMEM and writes their
concatenation, with any zero lanes after it, so each row reaches HBM once.

Left to XLA, the same slices and concatenation write each window to HBM
first wherever Ci is not a multiple of 128, every one padded to 128 lanes
(Ci = 3 pads 42x), and the concatenation reads them back.

Grid: (N / b,), b whole images a step; or, for an image too large for one
step, (N, Ho / band), one image a step in bands of `band` output rows,
each band's input rows read with their kh - stride row halo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Output rows plus window copies of one grid step, in bytes of VMEM
# (double buffering comes on top; the v5e default scoped limit is 16 MiB).
STEP_BYTES = 4 << 20
# An image whose step would pass this is cut into bands of output rows:
# Mosaic refuses whole 64x64 images at Ci = 3 (21 MB by the same count)
# on a v5e, and takes the 32x32 ones of the benchmark's models (<= 6.3 MB).
IMAGE_BYTES = 8 << 20


def _step_shape(n: int, ho: int, wo: int, c: int, k_pad: int, kk: int,
                itemsize: int) -> tuple[int, int]:
    """(images, output rows of each) that one grid step writes.

    Whole images where one fits IMAGE_BYTES: the most, a power of two
    dividing n, whose rows fill 8-row sublane tiles and whose output
    block and window copies fit STEP_BYTES (all n where no power of two
    fills the tiles). A larger image alone, in the most output rows that
    divide Ho, fill the tiles and fit STEP_BYTES (the fewest that fill
    the tiles where none fits; whole where no fewer rows fill them)."""
    lanes = -(-c // 128) * 128
    per_row = wo * (k_pad + kk * lanes) * itemsize
    bands = [r for r in range(1, ho) if ho % r == 0 and r * wo % 8 == 0]
    if ho * per_row > IMAGE_BYTES and bands:
        under = [r for r in bands if r * per_row <= STEP_BYTES]
        return 1, max(under) if under else bands[0]
    rows = ho * wo
    b = 1
    while n % (2 * b) == 0 and (b * rows % 8
                                or 2 * b * ho * per_row <= STEP_BYTES):
        b *= 2
    return (b if b * rows % 8 == 0 else n), ho


def _im2col_kernel(x_ref, o_ref, *, kh, kw, stride, ho, wo, pad_lanes):
    b, c = x_ref.shape[0], x_ref.shape[-1]
    blocks = [x_ref[:, dy:dy + (ho - 1) * stride + 1:stride,
                    dx:dx + (wo - 1) * stride + 1:stride, :]
              .reshape(b * ho * wo, c)
              for dy in range(kh) for dx in range(kw)]
    if pad_lanes:
        blocks.append(jnp.zeros((b * ho * wo, pad_lanes), o_ref.dtype))
    o_ref[...] = jnp.concatenate(blocks, axis=-1)


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride",
                                             "out_hw", "k_pad", "interpret"))
def im2col_pallas(xp: jax.Array, kh: int, kw: int, stride: int,
                  out_hw: tuple, k_pad: int, *,
                  interpret: bool | None = None) -> jax.Array:
    """(N, Hp, Wp, C) zero-padded map -> (N*Ho*Wo, k_pad) patch rows, the
    features in (kh, kw, C) order and zeros from kh*kw*C to k_pad."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, hp, wp, c = xp.shape
    ho, wo = out_hw
    k = kh * kw * c
    b, band = _step_shape(n, ho, wo, c, k_pad, kh * kw, xp.dtype.itemsize)
    nb = ho // band
    if nb == 1:
        grid = (n // b,)
        in_spec = pl.BlockSpec((b, hp, wp, c), lambda i: (i, 0, 0, 0))
        out_spec = pl.BlockSpec((b * ho * wo, k_pad), lambda i: (i, 0))
    else:   # bands overlap, so a band's rows start at an element offset
        grid = (n, nb)
        in_spec = pl.BlockSpec(
            tuple(map(pl.Element, (1, (band - 1) * stride + kh, wp, c))),
            lambda i, j: (i, j * band * stride, 0, 0))
        out_spec = pl.BlockSpec((band * wo, k_pad),
                                lambda i, j: (i * nb + j, 0))
    kernel = functools.partial(_im2col_kernel, kh=kh, kw=kw, stride=stride,
                               ho=band, wo=wo, pad_lanes=k_pad - k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n * ho * wo, k_pad), xp.dtype),
        interpret=interpret,
        name="im2col",
    )(xp)

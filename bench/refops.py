"""Plain float32 building blocks of the benchmark's reference networks.

Straight `jax.numpy`/`lax` with no kernel, no event metadata and nothing
imported from the program under test. Every contraction takes the
precision it runs at:

- ``"highest"``: float32 operands at `lax.Precision.HIGHEST`, which is
  float32 arithmetic on the TPU as on the CPU;
- ``"high"``: the three-pass bfloat16 product (each operand split into a
  bfloat16 high part and a bfloat16 remainder; the remainder-by-remainder
  term dropped; float32 accumulation). It is what XLA's `HIGH` runs on a
  TPU, written out so that it computes the same on every platform. It
  serves as the control: the precision one step below the configuration's.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "high")
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _three_pass(op, a, b):
    a_hi, a_lo = _split(a.astype(jnp.float32))
    b_hi, b_lo = _split(b.astype(jnp.float32))
    return op(a_hi, b_hi) + (op(a_hi, b_lo) + op(a_lo, b_hi))


def matmul(a, b, precision: str):
    """(..., K) @ (K, N) in float32 at `precision`."""
    if precision == "highest":
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
    if precision == "high":
        return _three_pass(lambda x, y: jnp.matmul(
            x, y, preferred_element_type=jnp.float32), a, b)
    raise ValueError(f"unknown precision {precision!r}")


def conv3x3(x, w, precision: str):
    """Stride-1 SAME convolution, NHWC activations and HWIO weights."""
    def conv(a, b, **kw):
        return lax.conv_general_dilated(a, b, (1, 1), "SAME",
                                        dimension_numbers=_CONV_DIMS, **kw)
    if precision == "highest":
        return conv(x.astype(jnp.float32), w.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
    if precision == "high":
        return _three_pass(lambda a, b: conv(
            a, b, preferred_element_type=jnp.float32), x, w)
    raise ValueError(f"unknown precision {precision!r}")


def lif(drive, decay: float, v_th: float):
    """Leaky integrate-and-fire over the leading time axis with soft reset:
    v <- decay * v + x;  s = [v >= v_th];  v <- v - s * v_th."""
    def step(v, x):
        v = v * decay + x
        s = (v >= v_th).astype(jnp.float32)
        return v - s * v_th, s
    _, s = lax.scan(step, jnp.zeros_like(drive[0], jnp.float32),
                    drive.astype(jnp.float32))
    return s


def maxpool2(s):
    """2x2 max-pool, stride 2, over the (H, W) axes of (..., H, W, C)."""
    *lead, h, w, c = s.shape
    return s.reshape(*lead, h // 2, 2, w // 2, 2, c).max(axis=(-4, -2))


def conv_time(s, w, precision: str):
    """A convolution applied to every time step of (T, B, H, W, C)."""
    t, b = s.shape[:2]
    out = conv3x3(s.reshape((t * b,) + s.shape[2:]), w, precision)
    return out.reshape((t, b) + out.shape[1:])


def patches3x3(s):
    """The (N*H*W, C*9) patch matrix of a stride-1 SAME 3x3 convolution
    over (N, H, W, C), features ordered (C, kh, kw)."""
    p = lax.conv_general_dilated_patches(s, (3, 3), (1, 1), "SAME",
                                         dimension_numbers=_CONV_DIMS)
    return p.reshape(-1, p.shape[-1])


def operand_stats(mat, n_out: int, bits: int = 1) -> dict:
    """Non-zeros and occupied (128, 128) tiles of a 2-D matmul operand."""
    m, k = mat.shape
    nz = (mat != 0)
    padded = jnp.pad(nz, ((0, (-m) % 128), (0, (-k) % 128)))
    tiles = padded.reshape(padded.shape[0] // 128, 128,
                           padded.shape[1] // 128, 128).any(axis=(1, 3))
    return {"nnz": jnp.sum(nz, dtype=jnp.int32),
            "occupied": jnp.sum(tiles, dtype=jnp.int32),
            "tiles": tiles.size, "m": m, "k": k, "n": n_out, "bits": bits}

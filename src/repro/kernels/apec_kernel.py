"""APEC overlap/residual extraction — Pallas TPU kernel on packed spikes.

Fig. 5's compression step in hardware form: for each group of g adjacent
positions, overlap = AND of the packed spike words, residual_i =
s_i AND NOT overlap. Pure VPU bitwise ops on uint32 lanes — one pass over
HBM, 32 channels per lane. The event-driven matmul then processes
[overlap | residuals], whose residual tiles are strictly sparser
(higher tile-skip rate in spike_matmul).

Grid: (P/(g*bm), dw/bn); each program handles bm groups x bn words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _apec_kernel(s_ref, ov_ref, res_ref, *, g: int):
    s = s_ref[...]                       # (g*bm, bn) uint32
    bm = s.shape[0] // g
    grp = s.reshape(bm, g, s.shape[1])
    ov = grp[:, 0, :]
    for i in range(1, g):
        ov = ov & grp[:, i, :]           # Eq. 1: AND across the group
    res = grp & ~ov[:, None, :]          # s_i AND NOT overlap
    ov_ref[...] = ov
    res_ref[...] = res.reshape(s.shape)


def apec_decompose_packed(
    s_packed: jax.Array, g: int = 2, *, block_m: int = 8,
    block_n: int = 128, interpret: bool | None = None,
):
    """(P, dw) packed spikes -> (overlap (P/g, dw), residual (P, dw)).

    P must be divisible by g*block_m and dw by block_n (pad upstream; the
    ops.py wrapper handles it).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    p, dw = s_packed.shape
    block_n = min(block_n, dw)
    if p % (g * block_m) or dw % block_n:
        raise ValueError(f"({p},{dw}) not tileable by (g*{block_m},{block_n})")
    kernel = functools.partial(_apec_kernel, g=g)
    return pl.pallas_call(
        kernel,
        grid=(p // (g * block_m), dw // block_n),
        in_specs=[pl.BlockSpec((g * block_m, block_n),
                               lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((g * block_m, block_n), lambda i, j: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((p // g, dw), jnp.uint32),
            jax.ShapeDtypeStruct((p, dw), jnp.uint32),
        ),
        interpret=interpret,
        name="apec_decompose",
    )(s_packed)

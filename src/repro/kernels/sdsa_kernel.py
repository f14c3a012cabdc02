"""Spike-driven self-attention — Pallas TPU kernels on bit-packed spikes.

The Attention Core (Fig. 6) is pure logic: kv = K AND V, status = column-
OR(kv), out = Q AND status. On TPU this is a VPU workload; we run it on
uint32-packed spike words (32 channels per lane), which cuts HBM traffic
32x vs bf16 0/1 tensors and turns AND/OR into single vector ops — the
closest TPU analogue to the paper's bit-parallel logic lanes.

Two kernels (stage 1 is a reduction, stage 2 elementwise, matching the
paper's two hardware stages):

  status:  grid (BH, N/bn); each program ORs a (bn, dw) K AND V block into
           a (1, 1, dw) status row of a (BH, 1, dw) array — the trailing
           (1, dw) block equals the array's dims, which Mosaic's (8, 128)
           block rule accepts. The N-axis is the innermost (sequential)
           grid dim, so revisiting the same output block accumulates.
  apply:   grid (BH, N/bn); out = Q AND broadcast(status).

dw = d/32 packed words; bn a multiple of 8 (sublane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _or_fold_rows(x):
    """(r, dw) -> (1, dw) column-OR by halving static slices (Mosaic has
    no lowering for a bitwise-OR `reduce`)."""
    while x.shape[0] > 1:
        r = x.shape[0]
        h = (r + 1) // 2
        folded = x[:r - h] | x[h:]
        x = folded if r == 2 * h else jnp.concatenate(
            [folded, x[r - h:h]], axis=0)
    return x


def _status_kernel(k_ref, v_ref, status_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        status_ref[...] = jnp.zeros_like(status_ref)

    kv = k_ref[0] & v_ref[0]                       # (bn, dw) AND
    status_ref[0] |= _or_fold_rows(kv)


def _apply_kernel(q_ref, status_ref, out_ref):
    out_ref[...] = q_ref[...] & status_ref[...]    # broadcast over bn rows


def sdsa_status_pallas(
    k_packed: jax.Array, v_packed: jax.Array, *, block_n: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(BH, N, dw) uint32 -> (BH, dw) packed status vectors."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bh, n, dw = k_packed.shape
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} must tile by block_n={block_n}")
    out = pl.pallas_call(
        _status_kernel,
        grid=(bh, n // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dw), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dw), jnp.uint32),
        interpret=interpret,
        name="sdsa_status",
    )(k_packed, v_packed)
    return out[:, 0, :]


def sdsa_apply_pallas(
    q_packed: jax.Array, status: jax.Array, *, block_n: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(BH, N, dw), (BH, dw) -> (BH, N, dw): out = Q AND status."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bh, n, dw = q_packed.shape
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} must tile by block_n={block_n}")
    return pl.pallas_call(
        _apply_kernel,
        grid=(bh, n // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, dw), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, dw), jnp.uint32),
        interpret=interpret,
        name="sdsa_apply",
    )(q_packed, status[:, None, :])


def sdsa_packed(
    q_packed: jax.Array, k_packed: jax.Array, v_packed: jax.Array,
    *, block_n: int = 256, interpret: bool | None = None,
) -> jax.Array:
    """Full packed SDSA (OR form): both stages."""
    status = sdsa_status_pallas(k_packed, v_packed, block_n=block_n,
                                interpret=interpret)
    return sdsa_apply_pallas(q_packed, status, block_n=block_n,
                             interpret=interpret)


# ----------------------------------------------------------- causal (LM) form
def _causal_status_kernel(kv_ref, out_ref, carry_ref, *, block_n: int):
    """Prefix-OR over the token axis: out[i] = OR_{j<=i} kv[j].

    Within a (bn, dw) block, a Hillis-Steele doubling scan (log2(bn) vector
    OR + static shifts — no dynamic sublane indexing); across blocks, a
    (1, dw) VMEM carry holds the running status, the streaming form of the
    paper's on-the-fly OR during V write-back (Sec. III-C).
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = kv_ref[0]                                  # (bn, dw)
    shift = 1
    while shift < block_n:
        pad = jnp.zeros((shift,) + x.shape[1:], x.dtype)
        x = x | jnp.concatenate([pad, x[:-shift]], axis=0)
        shift *= 2
    x = x | carry_ref[...]                         # fold previous blocks
    out_ref[0] = x
    carry_ref[...] = x[block_n - 1:block_n]


def sdsa_causal_status_pallas(
    kv_packed: jax.Array, *, block_n: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(BH, N, dw) uint32 kv mask -> (BH, N, dw) causal (prefix-OR) status.

    The N-axis is the innermost (sequential) grid dim so the carry scratch
    accumulates across blocks of the same (b, h) row.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bh, n, dw = kv_packed.shape
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} must tile by block_n={block_n}")
    return pl.pallas_call(
        functools.partial(_causal_status_kernel, block_n=block_n),
        grid=(bh, n // block_n),
        in_specs=[pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, block_n, dw), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, dw), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((1, dw), jnp.uint32)],
        interpret=interpret,
        name="causal_sdsa_status",
    )(kv_packed)

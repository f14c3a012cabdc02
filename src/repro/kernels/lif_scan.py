"""Fused temporal LIF scan — Pallas TPU kernel.

The EPE Core's MPE stage keeps membrane potentials on-chip between eFIFO
pushes; the TPU analogue is keeping the membrane tensor resident in VMEM
across the T-step temporal loop instead of round-tripping it through HBM
per timestep (what a naive `lax.scan` of elementwise ops compiles to when
the tensor exceeds registers).

Grid: (M/bm, N/bn) over the flattened neuron axes; each program owns a
(T, bm, bn) input/output block and a (bm, bn) f32 VMEM scratch for the
membrane potential. VPU-aligned blocks: bm multiple of 8, bn multiple of
128. HBM traffic: read T*bm*bn once, write T*bm*bn once — the membrane
state never leaves VMEM.

Training: `lif_scan_pallas_sg` is the differentiable form. Its forward
kernel additionally emits the pre-threshold membrane residuals V (the
values the surrogate derivative is evaluated at), and its backward is a
second Pallas kernel running the temporal scan in REVERSE with the ATan
surrogate of `core/surrogate.py` — the cotangent of the carried membrane
stays resident in VMEM exactly like the membrane does in forward. The
gradient matches `jax.grad` through `core.lif.lif_scan` (the ref oracle)
to float32 round-off, so TPU training no longer needs to pin
``EXSPIKE_BACKEND=lif_scan=ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lif_kernel(x_ref, out_ref, v_ref, *, t_steps: int, decay: float,
                v_th: float, soft_reset: bool):
    v_ref[...] = jnp.zeros_like(v_ref)

    def body(t, _):
        v = v_ref[...] * decay + x_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        if soft_reset:
            v_ref[...] = v - s * v_th
        else:
            v_ref[...] = v * (1.0 - s)
        out_ref[t] = s.astype(out_ref.dtype)
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def lif_scan_pallas(
    x: jax.Array,
    *,
    decay: float = 0.5,
    v_th: float = 1.0,
    soft_reset: bool = True,
    block_m: int = 8,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """LIF over leading time axis. x: (T, M, N) -> binary spikes (T, M, N)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t_steps, m, n = x.shape
    if m % block_m or n % block_n:
        raise ValueError(f"(M,N)=({m},{n}) must tile by ({block_m},{block_n})")

    kernel = functools.partial(
        _lif_kernel, t_steps=t_steps, decay=decay, v_th=v_th,
        soft_reset=soft_reset)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[pl.BlockSpec((t_steps, block_m, block_n),
                               lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((t_steps, block_m, block_n),
                               lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="lif",
    )(x)


# ---------------------------------------------------- differentiable form
def _lif_fwd_kernel(x_ref, s_ref, vres_ref, v_ref, *, t_steps: int,
                    decay: float, v_th: float, soft_reset: bool):
    """Forward scan that also emits the pre-reset membrane V[t] (the value
    the Heaviside — and hence the surrogate derivative — is evaluated at)."""
    v_ref[...] = jnp.zeros_like(v_ref)

    def body(t, _):
        v = v_ref[...] * decay + x_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        vres_ref[t] = v
        if soft_reset:
            v_ref[...] = v - s * v_th
        else:
            v_ref[...] = v * (1.0 - s)
        s_ref[t] = s.astype(s_ref.dtype)
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def _lif_bwd_kernel(vres_ref, g_ref, dx_ref, u_ref, *, t_steps: int,
                    decay: float, v_th: float, soft_reset: bool,
                    surrogate_alpha: float):
    """Reversed temporal scan: u_ref carries the cotangent of the membrane
    state (the VMEM-resident mirror of forward's v_ref).

    Per step, with sg = ATan'(V[t] - v_th) and gs = cotangent of S[t]:
      dL/dV[t]  = gs * sg + u * d(reset)/dV
      d(reset)/dV = 1 - v_th*sg          (soft: v' = V - S*v_th)
                  = (1 - S) - V*sg       (hard: v' = V * (1 - S))
      dX[t]     = dL/dV[t];   u <- decay * dL/dV[t]
    matching jax.grad through core.lif.lif_scan term by term.
    """
    u_ref[...] = jnp.zeros_like(u_ref)
    half_pi_alpha = 0.5 * math.pi * surrogate_alpha

    def body(i, _):
        t = t_steps - 1 - i
        v = vres_ref[t]
        sg = surrogate_alpha / 2.0 / (1.0 + (half_pi_alpha * (v - v_th)) ** 2)
        gs = g_ref[t].astype(jnp.float32)
        if soft_reset:
            dreset = 1.0 - v_th * sg
        else:
            s = (v >= v_th).astype(jnp.float32)
            dreset = (1.0 - s) - v * sg
        dv = gs * sg + u_ref[...] * dreset
        dx_ref[t] = dv.astype(dx_ref.dtype)
        u_ref[...] = decay * dv
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def _lif_fwd_pallas(x, *, decay, v_th, soft_reset, block_m, block_n,
                    interpret=None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t_steps, m, n = x.shape
    if m % block_m or n % block_n:
        raise ValueError(f"(M,N)=({m},{n}) must tile by ({block_m},{block_n})")
    kernel = functools.partial(
        _lif_fwd_kernel, t_steps=t_steps, decay=decay, v_th=v_th,
        soft_reset=soft_reset)
    spec = pl.BlockSpec((t_steps, block_m, block_n), lambda i, j: (0, i, j))
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="grad_lif_fwd",
    )(x)


def _lif_bwd_pallas(vres, g, *, decay, v_th, soft_reset, surrogate_alpha,
                    block_m, block_n, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t_steps, m, n = vres.shape
    kernel = functools.partial(
        _lif_bwd_kernel, t_steps=t_steps, decay=decay, v_th=v_th,
        soft_reset=soft_reset, surrogate_alpha=surrogate_alpha)
    spec = pl.BlockSpec((t_steps, block_m, block_n), lambda i, j: (0, i, j))
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="grad_lif_bwd",
    )(vres, g)


# ------------------------------------------- fused occupancy emission
# The full-event pipeline's producer side: while the forward scan holds
# each spike tile in VMEM it also popcounts it, so the per-tile event
# counts leave the kernel as a second (scalar-memory) output with zero
# extra HBM traffic over the spikes themselves — occupancy becomes a
# byproduct of spike production instead of a dense re-read downstream.
# Counts are emitted per (timestep, block_m-row chunk, block_n-lane tile)
# and aggregated to the consumers' (128, 128) matmul tiling outside the
# kernel by `kernels.ops.lif_occ` (a reduction over the tiny count map,
# not the spike tensor).
#
# TPU layout: the counts are ONE whole-array SMEM table, flat
# (T * M/bm * N/bn,) int32, each grid step writing its own slots by
# program id. A per-step (1, 1) count block breaks Mosaic's (8, 128)
# block rule; a lane-dense VMEM count block would cost an HBM write per
# tile. SMEM holds 1 MiB, so `_occ_call` splits the rows over several
# calls whenever one table would pass `_COUNT_WORDS`.
_COUNT_WORDS = 64 * 1024


def _count_slots():
    """t -> flat SMEM slot of this grid step's (t, row-chunk, lane-tile)
    count. Grid ids are read here, at kernel top level: the interpreter
    cannot lower `program_id` inside the scan's loop body."""
    i, j = pl.program_id(0), pl.program_id(1)
    mb, nb = pl.num_programs(0), pl.num_programs(1)
    return lambda t: (t * mb + i) * nb + j


def _lif_occ_kernel(x_ref, s_ref, cnt_ref, v_ref, *, t_steps: int,
                    decay: float, v_th: float, soft_reset: bool):
    count_slot = _count_slots()
    v_ref[...] = jnp.zeros_like(v_ref)

    def body(t, _):
        v = v_ref[...] * decay + x_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        if soft_reset:
            v_ref[...] = v - s * v_th
        else:
            v_ref[...] = v * (1.0 - s)
        s_ref[t] = s.astype(s_ref.dtype)
        cnt_ref[count_slot(t)] = jnp.sum(s.astype(jnp.int32))  # popcount
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def _lif_occ_fwd_kernel(x_ref, s_ref, cnt_ref, vres_ref, v_ref, *,
                        t_steps: int, decay: float, v_th: float,
                        soft_reset: bool):
    """Autodiff forward: spikes + per-tile counts + pre-reset membrane
    residuals (what the surrogate backward consumes)."""
    count_slot = _count_slots()
    v_ref[...] = jnp.zeros_like(v_ref)

    def body(t, _):
        v = v_ref[...] * decay + x_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        vres_ref[t] = v
        if soft_reset:
            v_ref[...] = v - s * v_th
        else:
            v_ref[...] = v * (1.0 - s)
        s_ref[t] = s.astype(s_ref.dtype)
        cnt_ref[count_slot(t)] = jnp.sum(s.astype(jnp.int32))
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def _occ_call(kernel, x, payload, *, block_m, block_n, interpret, name):
    """Run a fused fire+count kernel, named `name`, over x (T, M, N).

    `payload`: ((dtype, width_div), ...), one per tensor output: block
    (T, block_m, block_n // width_div) of a (T, M, N // width_div) array.
    Returns the first payload output, the counts (T, M/bm, N/bn), then
    the other payload outputs — the kernel's output order — splitting the
    rows across calls when one SMEM count table would pass
    `_COUNT_WORDS`."""
    t_steps, m, n = x.shape
    nb = n // block_n
    rows_per_call = max(1, _COUNT_WORDS // (t_steps * nb)) * block_m
    if m > rows_per_call:
        parts = [_occ_call(kernel, x[:, a:a + rows_per_call], payload,
                           block_m=block_m, block_n=block_n,
                           interpret=interpret, name=name)
                 for a in range(0, m, rows_per_call)]
        return tuple(jnp.concatenate(o, axis=1) for o in zip(*parts))
    mb = m // block_m
    specs = tuple(
        pl.BlockSpec((t_steps, block_m, block_n // div),
                     lambda i, j: (0, i, j)) for _, div in payload)
    shapes = tuple(jax.ShapeDtypeStruct((t_steps, m, n // div), dt)
                   for dt, div in payload)
    outs = pl.pallas_call(
        kernel,
        grid=(mb, nb),
        in_specs=[pl.BlockSpec((t_steps, block_m, block_n),
                               lambda i, j: (0, i, j))],
        out_specs=specs[:1] + (pl.BlockSpec(memory_space=pltpu.SMEM),)
        + specs[1:],
        out_shape=shapes[:1]
        + (jax.ShapeDtypeStruct((t_steps * mb * nb,), jnp.int32),)
        + shapes[1:],
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name=name,
    )(x)
    return (outs[0], outs[1].reshape(t_steps, mb, nb), *outs[2:])


def _lif_occ_pallas(x, *, decay, v_th, soft_reset, block_m, block_n,
                    emit_vres: bool, interpret: bool | None = None):
    """x: (T, M, N) -> (spikes (T, M, N), counts (T, M/bm, N/bn) int32
    [, vres (T, M, N) f32]). Counts live in SMEM: one scalar per
    (t, row-chunk, lane-tile), written while the spike tile is resident."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t_steps, m, n = x.shape
    if m % block_m or n % block_n:
        raise ValueError(f"(M,N)=({m},{n}) must tile by ({block_m},{block_n})")
    kernel = functools.partial(
        _lif_occ_fwd_kernel if emit_vres else _lif_occ_kernel,
        t_steps=t_steps, decay=decay, v_th=v_th, soft_reset=soft_reset)
    payload = ((x.dtype, 1),) + (((jnp.float32, 1),) if emit_vres else ())
    return _occ_call(kernel, x, payload, block_m=block_m, block_n=block_n,
                     interpret=interpret,
                     name="grad_lif_occ_fwd" if emit_vres else "lif_occ")


def _lif_occ_packed_kernel(x_ref, p_ref, cnt_ref, v_ref, *, t_steps: int,
                           decay: float, v_th: float, soft_reset: bool):
    """Fire + PACK: while the spike tile is VMEM-resident for the scan,
    emit it as uint32 words (bit i of word w = lane w*32+i, the
    `core.spikes.pack_spikes` layout) and derive the per-tile event count
    from the words' popcounts — occupancy becomes a free byproduct of
    packing, and the f32 spike tile never reaches HBM at all (32x less
    spike traffic out of the producer).

    Interpret mode only: the (T, block_m, block_n/32) word block (4 lanes
    at the default 128) breaks Mosaic's (8, 128) block rule, so no TPU
    backend reaches this kernel (see the packed family in
    `kernels.dispatch`).
    """
    count_slot = _count_slots()
    v_ref[...] = jnp.zeros_like(v_ref)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def body(t, _):
        v = v_ref[...] * decay + x_ref[t].astype(jnp.float32)
        s = (v >= v_th).astype(jnp.float32)
        if soft_reset:
            v_ref[...] = v - s * v_th
        else:
            v_ref[...] = v * (1.0 - s)
        bm, bn = s.shape
        bits = s.reshape(bm, bn // 32, 32).astype(jnp.uint32)
        words = jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)
        p_ref[t] = words
        cnt_ref[count_slot(t)] = jnp.sum(
            jax.lax.population_count(words).astype(jnp.int32))
        return ()

    jax.lax.fori_loop(0, t_steps, body, ())


def lif_scan_occ_packed_pallas(x, *, decay: float = 0.5, v_th: float = 1.0,
                               soft_reset: bool = True, block_m: int = 8,
                               block_n: int = 128,
                               interpret: bool | None = None):
    """Fused packed emission: x (T, M, N) -> (packed words
    (T, M, N/32) uint32, counts (T, M/bm, N/bn) int32).

    FORWARD-ONLY by contract (the packed payload is inference-mode event
    transport; both outputs are integer-typed and the drive is
    stop_gradient'ed — training paths run the differentiable dense
    emission and pack nothing). N must tile by block_n (>= and a multiple
    of 32), which the `ops.lif_occ` wrapper's 128-lane padding guarantees.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    x = jax.lax.stop_gradient(x)
    t_steps, m, n = x.shape
    if m % block_m or n % block_n or block_n % 32:
        raise ValueError(f"(M,N)=({m},{n}) must tile by ({block_m},{block_n})"
                         f" with block_n a multiple of 32")
    kernel = functools.partial(
        _lif_occ_packed_kernel, t_steps=t_steps, decay=decay, v_th=v_th,
        soft_reset=soft_reset)
    return _occ_call(kernel, x, ((jnp.uint32, 32),), block_m=block_m,
                     block_n=block_n, interpret=interpret,
                     name="lif_occ_packed")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def lif_scan_occ_pallas_sg(x, decay: float = 0.5, v_th: float = 1.0,
                           soft_reset: bool = True,
                           surrogate_alpha: float = 2.0,
                           block_m: int = 8, block_n: int = 128,
                           interpret: bool | None = None):
    """Differentiable fused LIF with occupancy emission.

    x: (T, M, N) drive -> (spikes (T, M, N), counts (T, M/bm, N/bn)).
    Spikes are bit-identical to `lif_scan_pallas`; counts are the
    non-differentiated aux (their cotangent is discarded — occupancy is
    metadata, not signal). `jax.grad` runs the same reversed-scan
    surrogate kernel as `lif_scan_pallas_sg`.
    """
    return _lif_occ_pallas(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                           block_m=block_m, block_n=block_n, emit_vres=False,
                           interpret=interpret)


def _occ_sg_fwd(x, decay, v_th, soft_reset, surrogate_alpha, block_m,
                block_n, interpret):
    s, cnt, vres = _lif_occ_pallas(
        x, decay=decay, v_th=v_th, soft_reset=soft_reset, block_m=block_m,
        block_n=block_n, emit_vres=True, interpret=interpret)
    return (s, cnt), vres


def _occ_sg_bwd(decay, v_th, soft_reset, surrogate_alpha, block_m, block_n,
                interpret, vres, g):
    gs, _g_cnt = g          # occupancy aux carries no gradient
    dx = _lif_bwd_pallas(vres, gs, decay=decay, v_th=v_th,
                         soft_reset=soft_reset,
                         surrogate_alpha=surrogate_alpha,
                         block_m=block_m, block_n=block_n,
                         interpret=interpret)
    return (dx,)


lif_scan_occ_pallas_sg.defvjp(_occ_sg_fwd, _occ_sg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def lif_scan_pallas_sg(x, decay: float = 0.5, v_th: float = 1.0,
                       soft_reset: bool = True, surrogate_alpha: float = 2.0,
                       block_m: int = 8, block_n: int = 128,
                       interpret: bool | None = None):
    """Differentiable fused LIF: Pallas forward, Pallas surrogate backward.

    x: (T, M, N) membrane drive -> binary spikes (T, M, N). Forward output
    is bit-identical to `lif_scan_pallas`; `jax.grad` runs the reversed-
    scan kernel with the ATan surrogate (SpikingJelly convention), matching
    the ref oracle `core.lif.lif_scan`. The primal runs the plain forward
    kernel — the f32 membrane-residual write only happens under autodiff
    (custom_vjp fwd), so inference pays nothing for differentiability.
    """
    return lif_scan_pallas(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                           block_m=block_m, block_n=block_n,
                           interpret=interpret)


def _sg_fwd(x, decay, v_th, soft_reset, surrogate_alpha, block_m, block_n,
            interpret):
    s, vres = _lif_fwd_pallas(x, decay=decay, v_th=v_th,
                              soft_reset=soft_reset, block_m=block_m,
                              block_n=block_n, interpret=interpret)
    return s, vres


def _sg_bwd(decay, v_th, soft_reset, surrogate_alpha, block_m, block_n,
            interpret, vres, g):
    dx = _lif_bwd_pallas(vres, g, decay=decay, v_th=v_th,
                         soft_reset=soft_reset,
                         surrogate_alpha=surrogate_alpha,
                         block_m=block_m, block_n=block_n,
                         interpret=interpret)
    return (dx,)


lif_scan_pallas_sg.defvjp(_sg_fwd, _sg_bwd)

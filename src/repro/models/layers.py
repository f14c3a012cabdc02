"""Shared model layers: params as plain pytrees, pure apply functions.

Conventions
-----------
* Params are nested dicts of jax.Arrays; init functions are traceable so
  `jax.eval_shape(init)` yields allocation-free abstract trees for the
  dry-run (ShapeDtypeStruct stand-ins).
* Sharding is name-based: `runtime.sharding` maps param-tree paths to
  PartitionSpecs, so layers stay sharding-agnostic.
* Spiking layers take/return an explicit leading T axis (micro-timesteps);
  LIF is the only op that couples timesteps.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.events import EventTensor
from repro.core.lif import LIFConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------- init utils
def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.truncated_normal(key, -2, 2, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.truncated_normal(key, -2, 2, (vocab, d), jnp.float32)
            * 0.02).astype(dtype)


# ------------------------------------------------------------------- norms
def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


def layernorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# -------------------------------------------------------------------- RoPE
def rope_angles(positions: jax.Array, d_head: int, theta: float = 1e4) -> tuple:
    """positions: (..., N) int -> (sin, cos) of shape (..., N, d_head/2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., N, H, d_head); sin/cos: (..., N, d_head/2) broadcastable."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    s, c = sin[..., None, :], cos[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ hybrid scope
def hybrid_scope(spiking_cfg):
    """Dispatch scope a model's apply body runs under.

    `SpikingConfig.hybrid=True` turns on density-adaptive routing: every
    matmul-form op that receives a carried occupancy map picks dense vs
    event per call from the calibrated cost model (bucketed, so jit sees
    a bounded route set). Off (the default) keeps auto/override
    resolution exactly as before — zero behavior change.
    """
    import contextlib
    if getattr(spiking_cfg, "hybrid", False):
        from repro.kernels.dispatch import use_hybrid
        return use_hybrid()
    return contextlib.nullcontext()


# --------------------------------------------------------------- LIF helper
def lif_fire(x: jax.Array, lif_cfg: LIFConfig) -> jax.Array:
    """Binarize pre-activations into spikes over the leading T axis.

    x: (T, ...) membrane drive -> (T, ...) binary spikes. This is the FPE
    fire stage; in spiking mode every heavy op consumes its output.
    Routed through the backend registry: `ref` (lax.scan) by default on
    CPU, the fused Pallas kernel on TPU / under ``EXSPIKE_BACKEND``
    override. Every backend carries the ATan surrogate gradient (the
    Pallas kernel via its reversed-scan backward kernel), so training
    resolves backends exactly like inference — no ref pin.
    """
    from repro.kernels.dispatch import dispatch
    return dispatch("lif_scan", x, decay=lif_cfg.decay, v_th=lif_cfg.v_th,
                    soft_reset=lif_cfg.soft_reset,
                    surrogate_alpha=lif_cfg.surrogate_alpha)


def lif_fire_events(x: jax.Array, lif_cfg: LIFConfig,
                    packed: bool = False) -> EventTensor:
    """Fire AND carry the event metadata: the full-event producer.

    Routes through `lif_scan_occ`, whose Pallas backend emits the
    (128, 128) per-tile occupancy map while the spike tile is still in
    VMEM (ref computes it with `tile_occupancy` — identical map). The
    returned `EventTensor` flows to the next layer's event op, which
    skips its own dense occupancy pre-pass; the map is stop-gradient aux,
    so `jax.grad` matches the dense-spike forward exactly.

    `packed=True` makes the uint32 spike words the canonical payload:
    the fused kernel packs in the same VMEM pass that popcounts (the
    occupancy map is a free byproduct of packing), the returned
    EventTensor is packed-only (spikes=None — no f32 spike tensor ever
    materializes between layers), and dispatch routes it to `packed-csr`
    backends. Forward-only: the words are stop-gradient aux, so packed
    mode is an inference path (training keeps dense spikes).
    """
    from repro.kernels.dispatch import dispatch, packed_kernels_available
    if packed and not packed_kernels_available():
        raise NotImplementedError(
            f"packed spike payloads have no {jax.default_backend()} kernels "
            f"(the packed-csr family is registered for the CPU interpreter "
            f"only); run with SpikingConfig(packed=False)")
    s, occ, chunks = dispatch("lif_scan_occ", x, decay=lif_cfg.decay,
                              v_th=lif_cfg.v_th,
                              soft_reset=lif_cfg.soft_reset,
                              surrogate_alpha=lif_cfg.surrogate_alpha,
                              packed=packed)
    if packed:
        return EventTensor(None, occ, chunks=chunks, packed=s,
                           feature_size=x.shape[-1])
    return EventTensor(s, occ, chunks=chunks)


# --------------------------------------------------------------- SwiGLU MLP
def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_apply(p: Params, x: jax.Array, spiking: bool,
              lif_cfg: LIFConfig | None = None) -> jax.Array:
    """SwiGLU in dense mode; spike-gated two-matmul MLP in spiking mode.

    Spiking mode (x is binary (T, ...)): hidden drive = x @ (w_gate + w_up)
    is fired through LIF (binary hidden spikes), then down-projected —
    every matmul sees binary activations (full-event execution). SiLU
    gating is replaced by the LIF threshold, the FPE analog.

    Full-event mode (x is an `EventTensor`): both up-projections consume
    the ONE carried occupancy map, the hidden fire re-emits metadata
    fused, and the down-projection consumes that — zero standalone
    occupancy pre-passes inside the block. (The dispatch route passes the
    map; work-list compaction from it is tiny-map work per consumer. The
    per-instance `EventTensor.csr()` cache serves direct `kernels.ops`
    callers.)
    """
    if isinstance(x, EventTensor):
        from repro.kernels import dispatch as _d
        h = _d.spike_matmul(x, p["w_gate"]) + _d.spike_matmul(x, p["w_up"])
        # Packedness propagates: a packed input re-fires packed, so the
        # hidden spikes also never materialize as f32.
        h = lif_fire_events(h, lif_cfg, packed=x.is_packed)
        return _d.spike_matmul(h, p["w_down"])
    if spiking:
        h = x @ (p["w_gate"].astype(x.dtype))
        h = h + x @ (p["w_up"].astype(x.dtype))
        h = lif_fire(h, lif_cfg)
        return h @ p["w_down"].astype(h.dtype)
    g = x @ p["w_gate"].astype(x.dtype)
    u = x @ p["w_up"].astype(x.dtype)
    return (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u) \
        @ p["w_down"].astype(x.dtype)

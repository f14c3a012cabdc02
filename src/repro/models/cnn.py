"""The paper's own SCNN workloads: spiking VGG11, ResNet18, SegNet.

Faithful to the evaluated stack (Sec. IV): LIF neurons (tau=0.5), T=4
timesteps, direct-coded first layer (OPT1), event-driven-equivalent convs
(OPT2), and an EAFC avgpool+FC head (OPT3). Residual connections add
membrane drives before the fire stage — the Residual Spike SRAM path of
Fig. 3.

Every conv — stem, strided downsamples, and the segmentation decoder's
transposed convs — routes through the backend registry (`econv` / `tconv`
ops) with micro-timesteps folded into the batch axis, so the whole stack
is parity-tested, benchmarked, and differentiable per backend. The first
layer eats the direct-coded (multi-bit) drive: the ref/pallas backends are
exact for it; the per-event scatter (``econv=jnp``) assumes binary inputs
and is only meaningful from the first spiking layer on (OPT1 territory).

`apply(..., collect_stats=True)` returns per-layer spike maps for the
Fig. 2 / Fig. 7 sparsity + APEC benchmarks.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import CNNConfig, CNNLayer
from repro.core.direct_coding import quantize
from repro.core.econv import conv_transpose, econv
from repro.core.eafc import eafc
from repro.core.events import EventTensor, max_pool_events
from repro.core.lif import LIFConfig
from .layers import hybrid_scope, lif_fire_events

Params = Dict[str, Any]


def _fire(drive: jax.Array, lif: LIFConfig,
          packed: bool = False) -> EventTensor:
    """Fire stage with fused metadata emission: spikes + occupancy leave
    the LIF together (`lif_scan_occ`), so the next conv's event kernel
    consumes the carried map instead of re-scanning the activation.
    `packed=True` emits uint32 words as the canonical payload (no f32
    spike tensor between layers; inference-only)."""
    return lif_fire_events(drive, lif, packed=packed)


def _conv_seq(s, w: jax.Array, stride: int = 1) -> jax.Array:
    """(T,B,H,W,C) drive through the registry `econv` op, T folded into
    the batch (one conv on T*B images instead of a vmap of T convs).
    `s` may be an `EventTensor` — the (T,B)->(T*B) fold preserves the
    trailing channel axis, so the carried map survives into the conv."""
    t, b = s.shape[:2]
    out = econv(s.reshape((t * b,) + s.shape[2:]), w, stride=stride)
    return out.reshape((t, b) + out.shape[1:])


def _tconv_seq(s, w: jax.Array, stride: int) -> jax.Array:
    """(T,B,H,W,C) spikes through the registry `tconv` (transposed conv)."""
    t, b = s.shape[:2]
    out = conv_transpose(s.reshape((t * b,) + s.shape[2:]), w, stride=stride)
    return out.reshape((t, b) + out.shape[1:])

# ------------------------------------------------------- model definitions
VGG11_LAYERS: Tuple[CNNLayer, ...] = (
    CNNLayer("conv", 64), CNNLayer("maxpool"),
    CNNLayer("conv", 128), CNNLayer("maxpool"),
    CNNLayer("conv", 256), CNNLayer("conv", 256), CNNLayer("maxpool"),
    CNNLayer("conv", 512), CNNLayer("conv", 512), CNNLayer("maxpool"),
    CNNLayer("conv", 512), CNNLayer("conv", 512),
)

SEGNET_LAYERS: Tuple[CNNLayer, ...] = (   # 8C3-16C3-32C3-32C3-16TC3-2TC3
    CNNLayer("conv", 8), CNNLayer("conv", 16, stride=2),
    CNNLayer("conv", 32, stride=2), CNNLayer("conv", 32),
    CNNLayer("tconv", 16, stride=2), CNNLayer("tconv", 2, stride=2),
)

RESNET18_STAGES = ((64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2))


def _conv_init(key, k: int, ci: int, co: int) -> jax.Array:
    scale = (2.0 / (k * k * ci)) ** 0.5
    return jax.random.normal(key, (k, k, ci, co), jnp.float32) * scale


# ------------------------------------------------------------------- VGG11
def vgg11_init(cfg: CNNConfig, key) -> Params:
    p: Params = {"convs": []}
    ci = cfg.in_ch
    keys = jax.random.split(key, len(VGG11_LAYERS) + 1)
    spatial = cfg.img
    for i, layer in enumerate(VGG11_LAYERS):
        if layer.kind == "conv":
            p["convs"].append(_conv_init(keys[i], layer.kernel, ci, layer.out_ch))
            ci = layer.out_ch
        else:
            p["convs"].append(None)
            spatial //= 2
    pooled = spatial // cfg.fc_pool
    p["fc"] = jax.random.normal(
        keys[-1], (pooled * pooled * ci, cfg.n_classes), jnp.float32) \
        * (1.0 / (pooled * pooled * ci)) ** 0.5
    return p


def vgg11_apply(cfg: CNNConfig, p: Params, x: jax.Array,
                collect_stats: bool = False):
    """x: (B, H, W, C) image -> logits (B, n_classes) [, spike maps]."""
    with hybrid_scope(cfg.spiking):
        return _vgg11_body(cfg, p, x, collect_stats)


def _vgg11_body(cfg, p, x, collect_stats):
    # Each layer runs under a `named_scope` (`encode`, `conv.{i}`,
    # `pool.{i}`, `head`), so that a profile of the compiled program
    # attributes device time to layers.
    lif = LIFConfig(decay=cfg.spiking.lif_decay, v_th=cfg.spiking.lif_vth)
    t = cfg.spiking.t_steps
    with jax.named_scope("encode"):
        q, scale = quantize(x, cfg.direct_coding_bits)
        s = jnp.broadcast_to((q.astype(jnp.float32) * scale)[None],
                             (t,) + x.shape)   # direct-coded drive, each step
    packed = getattr(cfg.spiking, "packed", False)
    stats: List[jax.Array] = []
    n_seen = {"conv": 0, "maxpool": 0}
    for layer, w in zip(VGG11_LAYERS, p["convs"]):
        i = n_seen[layer.kind]
        n_seen[layer.kind] += 1
        if layer.kind == "maxpool":
            # pooling keeps the carried map alive (tile-map dilation);
            # a packed payload pools its words bitwise-OR.
            with jax.named_scope(f"pool.{i}"):
                s = max_pool_events(s, layer.pool)
            continue
        with jax.named_scope(f"conv.{i}"):
            drive = _conv_seq(s, w)
            s = _fire(drive, lif, packed)  # binary spikes + occupancy map
        if collect_stats:
            stats.append(s.dense())
    # EAFC head (OPT3): event-driven avgpool+FC over every timestep.
    # `.dense()` is the one explicit unpack point for a packed payload
    # (eafc has no packed backend).
    with jax.named_scope("head"):
        logits = jnp.mean(jax.vmap(lambda st: eafc(st, p["fc"],
                                                   cfg.fc_pool))(s.dense()),
                          axis=0)
    return (logits, stats) if collect_stats else logits


# ---------------------------------------------------------------- ResNet18
def resnet18_init(cfg: CNNConfig, key) -> Params:
    keys = iter(jax.random.split(key, 64))
    p: Params = {"stem": _conv_init(next(keys), 3, cfg.in_ch, 64), "blocks": []}
    ci = 64
    for co, n_blocks, stride in RESNET18_STAGES:
        for b in range(n_blocks):
            s0 = stride if b == 0 else 1
            blk = {
                "conv1": _conv_init(next(keys), 3, ci, co),
                "conv2": _conv_init(next(keys), 3, co, co),
                "stride": s0,
            }
            if s0 != 1 or ci != co:
                blk["proj"] = _conv_init(next(keys), 1, ci, co)
            p["blocks"].append(blk)
            ci = co
    pooled = cfg.img // 8 // cfg.fc_pool
    p["fc"] = jax.random.normal(
        next(keys), (pooled * pooled * ci, cfg.n_classes), jnp.float32) \
        * (1.0 / (pooled * pooled * ci)) ** 0.5
    return p


def resnet18_apply(cfg: CNNConfig, p: Params, x: jax.Array,
                   collect_stats: bool = False):
    with hybrid_scope(cfg.spiking):
        return _resnet18_body(cfg, p, x, collect_stats)


def _resnet18_body(cfg, p, x, collect_stats):
    lif = LIFConfig(decay=cfg.spiking.lif_decay, v_th=cfg.spiking.lif_vth)
    t = cfg.spiking.t_steps
    q, scale = quantize(x, cfg.direct_coding_bits)
    xin = jnp.broadcast_to((q.astype(jnp.float32) * scale)[None],
                           (t,) + x.shape)
    drive = _conv_seq(xin, p["stem"])
    packed = getattr(cfg.spiking, "packed", False)
    s = _fire(drive, lif, packed)
    stats: List[jax.Array] = [s.dense()] if collect_stats else []
    for blk in p["blocks"]:
        st0 = blk["stride"]
        h = _conv_seq(s, blk["conv1"], stride=st0)
        h = _fire(h, lif, packed)
        h2 = _conv_seq(h, blk["conv2"])
        # Residual Spike SRAM path: shortcut drives added pre-fire (the
        # sum is membrane drive, not spikes — metadata re-emits at _fire).
        # The identity shortcut is a drive-summand, so it goes through
        # `.dense()` — an explicit unpack, never a silent densify.
        short = _conv_seq(s, blk["proj"], stride=st0) if "proj" in blk \
            else s.dense()
        s = _fire(h2 + short, lif, packed)
        if collect_stats:
            stats.append(s.dense())
    logits = jnp.mean(jax.vmap(lambda ss: eafc(ss, p["fc"],
                                               cfg.fc_pool))(s.dense()),
                      axis=0)
    return (logits, stats) if collect_stats else logits


# ------------------------------------------------------------------ SegNet
def segnet_init(cfg: CNNConfig, key) -> Params:
    keys = iter(jax.random.split(key, 16))
    p: Params = {"convs": []}
    ci = cfg.in_ch
    for layer in SEGNET_LAYERS:
        p["convs"].append(_conv_init(next(keys), layer.kernel, ci,
                                     layer.out_ch))
        ci = layer.out_ch
    return p


def segnet_apply(cfg: CNNConfig, p: Params, x: jax.Array,
                 collect_stats: bool = False):
    """x: (B, H, W, C) -> per-pixel logits (B, H, W, 2)."""
    with hybrid_scope(cfg.spiking):
        return _segnet_body(cfg, p, x, collect_stats)


def _segnet_body(cfg, p, x, collect_stats):
    lif = LIFConfig(decay=cfg.spiking.lif_decay, v_th=cfg.spiking.lif_vth)
    t = cfg.spiking.t_steps
    q, scale = quantize(x, cfg.direct_coding_bits)
    s = jnp.broadcast_to((q.astype(jnp.float32) * scale)[None], (t,) + x.shape)
    packed = getattr(cfg.spiking, "packed", False)
    stats: List[jax.Array] = []
    mp_total = jnp.zeros(())
    for i, (layer, w) in enumerate(zip(SEGNET_LAYERS, p["convs"])):
        last = i == len(SEGNET_LAYERS) - 1
        if layer.kind == "conv":
            drive = _conv_seq(s, w, stride=layer.stride)
        else:  # transposed conv (decoder upsampling): registry `tconv` op
            drive = _tconv_seq(s, w, stride=layer.stride)
        if last:
            return (jnp.mean(drive, axis=0), stats) if collect_stats \
                else jnp.mean(drive, axis=0)
        s = _fire(drive, lif, packed)
        if collect_stats:
            stats.append(s.dense())
    raise AssertionError("unreachable")

"""SpikingFormer-L-D (the paper's transformer workloads, Table II).

Structure per the SpikingFormer line of work, matching the paper's
benchmark split (Fig. 7): a Spiking Patch Splitting (SPS) conv stem that
downsamples 32x32 CIFAR images into 8x8 = 64 tokens of dimension D, then
L encoder blocks of spike-driven self-attention (SSA — the Attention Core
semantics) + spiking MLP (FFN). Membrane shortcut residuals; rate-decoded
classification head.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import SpikingConfig
from repro.core.events import max_pool_events
from repro.core.lif import LIFConfig
from repro.kernels import dispatch
from .cnn import _conv_init
from .layers import dense_init, hybrid_scope, lif_fire, lif_fire_events

Params = Dict[str, Any]


def spikingformer_init(key, depth: int, dim: int, n_classes: int = 10,
                       in_ch: int = 3) -> Params:
    keys = iter(jax.random.split(key, 16 + 8 * depth))
    sps_dims = (dim // 8, dim // 4, dim // 2, dim)
    p: Params = {"sps": [], "blocks": []}
    ci = in_ch
    for co in sps_dims:
        p["sps"].append(_conv_init(next(keys), 3, ci, co))
        ci = co
    for _ in range(depth):
        p["blocks"].append({
            "w_q": dense_init(next(keys), dim, dim, jnp.float32),
            "w_k": dense_init(next(keys), dim, dim, jnp.float32),
            "w_v": dense_init(next(keys), dim, dim, jnp.float32),
            "w_o": dense_init(next(keys), dim, dim, jnp.float32),
            "w_fc1": dense_init(next(keys), dim, 4 * dim, jnp.float32),
            "w_fc2": dense_init(next(keys), 4 * dim, dim, jnp.float32),
        })
    p["head"] = dense_init(next(keys), dim, n_classes, jnp.float32)
    return p


def spikingformer_apply(p: Params, x: jax.Array, n_heads: int = 8,
                        spiking_cfg: SpikingConfig = SpikingConfig(t_steps=4),
                        collect_stats: bool = False):
    """x: (B, 32, 32, C) -> logits (B, n_classes) [, spike maps]."""
    with hybrid_scope(spiking_cfg):
        return _spikingformer_body(p, x, n_heads, spiking_cfg, collect_stats)


def _spikingformer_body(p, x, n_heads, spiking_cfg, collect_stats):
    # Each layer runs under a `named_scope` (`encode`, `sps.{i}`,
    # `block.{j}.attn`, `block.{j}.ffn`, `head`), so that a profile of the
    # compiled program attributes device time to layers.
    lif = LIFConfig(decay=spiking_cfg.lif_decay, v_th=spiking_cfg.lif_vth)
    t = spiking_cfg.t_steps
    b = x.shape[0]
    with jax.named_scope("encode"):
        s = jnp.broadcast_to(x[None], (t,) + x.shape)
    stats: List[jax.Array] = []

    # SPS: conv -> LIF x4, maxpool after stages 2 and 3 (32 -> 8).
    # Registry-routed econv over the flattened (T*B) batch: dense TConv on
    # CPU, im2col + occupancy-skipping spike matmul on TPU. Stage 0 eats
    # the direct-coded (multi-bit) image, which the event path doesn't
    # model (OPT1 territory) — it stays on the dense oracle. From stage 1
    # on the stream is full-event: the fire stage emits spikes WITH their
    # occupancy map (`lif_fire_events`), the (T,B)->(T*B) fold and the
    # pooling both carry it forward, and each econv consumes it instead
    # of re-deriving occupancy from the activation it was just handed.
    from repro.core.econv import econv, tconv
    packed = getattr(spiking_cfg, "packed", False)
    for i, w in enumerate(p["sps"]):
        with jax.named_scope(f"sps.{i}"):
            with jax.named_scope("conv"):
                tb = s.shape[:2]
                flat = s.reshape((-1,) + s.shape[2:])
                drive = tconv(flat, w) if i == 0 else econv(flat, w)
                drive = drive.reshape(tb + drive.shape[1:])
            with jax.named_scope("fire"):
                s = lif_fire_events(drive, lif, packed=packed)
            if i in (1, 2):
                with jax.named_scope("pool"):
                    s = max_pool_events(s, 2)  # packed pools bitwise-OR
            if collect_stats:
                stats.append(s.dense())

    with jax.named_scope(f"sps.{i}"):    # the last stage makes the tokens
        dim = s.shape[-1]
        n_tok = s.shape[2] * s.shape[3]
        tokens = s.reshape(t, b, n_tok, dim)     # (T,B,N,D), map survives
        # The membrane residual stream is continuous-valued from here on —
        # `.dense()` is the explicit unpack at the SPS/transformer boundary.
        x_mp = tokens.dense()

    for j, blk in enumerate(p["blocks"]):
        with jax.named_scope(f"block.{j}.attn"):
            # SSA: q/k/v spikes -> Attention Core (non-causal OR form).
            # The head split changes the trailing axis, so no map is
            # carried into SDSA (which consumes packed words, not
            # occupancy, anyway).
            with jax.named_scope("qkv"):
                sq = lif_fire(x_mp @ blk["w_q"], lif).reshape(
                    t, b, n_tok, n_heads, dim // n_heads)
                sk = lif_fire(x_mp @ blk["w_k"], lif).reshape(
                    t, b, n_tok, n_heads, dim // n_heads)
                sv = lif_fire(x_mp @ blk["w_v"], lif).reshape(
                    t, b, n_tok, n_heads, dim // n_heads)
            with jax.named_scope("sdsa"):
                attn = dispatch.sdsa(sq.swapaxes(2, 3), sk.swapaxes(2, 3),
                                     sv.swapaxes(2, 3),
                                     mode=spiking_cfg.sdsa_mode)
                attn = attn.swapaxes(2, 3).reshape(t, b, n_tok, dim)
            if collect_stats:
                stats.append(attn)
            with jax.named_scope("proj"):
                x_mp = x_mp + attn @ blk["w_o"]
        with jax.named_scope(f"block.{j}.ffn"):
            # Spiking MLP (FFN): full-event — both fires carry their maps
            # and both projections consume them through the registry
            # matmul. In packed mode both fires emit uint32 words and the
            # projections route to the packed-csr family (no f32 spikes
            # in between).
            h = lif_fire_events(x_mp, lif, packed=packed)
            h = lif_fire_events(dispatch.spike_matmul(h, blk["w_fc1"]), lif,
                                packed=packed)
            if collect_stats:
                stats.append(h.dense())
            x_mp = x_mp + dispatch.spike_matmul(h, blk["w_fc2"])

    with jax.named_scope("head"):
        feats = jnp.mean(lif_fire(x_mp, lif), axis=(0, 2))  # rate + tokens
        logits = feats @ p["head"]
    return (logits, stats) if collect_stats else logits

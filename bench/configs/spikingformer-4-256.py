"""SpikingFormer-4-256: the benchmark's weights, the program's call and
the work one call does.

`program` is the timed path: the program's `spikingformer_apply` with
automatic backend resolution. `init` makes the weights from the seed in
the pytree layout that call takes; the reference takes the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work


def init(cfg: dict, key) -> dict:
    """Weights in float32, made in one traceable call from `key`."""
    d, depth = cfg["dim"], cfg["depth"]
    keys = iter(jax.random.split(key, 4 + 6 * depth + 1))
    k = cfg["kernel"]

    def conv(ci, co):
        return jax.random.normal(next(keys), (k, k, ci, co),
                                 jnp.float32) * (2.0 / (k * k * ci)) ** 0.5

    def dense(n_in, n_out):
        return jax.random.truncated_normal(
            next(keys), -2.0, 2.0, (n_in, n_out),
            jnp.float32) * (2.0 / (n_in + n_out)) ** 0.5

    sps, ci = [], cfg["in_ch"]
    for co in cfg["sps_channels"]:
        sps.append(conv(ci, co))
        ci = co
    hidden = cfg["mlp_ratio"] * d
    blocks = [{"w_q": dense(d, d), "w_k": dense(d, d), "w_v": dense(d, d),
               "w_o": dense(d, d), "w_fc1": dense(d, hidden),
               "w_fc2": dense(hidden, d)} for _ in range(depth)]
    return {"sps": sps, "blocks": blocks, "head": dense(d, cfg["n_classes"])}


def program(cfg: dict):
    """The timed call: (weights, images (B, H, W, C)) -> logits."""
    from repro.configs.base import SpikingConfig
    from repro.models.spikingformer import spikingformer_apply
    # The program fixes what the file only states: pooling after stem
    # stages 1 and 2, a 3x3 stem and no RPE convolution.
    if cfg["sps_pool_after"] != [1, 2] or cfg["kernel"] != 3 \
            or cfg["sps_rpe_conv"]:
        raise ValueError("spikingformer_apply pools after stem stages 1 "
                         "and 2 with 3x3 convolutions and no RPE "
                         "convolution only")
    spiking = SpikingConfig(t_steps=cfg["t_steps"], lif_decay=cfg["lif_decay"],
                            lif_vth=cfg["lif_vth"], sdsa_mode=cfg["sdsa_mode"])

    def apply(params, x):
        return spikingformer_apply(params, x, n_heads=cfg["n_heads"],
                                   spiking_cfg=spiking)
    return apply


def _stem(cfg):
    """(h, w, ci, co, pooled) of each stem stage."""
    h, ci, out = cfg["img"], cfg["in_ch"], []
    for i, co in enumerate(cfg["sps_channels"]):
        pooled = i in cfg["sps_pool_after"]
        out.append((h, h, ci, co, pooled))
        h, ci = (h // 2 if pooled else h), co
    return out, h * h


def dense_flops_per_image(cfg: dict) -> int:
    """Dense-equivalent FLOPs of one image at all time steps."""
    stem, n_tok = _stem(cfg)
    d, hidden = cfg["dim"], cfg["mlp_ratio"] * cfg["dim"]
    per_step = sum(work.conv_flops(h, w, ci, co, cfg["kernel"])
                   for h, w, ci, co, _ in stem)
    per_step += cfg["depth"] * (4 * work.linear_flops(n_tok, d, d)
                                + work.linear_flops(n_tok, d, hidden)
                                + work.linear_flops(n_tok, hidden, d))
    return cfg["t_steps"] * per_step + work.linear_flops(1, d,
                                                         cfg["n_classes"])


def lif_calls(cfg: dict, batch: int) -> list:
    """(t, rows, k, maps) of every LIF call of one program call; `maps`
    marks the fires that emit occupancy maps (`lif_scan_occ`)."""
    stem, n_tok = _stem(cfg)
    t, d = cfg["t_steps"], cfg["dim"]
    calls = [(t, batch * h * w, co, True) for h, w, _, co, _ in stem]
    for _ in range(cfg["depth"]):
        calls += [(t, batch * n_tok, d, False)] * 3          # Q, K, V
        calls += [(t, batch * n_tok, d, True),
                  (t, batch * n_tok, cfg["mlp_ratio"] * d, True)]
    return calls + [(t, batch * n_tok, d, False)]           # the head's fire

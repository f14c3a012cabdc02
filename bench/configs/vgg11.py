"""Spiking VGG11: the benchmark's weights, the program's call and the work
one call does.

`program` is the timed path: the program's `vgg11_apply` with automatic
backend resolution. `init` makes the weights from the seed in the pytree
layout that call takes (`None` where a layer is a pooling); the reference
takes the same weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work


def _convs(cfg):
    """(h, w, ci, co) of each convolution, in order."""
    h, ci, out = cfg["img"], cfg["in_ch"], []
    for layer in cfg["layers"]:
        if layer == "M":
            h //= 2
        else:
            out.append((h, h, ci, layer))
            ci = layer
    return out, h, ci


def init(cfg: dict, key) -> dict:
    """Weights in float32, made in one traceable call from `key`."""
    k = cfg["kernel"]
    keys = jax.random.split(key, len(cfg["layers"]) + 1)
    convs, ci = [], cfg["in_ch"]
    for i, layer in enumerate(cfg["layers"]):
        if layer == "M":
            convs.append(None)
            continue
        convs.append(jax.random.normal(keys[i], (k, k, ci, layer), jnp.float32)
                     * (2.0 / (k * k * ci)) ** 0.5)
        ci = layer
    _, h, c = _convs(cfg)
    fan_in = (h // cfg["fc_pool"]) ** 2 * c
    fc = jax.random.normal(keys[-1], (fan_in, cfg["n_classes"]),
                           jnp.float32) * fan_in ** -0.5
    return {"convs": convs, "fc": fc}


def program(cfg: dict):
    """The timed call: (weights, images (B, H, W, C)) -> logits."""
    from repro.configs.base import CNNConfig, SpikingConfig
    from repro.models.cnn import VGG11_LAYERS, vgg11_apply
    # vgg11_apply walks the program's own layer table; the file must state
    # the same network.
    stated = [("maxpool", 0) if x == "M" else ("conv", x)
              for x in cfg["layers"]]
    if stated != [(l.kind, l.out_ch) for l in VGG11_LAYERS] \
            or any(l.kind == "conv" and l.kernel != cfg["kernel"]
                   for l in VGG11_LAYERS):
        raise ValueError("vgg11.json's layers differ from VGG11_LAYERS")
    model = CNNConfig(
        name="vgg11", layers=VGG11_LAYERS, in_ch=cfg["in_ch"], img=cfg["img"],
        n_classes=cfg["n_classes"], fc_pool=cfg["fc_pool"],
        direct_coding_bits=cfg["direct_coding_bits"],
        spiking=SpikingConfig(t_steps=cfg["t_steps"],
                              lif_decay=cfg["lif_decay"],
                              lif_vth=cfg["lif_vth"]))

    def apply(params, x):
        return vgg11_apply(model, params, x)
    return apply


def dense_flops_per_image(cfg: dict) -> int:
    """Dense-equivalent FLOPs of one image at all time steps."""
    convs, h, c = _convs(cfg)
    fc_in = (h // cfg["fc_pool"]) ** 2 * c
    per_step = sum(work.conv_flops(h_, w, ci, co, cfg["kernel"])
                   for h_, w, ci, co in convs)
    per_step += work.linear_flops(1, fc_in, cfg["n_classes"])
    return cfg["t_steps"] * per_step


def lif_calls(cfg: dict, batch: int) -> list:
    """(t, rows, k, maps) of every LIF call of one program call."""
    convs, _, _ = _convs(cfg)
    return [(cfg["t_steps"], batch * h * w, co, True)
            for h, w, _, co in convs]

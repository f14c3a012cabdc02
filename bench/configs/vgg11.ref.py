"""Plain reference of the spiking VGG11 that `vgg11.json` states.

The image is direct-coded once per request: x_max is the largest |x| of
the request's batch, q = clip(round(x / (x_max / 127)), -128, 127), and
q * x_max / 127 drives the first convolution at every time step. Each
3x3 SAME convolution is followed by a LIF fire ("M" is a 2x2 max-pool).
The classifier averages the last spikes over 2x2 windows, flattens them
(H, W, C) and applies one linear layer at every time step; the logits are
the mean over time steps. The LIF is the decay-multiplier form with soft
reset (`refops.lif`).

Nothing here comes from the program under test; the weights are the
benchmark's own (`vgg11.py` makes them from the seed).
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import refops


def forward(cfg: dict, params: dict, x, precision: str, stats: bool = False):
    """x: (R, B, H, W, C) images, R requests of B -> (logits (R, B,
    n_classes), info), as in `spikingformer-4-256.ref.py`."""
    r, b = x.shape[:2]
    t = cfg["t_steps"]
    qmax = 2 ** (cfg["direct_coding_bits"] - 1) - 1
    x = x.astype(jnp.float32)
    x_max = jnp.max(jnp.abs(x), axis=(1, 2, 3, 4), keepdims=True)
    scale = x_max / qmax
    coded = jnp.clip(jnp.round(x / scale), -(qmax + 1), qmax) * scale
    s = jnp.broadcast_to(coded.reshape((1, r * b) + x.shape[2:]),
                         (t, r * b) + x.shape[2:])
    rates, operands = {}, {}
    convs = iter(params["convs"])
    for i, layer in enumerate(cfg["layers"]):
        if layer == "M":
            s = refops.maxpool2(s)
            next(convs)
            continue
        w = next(convs)
        if stats:
            per = s.reshape((t, r, b) + s.shape[2:]).swapaxes(0, 1)
            bits = cfg["direct_coding_bits"] if i == 0 else 1
            operands[f"{len(operands):02d}.conv{i}"] = [
                refops.operand_stats(refops.patches3x3(
                    p.reshape((-1,) + p.shape[2:])), w.shape[-1], bits)
                for p in per]
        s = refops.lif(refops.conv_time(s, w, precision), cfg["lif_decay"],
                       cfg["lif_vth"])
        rates[f"{len(rates):02d}.conv{i}"] = jnp.mean(s)

    p = cfg["fc_pool"]
    _, n, h, w_, c = s.shape
    pooled = s.reshape(t, n, h // p, p, w_ // p, p, c).mean(axis=(3, 5))
    logits = refops.matmul(pooled.reshape(t, n, -1), params["fc"], precision)
    return jnp.mean(logits, axis=0).reshape(r, b, -1), \
        {"rates": rates, "operands": operands}

"""Plain reference of the SpikingFormer that `spikingformer-4-256.json` states.

A spiking patch-splitting stem of four 3x3 convolutions, each followed by
a LIF fire, with 2x2 max-pooling after the second and third (32 -> 8,
64 tokens of width D). Then L encoder blocks on a float32 membrane stream
x: Q, K, V = LIF(x W_q), LIF(x W_k), LIF(x W_v); spike-driven
self-attention per head, attn = Q AND (OR over tokens of K AND V);
x += attn W_o; h = LIF(x); h = LIF(h W_1); x += h W_2. The head averages
LIF(x) over time steps and tokens and applies one linear layer. The LIF
is the decay-multiplier form with soft reset (`refops.lif`).

Nothing here comes from the program under test; the weights are the
benchmark's own (`spikingformer-4-256.py` makes them from the seed).
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import refops


def forward(cfg: dict, params: dict, x, precision: str, stats: bool = False):
    """x: (R, B, H, W, C) images, R requests of B -> (logits (R, B,
    n_classes), info). `info["rates"]` maps every spiking layer, by an
    ordered name, to its firing rate; with `stats`, `info["operands"]` maps
    every event-matmul operand to its non-zeros and occupied tiles, one
    record per request."""
    r, b = x.shape[:2]
    t, dim, heads = cfg["t_steps"], cfg["dim"], cfg["n_heads"]

    def fire(d):
        return refops.lif(d, cfg["lif_decay"], cfg["lif_vth"])
    rates, operands = {}, {}

    def note(name, mat, n_out, bits=1):
        if stats:
            rows = mat.reshape(r, -1, mat.shape[-1])
            operands[f"{len(operands):02d}.{name}"] = [
                refops.operand_stats(rows[i], n_out, bits) for i in range(r)]

    imgs = x.reshape((r * b,) + x.shape[2:]).astype(jnp.float32)
    s = jnp.broadcast_to(imgs[None], (t,) + imgs.shape)
    for i, w in enumerate(params["sps"]):
        if i > 0:       # the stem's first convolution eats the image itself
            note(f"sps{i}", _per_request_patches(s, r), w.shape[-1])
        s = fire(refops.conv_time(s, w, precision))
        if i in cfg["sps_pool_after"]:
            s = refops.maxpool2(s)
        rates[f"{len(rates):02d}.sps{i}"] = jnp.mean(s)

    n_tok = s.shape[2] * s.shape[3]
    xm = s.reshape(t, r * b, n_tok, dim)
    for j, blk in enumerate(params["blocks"]):
        def head_split(a):
            return a.reshape(t, r * b, n_tok, heads, dim // heads)
        q, k, v = (head_split(fire(refops.matmul(xm, blk[n], precision)))
                   for n in ("w_q", "w_k", "w_v"))
        status = jnp.max(k * v, axis=2, keepdims=True)
        attn = (q * status).reshape(t, r * b, n_tok, dim)
        rates[f"{len(rates):02d}.block{j}.attn"] = jnp.mean(attn)
        xm = xm + refops.matmul(attn, blk["w_o"], precision)
        h = fire(xm)
        note(f"block{j}.fc1", _per_request_rows(h, r), blk["w_fc1"].shape[1])
        h = fire(refops.matmul(h, blk["w_fc1"], precision))
        rates[f"{len(rates):02d}.block{j}.ffn"] = jnp.mean(h)
        note(f"block{j}.fc2", _per_request_rows(h, r), blk["w_fc2"].shape[1])
        xm = xm + refops.matmul(h, blk["w_fc2"], precision)

    feats = jnp.mean(fire(xm), axis=(0, 2))
    logits = refops.matmul(feats, params["head"], precision)
    return logits.reshape(r, b, -1), {"rates": rates, "operands": operands}


def _per_request_rows(a, r: int):
    """(T, R*B, ...) -> (R, T*B*..., K): each request's rows in the order
    the program flattens its (T, B, ...) activations."""
    t = a.shape[0]
    a = a.reshape((t, r, -1) + a.shape[2:]).swapaxes(0, 1)
    return a.reshape(r, -1, a.shape[-1])


def _per_request_patches(s, r: int):
    t = s.shape[0]
    per = s.reshape((t, r, -1) + s.shape[2:]).swapaxes(0, 1)
    return jnp.stack([refops.patches3x3(p.reshape((-1,) + p.shape[2:]))
                      for p in per])

"""Step-function factories: train_step / serve_prefill / serve_step.

These close over the config and return pure functions suitable for
jax.jit(in_shardings=..., out_shardings=..., donate_argnums=...) — the
exact functions the dry-run lowers and the real launchers execute.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models import lm
from repro.optim import adamw, grad_compress, schedule as sched


def _under_mesh(fn: Callable, mesh, split_kernels: bool = False) -> Callable:
    """Wrap a step function so kernel dispatch resolves mesh-aware while
    it traces: every registry op inside sees the ambient mesh (per-shard
    capability checks, mesh_aware filtering). Resolution is trace-time,
    so wrapping the function — not the call site — is what guarantees a
    later retrace (new shapes, donated-buffer miss) still resolves under
    the mesh. `split_kernels` (the train step, whose params are placed on
    the mesh): compiled kernels run per data shard (`dispatch.use_mesh`)."""
    if mesh is None:
        return fn
    from repro.kernels import dispatch

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with dispatch.use_mesh(mesh, split_kernels=split_kernels):
            return fn(*args, **kwargs)
    return wrapped


def make_train_step(
    cfg: LMConfig,
    opt_cfg: Optional[adamw.AdamWConfig] = None,
    schedule_fn: Callable = sched.constant,
    spiking: Optional[bool] = None,
    grad_compression: bool = False,
    mesh=None,
) -> Callable:
    """train_step(params, opt_state, [ef_state,] batch) -> (... , metrics).

    Microbatch gradient accumulation (cfg.microbatches) runs as a scan so
    the per-microbatch backward (and its data-parallel collectives) overlap
    the next microbatch's forward in the XLA pipeline — the standard
    compute/comm overlap trick.

    `mesh`: the mesh the step will execute under — spike matmuls (and
    every other registry op) in the model then resolve mesh-aware, so the
    distributed path keeps the event-driven kernels instead of silently
    running dense math.
    """
    if opt_cfg is None:
        opt_cfg = adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    spk = cfg.spiking.enabled if spiking is None else spiking
    m = max(1, cfg.microbatches)

    def loss_of(params, batch):
        return lm.loss_fn(cfg, params, batch, spk)

    def grads_of(params, batch):
        if m == 1:
            return jax.value_and_grad(loss_of)(params, batch)
        micro = jax.tree.map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(loss_of)(params, mb)
            return (loss_acc + loss,
                    jax.tree.map(jnp.add, g_acc, g)), None

        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, g_sum), _ = jax.lax.scan(body, (0.0, zero_g), micro)
        return loss_sum / m, jax.tree.map(lambda g: g / m, g_sum)

    if not grad_compression:
        def train_step(params, opt_state, batch):
            loss, grads = grads_of(params, batch)
            lr_scale = schedule_fn(opt_state.step)
            new_params, new_opt = adamw.update(
                grads, opt_state, params, opt_cfg, lr_scale)
            metrics = {"loss": loss,
                       "grad_norm": adamw.global_norm(grads)}
            return new_params, new_opt, metrics
        return _under_mesh(train_step, mesh, split_kernels=True)

    def train_step_ef(params, opt_state, ef_state, batch):
        loss, grads = grads_of(params, batch)
        wire, scales, new_ef = grad_compress.compress(grads, ef_state)
        grads = grad_compress.decompress(wire, scales)
        lr_scale = schedule_fn(opt_state.step)
        new_params, new_opt = adamw.update(
            grads, opt_state, params, opt_cfg, lr_scale)
        metrics = {"loss": loss, "grad_norm": adamw.global_norm(grads)}
        return new_params, new_opt, new_ef, metrics
    return _under_mesh(train_step_ef, mesh, split_kernels=True)


def make_prefill(cfg: LMConfig, spiking: bool, mesh=None) -> Callable:
    def serve_prefill(params, batch: Dict[str, Any]):
        return lm.prefill(cfg, params, batch["tokens"], spiking,
                          frontend=batch.get("frontend"))
    return _under_mesh(serve_prefill, mesh)


def make_serve_step(cfg: LMConfig, spiking: bool, mesh=None) -> Callable:
    """serve_step(params, state, token (B,), pos) -> (logits, state).

    `pos` is a scalar (aligned stepping: streaming prefill, dry-run
    shapes) or a per-slot (B,) vector — the continuous-batching serve
    loop passes its per-slot position vector so every slot decodes at
    its own position (see lm.decode_step)."""
    def serve_step(params, state, token, pos):
        return lm.decode_step(cfg, params, state, token, pos, spiking)
    return _under_mesh(serve_step, mesh)


def make_prefill_state(cfg: LMConfig, spiking: bool, mesh=None,
                       max_seq: int = 256) -> Callable:
    """prefill_state(params, tokens (B, L), length (B,)) ->
    (last logits (B, vocab), decode state at per-slot pos = length).

    The bucketed masked prefill the serve scheduler admits requests
    with (prefill/decode disaggregation): one jit trace per (B, L)
    bucket, pad steps masked out of every state write. `max_seq` sizes
    the dense KV cache (ignored by O(d) spiking state)."""
    def prefill_state(params, tokens, length):
        return lm.prefill_chunked(cfg, params, tokens, length, spiking,
                                  max_seq)
    return _under_mesh(prefill_state, mesh)

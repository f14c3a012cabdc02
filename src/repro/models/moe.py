"""Mixture-of-experts with sort-based token dispatch (EP-shardable).

Dispatch is the MaxText/megablocks-style sort: top-k expert ids per token,
stable-sort token slots by expert, rank-within-expert capacity check, and
scatter into (E, capacity, d) expert batches. Under GSPMD with experts
sharded over the `model` axis and tokens over `data`, the scatter/gather
lower to all-to-all — the canonical EP collective.

Spiking mode: expert inputs are binary spike tensors, the router is an
event-driven FC (one weight-row accumulate per active spike — the EAFC
pattern applied to routing), and expert hidden activations re-binarize
through LIF. Shared experts (qwen2-moe) are fused into one wide always-on
MLP.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.lif import LIFConfig
from .layers import dense_init, lif_fire, mlp_apply, mlp_init

Params = Dict[str, Any]


def moe_init(key, d_model: int, d_ff_expert: int, n_experts: int,
             n_shared: int = 0, dtype=jnp.bfloat16,
             bank_size: int = 0) -> Params:
    """bank_size > n_experts pads the expert BANK with dead experts so the
    expert dim divides the mesh (even EP); the router stays n_experts wide,
    so dead experts never receive tokens."""
    bank = max(n_experts, bank_size)
    ks = jax.random.split(key, 5)

    def expert_bank(k, d_in, d_out):
        kk = jax.random.split(k, bank)
        return jax.vmap(lambda key_: dense_init(key_, d_in, d_out, dtype))(kk)

    p = {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "w_gate": expert_bank(ks[1], d_model, d_ff_expert),
        "w_up": expert_bank(ks[2], d_model, d_ff_expert),
        "w_down": expert_bank(ks[3], d_ff_expert, d_model),
    }
    if n_shared:
        p["shared"] = mlp_init(ks[4], d_model, n_shared * d_ff_expert, dtype)
    return p


def _maybe_constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint when the ambient mesh has the axes; no-op
    on meshless CPU tests."""
    from repro.launch.mesh import current_mesh
    try:
        mesh = current_mesh()
        names = set(getattr(mesh, "axis_names", ()) or ())
        wanted = {a for s_ in spec if s_ is not None
                  for a in ((s_,) if isinstance(s_, str) else s_)}
        if wanted and wanted.issubset(names):
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(*spec))
    except Exception:
        pass
    return x


def moe_apply(
    p: Params, x: jax.Array, *, top_k: int, capacity_factor: float = 1.25,
    normalize_weights: bool = True, spiking: bool = False,
    lif_cfg: LIFConfig | None = None, dispatch_groups: int = 1,
) -> jax.Array:
    """x: (..., N, D) -> (..., N, D). Leading axes (incl. T) are token-flattened.

    dispatch_groups > 1 splits tokens into data-shard-aligned groups
    (leading dim sharded over `data`): the scatter/gather of the sort-based
    dispatch then stays shard-local (a vmapped local scatter) and only the
    grouped expert buffer — the true EP dispatch payload — crosses devices
    as an all-to-all. Without this, GSPMD lowers the global scatter as
    zero-buffer + full all-reduce of (E, C, D) per layer (§Perf cell B).
    """
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    s = xt.shape[0]
    e = p["router"].shape[-1]          # routable experts
    e_bank = p["w_gate"].shape[0]      # possibly padded bank (even EP)
    g = max(1, dispatch_groups)
    if s % g:
        g = 1
    s_loc = s // g

    capacity = int(s_loc * top_k / e * capacity_factor)
    capacity = max(8, -(-capacity // 8) * 8)                # round up to 8

    xg = _maybe_constrain(xt.reshape(g, s_loc, d), "data", None, None)

    def dispatch_one(xl):
        """(s_loc, d) -> ((e_bank, C, d), combine aux) — purely local."""
        logits = (xl.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)          # (s_loc, k)
        if normalize_weights:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        flat_ids = ids.reshape(-1)
        sort_idx = jnp.argsort(flat_ids, stable=True)
        sorted_ids = flat_ids[sort_idx]
        starts = jnp.searchsorted(sorted_ids, jnp.arange(e), side="left")
        rank = jnp.arange(s_loc * top_k) - starts[sorted_ids]
        keep = rank < capacity
        dest = jnp.where(keep, sorted_ids * capacity + rank,
                         e_bank * capacity)
        tok_idx = sort_idx // top_k
        gathered = xl[tok_idx] * keep[:, None].astype(xl.dtype)
        buf = jnp.zeros((e_bank * capacity + 1, d), xl.dtype
                        ).at[dest].set(gathered)
        return (buf[: e_bank * capacity].reshape(e_bank, capacity, d),
                (tok_idx, dest, weights.reshape(-1)[sort_idx], keep))

    expert_in_g, aux = jax.vmap(dispatch_one)(xg)   # (g, e_bank, C, d)
    expert_in_g = _maybe_constrain(expert_in_g, "data", None, None, None)
    # EP regroup: (g, e, C, d) -> (e, g*C, d); data->model all-to-all.
    expert_in = expert_in_g.transpose(1, 0, 2, 3).reshape(
        e_bank, g * capacity, d)
    expert_in = _maybe_constrain(expert_in, "model", None, None)

    # Expert FFN (binary activations in spiking mode -> LIF re-fire).
    h = jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"].astype(xt.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(xt.dtype))
    if spiking:
        h = lif_fire((h + u)[None], lif_cfg)[0]
    else:
        h = jax.nn.silu(h.astype(jnp.float32)).astype(xt.dtype) * u
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(xt.dtype))
    expert_out = _maybe_constrain(expert_out, "model", None, None)

    out_g = expert_out.reshape(e_bank, g, capacity, d).transpose(1, 0, 2, 3)
    out_g = _maybe_constrain(out_g, "data", None, None, None)

    def combine_one(eo, aux_one):
        tok_idx, dest, w_sorted, keep = aux_one
        flat = eo.reshape(e_bank * capacity, d)
        out_sorted = flat[jnp.minimum(dest, e_bank * capacity - 1)]
        out_sorted = out_sorted * keep[:, None].astype(flat.dtype)
        return jnp.zeros((s_loc, d), flat.dtype).at[tok_idx].add(
            out_sorted * w_sorted[:, None].astype(flat.dtype))

    combined = jax.vmap(combine_one)(out_g, aux).reshape(s, d)

    if "shared" in p:
        combined = combined + mlp_apply(
            p["shared"], xt, spiking=spiking, lif_cfg=lif_cfg).reshape(s, d)
    return combined.reshape(orig_shape)


def moe_apply_shard_map(
    p: Params, x: jax.Array, *, top_k: int, capacity_factor: float = 1.25,
    normalize_weights: bool = True, spiking: bool = False,
    lif_cfg: LIFConfig | None = None,
) -> jax.Array:
    """Manual-EP MoE via shard_map — the collective-optimal formulation.

    Layout facts this exploits: activations are batch-sharded over
    (pod, data) and REPLICATED over `model`; expert banks are EP-sharded
    over `model`. So every model shard already holds every token: it can
    locally select the tokens routed to its own experts (no dispatch
    collective at all), run its local expert FFNs, and contribute its
    partial outputs to a single psum over `model` — (s_loc, d) bf16 per
    layer, the information-theoretic minimum for EP combine. GSPMD's
    lowering of the same math scatter/gathers multi-TB zero-buffers
    (§Perf cell B: 409 s -> see EXPERIMENTS.md).
    """
    from repro.launch.mesh import current_mesh
    mesh = current_mesh()
    names = set(getattr(mesh, "axis_names", ()) or ())
    if "model" not in names:
        return moe_apply(p, x, top_k=top_k, capacity_factor=capacity_factor,
                         normalize_weights=normalize_weights,
                         spiking=spiking, lif_cfg=lif_cfg)
    bt_axes = tuple(a for a in ("pod", "data") if a in names)
    e_bank = p["w_gate"].shape[0]
    e = p["router"].shape[-1]
    m = mesh.shape["model"]
    e_loc = e_bank // m
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    s = xt.shape[0]
    n_b = 1
    for a in bt_axes:
        n_b *= mesh.shape[a]
    s_loc = s // n_b
    capacity = int(s_loc * top_k / e * capacity_factor)
    capacity = max(8, -(-capacity // 8) * 8)

    def block(xl, router, wg, wu, wd):
        xl = xl.reshape(-1, d)                       # (s_loc, d) replicated
        j = jax.lax.axis_index("model")
        logits = (xl.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        if normalize_weights:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        flat_ids = ids.reshape(-1)
        sort_idx = jnp.argsort(flat_ids, stable=True)
        sorted_ids = flat_ids[sort_idx]
        starts = jnp.searchsorted(sorted_ids, jnp.arange(e), side="left")
        rank = jnp.arange(s_loc * top_k) - starts[sorted_ids]
        mine = (sorted_ids // e_loc) == j            # my experts only
        keep = (rank < capacity) & mine
        dest = jnp.where(keep, (sorted_ids % e_loc) * capacity + rank,
                         e_loc * capacity)
        tok_idx = sort_idx // top_k
        gathered = xl[tok_idx] * keep[:, None].astype(xl.dtype)
        buf = jnp.zeros((e_loc * capacity + 1, d), xl.dtype
                        ).at[dest].set(gathered)
        expert_in = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
        h = jnp.einsum("ecd,edf->ecf", expert_in, wg.astype(xl.dtype))
        u = jnp.einsum("ecd,edf->ecf", expert_in, wu.astype(xl.dtype))
        if spiking:
            h = lif_fire((h + u)[None], lif_cfg)[0]
        else:
            h = jax.nn.silu(h.astype(jnp.float32)).astype(xl.dtype) * u
        eo = jnp.einsum("ecf,efd->ecd", h, wd.astype(xl.dtype))
        flat = eo.reshape(e_loc * capacity, d)
        out_sorted = flat[jnp.minimum(dest, e_loc * capacity - 1)]
        out_sorted = out_sorted * keep[:, None].astype(flat.dtype)
        w_sorted = weights.reshape(-1)[sort_idx].astype(flat.dtype)
        local = jnp.zeros((s_loc, d), flat.dtype).at[tok_idx].add(
            out_sorted * w_sorted[:, None])
        return jax.lax.psum(local, "model")          # EP combine: (s_loc, d)

    P = jax.sharding.PartitionSpec
    out = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(bt_axes or None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(bt_axes or None, None),
        check_vma=False,
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        out = out + mlp_apply(
            p["shared"], xt, spiking=spiking, lif_cfg=lif_cfg).reshape(s, d)
    return out.reshape(orig_shape)


def aux_load_balance_loss(logits: jax.Array, ids: jax.Array, n_experts: int,
                          top_k: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (used by train loops)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(ids, n_experts).sum(axis=1) / top_k
    ce = jnp.mean(one_hot, axis=0)
    return n_experts * jnp.sum(me * ce)

"""Sharding rules: param-tree paths / state structures -> PartitionSpecs.

Policy (DESIGN.md §5):
  * tensor-parallel over `model`: vocab, d_ff, flattened head dims, experts
    (EP when n_experts divides the axis, else TP inside experts);
  * batch over (`pod`, `data`) — as many of those axes as divide B;
  * FSDP (cfg.fsdp): the non-TP matrix dim of params & optimizer moments is
    additionally sharded over `data` (ZeRO-3 analogue; GSPMD inserts the
    all-gathers);
  * KV caches: kv-heads over `model` when divisible, else sequence over
    `model`; SDSA statuses: heads over `model`;
  * block params carry a leading layer-group axis (scan stacking) — specs
    get a None prefix.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import LMConfig


# ------------------------------------------------------------ mesh helpers
def model_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def batch_axes(mesh: Mesh, b: int, include_model: bool = False
               ) -> Tuple[str, ...]:
    """Largest prefix of ('pod','data'[,'model']) whose product divides b.

    include_model=True is the pure-FSDP regime: no tensor parallelism, the
    whole mesh is data-parallel (small-model training)."""
    names = ("pod", "data", "model") if include_model else ("pod", "data")
    axes = [a for a in names if a in mesh.shape]
    out, prod = [], 1
    for a in axes:
        if b % (prod * mesh.shape[a]) == 0:
            out.append(a)
            prod *= mesh.shape[a]
    return tuple(out)


def _bspec(mesh: Mesh, b: int):
    ax = batch_axes(mesh, b)
    return ax if ax else None


# ------------------------------------------------------------ param specs
_COL_NAMES = {"w_q", "w_k", "w_v", "w_gate", "w_up", "in_proj", "dt_proj",
              "frontend_proj", "w_i", "w_f", "w_z", "lm_head"}
_ROW_NAMES = {"w_o", "w_down", "out_proj", "x_proj", "w_out"}


def tp_axes(cfg: LMConfig, mesh: Mesh):
    """Tensor-parallel mesh axes: ('model',) normally; (data, model) for
    the tp2d serving regime (weights resident, no per-step FSDP gather)."""
    if getattr(cfg, "tp2d", False):
        return tuple(a for a in ("data", "model") if a in mesh.shape)
    return ("model",)


def _param_rule(path: Tuple[str, ...], shape: Tuple[int, ...],
                cfg: LMConfig, mesh: Mesh) -> P:
    tp = tp_axes(cfg, mesh)
    m = int(np.prod([mesh.shape[a] for a in tp]))
    tp_spec = tp if len(tp) > 1 else tp[0]
    fsdp = "data" if (cfg.fsdp and "data" in mesh.shape
                      and not getattr(cfg, "tp2d", False)) else None
    name = path[-1]
    parent = path[-2] if len(path) > 1 else ""
    in_blocks = "blocks" in path

    def wrap(*spec):
        if in_blocks:
            return P(None, *spec)
        return P(*spec)

    core = shape[1:] if in_blocks else shape
    m1 = model_size(mesh)   # single-axis fallback when 2D doesn't divide

    if getattr(cfg, "pure_fsdp", False):
        # ZeRO-3: no TP — shard ONE (largest divisible) dim of every matrix
        # over the full (data x model) mesh purely for storage; GSPMD
        # gathers weights per layer because activations are batch-sharded
        # over the whole mesh.
        axes_all = tuple(a for a in ("data", "model") if a in mesh.shape)
        import numpy as _np
        n_all = int(_np.prod([mesh.shape[a] for a in axes_all]))
        if len(core) >= 2:
            order = sorted(range(len(core)), key=lambda i: -core[i])
            for nshards, ax in ((n_all, axes_all), (m1, "model")):
                for i in order:
                    if core[i] % nshards == 0:
                        return wrap(*[ax if j == i else None
                                      for j in range(len(core))])
        return wrap(*([None] * len(core)))

    def tp_for(dim: int):
        """Largest of (2D tp axes, model-only, nothing) dividing `dim`."""
        if dim % m == 0:
            return tp_spec
        if dim % m1 == 0:
            return "model"
        return None

    if name == "embed":
        v_ax = tp_for(shape[0])
        if v_ax is not None:
            return P(v_ax, fsdp)                     # vocab-sharded table
        return P(None, tp_for(shape[1]) or fsdp)     # odd vocab (whisper)
    if name == "lm_head":
        v_ax = tp_for(shape[1])
        if v_ax is not None:
            return P(fsdp, v_ax)
        return P(tp_for(shape[0]) or fsdp, None)
    if name in ("r_i", "r_f", "r_z", "r_o"):         # tiny per-head recurrences
        return wrap(*([None] * len(core)))
    if len(core) == 3 and name in ("w_gate", "w_up", "w_down"):
        e = core[0]
        e_ax = tp_for(e)
        # (pjit in_shardings require even splits, so uneven expert counts
        # must be padded at the model level — MoESpec.pad_experts_to.)
        if e_ax is not None:                         # expert parallelism
            return wrap(e_ax, fsdp, None) if name != "w_down" \
                else wrap(e_ax, None, fsdp)
        # TP inside experts (mixtral 8e on 16-way model)
        if name == "w_down":
            return wrap(None, tp_for(core[1]), fsdp)
        return wrap(None, fsdp, tp_for(core[2]))
    if name in ("w_i", "w_f") and len(core) == 2 and core[1] <= 128:
        return wrap(None, None)                      # mLSTM gate vectors
    if name in _COL_NAMES and len(core) == 2:
        ax = tp_for(core[1])
        if ax is None:
            return wrap(fsdp, None)
        return wrap(fsdp, ax)
    if name in _ROW_NAMES and len(core) == 2:
        ax = tp_for(core[0])
        if ax is None:
            return wrap(None, fsdp)
        return wrap(ax, fsdp)
    if name == "conv_w":
        return wrap(None, tp_for(core[1]))
    if name == "a_log":
        return wrap(tp_for(core[0]), None)
    if name == "d_skip":
        return wrap(tp_for(core[0]))
    # norms, router, everything else: replicate (tiny)
    return wrap(*([None] * len(core)))


def _path_str(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        else:
            out.append(str(k))
    return tuple(out)


def param_specs(cfg: LMConfig, abstract_params: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree matching the param tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    specs = []
    for path, leaf in flat:
        rule_path = tuple(x for x in _path_str(path) if not x.isdigit())
        spec = _param_rule(
            rule_path if rule_path else ("param",), leaf.shape, cfg, mesh)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ------------------------------------------------------------- batch specs
def batch_specs(cfg: LMConfig, batch: Dict[str, Any], mesh: Mesh) -> Dict:
    out = {}
    include_model = getattr(cfg, "pure_fsdp", False)
    for k, v in batch.items():
        b = v.shape[0]
        bs = batch_axes(mesh, b, include_model=include_model) or None
        out[k] = P(bs, *([None] * (v.ndim - 1)))
    return out


# ------------------------------------------------------------- state specs
def decode_state_specs(cfg: LMConfig, state: Any, mesh: Mesh) -> Any:
    """Specs for the (list of LayerState) decode state, built structurally
    from the LayerState fields (no shape guessing)."""
    from repro.models.lm import LayerState
    m = model_size(mesh)
    tp2d = getattr(cfg, "tp2d", False)
    tp = tp_axes(cfg, mesh)
    m2 = int(np.prod([mesh.shape[a] for a in tp]))

    def kv_cache_spec(x):            # (G, B, S, KV, dh)
        _, b, s_len, kv, _ = x.shape
        if tp2d:
            # weights own the data axis: keep B unsharded, spread the
            # sequence over every TP axis (cache slice stays local)
            if s_len % m2 == 0:
                return P(None, None, tp if len(tp) > 1 else tp[0],
                         None, None)
            return P(None, None, "model" if s_len % m == 0 else None,
                     None, None)
        bs = _bspec(mesh, b)
        if kv % m == 0:
            return P(None, bs, None, "model", None)
        if s_len % m == 0:
            return P(None, bs, "model", None, None)
        return P(None, bs, None, None, None)

    def bs_of(b):
        return None if tp2d else _bspec(mesh, b)

    def status_spec(x):              # (G, B, H, dh)
        _, b, h, _ = x.shape
        return P(None, bs_of(b), "model" if h % m == 0 else None, None)

    def dim2_model_spec(x):          # shard dim 2 over model if divisible
        rest = [None] * (x.ndim - 3)
        d2 = "model" if x.shape[2] % m == 0 else None
        return P(None, bs_of(x.shape[1]), d2, *rest)

    def dim3_model_spec(x):          # shard last dim over model if divisible
        mid = [None] * (x.ndim - 3)
        dl = "model" if x.shape[-1] % m == 0 else None
        return P(None, bs_of(x.shape[1]), *mid, dl)

    def batch_only_spec(x):
        return P(None, bs_of(x.shape[1]), *([None] * (x.ndim - 2)))

    def one(st: Any) -> Any:
        f = {}
        f["kv"] = jax.tree.map(kv_cache_spec, st.kv)
        f["sdsa"] = jax.tree.map(status_spec, st.sdsa)
        f["mamba"] = None
        if st.mamba is not None:
            f["mamba"] = type(st.mamba)(
                h=dim2_model_spec(st.mamba.h),
                conv=dim3_model_spec(st.mamba.conv))
        f["mlstm"] = jax.tree.map(batch_only_spec, st.mlstm)
        f["slstm"] = None
        if st.slstm is not None:
            f["slstm"] = jax.tree.map(dim2_model_spec, st.slstm)
        f["cross_kv"] = jax.tree.map(kv_cache_spec, st.cross_kv)
        f["cross_status"] = jax.tree.map(status_spec, st.cross_status)
        return LayerState(**f)

    return [one(st) for st in state]


# ----------------------------------------------- event ops under the mesh
def event_rows_axes(mesh: Mesh, rows: int) -> Tuple[str, ...]:
    """Mesh axes the event-row axis shards over: the batch-parallel
    ('pod', 'data') prefix that divides the row count. The 'model' axis
    shards features/heads and never event rows."""
    return batch_axes(mesh, rows)


def per_shard_occupied_tiles(s, n_shards: int, block_m: int = 128,
                             block_k: int = 128, *,
                             packed_k: int | None = None) -> list:
    """Occupied-tile count each row shard of `s` owns — the event-load
    signal `runtime.straggler.occupancy_imbalance` summarizes.

    Splits the SPIKE rows (flattened lead axes, contiguous chunks — what
    shard_map actually hands each shard) and runs every shard's own
    padded occupancy pre-pass, exactly what that shard would compute
    locally. Splitting the global occupancy map's tile rows instead would
    misattribute load whenever per-shard rows are not a block_m multiple
    (e.g. 512 rows over 8 shards: 4 tile rows split 8 ways reports half
    the shards empty when all carry equal load).

    `packed_k` marks `s` as uint32 spike words (trailing axis = words):
    per-shard counts come from word popcounts (`packed_tile_occupancy`),
    identical to the dense counts — no unpack."""
    import jax.numpy as jnp
    from repro.kernels import ops
    s2 = np.asarray(s).reshape(-1, s.shape[-1])
    if packed_k is not None:
        from repro.core.spikes import packed_tile_occupancy
        out = []
        for chunk in np.array_split(s2, n_shards, axis=0):
            pad = (-chunk.shape[0]) % block_m
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            out.append(int((np.asarray(packed_tile_occupancy(
                jnp.asarray(chunk), block_m, block_k)) > 0).sum()))
        return out
    return [int((np.asarray(ops.padded_occupancy(
                jnp.asarray(chunk), block_m, block_k)) > 0).sum())
            for chunk in np.array_split(s2, n_shards, axis=0)]


def event_op_sharded(mesh: Mesh, op: str, s, w, *, csr_stack=None,
                     occupancy=None, with_report: bool = False,
                     rebalance: bool = True, **kwargs):
    """Route a matmul-form registry op (`spike_matmul` / `apec_matmul`)
    through `shard_map` on `mesh`, with mesh-aware backend resolution.

    The event rows (leading axis of `s`) shard over the batch-parallel
    mesh axes; `w` is replicated. Resolution runs ONCE, outside the body,
    against the per-shard shapes (`dispatch.resolve(..., mesh=)` — the
    `pallas-csr` family holds while each shard's tile grid divides
    cleanly, else it degrades down its declared fallback chain), and the
    body pins the resolved backend so every shard runs the same kernel.
    Differentiable end to end: the pinned backend carries its registered
    VJP, and shard_map transposes the row sharding.

    `s` may be an `core.events.EventTensor` (or `occupancy=` a carried
    map): the sharded path then REUSES the producer's map instead of
    rebuilding local work lists from the resident spikes — a concrete map
    compacts straight into per-shard trimmed work lists
    (`shard_occupancy_to_csr` on the tiny map, no dense pre-pass and no
    gather), and a traced map shards row-contiguously into the body so
    each shard compacts its own slice. When the per-shard tile grid can't
    split the map evenly (ragged rows), the map is dropped with a warning
    and shards re-derive locally — never silently misgated.

    `csr_stack`: optional stacked per-shard `TileCSR`
    (`core.spikes.shard_occupancy_to_csr` + `stack_shard_csrs`) for
    `spike_matmul` on the CSR family — each shard consumes its own
    pre-built work list (leading shard axis sharded like the rows), so
    the trimmed eager grid survives sharding without gathering any
    global occupancy map.

    A packed `s` (packed-only `EventTensor`, or raw uint32 words with
    `packed_k=` in kwargs) shards its WORDS over the same row axes — the
    per-shard work lists from `shard_occupancy_to_csr` feed the
    packed-csr kernels directly, because the carried (128, 128) map's
    k-tiling coincides with the word tiling (ceil(ceil(K/32)/4) ==
    ceil(K/128)) and the 128-row shard-tile gate counts logical rows
    either way. Resolution routes by payload: packed shards land on the
    `packed-csr` family or degrade through the explicit unpack shim.

    `rebalance` (default on): when a CONCRETE carried map feeds the
    per-shard work lists and the payload is a plain (rows, K) matrix,
    split points are occupancy-weighted instead of row-contiguous
    (`core.spikes.rebalance_shard_plan` — greedy heaviest-row-first plus
    a stolen-tile swap tail): the payload's 128-row tile rows permute so
    every shard still owns one contiguous equal slice, outputs permute
    back, numerics are unchanged, and the most-occupied shard — the one
    a synchronous collective waits for — carries as close to the mean
    occupied-tile count as whole tile rows allow. Never gathers global
    occupancy (the plan reads only the tiny carried map); static maps /
    traced maps / explicit `csr_stack=` are untouched.

    `with_report=True` additionally returns the routing/straggler report:
    resolved backend + attribution, occupancy provenance
    (``occupancy_source``: carried / csr_stack / rederived), and (for
    concrete `s`) the per-shard occupied-tile `OccupancyImbalance`.
    """
    from repro.core.events import EventTensor
    from repro.core.spikes import (TileCSR, rebalance_shard_plan,
                                   shard_occupancy_to_csr,
                                   stack_shard_csrs)
    from repro.kernels import dispatch, ops

    if isinstance(s, EventTensor):
        if occupancy is None:
            occupancy = s.occupancy_for(128, 128)
        if s.is_packed:
            kwargs = dict(kwargs)
            kwargs["packed_k"] = s.feature_size
            s = s.packed
        else:
            s = s.spikes
    packed_k = kwargs.get("packed_k")

    axes = event_rows_axes(mesh, s.shape[0])
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    rows = int(np.prod(s.shape[:-1]))
    plan = None          # set iff occupancy-weighted rebalancing engages

    def _per_shard_routes(attribution):
        """Per-shard hybrid route choices ("event"/"dense") for the report:
        which kernel each shard's local occupied-tile count selects under
        the calibrated predicate — recorded only when this resolution went
        through hybrid routing (the traced cond branches per shard; this
        is the same decision, named per shard for the report)."""
        if "hybrid" not in attribution or n_shards <= 1 \
                or isinstance(s, jax.core.Tracer):
            return ()
        from repro.core import costmodel
        mt_l = -(-(rows // n_shards) // 128)
        kt = -(-int(s.shape[-1]) // 128)
        return tuple(
            "event" if costmodel.event_route_wins(
                op, costmodel.bucket_representative(
                    costmodel.pow2_bucket(c), mt_l * kt), mt_l, kt)
            else "dense"
            for c in per_shard_occupied_tiles(s, n_shards))

    def _report(backend, attribution, occupancy_source):
        if not with_report:
            return None
        from repro.runtime.straggler import occupancy_imbalance
        rep = {"op": op, "backend": backend, "attribution": attribution,
               "n_shards": n_shards, "occupancy": None,
               "occupancy_source": occupancy_source}
        if n_shards > 1 and not isinstance(s, jax.core.Tracer):
            if plan is not None:
                # Rebalanced run: per_shard is the executed (rebalanced)
                # assignment; the static-split counts ride as the pre-
                # rebalance column, straight off the plan.
                rep["occupancy"] = occupancy_imbalance(
                    plan.post_per_shard,
                    routes=_per_shard_routes(attribution),
                    pre_per_shard=plan.pre_per_shard)
            else:
                rep["occupancy"] = occupancy_imbalance(
                    per_shard_occupied_tiles(s, n_shards,
                                             packed_k=packed_k),
                    routes=_per_shard_routes(attribution))
        return rep

    if csr_stack is not None and op != "spike_matmul":
        raise ValueError(
            f"csr_stack is a spike_matmul pass-through; op {op!r} builds "
            f"its own (union) pre-pass in-kernel")
    if n_shards > 1 and occupancy is not None and (
            rows % n_shards or (rows // n_shards) % 128
            or occupancy.shape[0] % n_shards):
        # A carried map only splits into congruent per-shard maps when
        # every shard owns whole 128-row tiles (the same condition the
        # CSR mesh gate checks). Say so — the caller believes the carried
        # route is live. Checked BEFORE resolution: hybrid routing keys
        # off the occupancy kwarg, and resolving on a map that is about
        # to be dropped would pin a route the body can't feed.
        warnings.warn(
            f"exspike sharding: carried occupancy dropped for {op!r} — "
            f"{rows} rows over {n_shards} shards do not split into whole "
            f"128-row tiles; shards re-derive locally",
            RuntimeWarning, stacklevel=2)
        occupancy = None
    # Resolve against the shard count we will actually execute with (the
    # dividing axes), not the mesh's full batch capacity — when the rows
    # don't divide, execution stays unsharded and resolution must match.
    # The carried map joins resolution as the occupancy kwarg: hybrid
    # routing (dispatch.use_hybrid) decides dense-vs-event on it.
    res_kwargs = dict(kwargs)
    if occupancy is not None:
        res_kwargs["occupancy"] = occupancy
    be, attribution = dispatch.resolve_with_attribution(
        op, s, w, mesh=n_shards, **res_kwargs)
    if n_shards <= 1:
        if occupancy is not None:
            out = be.fn(s, w, occupancy=occupancy, **kwargs)
            src = "carried"
        else:
            out = be.fn(s, w, **kwargs)
            src = "csr_stack" if csr_stack is not None else "rederived"
        return (out, _report(be.name, attribution, src)) if with_report \
            else out

    lead = tuple(axes) if len(axes) > 1 else axes[0]
    row_spec = P(lead, *([None] * (s.ndim - 1)))
    w_spec = P(*([None] * w.ndim))

    # Which CSR family the resolved backend must belong to for pre-built
    # work lists to feed it (word tiling == dense tiling, so the SAME
    # `shard_occupancy_to_csr` compaction serves both payloads).
    csr_family = "packed-csr" if packed_k is not None else "pallas-csr"
    if occupancy is not None and csr_stack is None \
            and op == "spike_matmul" and be.name.startswith(csr_family) \
            and not isinstance(occupancy, jax.core.Tracer):
        # Concrete carried map -> per-shard TRIMMED work lists, built from
        # the tiny map alone (the whole point: no dense pre-pass, no
        # gather, and the producer's emission is what feeds the mesh).
        # With `rebalance`, the split points are occupancy-weighted
        # (`rebalance_shard_plan` on the same tiny map): the payload's
        # 128-row tile rows are permuted so each shard still owns one
        # contiguous equal slice, and the output is permuted back below —
        # numerics are identical, only who computes which rows moves.
        if rebalance and s.ndim == 2:
            plan = rebalance_shard_plan(occupancy, n_shards)
            if plan.identity or not plan.improves:
                plan = None      # nothing to win — skip the row gathers
        csr_stack = stack_shard_csrs(shard_occupancy_to_csr(
            occupancy, n_shards, tiling=(128, 128), plan=plan))
        occupancy = None
        occupancy_source = "carried"
    elif csr_stack is not None:
        occupancy_source = "csr_stack"
    elif occupancy is not None:
        occupancy_source = "carried"
    else:
        occupancy_source = "rederived"

    if csr_stack is not None and not be.name.startswith(csr_family):
        # Degraded off the CSR family (mesh gate / capability): the
        # pre-built work lists can't feed the resolved kernel. Say so —
        # the caller paid for the eager pre-pass and would otherwise
        # believe the trimmed grids are running.
        warnings.warn(
            f"exspike sharding: csr_stack ignored — {op!r} resolved to "
            f"{be.name!r} ({attribution}), not the CSR family",
            RuntimeWarning, stacklevel=2)
        csr_stack = None
        plan = None      # rebalanced lists died with the stack
        # A carried map passed alongside the stack still feeds the
        # sharded occupancy-operand path below — attribute it honestly.
        occupancy_source = "carried" if occupancy is not None \
            else "rederived"
    if csr_stack is not None:
        csr_arrays = tuple(csr_stack[:5])   # row_ptr/tile_m/tile_k/occ/valid
        csr_specs = tuple(P(lead) for _ in csr_arrays)
        pipelined = "-pipe" in be.name

        def body(sl, wl, *carrs):
            local = TileCSR(*[a[0] for a in carrs],
                            csr_stack.tiling, csr_stack.map_shape)
            if packed_k is not None:
                return ops.spike_matmul_packed(sl, wl, packed_k=packed_k,
                                               csr=local,
                                               pipeline=pipelined)
            return ops.spike_matmul_csr(sl, wl, local,
                                        pipeline=pipelined)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(row_spec, w_spec) + csr_specs,
                           out_specs=row_spec, check_vma=False)

        # The raw csr wrapper has no autodiff rule (the registry attaches
        # one per backend); give this pass-through the SAME gradient
        # contract the csr backends declare — the matmul transpose rule
        # on the global operands (packed words get a float0 cotangent;
        # dw replays through the unpacked view).
        bwd_static = {"packed_k": packed_k} if packed_k is not None else {}

        @jax.custom_vjp
        def run(s_, w_):
            return fn(s_, w_, *csr_arrays)

        def run_fwd(s_, w_):
            return fn(s_, w_, *csr_arrays), (s_, w_)

        def run_bwd(res, g):
            return tuple(dispatch._matmul_bwd(res, bwd_static, g))

        run.defvjp(run_fwd, run_bwd)
        if plan is not None:
            # Permute 128-row tile rows so the plan's assignment becomes
            # the contiguous equal split shard_map hands out, run, then
            # permute the output back. Both gathers sit OUTSIDE the
            # custom_vjp boundary: autodiff transposes them as ordinary
            # scatter/gather, and run's matmul-transpose rule sees the
            # permuted operands it actually multiplied. The work-list
            # rows (128 logical rows each) move wholesale, so the
            # per-shard CSR tile indices stay local and trimmed.
            mt_rows = len(plan.perm)
            tile = rows // mt_rows
            perm = jnp.asarray(plan.perm)
            inv = jnp.asarray(plan.inverse())
            k_tail = s.shape[1:]
            s_bal = jnp.take(s.reshape((mt_rows, tile) + k_tail), perm,
                             axis=0).reshape(s.shape)
            out = run(s_bal, w)
            out = jnp.take(out.reshape((mt_rows, tile) + out.shape[1:]),
                           inv, axis=0).reshape(out.shape)
        else:
            out = run(s, w)
    elif occupancy is not None:
        # Carried map, traced (or a non-spike_matmul op): shard the map
        # row-contiguously alongside the spikes — each shard's body
        # consumes its own slice (the CSR family compacts it in-shard;
        # the predicated family gates on it directly). The map rides as
        # a shard_map operand, so no shard re-derives from dense spikes.
        occ_spec = P(lead, None)
        registered = be.name in dispatch.backend_names(op)

        def body(sl, wl, occl):
            if not registered:
                # Synthetic hybrid cond backend (dispatch names it
                # "hybrid[event|dense@bN]" but never registers it): its fn
                # re-derives the bucket threshold from the LOCAL map shape
                # and cond-branches per shard — exactly the per-shard
                # routing the report's occ_routes field records.
                return be.fn(sl, wl, occupancy=occl, **kwargs)
            return dispatch.call_backend(op, be.name, sl, wl,
                                         occupancy=occl, **kwargs)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(row_spec, w_spec, occ_spec),
                           out_specs=row_spec, check_vma=False)
        out = fn(s, w, occupancy)
    else:
        registered = be.name in dispatch.backend_names(op)

        def body(sl, wl):
            if not registered:
                # The unpack shim (packed payload degraded off the
                # packed-csr family) is synthesized, never registered —
                # pin its fn directly.
                return be.fn(sl, wl, **kwargs)
            return dispatch.call_backend(op, be.name, sl, wl, **kwargs)

        fn = jax.shard_map(body, mesh=mesh, in_specs=(row_spec, w_spec),
                           out_specs=row_spec, check_vma=False)
        out = fn(s, w)
    return (out, _report(be.name, attribution, occupancy_source)) \
        if with_report else out


# ---------------------------------------------------------------- helpers
def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def validate_specs(abstract_tree: Any, spec_tree: Any, mesh: Mesh) -> list:
    """Check every sharded dim is splittable (jax pads uneven shards, so
    only dim < n_shards is fatal); returns list of problems."""
    problems = []
    flat_a = jax.tree_util.tree_flatten_with_path(abstract_tree)[0]
    flat_s = jax.tree.leaves(spec_tree,
                             is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_a, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if leaf.shape[dim] < size:
                problems.append(
                    (_path_str(path), leaf.shape, dim, ax, size))
    return problems

"""Backend dispatch registry for the ExSpike hot-path ops.

One event-driven dataflow (LIF -> spike encoding -> APEC -> occupancy-
skipping matmul / SDSA) serves every workload in this repo, but each op
has several numerically-equivalent realizations: a pure-jnp oracle, an
alternative vectorized jnp form, and the Pallas TPU kernels (compiled on
TPU, interpret mode on CPU). This module is the single switchboard:

  op          backends                         notes
  ----------  -------------------------------  ---------------------------
  lif_scan    ref | pallas-interpret | pallas  pallas: fused fwd + reversed-
                                               scan surrogate bwd kernels
  spike_matmul ref | jnp | pallas[-interpret]        pallas-csr: event-
              | pallas-csr[-interpret]              compacted grid (TPU
  apec_matmul ref | jnp | pallas[-interpret]         default; degrades to
              | pallas-csr[-interpret]              pallas, see `fallback`)
  sdsa        ref | jnp | pallas-interpret | pallas   packed paths: mode=or
  causal_sdsa ref | jnp | pallas-interpret | pallas   packed paths: mode=or
  econv       ref | jnp | pallas[-interpret]        jnp = event scatter;
              | pallas-csr[-interpret]              csr = im2col + CSR grid
  tconv       ref | jnp | pallas-interpret | pallas   transposed conv
                                               (decoder upsampling)

Every backend above is *differentiable*: `jax.grad` through `dispatch(...)`
produces the same surrogate-gradient cotangents as the `ref` oracle on any
resolved backend, so training never needs a backend pin. The registration
contract (see `register`) is one of:

  * ``differentiable=True`` — the fn is natively differentiable with
    ref-matching gradients (jnp oracles, custom_vjp'd kernels like the
    fused LIF);
  * ``vjp="ref"`` — the fn is wrapped in a `jax.custom_vjp` whose backward
    replays the ref oracle's VJP on the saved inputs (grad parity by
    construction; used for bit-packed / scatter paths whose natural
    gradients would be zero or tie-broken differently);
  * ``vjp=<callable>`` — an explicit backward rule
    ``(saved_args, kwargs, cotangent) -> grads`` (used for the matmul-form
    ops, where the transpose rule is cheaper than a ref replay).

Selection order per call:
  1. explicit override — `use_backend(...)` context or the
     ``EXSPIKE_BACKEND`` env var (``ref`` for all ops, or a comma list of
     ``op=backend`` entries, e.g. ``EXSPIKE_BACKEND=sdsa=pallas,ref``);
  2. otherwise the highest-priority backend registered for the current
     platform whose capability check (`supports`) passes;
  3. the `ref` oracle as the universal fallback — if an override or a
     chosen kernel can't handle the inputs (shape divisibility, dtype,
     unsupported mode), the call falls back to `ref` with a warning
     instead of erroring.

Resolution happens at trace time (shapes/dtypes are static under jit), so
dispatch adds zero runtime cost to compiled code.

Distributed execution resolves through the SAME registry: under
`resolve(..., mesh=)` or an ambient `use_mesh(...)` context (what
`launch.steps` pushes around sharded step tracing and
`runtime.sharding.event_op_sharded` uses inside shard_map), candidates
are filtered to backends declaring the `mesh_aware` capability and every
capability check runs on the PER-SHARD shapes, so "distributed" can never
silently mean "dense jnp math": the `pallas-csr` family stays selected
while each shard's tile grid divides cleanly and degrades down its
declared fallback chain (with `resolved_backends()` attribution) when it
doesn't.

Registering a new kernel is one `register(...)` call; the parity harness
(`tests/test_dispatch_parity.py`) enumerates every registered
(op x backend) pair against `ref` automatically, and
``benchmarks/run.py --backend`` sweeps it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import warnings
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

ENV_VAR = "EXSPIKE_BACKEND"
REF = "ref"
# Override value selecting density-adaptive hybrid resolution instead of a
# concrete backend: matmul-form calls carrying an occupancy map route
# per call between the predicated-dense and event-compacted kernel
# families on the cost model's calibrated crossover (see use_hybrid).
HYBRID = "hybrid"
# Ops hybrid resolution applies to: matmul-form consumers of a carried
# (MT, KT) occupancy map with a registered dense/event kernel pair.
HYBRID_OPS = ("spike_matmul", "apec_matmul", "econv")
ALL_PLATFORMS = ("cpu", "gpu", "tpu")


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered implementation of an op.

    `supports(*args, **kwargs) -> str | None` returns a reason string when
    the backend CANNOT handle the call (None means supported). `auto`
    backends participate in automatic selection; non-auto ones run only
    under an explicit override (and in the parity harness).
    """
    name: str
    fn: Callable[..., Any]
    platforms: Tuple[str, ...] = ALL_PLATFORMS
    priority: int = 0
    auto: bool = True
    supports: Optional[Callable[..., Optional[str]]] = None
    differentiable: bool = False
    # Name of the backend an explicit override degrades to when THIS
    # backend can't take the inputs (e.g. pallas-csr -> pallas keeps a
    # degraded sweep comparable: still the kernel family, not the ref
    # oracle). None falls straight to ref, the universal fallback.
    fallback: Optional[str] = None
    # Mesh capability: may this backend be picked when resolution runs
    # under a device mesh (`resolve(..., mesh=)` / `use_mesh(...)`, i.e.
    # the op will execute per data shard inside shard_map / sharded jit)?
    #   False     — never (the safe default for new registrations: a
    #               backend must declare shard-locality explicitly);
    #   True      — per-shard execution is safe whenever plain `supports`
    #               passes on the per-shard shapes;
    #   callable  — an extra per-shard gate with the `supports` signature,
    #               run on the per-shard (local) shapes; returns a reason
    #               string when the sharded execution should degrade (the
    #               CSR family uses this to require that each shard's row
    #               count fills whole 128-row tiles, keeping every shard's
    #               compacted tile grid congruent).
    mesh_aware: Union[bool, Callable[..., Optional[str]]] = False
    # Payload capability: which spike-payload representations this
    # backend may be AUTO-selected (or hybrid-routed) for. A call whose
    # spike operand is uint32 words (marked by the static ``packed_k=``
    # kwarg threaded from a packed `EventTensor`) resolves only among
    # backends declaring "packed"; every other call resolves only among
    # backends declaring "dense". When resolution must leave the packed
    # family (degrade chain, no packed backend on this platform), the
    # chosen dense backend is wrapped in an EXPLICIT unpack shim
    # (`_unpack_shim`, warn-once + ``+unpack`` attribution) — a packed
    # payload is never silently reinterpreted or densified. Explicit
    # overrides / `call_backend` bypass the filter: the packed-csr family
    # also accepts dense operands (packs internally), which is how the
    # parity harness covers it with dense example inputs.
    payload: Tuple[str, ...] = ("dense",)
    # A compiled (Mosaic) kernel that GSPMD cannot partition but that can
    # run per data shard, split on `SHARD_BATCH_AXIS` of every array
    # operand: a step traced under `use_mesh(mesh, split_kernels=True)`
    # runs it in a `shard_map` (`_gspmd_shard_wrap`). False leaves the
    # call to the partitioner (jnp code, interpret-mode kernels).
    per_data_shard: bool = False

    def unsupported_reason(self, *args, **kwargs) -> Optional[str]:
        platform = jax.default_backend()
        if platform not in self.platforms:
            return f"platform {platform} not in {self.platforms}"
        if self.supports is not None:
            return self.supports(*args, **kwargs)
        return None

    def mesh_unsupported_reason(self, *args, **kwargs) -> Optional[str]:
        """Like `unsupported_reason`, evaluated on PER-SHARD shapes, with
        the mesh-awareness capability folded in."""
        if self.mesh_aware is False:
            return "backend not declared mesh-aware"
        reason = self.unsupported_reason(*args, **kwargs)
        if reason is not None:
            return reason
        if callable(self.mesh_aware):
            return self.mesh_aware(*args, **kwargs)
        return None


@dataclasses.dataclass
class OpSpec:
    name: str
    make_example: Callable[[jax.Array], Tuple[tuple, dict]]
    backends: Dict[str, Backend] = dataclasses.field(default_factory=dict)


_REGISTRY: Dict[str, OpSpec] = {}
_OVERRIDES: list = []   # stack of {op_or_None: backend_name} dicts


# ----------------------------------------------------------- registration
def register_op(name: str, make_example) -> None:
    if name not in _REGISTRY:
        _REGISTRY[name] = OpSpec(name=name, make_example=make_example)


def _is_arrayish(v) -> bool:
    return isinstance(v, (jax.Array, jax.core.Tracer, np.ndarray))


def _zero_cotangent(x):
    """Symbolic-zero stand-in for a non-differentiated aux operand:
    float0 for integer/bool primals (what custom_vjp requires), zeros
    otherwise."""
    aval = jax.core.get_aval(x)
    if jnp.issubdtype(aval.dtype, jnp.inexact):
        return jnp.zeros(aval.shape, aval.dtype)
    return np.zeros(aval.shape, jax.dtypes.float0)


def _wrap_vjp(op: str, fn, rule):
    """Make `fn` differentiable under a custom backward rule.

    rule="ref": backward replays the ref oracle's VJP on the saved primal
    inputs — gradient parity with ref by construction, at the cost of one
    ref forward inside backward (cheap for the logic-form ops this is used
    on). rule=callable: explicit ``(saved_args, kwargs, g) -> grads``.
    Static kwargs (mode, g, stride) are closed over. Array-valued kwargs
    (the carried `occupancy` map, a `csr` work list) are NON-DIFFERENTIATED
    AUX OPERANDS: they thread through the custom_vjp as primal inputs (a
    tracer must not be closed over) but their cotangent is a symbolic zero
    — occupancy is metadata, gradients flow only through spikes/weights,
    exactly the stop_gradient contract the EventTensor pipeline declares.

    Packed payloads (static ``packed_k`` kwarg, spike operand = uint32
    words): pack is forward-only aux — the backward unpacks the saved
    words and the cotangents flow through the UNPACKED values (ref replay
    on the dense view; explicit rules receive `packed_k` and handle it),
    while the word operand itself gets the float0 cotangent its integer
    dtype mandates.
    """
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        aux_keys = tuple(sorted(
            k for k, v in kwargs.items()
            if any(_is_arrayish(l) for l in jax.tree_util.tree_leaves(v))))
        static = {k: v for k, v in kwargs.items() if k not in aux_keys}
        aux = {k: kwargs[k] for k in aux_keys}

        @jax.custom_vjp
        def inner(aux, *a):
            return fn(*a, **static, **aux)

        def inner_fwd(aux, *a):
            return fn(*a, **static, **aux), (aux, a)

        if rule == "ref":
            def inner_bwd(res, g):
                aux_r, a = res
                ref_fn = _REGISTRY[op].backends[REF].fn
                pk = static.get("packed_k")
                if pk is not None:
                    # Replay ref on the unpacked dense view; the word
                    # operand is non-differentiated (float0 by dtype).
                    from repro.core.spikes import unpack_spikes
                    ref_static = {k: v for k, v in static.items()
                                  if k != "packed_k"}
                    s0 = unpack_spikes(a[0], axis=-1,
                                       dtype=jnp.float32)[..., :pk]
                    _, pull = jax.vjp(
                        lambda *ar: ref_fn(s0, *ar, **ref_static, **aux_r),
                        *a[1:])
                    return (jax.tree.map(_zero_cotangent, aux_r),
                            _zero_cotangent(a[0])) + tuple(pull(g))
                _, pull = jax.vjp(
                    lambda *ar: ref_fn(*ar, **static, **aux_r), *a)
                return (jax.tree.map(_zero_cotangent, aux_r),) \
                    + tuple(pull(g))
        else:
            def inner_bwd(res, g):
                aux_r, a = res
                return (jax.tree.map(_zero_cotangent, aux_r),) \
                    + tuple(rule(a, static, g))

        inner.defvjp(inner_fwd, inner_bwd)
        return inner(aux, *args)
    return wrapper


def _matmul_bwd(res, kwargs, g):
    """Transpose rule for ops whose math is `out = s @ w` with optional
    leading batch axes on s (spike_matmul, apec_matmul): ds = g @ w.T,
    dw = sum over rows of s^T g — the ref oracle's exact cotangents.

    A packed spike operand (static ``packed_k`` present) contributes dw
    through its UNPACKED values and receives the float0 cotangent its
    integer dtype mandates — pack is forward-only aux."""
    s, w = res
    gf = g.astype(jnp.float32)
    pk = kwargs.get("packed_k")
    if pk is not None:
        from repro.core.spikes import unpack_spikes
        sf = unpack_spikes(s, axis=-1, dtype=jnp.float32)[..., :pk]
        dw = jnp.einsum("...mk,...mn->kn", sf, gf).astype(w.dtype)
        return _zero_cotangent(s), dw
    ds = jnp.matmul(gf, w.astype(jnp.float32).T).astype(s.dtype)
    dw = jnp.einsum("...mk,...mn->kn", s.astype(jnp.float32), gf).astype(w.dtype)
    return ds, dw


def register(op: str, name: str, *, platforms=ALL_PLATFORMS, priority=0,
             auto=True, supports=None, differentiable=False, vjp=None,
             fallback=None, mesh_aware=False, payload=("dense",),
             per_data_shard=False):
    """Decorator: register `fn` as backend `name` for `op`.

    Gradient contract: pass ``differentiable=True`` when `jax.grad`
    through `fn` natively matches the ref oracle's (surrogate) gradients,
    or ``vjp="ref"`` / ``vjp=<callable>`` to wrap `fn` in a custom_vjp
    (see `_wrap_vjp`) — wrapped backends are differentiable by definition.
    Declared pairs are grad-parity-tested against ref by
    tests/test_dispatch_parity.py automatically.

    ``fallback``: backend name an explicit override degrades to when this
    backend's capability check fails (chains until a supported backend;
    `ref` remains the terminal fallback). Auto-selection already falls
    through by priority and ignores this.

    ``mesh_aware``: mesh capability (see `Backend.mesh_aware`) — False
    (default) keeps the backend off every sharded path; True admits it
    whenever `supports` passes per shard; a callable is an extra
    per-shard gate run on local shapes.

    ``payload``: payload capability (see `Backend.payload`) — the default
    ``("dense",)`` keeps the backend off packed-payload calls; declare
    ``("packed",)`` for backends consuming uint32 spike words natively.

    ``per_data_shard``: a compiled kernel that runs per data shard under
    a GSPMD mesh (see `Backend.per_data_shard`).
    """
    def deco(fn):
        if op not in _REGISTRY:
            raise KeyError(f"unknown op {op!r}; register_op it first")
        wrapped = _wrap_vjp(op, fn, vjp) if vjp is not None else fn
        _REGISTRY[op].backends[name] = Backend(
            name=name, fn=wrapped, platforms=tuple(platforms),
            priority=priority, auto=auto, supports=supports,
            differentiable=differentiable or vjp is not None,
            fallback=fallback, mesh_aware=mesh_aware,
            payload=tuple(payload), per_data_shard=per_data_shard)
        return fn
    return deco


def op_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def backend_names(op: str) -> Tuple[str, ...]:
    return tuple(_REGISTRY[op].backends)


def get_backend(op: str, name: str) -> Backend:
    try:
        return _REGISTRY[op].backends[name]
    except KeyError:
        raise KeyError(
            f"op {op!r} has no backend {name!r}; "
            f"registered: {backend_names(op)}") from None


def example_inputs(op: str, key: jax.Array) -> Tuple[tuple, dict]:
    """Small CPU-friendly (args, kwargs) for the parity harness."""
    return _REGISTRY[op].make_example(key)


def differentiable_backend_names(op: str) -> Tuple[str, ...]:
    """Backends of `op` declaring the gradient contract (grad-parity set)."""
    return tuple(n for n, b in _REGISTRY[op].backends.items()
                 if b.differentiable)


def packed_kernels_available() -> bool:
    """Whether this platform registers packed-payload matmul kernels.
    Packed emission (`SpikingConfig.packed`) is refused where it is not:
    every consumer would otherwise densify through the unpack shim."""
    platform = jax.default_backend()
    return any("packed" in b.payload and platform in b.platforms
               for b in _REGISTRY["spike_matmul"].backends.values())


# -------------------------------------------------------------- overrides
@functools.lru_cache(maxsize=8)
def _parse_env(value: str) -> Tuple[Tuple[Optional[str], str], ...]:
    """'ref' -> ((None,'ref'),); 'sdsa=pallas,ref' -> per-op + global."""
    out = []
    for part in value.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            op, be = part.split("=", 1)
            out.append((op.strip(), be.strip()))
        else:
            out.append((None, part))
    return tuple(out)


def _override_for(op: str) -> Optional[str]:
    for frame in reversed(_OVERRIDES):
        if op in frame:
            return frame[op]
        if None in frame:
            return frame[None]
    env = os.environ.get(ENV_VAR, "")
    if env:
        glob = None
        for o, be in _parse_env(env):
            if o == op:
                return be
            if o is None:
                glob = be
        return glob
    return None


@contextlib.contextmanager
def use_backend(name: str, op: Optional[str] = None):
    """Force backend `name` for one op (or all ops when op=None)."""
    _OVERRIDES.append({op: name})
    try:
        yield
    finally:
        _OVERRIDES.pop()


@contextlib.contextmanager
def use_hybrid(op: Optional[str] = None):
    """Density-adaptive hybrid resolution (``EXSPIKE_BACKEND=hybrid`` is
    the env-var spelling): while active, matmul-form calls (HYBRID_OPS)
    that carry an occupancy map pick between the predicated-dense and
    event-compacted kernel routes PER CALL, on the cost model's
    calibrated dense/event crossover evaluated at the map's occupied-tile
    count — bucketed into pow2 bands so jit compiles at most
    O(log tiles) routes per map shape. Concrete maps resolve in Python
    (attribution ``<route><-hybrid[b<bucket>]``); traced maps resolve to
    a `lax.cond` on the bucketed count (attribution
    ``hybrid[<event>|<dense>@b<threshold>]``). Calls hybrid cannot route
    (no carried map, op outside HYBRID_OPS, no registered route pair)
    fall through to normal auto selection, tagged ``<-hybrid``."""
    with use_backend(HYBRID, op=op):
        yield


# ------------------------------------------------------------ mesh context
_MESH: list = []   # stack of ambient meshes for trace-time resolution
_SPLIT: list = []  # parallel stack: split per-data-shard kernels?
# Batch axis of the `per_data_shard` kernels' array operands (axis 0 is
# time for lif_scan and causal_sdsa).
SHARD_BATCH_AXIS = 1


@contextlib.contextmanager
def use_mesh(mesh, split_kernels: bool = False):
    """Ambient mesh for resolution: while active, `resolve`/`dispatch`
    treat every call as executing per data shard (capability checks run on
    per-shard shapes, non-mesh-aware backends are skipped). Push it around
    jit tracing of sharded step functions — resolution is trace-time, so
    the context must be live when the jit cache misses, not per step.
    `mesh` may be a jax Mesh/AbstractMesh or a plain int shard count.

    ``split_kernels``: the traced step's operands live on the concrete
    `mesh` (the sharded train step), so `per_data_shard` kernels run in a
    shard_map over its data axes. Off, they run whole: a step on unplaced
    operands (the serve steps) must not be moved onto the mesh."""
    _MESH.append(mesh)
    _SPLIT.append(split_kernels)
    try:
        yield
    finally:
        _MESH.pop()
        _SPLIT.pop()


def ambient_mesh():
    return _MESH[-1] if _MESH else None


def data_shard_count(mesh) -> int:
    """Number of data shards the row axis splits over: the product of the
    batch-parallel ('pod', 'data') mesh axes — the 'model' axis shards
    features/heads, not event rows. Ints pass through; no mesh -> 1."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return max(1, mesh)
    shape = getattr(mesh, "shape", None)
    if hasattr(shape, "get"):        # Mesh / AbstractMesh shape mapping
        n = 1
        for ax in ("pod", "data"):
            n *= int(shape.get(ax, 1))
        return max(1, n)
    return max(1, int(getattr(mesh, "size", 1)))


def _shard_view(args, n_shards: int):
    """Per-shard stand-ins for capability checks: the first positional
    (the event/activation operand — every registered op takes it first)
    has its leading axis divided by the shard count; weights and the rest
    are replicated. Uses ShapeDtypeStructs, which is all `supports` /
    `mesh_aware` gates may inspect (shapes/dtypes/static kwargs only).
    A non-dividing leading axis models GSPMD's padded shards (ceil)."""
    if not args:
        return args
    x = args[0]
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if not shape or dtype is None:
        return args
    lead = -(-int(shape[0]) // n_shards)
    local = jax.ShapeDtypeStruct((lead,) + tuple(shape[1:]), dtype)
    return (local,) + tuple(args[1:])


# -------------------------------------------------------------- resolution
# Degrade/fallback warnings fire once per (op, from-backend, to-backend,
# route) per process: resolution runs at trace time, and a retrace storm
# repeating the same RuntimeWarning hundreds of times buries the one
# occurrence that matters. The `route` component keeps hybrid routing's
# edges distinct — a dense-route degrade and an event-route degrade of
# the same op are different events, and muting the second because the
# first fired would hide that BOTH halves of the hybrid pair moved.
# `reset_fallback_warnings()` re-arms every key, route-qualified or not.
_WARNED: set = set()


def reset_fallback_warnings() -> None:
    _WARNED.clear()


def _warn_once(op: str, src: str, dst: str, msg: str,
               stacklevel: int = 3, route: Optional[str] = None) -> None:
    key = (op, src, dst, route)
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=stacklevel + 1)


# Observers appended by `watch_resolutions`: every resolve records
# {"op", "backend", "attribution"} — how benchmarks and the CI smoke
# assert which route hybrid actually chose, call by call.
_RESOLUTION_WATCHERS: list = []


@contextlib.contextmanager
def watch_resolutions():
    """Context manager yielding a list that receives one
    ``{"op", "backend", "attribution"}`` record per resolution (trace-time
    under jit, so one record per compiled route, per call when eager)."""
    rec: list = []
    _RESOLUTION_WATCHERS.append(rec)
    try:
        yield rec
    finally:
        _RESOLUTION_WATCHERS.remove(rec)


# ------------------------------------------------------------ guard policy
# The event stack rides on trusted metadata: carried occupancy maps gate
# which tiles the CSR kernels visit, and packed uint32 words ARE the
# payload. An under-counting or stale map silently drops spike
# contributions — wrong numerics with no exception. EXSPIKE_GUARD (or the
# `use_guard` context) threads a trust policy through every matmul-form
# dispatch that carries a map:
#
#   off    — (default) trust the metadata, zero added work, attribution
#            strings unchanged;
#   audit  — verify the carried map is a TRUE UPPER BOUND of the payload
#            support before running the backend. Packed payloads: a
#            per-word popcount against the map (~1/32 of the dense
#            bytes). Dense payloads: an exact per-tile any-nonzero check.
#            A concrete violation raises GuardViolationError; a traced
#            one (under jit) NaN-poisons the float outputs — a loud
#            sentinel downstream NaN guards catch (data-dependent raises
#            can't cross the jit boundary, and host callbacks are too
#            expensive for the hot path; traces built under an active
#            `watch_guard_events` additionally record the violation);
#   repair — a violated invariant stops trusting the metadata: the call
#            recomputes on the trusted-payload route (words unpacked, map
#            dropped, ref oracle) with warn-once `<be>+repaired`
#            attribution — never a silent wrong answer.
#
# Upper bound, not equality: propagated maps (conv windows, pooling)
# legitimately over-count, so only "support where the map claims empty"
# is a violation — over-counts are a performance fault, not a
# correctness fault, and never flag. See "Guarded execution" in
# kernels/README.md for the per-op audit-cost contract.
GUARD_ENV_VAR = "EXSPIKE_GUARD"
GUARD_MODES = ("off", "audit", "repair")
# Ops the guard wraps (the matmul-form consumers of a carried map). The
# payload-support audit runs where the first operand IS the matrix the
# map tiles; econv's map covers the im2col patch matrix (different
# rows/K from the raw input), so its audit is the static grid check —
# materializing patches just to audit would cost kh*kw payload reads.
GUARDED_OPS = HYBRID_OPS
_SUPPORT_AUDITED_OPS = ("spike_matmul", "apec_matmul")
_GUARD: list = []            # stack pushed by use_guard()


class GuardViolationError(ValueError):
    """A carried occupancy map failed the upper-bound invariant (payload
    support in a tile the map claims empty) or arrived on the wrong tile
    grid for its payload (stale / wrong tiling)."""


def guard_mode() -> str:
    """Active guard policy: innermost `use_guard` frame, else the
    EXSPIKE_GUARD env var, else "off". Consulted at RESOLUTION time
    (trace time under jit) — like EXSPIKE_BACKEND, flipping it does not
    re-trace already-compiled functions."""
    if _GUARD:
        return _GUARD[-1]
    env = os.environ.get(GUARD_ENV_VAR, "").strip().lower()
    if not env:
        return "off"
    if env not in GUARD_MODES:
        raise ValueError(
            f"{GUARD_ENV_VAR}={env!r}: expected one of {GUARD_MODES}")
    return env


@contextlib.contextmanager
def use_guard(mode: str):
    """Scoped guard policy (see the "guard policy" block above)."""
    if mode not in GUARD_MODES:
        raise ValueError(
            f"guard mode {mode!r}: expected one of {GUARD_MODES}")
    _GUARD.append(mode)
    try:
        yield
    finally:
        _GUARD.pop()


# Observers appended by `watch_guard_events`: one record per detected
# violation — {"op", "backend", "kind", "mode", "action", "attribution",
# "detail"}. Concrete violations append at call time; traced ones append
# at RUN time through `jax.debug.callback` (block on the result before
# asserting on the list).
_GUARD_WATCHERS: list = []


@contextlib.contextmanager
def watch_guard_events():
    rec: list = []
    _GUARD_WATCHERS.append(rec)
    try:
        yield rec
    finally:
        _GUARD_WATCHERS.remove(rec)


def _guard_record(event: dict) -> None:
    for rec in _GUARD_WATCHERS:
        rec.append(dict(event))


def _guard_grid(op: str, args: tuple, packed_k,
                kwargs: dict) -> Optional[Tuple[int, int]]:
    """Expected (MT, KT) 128x128 tile grid of the carried map for this
    payload — the same flattening `ops.padded_occupancy` and the fused
    emission use (rows = prod(leading dims), K = logical features). For
    econv the map tiles the im2col patch matrix, so the grid comes from
    the conv geometry. None: geometry unknown, skip the static check."""
    s = args[0]
    if op == "econv":
        if len(args) < 2 or getattr(s, "ndim", 0) < 4:
            return None
        kh, kw_, ci, _ = (int(d) for d in args[1].shape)
        h, w_ = int(s.shape[-3]), int(s.shape[-2])
        stride = int(kwargs.get("stride", 1))
        padding = kwargs.get("padding", "SAME")
        if padding == "SAME":
            ho, wo = -(-h // stride), -(-w_ // stride)
        elif padding == "VALID":
            ho, wo = (h - kh) // stride + 1, (w_ - kw_) // stride + 1
        else:
            return None
        rows = int(np.prod(s.shape[:-3])) * ho * wo
        k = ci * kh * kw_
    else:
        rows = int(np.prod(s.shape[:-1]))
        k = int(packed_k) if packed_k is not None else int(s.shape[-1])
    return (-(-rows // 128), -(-k // 128))


def _support_violation(s, occupancy, packed_k):
    """Scalar bool: the payload has support in a tile the carried map
    claims empty. Exact, not sampled — detection must be total for the
    guard's contract; the packed form reads ~1/32 of the dense bytes
    (popcount per word), the dense form one comparison pass."""
    mt, kt = (int(d) for d in occupancy.shape)
    empty = occupancy == 0
    if packed_k is not None:
        from repro.core.spikes import PACK, popcount
        words = s.reshape(-1, s.shape[-1])
        r, nw = (int(d) for d in words.shape)
        wpt = 128 // PACK               # uint32 words per 128-col k-tile
        words = jnp.pad(words, ((0, mt * 128 - r), (0, kt * wpt - nw)))
        counts = popcount(words).astype(jnp.int32) \
            .reshape(mt, 128, kt, wpt).sum(axis=(1, 3))
        support = counts > 0
    else:
        x = s.reshape(-1, s.shape[-1])
        r, k = (int(d) for d in x.shape)
        nz = jnp.pad(x != 0, ((0, mt * 128 - r), (0, kt * 128 - k)))
        support = jnp.any(nz.reshape(mt, 128, kt, 128), axis=(1, 3))
    return jnp.any(support & empty)


def _repair_route(op: str, args: tuple, kwargs: dict):
    """The guard's safe route: trust only the payload — unpack words,
    drop the map / work list, run the ref oracle (dense math, the
    gradient oracle — a repaired call keeps the op's grad contract)."""
    kw = {k: v for k, v in kwargs.items()
          if k not in ("occupancy", "packed_k", "csr")}
    s = args[0]
    pk = kwargs.get("packed_k")
    if pk is not None:
        from repro.core.spikes import unpack_spikes
        s = unpack_spikes(s, axis=-1, dtype=jnp.float32)[..., :pk]
    return _REGISTRY[op].backends[REF].fn(s, *args[1:], **kw)


def _guard_shim(be: Backend, op: str, mode: str) -> Backend:
    """Wrap a resolved backend in the active guard policy. The backend
    name/attribution are unchanged (the guard is policy, not routing);
    detections surface through GuardViolationError / `watch_guard_events`
    records / the warn-once `<be>+repaired` repair attribution."""
    inner = be.fn
    repaired = f"{be.name}+repaired"

    @functools.wraps(inner)
    def fn(*args, **kwargs):
        occ = kwargs.get("occupancy")
        pk = kwargs.get("packed_k")
        if occ is None or getattr(occ, "ndim", 0) != 2:
            return inner(*args, **kwargs)
        expected = _guard_grid(op, args, pk, kwargs)
        if expected is not None and tuple(occ.shape) != expected:
            # Shapes are static: this check is free and may raise even
            # under jit.
            detail = (f"carried map grid {tuple(occ.shape)} != expected "
                      f"{expected} for the payload (stale/wrong tiling)")
            if mode == "audit":
                _guard_record({"op": op, "backend": be.name, "kind": "grid",
                               "mode": mode, "action": "raise",
                               "attribution": be.name, "detail": detail})
                raise GuardViolationError(f"guard[{op}/{be.name}]: {detail}")
            _guard_record({"op": op, "backend": be.name, "kind": "grid",
                           "mode": mode, "action": "repair",
                           "attribution": repaired, "detail": detail})
            _warn_once(op, be.name, repaired,
                       f"exspike guard: {detail}; repairing op {op!r} on "
                       f"the trusted-payload route ({repaired!r})",
                       route="guard")
            return _repair_route(op, args, kwargs)
        if op not in _SUPPORT_AUDITED_OPS:
            return inner(*args, **kwargs)
        violated = _support_violation(args[0], occ, pk)
        detail = ("carried map claims empty tiles that hold payload "
                  "support (occupancy undercount / corrupted payload)")
        event = {"op": op, "backend": be.name, "kind": "undercount",
                 "mode": mode, "detail": detail}
        if not isinstance(violated, jax.core.Tracer):
            if not bool(violated):
                return inner(*args, **kwargs)
            if mode == "audit":
                _guard_record({**event, "action": "raise",
                               "attribution": be.name})
                raise GuardViolationError(f"guard[{op}/{be.name}]: {detail}")
            _guard_record({**event, "action": "repair",
                           "attribution": repaired})
            _warn_once(op, be.name, repaired,
                       f"exspike guard: {detail}; repairing op {op!r} on "
                       f"the trusted-payload route ({repaired!r})",
                       route="guard")
            return _repair_route(op, args, kwargs)
        # Traced map/payload: a data-dependent raise can't cross the jit
        # boundary, and a host callback can't ride in the hot path — the
        # mere PRESENCE of the callback effect in the jitted program
        # costs ~700us/call on CPU (measured: it serializes dispatch),
        # voiding the audit-cost contract even when the branch never
        # fires. So the traced path stays effect-free:
        #   audit  — NaN-poison the (float) outputs when violated. The
        #            wrong answer the undercount would cause becomes a
        #            loud sentinel the downstream NaN guards catch (the
        #            serve loop quarantines non-finite logits; loss
        #            checks trip) instead of a plausible wrong number.
        #   repair — lax.cond branches to the trusted-payload route
        #            on-device; the answer is correct either way.
        # The watcher record (attribution for tests/CI) is attached only
        # when `watch_guard_events` is active AT TRACE TIME — a cached
        # trace keeps whatever observability it was built with.
        action = "record" if mode == "audit" else "repair"
        attribution = be.name if mode == "audit" else repaired

        def _on_violation():
            _guard_record({**event, "action": action, "traced": True,
                           "attribution": attribution})
            _warn_once(op, be.name, attribution,
                       f"exspike guard: {detail} (op {op!r}, detected "
                       f"at run time under jit"
                       + ("; repaired on the trusted-payload route"
                          if mode == "repair" else "") + ")",
                       route="guard")
        if _GUARD_WATCHERS:          # trace-time binding, see above
            jax.lax.cond(violated,
                         lambda: jax.debug.callback(_on_violation),
                         lambda: None)
        if mode == "audit":
            out = inner(*args, **kwargs)
            poison = jnp.where(violated, jnp.nan, 1.0)  # *1.0 is exact,
            return jax.tree.map(                        # fuses into the
                lambda x: x * poison.astype(x.dtype)    # matmul epilogue
                if jnp.issubdtype(x.dtype, jnp.inexact) else x, out)
        return jax.lax.cond(
            violated,
            lambda: _repair_route(op, args, kwargs),
            lambda: inner(*args, **kwargs))
    return dataclasses.replace(be, fn=fn)


def _fallback(op: str, wanted: str, reason: str) -> Backend:
    _warn_once(
        op, wanted, REF,
        f"exspike dispatch: backend {wanted!r} for op {op!r} unavailable "
        f"({reason}); falling back to {REF!r}", stacklevel=3)
    return _REGISTRY[op].backends[REF]


def _walk_fallback_chain(op: str, spec: OpSpec, be: Backend,
                         reason: Optional[str],
                         reason_of) -> Tuple[Backend, Optional[str]]:
    """Degrade along the declared fallback chain while `reason_of`
    refuses, warning once per edge. Returns the last backend reached and
    its reason (None iff some link accepted the call)."""
    seen = {be.name}
    while reason is not None and be.fallback is not None \
            and be.fallback not in seen:
        nxt = spec.backends.get(be.fallback)
        if nxt is None:
            break
        _warn_once(
            op, be.name, nxt.name,
            f"exspike dispatch: backend {be.name!r} for op {op!r} "
            f"unavailable ({reason}); degrading to {nxt.name!r}",
            stacklevel=5)
        seen.add(nxt.name)
        be, reason = nxt, reason_of(nxt)
    return be, reason


# ---------------------------------------------------- hybrid resolution
def _hybrid_route_pair(spec: OpSpec) -> Optional[Tuple[Backend, Backend]]:
    """(event_route, dense_route) for this platform: the highest-priority
    event-compacted (csr-family) backend and its declared dense fallback —
    the same pair the override fallback chain walks, so hybrid's routes
    are exactly the two kernels the BENCH trajectory has been comparing.
    None when either half is missing (hybrid then disengages)."""
    platform = jax.default_backend()

    def _dense_fallback(b):
        # The pair's dense half is the event backend's DECLARED fallback.
        # Pipelined csr variants declare their *serial* csr kernel as
        # fallback (degrade stays inside the event family), so they are
        # structurally not pair candidates — the documented contract is
        # "carries csr in its name, declares a dense fallback".
        fb = spec.backends.get(b.fallback) if b.fallback else None
        return fb is not None and "csr" not in fb.name

    event = max(
        (b for b in spec.backends.values()
         if "csr" in b.name and platform in b.platforms
         and "dense" in b.payload    # hybrid routes dense payloads only
         and _dense_fallback(b)),
        key=lambda b: b.priority, default=None)
    if event is None:
        return None
    dense = spec.backends.get(event.fallback)
    if dense is None or platform not in dense.platforms:
        return None
    return event, dense


def _hybrid_cond_fn(op: str, event_be: Backend, dense_be: Backend,
                    threshold: int):
    """Traced-occupancy hybrid body: branch between the two routes with
    `lax.cond` on the pow2-bucketed occupied-tile count. The bucket
    threshold is re-derived from the occupancy actually received (static
    shape at trace time), so inside shard_map each shard branches on ITS
    OWN local map — per-shard routing can differ, by design. Both routes
    are custom_vjp-wrapped already, so the cond stays differentiable."""
    del threshold   # attribution-time value; the fn recomputes per shape

    def fn(*args, occupancy=None, **kw):
        from repro.core import costmodel
        mt, kt = occupancy.shape
        thresh = costmodel.hybrid_event_bucket_threshold(op, mt, kt)
        n_buckets = costmodel.num_buckets(mt * kt)
        if thresh < 0:
            return dense_be.fn(*args, occupancy=occupancy, **kw)
        if thresh >= n_buckets - 1:
            return event_be.fn(*args, occupancy=occupancy, **kw)
        count = jnp.sum((occupancy > 0).astype(jnp.int32))
        bucket = costmodel.pow2_bucket_traced(count, (mt * kt).bit_length())
        return jax.lax.cond(
            bucket <= thresh,
            lambda: event_be.fn(*args, occupancy=occupancy, **kw),
            lambda: dense_be.fn(*args, occupancy=occupancy, **kw))
    return fn


def _hybrid_resolution(spec: OpSpec, op: str, kwargs, reason_of,
                       n_shards: int) -> Optional[Tuple[Backend, str]]:
    """Resolve under the HYBRID override. Returns (backend, attribution)
    or None to disengage (no carried map / no route pair / op outside
    HYBRID_OPS) — the caller then falls through to auto selection."""
    occ = kwargs.get("occupancy")
    if op not in HYBRID_OPS or occ is None or getattr(occ, "ndim", 0) != 2:
        return None
    if kwargs.get("packed_k") is not None:
        # Packed payloads route by the `payload` capability, not by
        # density: the packed-csr family's bytes-moved advantage holds at
        # every occupancy, so hybrid disengages (auto selection, tagged).
        return None
    pair = _hybrid_route_pair(spec)
    if pair is None:
        return None
    event_be, dense_be = pair
    event_reason = reason_of(event_be)
    dense_reason = reason_of(dense_be)
    if event_reason is not None and dense_reason is not None:
        return None          # both routes refuse: normal chain takes over
    if event_reason is not None:
        _warn_once(op, event_be.name, dense_be.name,
                   f"exspike dispatch: hybrid event route {event_be.name!r} "
                   f"for op {op!r} unavailable ({event_reason}); pinning "
                   f"dense route {dense_be.name!r}",
                   stacklevel=5, route="event")
        return dense_be, f"{dense_be.name}<-{HYBRID}"
    if dense_reason is not None:
        _warn_once(op, dense_be.name, event_be.name,
                   f"exspike dispatch: hybrid dense route {dense_be.name!r} "
                   f"for op {op!r} unavailable ({dense_reason}); pinning "
                   f"event route {event_be.name!r}",
                   stacklevel=5, route="dense")
        return event_be, f"{event_be.name}<-{HYBRID}"
    from repro.core import costmodel
    mt, kt = occ.shape
    mt_local = mt // n_shards if n_shards > 1 and mt % n_shards == 0 else mt
    if not isinstance(occ, jax.core.Tracer):
        # Concrete map (eager pre-pass): pick in Python on the band's
        # representative count — same decision jit would bake in, zero
        # runtime cost, and the bucket lands in the attribution.
        count = int(np.count_nonzero(np.asarray(occ) > 0))
        bucket = costmodel.pow2_bucket(-(-count // n_shards)
                                       if n_shards > 1 else count)
        rep = costmodel.bucket_representative(bucket, mt_local * kt)
        event = costmodel.event_route_wins(op, rep, mt_local, kt)
        be = event_be if event else dense_be
        return be, f"{be.name}<-{HYBRID}[b{bucket}]"
    threshold = costmodel.hybrid_event_bucket_threshold(op, mt_local, kt)
    cond = Backend(
        name=f"{HYBRID}[{event_be.name}|{dense_be.name}@b{threshold}]",
        fn=_hybrid_cond_fn(op, event_be, dense_be, threshold),
        platforms=event_be.platforms, priority=0, auto=False,
        differentiable=event_be.differentiable and dense_be.differentiable,
        mesh_aware=event_be.mesh_aware)
    return cond, cond.name


def resolve_with_attribution(op: str, *args, mesh=None,
                             **kwargs) -> Tuple[Backend, str]:
    """Pick the backend `dispatch` would run, plus an attribution string:
    the backend name, suffixed ``<-requested`` when resolution degraded
    from a higher-preference backend (override fallback chain or a
    mesh/capability gate) — `resolved_backends()` surfaces this so sweeps
    and serve logs show what *actually* ran and why it moved. Under
    `use_hybrid` the attribution carries the chosen route and its
    occupancy bucket (see `use_hybrid` for the formats). `resolve` /
    `resolve_attribution` are the single-value projections."""
    be, attribution = _resolve_impl(op, *args, mesh=mesh, **kwargs)
    for rec in _RESOLUTION_WATCHERS:
        rec.append({"op": op, "backend": be.name,
                    "attribution": attribution})
    return be, attribution


def _unpack_shim(be: Backend, packed_k: int) -> Backend:
    """Wrap a dense-payload backend so a packed call can reach it
    EXPLICITLY: the uint32 words are unpacked to the logical dense spikes
    at entry (f32 — the consumers' compute dtype) and the ``packed_k``
    marker is consumed. The ``+unpack`` attribution suffix plus the
    warn-once at the wrap site keep the densify visible — a packed
    payload never silently reinterprets as dense math."""
    from repro.core.spikes import unpack_spikes

    @functools.wraps(be.fn)
    def fn(s, *rest, packed_k=None, **kw):
        dense = unpack_spikes(s, axis=-1, dtype=jnp.float32)
        return be.fn(dense[..., :packed_k], *rest, **kw)
    return dataclasses.replace(be, fn=fn, name=f"{be.name}+unpack")


def _gspmd_shard_wrap(be: Backend, args) -> Backend:
    """Run a compiled kernel per data shard inside a step traced under
    `use_mesh(mesh, split_kernels=True)`: Mosaic kernels cannot be
    partitioned automatically, so the call goes into a `shard_map` over
    the mesh's batch axes, splitting `SHARD_BATCH_AXIS` of every
    positional (array) operand and of the output; keyword arguments are
    static. A batch the shard count does not divide runs whole on every
    device (replicated specs). No-op unless split_kernels is asked for
    under a concrete Mesh with data shards, and inside a shard_map that
    already made those axes manual."""
    mesh = ambient_mesh()
    n_shards = data_shard_count(mesh)
    if not (_SPLIT and _SPLIT[-1]) \
            or not isinstance(mesh, jax.sharding.Mesh) or n_shards < 2:
        return be
    axes = tuple(a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1)
    if set(axes) & set(jax.sharding.get_abstract_mesh().manual_axes):
        return be
    if all(a.shape[SHARD_BATCH_AXIS] % n_shards == 0 for a in args):
        spec = jax.sharding.PartitionSpec(
            *([None] * SHARD_BATCH_AXIS), axes)
    else:
        spec = jax.sharding.PartitionSpec()
    inner = be.fn

    def fn(*a, **kw):
        return jax.shard_map(lambda *x: inner(*x, **kw), mesh=mesh,
                             in_specs=(spec,) * len(a), out_specs=spec,
                             check_vma=False)(*a)
    return dataclasses.replace(be, fn=fn)


def _resolve_impl(op: str, *args, mesh=None,
                  **kwargs) -> Tuple[Backend, str]:
    be, attribution = _resolve_payload_blind(op, *args, mesh=mesh, **kwargs)
    packed_k = kwargs.get("packed_k")
    if packed_k is not None and "packed" not in be.payload:
        _warn_once(
            op, "packed", be.name,
            f"exspike dispatch: packed payload for op {op!r} leaving the "
            f"packed-csr family; unpacking to dense for {be.name!r} "
            f"(explicit unpack shim)", stacklevel=5, route="payload")
        shim = _unpack_shim(be, packed_k)
        attribution = shim.name + attribution[len(be.name):]
        be = shim
    if be.per_data_shard:
        be = _gspmd_shard_wrap(be, args)
    # Guard policy (audit/repair) wraps OUTERMOST so the audit sees the
    # payload exactly as carried (packed words before any unpack shim).
    # Off (the default) adds nothing — attributions stay byte-identical.
    mode = guard_mode()
    if mode != "off" and op in GUARDED_OPS \
            and kwargs.get("occupancy") is not None:
        be = _guard_shim(be, op, mode)
    return be, attribution


def _resolve_payload_blind(op: str, *args, mesh=None,
                           **kwargs) -> Tuple[Backend, str]:
    spec = _REGISTRY[op]
    mesh = mesh if mesh is not None else ambient_mesh()
    n_shards = data_shard_count(mesh)
    if n_shards > 1:
        check_args = _shard_view(args, n_shards)

        def reason_of(be: Backend) -> Optional[str]:
            return be.mesh_unsupported_reason(*check_args, **kwargs)
    else:
        def reason_of(be: Backend) -> Optional[str]:
            return be.unsupported_reason(*args, **kwargs)

    def attributed(be: Backend, requested: Optional[str]) -> Tuple[Backend, str]:
        if requested is None or requested == be.name:
            if hybrid_requested:
                # hybrid disengaged (no carried map / no route pair):
                # normal selection ran, but the tag keeps visible that
                # hybrid was asked for and stepped aside.
                return be, f"{be.name}<-{HYBRID}"
            return be, be.name
        return be, f"{be.name}<-{requested}"

    override = _override_for(op)
    # Hybrid only means anything for the matmul-form ops with a dense/
    # event pair; on every other op a blanket use_hybrid() is a plain
    # no-op (auto selection, untagged) — not a disengage.
    hybrid_requested = override == HYBRID and op in HYBRID_OPS
    if override == HYBRID and not hybrid_requested:
        override = None
    if hybrid_requested:
        routed = _hybrid_resolution(spec, op, kwargs, reason_of, n_shards)
        if routed is not None:
            return routed
        override = None      # disengage -> auto selection, tagged above
    if override is not None:
        be = spec.backends.get(override)
        if be is None:
            return attributed(_fallback(op, override, "not registered"),
                              override)
        reason = reason_of(be)
        # Walk the declared fallback chain (packed-csr -> pallas-csr ->
        # pallas -> ...) before surrendering to ref, so a constraint
        # failure degrades to the nearest comparable kernel, not all the
        # way to the oracle.
        be, reason = _walk_fallback_chain(op, spec, be, reason, reason_of)
        if reason is not None:
            return attributed(_fallback(op, be.name, reason), override)
        return attributed(be, override)
    platform = jax.default_backend()
    # Payload filtering is silent, like platform filtering: a dense call
    # never auto-selects a packed-only backend and vice versa (the shim
    # wrap in `_resolve_impl` covers a packed call that finds no packed
    # candidate at all — including the terminal ref fallback).
    want_payload = "packed" if kwargs.get("packed_k") is not None else "dense"
    candidates = sorted(
        (b for b in spec.backends.values()
         if b.auto and platform in b.platforms
         and (want_payload in b.payload or b.name == REF)),
        key=lambda b: -b.priority)
    cap_failure = None
    for be in candidates:
        if be.name == REF:
            break
        reason = reason_of(be)
        if reason is None:
            return attributed(be, cap_failure[0] if cap_failure else None)
        if cap_failure is None:
            cap_failure = (be.name, reason)
    if cap_failure is not None:
        if want_payload == "packed":
            # No other packed candidate: degrade along the refused
            # backend's DECLARED chain (packed-csr -> pallas-csr) so the
            # call stays on the nearest comparable kernel — the caller's
            # shim wrap makes the densify explicit.
            be, reason = _walk_fallback_chain(
                op, spec, spec.backends[cap_failure[0]], cap_failure[1],
                reason_of)
            if reason is None:
                return attributed(be, cap_failure[0])
        # A capability failure (shape/dtype/mode/mesh gate) silently
        # degrading to the oracle would hide lost compression/kernel
        # coverage — warn. (Platform filtering stays silent.)
        return attributed(_fallback(op, *cap_failure), cap_failure[0])
    return attributed(spec.backends[REF], None)


def resolve(op: str, *args, mesh=None, **kwargs) -> Backend:
    """Pick the backend that `dispatch` would run for these inputs.

    `mesh`: resolve as if executing per data shard of that mesh (or the
    ambient `use_mesh` one) — mesh-aware filtering + per-shard capability
    checks. None with no ambient mesh is the plain single-device path.
    """
    return resolve_with_attribution(op, *args, mesh=mesh, **kwargs)[0]


def resolve_name(op: str, *args, mesh=None, **kwargs) -> str:
    return resolve(op, *args, mesh=mesh, **kwargs).name


def resolve_attribution(op: str, *args, mesh=None, **kwargs) -> str:
    """Attribution string for this resolution: ``name`` normally,
    ``name<-requested`` when a fallback chain / mesh gate degraded it."""
    return resolve_with_attribution(op, *args, mesh=mesh, **kwargs)[1]


def dispatch(op: str, *args, mesh=None, **kwargs):
    """Run `op` on the resolved backend (`mesh` steers resolution only —
    it is never forwarded to the backend fn), under a `named_scope` named
    after the op, so that a profile attributes its device time to it."""
    with jax.named_scope(op):
        return resolve(op, *args, mesh=mesh, **kwargs).fn(*args, **kwargs)


def call_backend(op: str, name: str, *args, **kwargs):
    """Run a specific backend, erroring (not falling back) if unsupported.

    The parity harness uses this so an unsupported pair is an explicit
    skip, never a silent ref-vs-ref comparison.
    """
    be = get_backend(op, name)
    if be.supports is not None:
        reason = be.supports(*args, **kwargs)
        if reason is not None:
            raise ValueError(f"{op}/{name} unsupported: {reason}")
    return be.fn(*args, **kwargs)


def resolved_backends(mesh=None) -> Dict[str, str]:
    """op -> backend that would run on this platform/override for each
    op's canonical example shapes (serve startup log). With `mesh` (or an
    ambient `use_mesh`), resolution is mesh-aware and values carry degrade
    attribution: ``name`` when the preferred backend held,
    ``name<-requested`` when a fallback chain or per-shard gate moved it.
    """
    out = {}
    # This is a read-only snapshot: suppress the degrade warnings AND
    # restore the warn-once ledger afterwards, so a startup log call
    # doesn't consume an (op, from, to) edge and mute the one warning a
    # later real-model degrade on that same edge would have fired.
    saved_warned = set(_WARNED)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for op in op_names():
                ex_args, ex_kwargs = example_inputs(op, jax.random.PRNGKey(0))
                out[op] = resolve_attribution(op, *ex_args, mesh=mesh,
                                              **ex_kwargs)
    finally:
        _WARNED.clear()
        _WARNED.update(saved_warned)
    return out


def table() -> str:
    """Human-readable registry dump with the grad-capability column
    (debugging / REPL aid; printed by the CI `dispatch table` check)."""
    lines = []
    for op, spec in _REGISTRY.items():
        bes = ", ".join(
            f"{b.name}(p{b.priority}{'' if b.auto else ',manual'}"
            f"{',grad' if b.differentiable else ''}"
            f"{',mesh' if b.mesh_aware is not False else ''}"
            f"{',packed' if 'packed' in b.payload else ''})"
            for b in sorted(spec.backends.values(), key=lambda b: -b.priority))
        lines.append(f"{op:14s} -> {bes}")
        pair = _hybrid_route_pair(spec) if op in HYBRID_OPS else None
        if pair is not None:
            from repro.core import costmodel
            r, h = costmodel.calibrated_route_params(op)
            lines.append(
                f"{'':14s}    hybrid: event={pair[0].name} | "
                f"dense={pair[1].name} (calibrated r={r:.2f}, h={h:.2f})")
    return "\n".join(lines)


# ======================================================================
# Op definitions + backend implementations
# ======================================================================
def _csr_shard_gate(s, *rest, block_m: int = 128, **kwargs) -> Optional[str]:
    """Per-shard gate for the `pallas-csr` family (`Backend.mesh_aware`):
    the compacted grid is worth building per shard only when the shard's
    flattened row count fills whole `block_m`-row tiles — then every
    shard's tile grid is congruent (one compiled grid shape serves all
    shards) and no shard pays a ragged padding tile per step. Called on
    the per-shard local view; rows = prod(shape[:-1]) matches how the ops
    wrappers flatten leading axes into the row axis (for strided econv
    the output-row count shrinks, which only makes the gate conservative).
    """
    del kwargs
    rows = int(np.prod(s.shape[:-1]))
    if rows % block_m:
        return (f"per-shard rows {rows} do not fill {block_m}-row tiles "
                f"(ragged per-shard tile grid)")
    return None


# ------------------------------------------------------------- lif_scan
def _lif_example(key):
    x = jax.random.normal(key, (4, 3, 40)) * 2.0
    return (x,), {"decay": 0.5, "v_th": 1.0, "soft_reset": True}


register_op("lif_scan", _lif_example)


@register("lif_scan", REF, priority=0, differentiable=True, mesh_aware=True)
def _lif_ref(x, *, decay=0.5, v_th=1.0, soft_reset=True,
             surrogate_alpha=2.0):
    from repro.core.lif import LIFConfig, lif_scan
    cfg = LIFConfig(decay=decay, v_th=v_th, soft_reset=soft_reset,
                    surrogate_alpha=surrogate_alpha)
    return lif_scan(x.astype(jnp.float32), cfg).astype(x.dtype)


def _lif_pallas(x, *, decay=0.5, v_th=1.0, soft_reset=True,
                surrogate_alpha=2.0):
    # Fused kernel pair: forward-exact vs ref, and `jax.grad` runs the
    # reversed-scan Pallas kernel with the ATan surrogate (kernels/lif_scan
    # custom_vjp) — TPU training no longer pins lif_scan=ref.
    from repro.kernels import ops
    return ops.lif(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                   surrogate_alpha=surrogate_alpha)


# NOTE: lif's leading axis is TIME, which no mesh axis shards (batch is
# axis 1) — the scan is elementwise over trailing dims, so the per-shard
# view's divided leading axis is still a valid shape for it.
register("lif_scan", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, differentiable=True, mesh_aware=True)(_lif_pallas)
register("lif_scan", "pallas", platforms=("tpu",), priority=20,
         differentiable=True, mesh_aware=True, per_data_shard=True)(_lif_pallas)


# --------------------------------------------------------- lif_scan_occ
# The full-event producer: fire AND emit the spike tensor's (128, 128)
# per-tile occupancy map (plus its 8-row chunk refinement, which window
# propagation dilates) in the same pass, so downstream event consumers
# never re-derive it from the dense activation. Returns (spikes, map,
# chunks); the maps are non-differentiated aux (int32 — zero-tangent by
# dtype on the jnp paths, cotangent-discarded by the Pallas custom_vjp),
# which is the gradient contract models rely on when they wrap the
# triple in an `EventTensor`.
def _lif_occ_example(key):
    x = jax.random.normal(key, (3, 8, 40)) * 2.0
    return (x,), {"decay": 0.5, "v_th": 1.0, "soft_reset": True}


register_op("lif_scan_occ", _lif_occ_example)


@register("lif_scan_occ", REF, priority=0, differentiable=True,
          mesh_aware=True)
def _lif_occ_ref(x, *, decay=0.5, v_th=1.0, soft_reset=True,
                 surrogate_alpha=2.0, packed=False):
    s = _lif_ref(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                 surrogate_alpha=surrogate_alpha)
    # One chunk-granular pre-pass; the tile map is its 16:1 aggregation
    # (identical to the fused kernel's emission, counts and all).
    chunks = jax.lax.stop_gradient(_ref_chunk_occupancy(s))
    occ = jnp.sum(chunks.reshape(-1, 16, chunks.shape[1]), axis=1)
    if packed:
        # Forward-only packed emission (oracle form: fire dense, then
        # pack — value-identical to the fused kernel's in-VMEM packing).
        from repro.core.spikes import pack_spikes_padded
        return jax.lax.stop_gradient(pack_spikes_padded(s)), occ, chunks
    return s, occ, chunks


def _ref_chunk_occupancy(s):
    from repro.core.spikes import tile_occupancy
    k = s.shape[-1]
    s2 = s.reshape(-1, k)
    s2 = jnp.pad(s2, ((0, (-s2.shape[0]) % 128), (0, (-k) % 128)))
    return tile_occupancy(s2, 8, 128)


def _lif_occ_supports(x, **kwargs) -> Optional[str]:
    del kwargs
    r = int(np.prod(x.shape[1:-1])) if x.ndim > 2 else 1
    if r % 8:
        return (f"fused occupancy emission needs the middle axes to fill "
                f"8-row chunks, got R={r}")
    return None


def _lif_occ_pallas(x, *, decay=0.5, v_th=1.0, soft_reset=True,
                    surrogate_alpha=2.0, packed=False):
    from repro.kernels import ops
    return ops.lif_occ(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                       surrogate_alpha=surrogate_alpha, packed=packed)


register("lif_scan_occ", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, supports=_lif_occ_supports, differentiable=True,
         fallback=REF, mesh_aware=True)(_lif_occ_pallas)
register("lif_scan_occ", "pallas", platforms=("tpu",), priority=20,
         supports=_lif_occ_supports, differentiable=True, fallback=REF,
         mesh_aware=True)(_lif_occ_pallas)


# --------------------------------------------------------- spike_matmul
def _spike_matmul_example(key):
    k1, k2 = jax.random.split(key)
    s = (jax.random.uniform(k1, (2, 48, 96)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(k2, (96, 56), jnp.float32)
    return (s, w), {}


register_op("spike_matmul", _spike_matmul_example)


@register("spike_matmul", REF, priority=0, differentiable=True,
          mesh_aware=True)
def _spike_matmul_ref(s, w, occupancy=None):
    del occupancy    # metadata for the event kernels; the oracle is dense
    return jnp.dot(s, w, preferred_element_type=jnp.float32).astype(w.dtype)


@register("spike_matmul", "jnp", priority=5, auto=False, vjp=_matmul_bwd,
          mesh_aware=True)
def _spike_matmul_jnp(s, w, block_m: int = 8, block_k: int = 32,
                      occupancy=None):
    """Tile-masked jnp emulation of the occupancy-skipping kernel: per-tile
    partial products are gated by the same occupancy map the Pallas kernel
    consumes (numerically identical to dense — empty tiles contribute 0).
    Its (8, 32) emulation tiling never matches the carried (128, 128)
    maps, so a supplied `occupancy` is ignored (manual backend)."""
    del occupancy
    lead = s.shape[:-2]
    m, k = s.shape[-2:]
    s2 = s.reshape((-1, k)).astype(jnp.float32)
    rows = s2.shape[0]
    pad_m, pad_k = (-rows) % block_m, (-k) % block_k
    s2 = jnp.pad(s2, ((0, pad_m), (0, pad_k)))
    w2 = jnp.pad(w.astype(jnp.float32), ((0, pad_k), (0, 0)))
    mt, kt = s2.shape[0] // block_m, s2.shape[1] // block_k
    st = s2.reshape(mt, block_m, kt, block_k)
    wt = w2.reshape(kt, block_k, w.shape[1])
    occ = (jnp.sum(st, axis=(1, 3)) > 0).astype(jnp.float32)  # (mt, kt)
    part = jnp.einsum("aibk,bkn->abin", st, wt)               # per-tile dots
    out = jnp.sum(part * occ[:, :, None, None], axis=1)
    out = out.reshape(mt * block_m, -1)[:rows]
    return out.reshape(lead + (m, w.shape[1])).astype(w.dtype)


def _spike_matmul_pallas(s, w, occupancy=None):
    from repro.kernels import ops
    return ops.spike_matmul(s, w, occupancy=occupancy)


register("spike_matmul", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, vjp=_matmul_bwd, mesh_aware=True)(_spike_matmul_pallas)
register("spike_matmul", "pallas", platforms=("tpu",),
         priority=20, vjp=_matmul_bwd, mesh_aware=True)(_spike_matmul_pallas)


def _spike_matmul_csr(s, w, occupancy=None):
    # Event-compacted grid (scalar-prefetch CSR dispatch): occupied tiles
    # only; see kernels/spike_matmul.py. Wrapper pads arbitrary shapes;
    # a carried `occupancy` replaces the dense pre-pass (the work list
    # compacts from the tiny map).
    from repro.kernels import ops
    return ops.spike_matmul_csr(s, w, occupancy=occupancy)


register("spike_matmul", "pallas-csr-interpret", platforms=("cpu",),
         priority=2, auto=False, fallback="pallas-interpret",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate)(_spike_matmul_csr)
register("spike_matmul", "pallas-csr", platforms=("tpu",), priority=25,
         fallback="pallas", vjp=_matmul_bwd,
         mesh_aware=_csr_shard_gate)(_spike_matmul_csr)


# The packed-csr family (spike_matmul, apec_matmul, econv) is registered
# for the CPU interpreter only. Its word operand arrives in
# (block_m, block_k/32) = (128, 4) uint32 blocks, which Mosaic refuses
# (a block's last two dims must divide by (8, 128) or span the array),
# so there is no TPU registration; `packed_kernels_available()` is False
# there and packed emission raises instead of densifying.
def _spike_matmul_packed(s, w, occupancy=None, packed_k=None):
    # packed-csr: the spike operand stays uint32 words end to end; each
    # occupied tile unpacks VMEM-resident inside the CSR grid step (see
    # kernels/spike_matmul.spike_matmul_packed_csr_pallas). Dense input
    # (packed_k=None) is packed at entry — parity-harness coverage.
    from repro.kernels import ops
    return ops.spike_matmul_packed(s, w, packed_k=packed_k,
                                   occupancy=occupancy)


register("spike_matmul", "packed-csr-interpret", platforms=("cpu",),
         priority=3, auto=False, fallback="pallas-csr-interpret",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate,
         payload=("packed",))(_spike_matmul_packed)


def _spike_matmul_csr_pipe(s, w, occupancy=None):
    # Double-buffered weight-tile DMA variant of the CSR walk
    # (pipeline=True selects the 2-slot rotation kernel): same work list,
    # same math, occupied step t's dot overlaps step t+1's weight fetch.
    # The fallback chain points at the serial CSR kernel, so parity /
    # grad / mesh coverage and the degrade story are inherited unchanged.
    from repro.kernels import ops
    return ops.spike_matmul_csr(s, w, occupancy=occupancy, pipeline=True)


register("spike_matmul", "pallas-csr-pipe-interpret", platforms=("cpu",),
         priority=4, auto=False, fallback="pallas-csr-interpret",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate)(_spike_matmul_csr_pipe)
register("spike_matmul", "pallas-csr-pipe", platforms=("tpu",), priority=26,
         fallback="pallas-csr", vjp=_matmul_bwd,
         mesh_aware=_csr_shard_gate)(_spike_matmul_csr_pipe)


def _spike_matmul_packed_pipe(s, w, occupancy=None, packed_k=None):
    # Pipelined packed-csr: word unpack and MXU dot overlap the next
    # step's weight fetch; the spike-side read stays 1/32 of f32.
    from repro.kernels import ops
    return ops.spike_matmul_packed(s, w, packed_k=packed_k,
                                   occupancy=occupancy, pipeline=True)


register("spike_matmul", "packed-csr-pipe-interpret", platforms=("cpu",),
         priority=6, auto=False, fallback="packed-csr-interpret",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate,
         payload=("packed",))(_spike_matmul_packed_pipe)


# ---------------------------------------------------------- apec_matmul
def _apec_example(key):
    k1, k2 = jax.random.split(key)
    s = (jax.random.uniform(k1, (2, 16, 48)) < 0.4).astype(jnp.float32)
    w = jax.random.normal(k2, (48, 24), jnp.float32)
    return (s, w), {"g": 2}


register_op("apec_matmul", _apec_example)


def _apec_divisibility(s, w, *, g=2, **kwargs) -> Optional[str]:
    del w, kwargs
    if s.shape[-2] % g:
        return f"positions {s.shape[-2]} not divisible by group {g}"
    return None


@register("apec_matmul", REF, priority=0, differentiable=True,
          mesh_aware=True)
def _apec_matmul_ref(s, w, *, g=2, occupancy=None):
    del g, occupancy    # the oracle is the plain dense accumulation s @ w
    return jnp.dot(s.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(w.dtype)


# The overlap/residual decomposition equals s @ w in value but not under
# autodiff (min() tie-breaking would split cotangents across group
# members), so the explicit transpose rule supplies the exact gradients.
@register("apec_matmul", "jnp", priority=10, supports=_apec_divisibility,
          vjp=_matmul_bwd, mesh_aware=True)
def _apec_matmul_jnp(s, w, *, g=2, occupancy=None):
    del occupancy       # its own packed form re-derives what it gates on
    from repro.core.apec import apec_matmul_jnp
    return apec_matmul_jnp(s, w, g)


def _apec_matmul_pallas(s, w, *, g=2, occupancy=None):
    from repro.kernels import ops
    return ops.apec_matmul(s, w, g=g, occupancy=occupancy)


register("apec_matmul", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, supports=_apec_divisibility,
         vjp=_matmul_bwd, mesh_aware=True)(_apec_matmul_pallas)
register("apec_matmul", "pallas", platforms=("tpu",), priority=20,
         supports=_apec_divisibility, vjp=_matmul_bwd,
         mesh_aware=True)(_apec_matmul_pallas)


def _apec_csr_supports(s, w, *, g=2, **kwargs) -> Optional[str]:
    # The fused kernel maps each output row tile onto a (block_m/g)-row
    # overlap tile, so the group size must divide the 128-row block.
    del kwargs
    reason = _apec_divisibility(s, w, g=g)
    if reason is not None:
        return reason
    if 128 % g:
        return f"group {g} does not divide the 128-row tile"
    return None


def _apec_matmul_csr(s, w, *, g=2, occupancy=None):
    # Fused event-compacted APEC: union-CSR grid, overlap partial sums
    # accumulated into the g member rows in-kernel (no repeat pass). A
    # carried map IS the union gate (s-tile occupied iff res or ov is).
    from repro.kernels import ops
    return ops.apec_matmul_csr(s, w, g=g, occupancy=occupancy)


register("apec_matmul", "pallas-csr-interpret", platforms=("cpu",),
         priority=2, auto=False, supports=_apec_csr_supports,
         fallback="pallas-interpret", vjp=_matmul_bwd,
         mesh_aware=_csr_shard_gate)(_apec_matmul_csr)
register("apec_matmul", "pallas-csr", platforms=("tpu",), priority=25,
         supports=_apec_csr_supports, fallback="pallas",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate)(_apec_matmul_csr)


def _apec_matmul_packed(s, w, *, g=2, occupancy=None, packed_k=None):
    # packed-csr APEC: decomposition is already bitwise on uint32 words
    # (apec_decompose_packed), so the payload never round-trips through
    # f32 — union-CSR grid with in-VMEM unpack of both operands' tiles.
    from repro.kernels import ops
    return ops.apec_matmul_packed(s, w, g=g, packed_k=packed_k,
                                  occupancy=occupancy)


register("apec_matmul", "packed-csr-interpret", platforms=("cpu",),
         priority=3, auto=False, supports=_apec_csr_supports,
         fallback="pallas-csr-interpret", vjp=_matmul_bwd,
         mesh_aware=_csr_shard_gate,
         payload=("packed",))(_apec_matmul_packed)


def _apec_matmul_csr_pipe(s, w, *, g=2, occupancy=None):
    # Pipelined fused APEC: one prefetched weight tile serves both dots
    # of a union step (DMA gate = either operand live).
    from repro.kernels import ops
    return ops.apec_matmul_csr(s, w, g=g, occupancy=occupancy,
                               pipeline=True)


register("apec_matmul", "pallas-csr-pipe-interpret", platforms=("cpu",),
         priority=4, auto=False, supports=_apec_csr_supports,
         fallback="pallas-csr-interpret", vjp=_matmul_bwd,
         mesh_aware=_csr_shard_gate)(_apec_matmul_csr_pipe)
register("apec_matmul", "pallas-csr-pipe", platforms=("tpu",), priority=26,
         supports=_apec_csr_supports, fallback="pallas-csr",
         vjp=_matmul_bwd, mesh_aware=_csr_shard_gate)(_apec_matmul_csr_pipe)


# ------------------------------------------------------------------ sdsa
def _sdsa_example(key):
    ks = jax.random.split(key, 3)
    q, k, v = ((jax.random.uniform(kk, (2, 3, 24, 40)) < 0.4)
               .astype(jnp.float32) for kk in ks)
    return (q, k, v), {"mode": "or"}


register_op("sdsa", _sdsa_example)


def _sdsa_or_only(q, k, v, *, mode="or") -> Optional[str]:
    del q, k, v
    if mode != "or":
        return f"packed bitwise path supports mode='or' only, got {mode!r}"
    return None


@register("sdsa", REF, priority=0, differentiable=True, mesh_aware=True)
def _sdsa_ref(q, k, v, *, mode="or"):
    from repro.core.sdsa import sdsa_jnp
    return sdsa_jnp(q, k, v, mode=mode)


# Bitwise paths have no gradient at all (uint32 words); vjp="ref" replays
# the oracle's VJP, preserving its max-tie cotangent splitting.
@register("sdsa", "jnp", priority=5, auto=False, supports=_sdsa_or_only,
          vjp="ref", mesh_aware=True)
def _sdsa_packed_jnp(q, k, v, *, mode="or"):
    """Bit-packed pure-jnp path (the kernels' uint32 semantics without
    Pallas): pack -> AND / column-OR / AND -> unpack."""
    del mode
    from repro.core.spikes import PACK, pack_spikes, unpack_spikes
    from repro.kernels.ref import sdsa_packed_ref
    lead, (n, d) = q.shape[:-2], q.shape[-2:]
    pad = (-d) % PACK

    def prep(x):
        x = x.reshape((-1, n, d))
        return pack_spikes(jnp.pad(x, ((0, 0), (0, 0), (0, pad))), axis=-1)

    out_p = sdsa_packed_ref(prep(q), prep(k), prep(v))
    out = unpack_spikes(out_p, axis=-1, dtype=q.dtype)[..., :d]
    return out.reshape(lead + (n, d))


def _sdsa_pallas(q, k, v, *, mode="or"):
    del mode
    from repro.kernels import ops
    return ops.sdsa_or(q, k, v)


# Attention is token-local over the batch/head axes the mesh shards (the
# token axis N stays shard-resident), so the packed paths are mesh-aware.
register("sdsa", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, supports=_sdsa_or_only, vjp="ref",
         mesh_aware=True)(_sdsa_pallas)
register("sdsa", "pallas", platforms=("tpu",), priority=20,
         supports=_sdsa_or_only, vjp="ref", mesh_aware=True)(_sdsa_pallas)


# ----------------------------------------------------------- causal_sdsa
def _causal_sdsa_example(key):
    ks = jax.random.split(key, 3)
    q, k, v = ((jax.random.uniform(kk, (2, 2, 2, 12, 40)) < 0.4)
               .astype(jnp.float32) for kk in ks)
    return (q, k, v), {"mode": "or"}


register_op("causal_sdsa", _causal_sdsa_example)


def _causal_or_only(q, k, v, *, mode="or") -> Optional[str]:
    del q, k, v
    if mode != "or":
        return f"packed causal path supports mode='or' only, got {mode!r}"
    return None


@register("causal_sdsa", REF, priority=0, differentiable=True,
          mesh_aware=True)
def _causal_sdsa_ref(q, k, v, *, mode="or"):
    from repro.core.sdsa import causal_sdsa_jnp
    return causal_sdsa_jnp(q, k, v, mode=mode)


@register("causal_sdsa", "jnp", priority=5, auto=False,
          supports=_causal_or_only, vjp="ref", mesh_aware=True)
def _causal_sdsa_packed(q, k, v, *, mode="or"):
    from repro.core.sdsa import causal_sdsa_packed_jnp
    return causal_sdsa_packed_jnp(q, k, v, mode=mode)


def _causal_sdsa_pallas(q, k, v, *, mode="or"):
    del mode
    from repro.kernels import ops
    return ops.causal_sdsa_or(q, k, v)


register("causal_sdsa", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, supports=_causal_or_only, vjp="ref",
         mesh_aware=True)(_causal_sdsa_pallas)
register("causal_sdsa", "pallas", platforms=("tpu",), priority=20,
         supports=_causal_or_only, vjp="ref", mesh_aware=True,
         per_data_shard=True)(_causal_sdsa_pallas)


# ----------------------------------------------------------------- econv
def _econv_example(key):
    k1, k2 = jax.random.split(key)
    s = (jax.random.uniform(k1, (2, 8, 8, 6)) < 0.25).astype(jnp.float32)
    w = jax.random.normal(k2, (3, 3, 6, 10), jnp.float32)
    return (s, w), {"stride": 1, "padding": "SAME"}


register_op("econv", _econv_example)


def _econv_scatter_supports(s, w, *, stride=1, padding="SAME", **kwargs):
    del s, kwargs
    kh, kw = w.shape[:2]
    if kh % 2 == 0 or kw % 2 == 0:
        return f"event scatter needs odd kernels, got {(kh, kw)}"
    if stride != 1 or padding != "SAME":
        return f"event scatter is stride-1/SAME only, got {stride}/{padding}"
    return None


@register("econv", REF, priority=0, differentiable=True, mesh_aware=True)
def _econv_ref(s, w, *, stride=1, padding="SAME", occupancy=None):
    del occupancy    # dense lax conv: no event metadata consumed
    from repro.core.econv import tconv
    return tconv(s, w, stride=stride, padding=padding)


# Event extraction (nonzero) + fori scatter has no reverse-mode path;
# vjp="ref" replays the dense conv's VJP instead. Deliberately NOT
# mesh-aware: the serialized event scan's step count is sized from the
# global event budget, and per-shard it degenerates (each shard walks the
# full budget over a fraction of the events) — the mesh path degrades it
# to the tiled kernels instead.
@register("econv", "jnp", priority=5, auto=False,
          supports=_econv_scatter_supports, vjp="ref")
def _econv_scatter(s, w, *, stride=1, padding="SAME", occupancy=None):
    del stride, padding, occupancy
    from repro.core.econv import econv_scatter
    return econv_scatter(s, w)


def _econv_im2col(s, w, stride, padding, matmul, occupancy=None):
    """im2col + an occupancy-skipping spike matmul: binary patches of a
    binary map stay binary, so the event matmul kernel is the conv's MXU
    form. `matmul` picks the realization (predicated ops.spike_matmul or
    event-compacted ops.spike_matmul_csr). `occupancy` is a map for the
    PATCH matrix — the input map propagated through the im2col window
    (`core.events.conv_patch_occupancy`), never a re-scan of the
    (kh*kw-times larger) patch tensor.

    The `im2col` scope holds the patch matrix's whole layout, padded to
    the kernel's (128, 128) tiles here rather than inside `matmul`, so
    that a profile tells patch layout from the kernel."""
    from repro.kernels.ops import _pad_to
    kh, kw, ci, co = w.shape
    with jax.named_scope("im2col"):
        patches = jax.lax.conv_general_dilated_patches(
            s, (kh, kw), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        n, ho, wo, _ = patches.shape
        rows, _ = _pad_to(patches.reshape(n * ho * wo, -1), 0, 128)
        rows, _ = _pad_to(rows, 1, 128)
    # patch features are ordered (Ci, kh, kw): transpose weights to match
    # (the carried map is order-agnostic: its k-tiles bound whole rows)
    w2 = jnp.transpose(w, (2, 0, 1, 3)).reshape(ci * kh * kw, co)
    out = matmul(rows, w2.astype(jnp.float32), occupancy=occupancy)
    return out[:n * ho * wo].reshape(n, ho, wo, co)


def _econv_pallas(s, w, *, stride=1, padding="SAME", occupancy=None):
    from repro.kernels import ops
    return _econv_im2col(s, w, stride, padding, ops.spike_matmul,
                         occupancy)


register("econv", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, vjp="ref", mesh_aware=True)(_econv_pallas)
register("econv", "pallas", platforms=("tpu",), priority=20,
         vjp="ref", mesh_aware=True)(_econv_pallas)


def _econv_csr(s, w, *, stride=1, padding="SAME", occupancy=None):
    """Same im2col form, but patch-row tiles with no events cost no grid
    steps/DMA on the event-compacted kernel."""
    from repro.kernels import ops
    return _econv_im2col(s, w, stride, padding, ops.spike_matmul_csr,
                         occupancy)


register("econv", "pallas-csr-interpret", platforms=("cpu",), priority=2,
         auto=False, fallback="pallas-interpret", vjp="ref",
         mesh_aware=_csr_shard_gate)(_econv_csr)
register("econv", "pallas-csr", platforms=("tpu",), priority=25,
         fallback="pallas", vjp="ref", mesh_aware=_csr_shard_gate)(_econv_csr)


def _econv_packed_supports(s, w, *, stride=1, padding="SAME", **kwargs):
    del s, w, kwargs
    if padding not in ("SAME", "VALID"):
        return (f"packed im2col computes its own halos and supports "
                f"SAME/VALID only, got {padding!r}")
    if stride < 1:
        return f"stride must be >= 1, got {stride}"
    return None


def _econv_packed_csr(s, w, *, stride=1, padding="SAME", occupancy=None,
                      packed_k=None):
    # packed-csr conv: im2col runs in the WORD domain (strided shifted
    # slices of the padded word array — bit patterns are per-channel, so
    # window extraction never repacks), then the packed CSR matmul. See
    # ops.econv_packed for the weight relayout matching the word-aligned
    # patch feature order.
    from repro.kernels import ops
    return ops.econv_packed(s, w, stride=stride, padding=padding,
                            packed_k=packed_k, occupancy=occupancy)


register("econv", "packed-csr-interpret", platforms=("cpu",), priority=3,
         auto=False, supports=_econv_packed_supports,
         fallback="pallas-csr-interpret", vjp="ref",
         mesh_aware=_csr_shard_gate, payload=("packed",))(_econv_packed_csr)


def _econv_csr_pipe(s, w, *, stride=1, padding="SAME", occupancy=None):
    # im2col feeding the pipelined CSR matmul: patch-row weight tiles
    # stream one occupied step ahead of the dot.
    from repro.kernels import ops
    return _econv_im2col(s, w, stride, padding,
                         functools.partial(ops.spike_matmul_csr,
                                           pipeline=True), occupancy)


register("econv", "pallas-csr-pipe-interpret", platforms=("cpu",),
         priority=4, auto=False, fallback="pallas-csr-interpret",
         vjp="ref", mesh_aware=_csr_shard_gate)(_econv_csr_pipe)
register("econv", "pallas-csr-pipe", platforms=("tpu",), priority=26,
         fallback="pallas-csr", vjp="ref",
         mesh_aware=_csr_shard_gate)(_econv_csr_pipe)


# ----------------------------------------------------------------- tconv
# NOTE on naming: in this repo "TConv" (econv's ref backend) is the
# traditional *forward* conv baseline of paper Fig. 1; the `tconv` op here
# is the *transposed* conv — the segmentation decoder's upsampling layers
# (SegNet 16TC3/2TC3) — promoted from inline lax.conv_transpose calls in
# models/cnn.py into a registry op.
def _tconv_example(key):
    k1, k2 = jax.random.split(key)
    s = (jax.random.uniform(k1, (2, 6, 6, 5)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(k2, (3, 3, 5, 4), jnp.float32)
    return (s, w), {"stride": 2, "padding": "SAME"}


register_op("tconv", _tconv_example)


def _tconv_pad_supports(s, w, *, stride=2, padding="SAME") -> Optional[str]:
    del s, w
    if padding not in ("SAME", "VALID"):
        return f"upsample form supports SAME/VALID, got {padding!r}"
    if stride < 1:
        return f"stride must be >= 1, got {stride}"
    return None


@register("tconv", REF, priority=0, differentiable=True, mesh_aware=True)
def _tconv_ref(s, w, *, stride=2, padding="SAME"):
    from repro.core.econv import conv_transpose_ref
    return conv_transpose_ref(s, w, stride=stride, padding=padding)


# Zero-insertion + stride-1 conv: same linear map as the oracle, so its
# native autodiff cotangents coincide with ref's.
@register("tconv", "jnp", priority=5, auto=False,
          supports=_tconv_pad_supports, differentiable=True, mesh_aware=True)
def _tconv_upsampled(s, w, *, stride=2, padding="SAME"):
    from repro.core.econv import conv_transpose_upsampled
    return conv_transpose_upsampled(s, w, stride=stride, padding=padding)


def _tconv_pallas(s, w, *, stride=2, padding="SAME"):
    """Zero-insert (events keep binarity, addresses dilate), then im2col +
    the occupancy-skipping spike matmul — the MXU form of the decoder's
    upsampling conv, mirroring `_econv_pallas`."""
    from repro.core.econv import upsample_events
    from repro.kernels import ops
    kh, kw, ci, co = w.shape
    up = upsample_events(s, stride, kh, kw, padding)
    patches = jax.lax.conv_general_dilated_patches(
        up, (kh, kw), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, ho, wo, _ = patches.shape
    w2 = jnp.transpose(w, (2, 0, 1, 3)).reshape(ci * kh * kw, co)
    out = ops.spike_matmul(patches.reshape(n * ho * wo, -1),
                           w2.astype(jnp.float32))
    return out.reshape(n, ho, wo, co)


register("tconv", "pallas-interpret", platforms=("cpu",), priority=1,
         auto=False, supports=_tconv_pad_supports, vjp="ref",
         mesh_aware=True)(_tconv_pallas)
register("tconv", "pallas", platforms=("tpu",), priority=20,
         supports=_tconv_pad_supports, vjp="ref",
         mesh_aware=True)(_tconv_pallas)


# --------------------------------------------------- dispatch entry points
# The typed entries accept an `EventTensor` in place of dense spikes and
# unpack it into (spikes, occupancy-kwarg) for the registered backends:
# event backends consume the carried map, oracles ignore it, and either
# way the values are identical — occupancy only gates what is provably
# zero. A map carried for the wrong tiling raises before resolution.
def _event_args(s, kw=None):
    from repro.core.events import EventTensor
    kw = dict(kw or {})
    if isinstance(s, EventTensor):
        occ = s.occupancy_for(128, 128)
        if occ is not None:
            kw["occupancy"] = occ
        if s.is_packed:
            # Packed payload: the words become the positional operand and
            # the static packed_k marker routes resolution to backends
            # declaring payload="packed" (non-declaring fallbacks get the
            # explicit unpack shim, never a silent densify).
            kw["packed_k"] = s.feature_size
            s = s.packed
        else:
            s = s.spikes
    return s, kw


def lif_scan(x, *, decay=0.5, v_th=1.0, soft_reset=True, surrogate_alpha=2.0):
    return dispatch("lif_scan", x, decay=decay, v_th=v_th,
                    soft_reset=soft_reset, surrogate_alpha=surrogate_alpha)


def lif_scan_occ(x, *, decay=0.5, v_th=1.0, soft_reset=True,
                 surrogate_alpha=2.0, packed=False):
    """Fire + emit the occupancy maps: returns (spikes, (128,128) tile
    map, 8-row chunk map) — wrap in an EventTensor via
    `models.layers.lif_fire_events`. With ``packed=True`` the first
    element is the uint32 word tensor instead (forward-only; the fused
    kernel packs in-VMEM and takes the counts from word popcounts, so no
    f32 spike tensor reaches HBM)."""
    return dispatch("lif_scan_occ", x, decay=decay, v_th=v_th,
                    soft_reset=soft_reset, surrogate_alpha=surrogate_alpha,
                    packed=packed)


def spike_matmul(s, w):
    s, kw = _event_args(s)
    return dispatch("spike_matmul", s, w, **kw)


def apec_matmul(s, w, *, g=2):
    s, kw = _event_args(s, {"g": g})
    return dispatch("apec_matmul", s, w, **kw)


def sdsa(q, k, v, *, mode="or"):
    from repro.core.events import as_spikes
    return dispatch("sdsa", as_spikes(q), as_spikes(k), as_spikes(v),
                    mode=mode)


def causal_sdsa(q, k, v, *, mode="or"):
    from repro.core.events import as_spikes
    return dispatch("causal_sdsa", as_spikes(q), as_spikes(k), as_spikes(v),
                    mode=mode)


def econv(s, w, *, stride=1, padding="SAME"):
    from repro.core.events import EventTensor, conv_patch_occupancy
    kw = {"stride": stride, "padding": padding}
    if isinstance(s, EventTensor):
        # The carried map is for the INPUT flattening — the im2col patch
        # matrix has different rows/K, so the map is propagated through
        # the window (tile-granular dilation), not passed through as-is.
        occ = conv_patch_occupancy(s, w.shape, stride, padding)
        if occ is not None:
            kw["occupancy"] = occ
        if s.is_packed:
            kw["packed_k"] = s.feature_size
            s = s.packed
        else:
            s = s.spikes
    return dispatch("econv", s, w, **kw)


def tconv(s, w, *, stride=2, padding="SAME"):
    # Transposed conv dilates event addresses (zero-insertion): a carried
    # map does not survive — dense view only (documented invalidation).
    from repro.core.events import as_spikes
    return dispatch("tconv", as_spikes(s), w, stride=stride, padding=padding)

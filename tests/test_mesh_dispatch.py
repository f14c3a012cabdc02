"""Mesh-aware dispatch: resolution semantics (single process) + the
8-device shard_map parity payload (shared multi-device subprocess).

The single-process tests drive `resolve(..., mesh=)` with plain shard
counts — mesh-aware resolution is a pure function of shapes and the
registry, so it needs no devices. The actual 8-way shard_map execution
(forward/backward parity vs the single-device oracle, per-shard CSR work
lists, degrade attribution) runs in conftest's MULTIDEVICE_SCRIPT
`MESH_DISPATCH` section and is asserted here via its marker.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch

CSR = "pallas-csr-interpret"


@pytest.fixture(autouse=True)
def _fresh_dispatch_state(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    dispatch.reset_fallback_warnings()


def _spikes(key, shape, density=0.1):
    return (jax.random.uniform(key, shape) < density).astype(jnp.float32)


# ------------------------------------------------- resolution semantics
def test_mesh_resolution_keeps_csr_when_shards_tile_cleanly():
    s = _spikes(jax.random.PRNGKey(0), (1024, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        assert dispatch.resolve_name("spike_matmul", s, w, mesh=8) == CSR
        assert dispatch.resolve_attribution("spike_matmul", s, w,
                                            mesh=8) == CSR


def test_mesh_resolution_degrades_csr_on_ragged_shard_grids():
    # 512 rows / 8 shards = 64 < one 128-row tile per shard
    s = _spikes(jax.random.PRNGKey(1), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        assert dispatch.resolve_name("spike_matmul", s, w) == CSR
        with pytest.warns(RuntimeWarning, match="per-shard rows"):
            assert dispatch.resolve_name("spike_matmul", s, w, mesh=8) \
                == "pallas-interpret"
        assert dispatch.resolve_attribution("spike_matmul", s, w, mesh=8) \
            == f"pallas-interpret<-{CSR}"


def test_use_mesh_context_is_ambient_and_scoped():
    s = _spikes(jax.random.PRNGKey(2), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            with dispatch.use_mesh(8):
                assert dispatch.ambient_mesh() == 8
                assert dispatch.resolve_name("spike_matmul", s, w) \
                    == "pallas-interpret"
        assert dispatch.ambient_mesh() is None
        assert dispatch.resolve_name("spike_matmul", s, w) == CSR


def test_non_mesh_aware_backend_is_refused_under_mesh():
    """econv's serialized event-scatter path never declared `mesh_aware`;
    under a mesh an explicit override must degrade it to ref (it has no
    declared fallback), not run it per shard."""
    args, kwargs = dispatch.example_inputs("econv", jax.random.PRNGKey(3))
    with dispatch.use_backend("jnp", op="econv"):
        assert dispatch.resolve_name("econv", *args, **kwargs) == "jnp"
        with pytest.warns(RuntimeWarning, match="not declared mesh-aware"):
            assert dispatch.resolve_name("econv", *args, mesh=2,
                                         **kwargs) == dispatch.REF


def test_mesh_auto_resolution_records_degrade_attribution():
    """No override: auto selection under a mesh skips gated candidates by
    priority and resolved_backends carries the `<-requested` attribution
    (canonical example shapes never fill a per-shard tile)."""
    with dispatch.use_backend(CSR, op="apec_matmul"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rb = dispatch.resolved_backends(mesh=8)
    assert rb["apec_matmul"] == f"pallas-interpret<-{CSR}"
    # and without a mesh the same map keeps plain (undegraded) names
    with dispatch.use_backend(CSR, op="apec_matmul"):
        assert dispatch.resolved_backends()["apec_matmul"] == CSR


def test_data_shard_count_reads_batch_axes_only():
    from jax.sharding import AbstractMesh
    assert dispatch.data_shard_count(None) == 1
    assert dispatch.data_shard_count(8) == 8
    m = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    assert dispatch.data_shard_count(m) == 4          # pod*data, not model
    assert dispatch.data_shard_count(
        AbstractMesh((4, 2), ("data", "model"))) == 4


def test_mesh_one_shard_is_plain_resolution():
    from jax.sharding import AbstractMesh
    s = _spikes(jax.random.PRNGKey(4), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        assert dispatch.resolve_name("spike_matmul", s, w, mesh=1) == CSR
        # a model-only mesh shards features, not event rows: no gate
        assert dispatch.resolve_name(
            "spike_matmul", s, w,
            mesh=AbstractMesh((4,), ("model",))) == CSR


def test_dispatch_entry_accepts_mesh_and_matches_oracle():
    s = _spikes(jax.random.PRNGKey(5), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(6), (128, 32), jnp.float32)
    out = dispatch.dispatch("spike_matmul", s, w, mesh=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w),
                               atol=1e-5)


def test_steps_factory_traces_under_mesh():
    """launch.steps wraps step fns in use_mesh: resolution inside the jit
    trace must see the ambient mesh. Probed with a fn that records the
    ambient mesh at trace time."""
    from repro.launch import steps as steps_mod
    seen = []

    def probe(x):
        seen.append((dispatch.ambient_mesh(), dispatch._SPLIT[-1]))
        return x

    jax.jit(steps_mod._under_mesh(probe, 8))(jnp.zeros((2,)))
    jax.jit(steps_mod._under_mesh(probe, 8, split_kernels=True))(
        jnp.zeros((3,)))
    assert seen == [(8, False), (8, True)]
    assert dispatch._SPLIT == []                 # context popped
    assert steps_mod._under_mesh(probe, None) is probe


# ------------------------------------------------------ warn-once dedup
def test_degrade_chain_warns_exactly_once_per_op_per_process():
    """The csr->pallas degrade and the pallas->ref surrender each fire ONE
    RuntimeWarning per (op, from, to) per process — resolution happens at
    trace time, and a retrace storm repeating the warning would bury it."""
    s = _spikes(jax.random.PRNGKey(7), (10, 32), 0.5)
    w = jnp.zeros((32, 8), jnp.float32)
    with dispatch.use_backend(CSR, op="apec_matmul"):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(3):          # retraces / repeated resolutions
                dispatch.resolve("apec_matmul", s, w, g=3)
        msgs = [str(r.message) for r in rec
                if issubclass(r.category, RuntimeWarning)]
        assert len(msgs) == 2, msgs     # one degrade + one ref surrender
        assert any("degrading to 'pallas-interpret'" in m for m in msgs)
        assert any("falling back to 'ref'" in m for m in msgs)
        # re-armed explicitly -> fires again (fresh-process behavior)
        dispatch.reset_fallback_warnings()
        with warnings.catch_warnings(record=True) as rec2:
            warnings.simplefilter("always")
            dispatch.resolve("apec_matmul", s, w, g=3)
        assert len([r for r in rec2
                    if issubclass(r.category, RuntimeWarning)]) == 2


def test_mesh_degrade_warns_once_and_separately_from_flat_path():
    """The mesh gate's degrade is its own (op, from, to) edge only when it
    lands elsewhere; same-edge degrades share one warning with the flat
    path — per op per process means per resolution edge, not per call."""
    s = _spikes(jax.random.PRNGKey(8), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(4):
                dispatch.resolve("spike_matmul", s, w, mesh=8)
        msgs = [str(r.message) for r in rec
                if issubclass(r.category, RuntimeWarning)]
        assert len(msgs) == 1, msgs
        assert "per-shard rows" in msgs[0]
        # flat path resolves csr fine -> no new warning
        with warnings.catch_warnings(record=True) as rec2:
            warnings.simplefilter("always")
            dispatch.resolve("spike_matmul", s, w)
        assert not rec2


def test_resolved_backends_snapshot_does_not_consume_warn_budget():
    """The serve/train startup log calls resolved_backends() with
    warnings suppressed; that read-only snapshot must not eat the
    once-per-edge budget, or the first REAL degrade on the same edge
    would be silent for the rest of the process."""
    s = _spikes(jax.random.PRNGKey(9), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    with dispatch.use_backend(CSR, op="spike_matmul"):
        rb = dispatch.resolved_backends(mesh=8)   # degrades internally
        assert rb["spike_matmul"] == f"pallas-interpret<-{CSR}"
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            dispatch.resolve("spike_matmul", s, w, mesh=8)
        assert len([r for r in rec
                    if issubclass(r.category, RuntimeWarning)]) == 1


def test_per_shard_occupied_tiles_splits_spike_rows_not_tile_rows():
    """512 uniform rows over 8 shards: every 64-row shard pads to one
    occupied 128-tile. Splitting the global map's 4 TILE rows instead
    would report half the shards empty — the straggler signal must track
    the rows shard_map actually hands each shard."""
    from repro.runtime import sharding as rs
    s = jnp.ones((512, 128), jnp.float32)
    assert rs.per_shard_occupied_tiles(s, 8) == [1] * 8
    # clustered case: only the first shard's rows hold events
    s2 = jnp.zeros((1024, 128), jnp.float32).at[:128].set(1.0)
    per = rs.per_shard_occupied_tiles(s2, 8)
    assert per == [1] + [0] * 7


def test_event_op_sharded_rejects_csr_stack_for_other_ops():
    from repro.core.spikes import shard_occupancy_to_csr, stack_shard_csrs
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding as rs
    s = _spikes(jax.random.PRNGKey(10), (256, 128))
    w = jnp.zeros((128, 64), jnp.float32)
    stack = stack_shard_csrs(shard_occupancy_to_csr(
        ops.padded_occupancy(s), 2, tiling=(128, 128)))
    mesh1 = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="spike_matmul pass-through"):
        rs.event_op_sharded(mesh1, "apec_matmul", s, w, g=2,
                            csr_stack=stack)


# ----------------------------------------- hybrid route-keyed warn-once
def test_hybrid_route_warn_not_suppressed_by_plain_degrade():
    """The plain override degrade and hybrid's event-route refusal share
    the same (op, from, to) edge — csr -> its dense fallback. Warn-once
    state is keyed by route too, so the first HYBRID warning must fire
    even after the plain degrade already consumed the route-less key
    (each names a different decision the user needs to see once)."""
    from repro.kernels import ops
    s = _spikes(jax.random.PRNGKey(30), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    occ = ops.padded_occupancy(s)
    # 1) plain degrade eats the route-less (op, csr, dense) key
    with dispatch.use_backend(CSR, op="spike_matmul"):
        with pytest.warns(RuntimeWarning, match="per-shard rows"):
            dispatch.resolve("spike_matmul", s, w, mesh=8)
    # 2) hybrid's event-route refusal on the same edge still warns once
    with dispatch.use_hybrid("spike_matmul"):
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(3):
                be, attr = dispatch.resolve_with_attribution(
                    "spike_matmul", s, w, mesh=8, occupancy=occ)
        msgs = [str(r.message) for r in rec
                if issubclass(r.category, RuntimeWarning)]
        assert len(msgs) == 1, msgs
        assert "hybrid event route" in msgs[0]
        assert be.name == "pallas-interpret"
        assert attr == f"pallas-interpret<-{dispatch.HYBRID}"


def test_hybrid_route_warn_rearms_after_reset():
    """reset_fallback_warnings covers the route-keyed entries too: after a
    reset, the hybrid route warning fires again (fresh-process behavior),
    exactly like the plain degrade chain's."""
    from repro.kernels import ops
    s = _spikes(jax.random.PRNGKey(31), (512, 256))
    w = jnp.zeros((256, 64), jnp.float32)
    occ = ops.padded_occupancy(s)
    with dispatch.use_hybrid("spike_matmul"):
        with pytest.warns(RuntimeWarning, match="hybrid event route"):
            dispatch.resolve_with_attribution(
                "spike_matmul", s, w, mesh=8, occupancy=occ)
        dispatch.reset_fallback_warnings()
        with pytest.warns(RuntimeWarning, match="hybrid event route"):
            dispatch.resolve_with_attribution(
                "spike_matmul", s, w, mesh=8, occupancy=occ)


def test_occupancy_imbalance_carries_routes():
    """The straggler report's occ_routes field: per-shard hybrid route
    choices ride alongside the occupied-tile skew (positional, shard
    order) and stay out of the fields string when hybrid is off."""
    from repro.runtime.straggler import occupancy_imbalance
    imb = occupancy_imbalance([4, 0, 1], routes=("dense", "event", "event"))
    assert imb.routes == ("dense", "event", "event")
    assert "occ_routes=dense:event:event" in imb.as_fields()
    assert "occ_routes" not in occupancy_imbalance([4, 0, 1]).as_fields()


def test_event_op_sharded_reports_per_shard_hybrid_routes():
    """A skewed concrete map under hybrid: the with_report occupancy
    imbalance names each shard's route — a sparse shard on the event
    kernel while dense shards run predicated is the feature, and
    `occ_routes` is where it surfaces."""
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding as rs
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    n_dev = 2
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    # shard 0 dense, shard 1 nearly empty
    s = jnp.zeros((256 * n_dev, 256), jnp.float32).at[:256].set(1.0)
    s = s.at[256, 0].set(1.0)
    w = jnp.zeros((256, 64), jnp.float32)
    occ = ops.padded_occupancy(s)
    with dispatch.use_hybrid("spike_matmul"):
        out, rep = rs.event_op_sharded(mesh, "spike_matmul", s, w,
                                       occupancy=occ, with_report=True)
    assert dispatch.HYBRID in rep["attribution"]
    routes = rep["occupancy"].routes
    assert len(routes) == n_dev
    assert routes[0] == "dense" and routes[1] == "event", routes
    assert "occ_routes=dense:event" in rep["occupancy"].as_fields()


# ------------------------------------------------- 8-device subprocess
def test_mesh_dispatch_multidevice_parity(multidevice_run):
    """8-way mesh: spike/apec matmuls resolve to the csr family inside
    shard_map, match single-device outputs within 1e-5 forward AND
    backward, per-shard CSR work lists compose, and the ragged-grid case
    degrades with attribution. (Payload in conftest.MULTIDEVICE_SCRIPT.)
    """
    multidevice_run.check("MESH_DISPATCH")


def test_compiled_kernels_run_per_shard_under_a_gspmd_mesh(multidevice_run):
    """A `per_data_shard` backend runs in a shard_map over the data axes
    when the step asks for it under a concrete Mesh (Mosaic kernels
    cannot be partitioned by GSPMD), matching the oracle forward and
    through the surrogate gradient; a serve step under the mesh, or a
    batch the mesh cannot split, runs it whole and still matches.
    (Payload in conftest.MULTIDEVICE_SCRIPT.)"""
    multidevice_run.check("GSPMD_SHARD")

"""Full-event forward pipeline: EventTensor carrier invariants, consumer
pass-throughs, and the jaxpr-level proof that the fused model forwards run
ZERO standalone dense occupancy reductions between spiking layers.

The jaxpr detector looks for the `tile_occupancy` signature — a reduce_sum
eliminating a whole (tile_m x tile_k) block of a spike-sized tensor
(reduced-size product >= 4096; the fused LIF emission's count-map
aggregation reduces 16-element chunks and every norm/head reduction in
these models is far smaller, so the signature is unambiguous).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import spikes as spikes_mod
from repro.core.events import (EventTensor, conv_patch_occupancy,
                               max_pool_events)
from repro.kernels import dispatch, ops

ATOL = 1e-5


def _clustered(key, m, k, density=0.05):
    return (jax.random.uniform(key, (m, k)) < density).astype(jnp.float32)


# ------------------------------------------------------ carrier invariants
def test_event_tensor_pytree_roundtrip_and_jit():
    s = _clustered(jax.random.PRNGKey(0), 256, 128)
    et = EventTensor.from_spikes(s)
    leaves, treedef = jax.tree.flatten(et)
    et2 = jax.tree.unflatten(treedef, leaves)
    assert isinstance(et2, EventTensor) and et2.tiling == (128, 128)

    @jax.jit
    def through(e):
        return e.reshape(2, 128, 128)

    out = through(et)
    assert isinstance(out, EventTensor)
    assert out.occupancy is not None          # trailing axis preserved
    np.testing.assert_array_equal(np.asarray(out.spikes),
                                  np.asarray(s.reshape(2, 128, 128)))


def test_reshape_rule_preserves_or_drops_map():
    et = EventTensor.from_spikes(_clustered(jax.random.PRNGKey(1), 256, 128))
    assert et.reshape(4, 64, 128).occupancy is not None   # last axis kept
    assert et.reshape(256 * 128).occupancy is None        # flattened: drop
    assert et.reshape(256, 2, 64).occupancy is None       # axis split: drop


def test_wrong_tiling_rejected_loudly():
    et = EventTensor.from_spikes(_clustered(jax.random.PRNGKey(2), 256, 128))
    with pytest.raises(ValueError, match="tiling"):
        et.occupancy_for(64, 64)
    with pytest.raises(ValueError, match="does not cover"):
        EventTensor(et.spikes, jnp.zeros((7, 7), jnp.int32))
    # a map whose grid mismatches the consumer's padded tiling must raise
    with pytest.raises(ValueError, match="does not match"):
        ops.spike_matmul_csr(et.spikes[:128], et.spikes.reshape(-1, 128).T
                             [:128, :64], occupancy=et.occupancy)


def test_fused_emission_matches_rederived_map():
    """The producer's map (lif_scan_occ, any backend) must equal the
    consumer's re-derivation exactly — counts, not just support."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 200)) * 2.0
    for be in ("ref", "pallas-interpret"):
        s, occ, chunks = dispatch.call_backend("lif_scan_occ", be, x)
        np.testing.assert_array_equal(np.asarray(occ),
                                      np.asarray(ops.padded_occupancy(s)))
        np.testing.assert_array_equal(
            np.asarray(occ),
            np.asarray(chunks).reshape(-1, 16, occ.shape[1]).sum(axis=1))


# ------------------------------------------------- consumer pass-throughs
def test_spike_matmul_csr_accepts_occupancy_without_csr():
    """Satellite: a caller holding the map but no work list must not pay a
    second dense pre-pass — the compaction runs on the tiny map alone."""
    s = _clustered(jax.random.PRNGKey(4), 256, 256)
    w = jax.random.normal(jax.random.PRNGKey(5), (256, 64))
    occ = ops.padded_occupancy(s)
    with spikes_mod.watch_occupancy_prepasses() as rec:
        out = ops.spike_matmul_csr(s, w, occupancy=occ)
    assert rec["calls"] == 0, rec
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=ATOL)
    with spikes_mod.watch_occupancy_prepasses() as rec2:
        out2 = ops.apec_matmul_csr(s, w, g=2, occupancy=occ)
    assert rec2["calls"] == 0, rec2
    np.testing.assert_allclose(np.asarray(out2), np.asarray(s @ w),
                               atol=ATOL)


def test_apec_matmul_accepts_decomposed_operands_and_maps():
    """Satellite: the predicated path aligns with the CSR path — a caller
    that already decomposed passes (residual, overlap) + occupancies and
    no fresh per-operand pre-pass runs."""
    s = _clustered(jax.random.PRNGKey(6), 256, 128, density=0.2)
    w = jax.random.normal(jax.random.PRNGKey(7), (128, 64))
    ov, res = ops.apec_decompose(s, 2)
    occ_res = ops.padded_occupancy(res)
    occ_ov = ops.padded_occupancy(ov)
    with spikes_mod.watch_occupancy_prepasses() as rec:
        out = ops.apec_matmul(s, w, g=2, decomposed=(res, ov),
                              occ_res=occ_res, occ_ov=occ_ov)
    assert rec["calls"] == 0, rec
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=1e-4)
    # carried map of the undecomposed spikes serves both operands too
    et = EventTensor.from_spikes(s)
    with spikes_mod.watch_occupancy_prepasses() as rec2:
        out2 = ops.apec_matmul(et, w, g=2)
    assert rec2["calls"] == 0, rec2
    np.testing.assert_allclose(np.asarray(out2), np.asarray(s @ w),
                               atol=1e-4)


@pytest.mark.parametrize("h,w,k,stride,padding", [
    (7, 7, 3, 2, "SAME"),        # non-divisible H/W: ho = ceil(7/2) = 4
    (9, 9, 3, 2, "SAME"),
    (15, 15, 3, 2, "SAME"),
    (7, 7, 2, 2, "VALID"),       # pooling analog
])
def test_window_occupancy_edge_parity_nondivisible(h, w, k, stride, padding):
    """Boundary dilation with stride > 1 on non-divisible H/W: the numpy
    fast path and the traced path must agree exactly, and neither may
    mark an out-of-image chunk occupied when the straddling window's
    in-image half is empty (the old symmetric halo over-dilated backward
    past the image start)."""
    c = 32
    key = jax.random.PRNGKey(h * 31 + stride)
    sp = (jax.random.uniform(key, (2, h, w, c)) < 0.05).astype(jnp.float32)
    # image 0 fully empty; image 1 events only in the top-left quadrant,
    # so every bottom/right edge window straddles into empty territory
    sp = sp.at[0].set(0.0).at[1, h // 2:].set(0.0).at[1, :, w // 2:].set(0.0)
    et = EventTensor.from_spikes(sp)
    occ_np = conv_patch_occupancy(et, (k, k, c, c), stride, padding)
    occ_tr = jax.jit(lambda e: conv_patch_occupancy(
        e, (k, k, c, c), stride, padding))(et)
    np.testing.assert_array_equal(np.asarray(occ_np), np.asarray(occ_tr))
    patches = jax.lax.conv_general_dilated_patches(
        sp, (k, k), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    true_occ = np.asarray(ops.padded_occupancy(
        patches.reshape(-1, patches.shape[-1])))
    # conservative: never marks a truly occupied tile empty
    assert bool(np.all((true_occ == 0) | (np.asarray(occ_np) > 0)))


def test_window_occupancy_empty_image_stays_empty_under_stride():
    """Chunk-aligned geometry (8x8 images: 64 input rows per image divide
    the 8-row chunks exactly): an all-empty image must contribute ZERO
    occupied output chunks under strided windows, even with a fully dense
    neighbor image — the edge clamp must not bleed across the boundary."""
    from repro.core.events import window_occupancy
    n, h, w, c = 2, 8, 8, 128
    sp = jnp.zeros((n, h, w, c), jnp.float32).at[1].set(1.0)
    et = EventTensor.from_spikes(sp)
    occ, chunks = window_occupancy(et, (2, 2), 2, (4, 4), c)
    ch = np.asarray(chunks)
    # image 0 owns output rows 0..15 = chunks 0..1: all empty
    assert int(ch[:2].sum()) == 0, ch[:, 0]
    assert int(ch[2:4].sum()) > 0        # image 1's chunks are live
    sp = (jax.random.uniform(jax.random.PRNGKey(8), (2, 16, 16, 32)) < 0.02
          ).astype(jnp.float32).at[0].set(0.0)
    et = EventTensor.from_spikes(sp)
    w = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 32, 8))
    occ_p = conv_patch_occupancy(et, w.shape, 1, "SAME")
    patches = jax.lax.conv_general_dilated_patches(
        sp, (3, 3), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    true_occ = np.asarray(ops.padded_occupancy(
        patches.reshape(2 * 16 * 16, -1)))
    assert occ_p.shape == true_occ.shape
    # conservative: never marks an occupied tile empty
    assert bool(np.all((true_occ == 0) | (np.asarray(occ_p) > 0)))
    # useful: the empty image's tiles stay empty in the propagated map
    assert int((np.asarray(occ_p) == 0).sum()) > 0
    pooled = max_pool_events(et, 2)
    true_pool = np.asarray(ops.padded_occupancy(
        pooled.spikes.reshape(-1, 32)))
    assert bool(np.all((true_pool == 0) | (np.asarray(pooled.occupancy) > 0)))


# ------------------------------------------- jaxpr: zero dense pre-passes
def _dense_occ_reductions(jaxpr, min_reduced=4096):
    """Count reduce_sum eqns eliminating >= `min_reduced` elements — the
    dense `tile_occupancy` signature — recursively through sub-jaxprs."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "reduce_sum":
                axes = eqn.params.get("axes", ())
                shape = eqn.invars[0].aval.shape
                red = int(np.prod([shape[a] for a in axes])) if axes else 1
                if red >= min_reduced:
                    found.append((shape, axes))
            for v in eqn.params.values():
                for sub in jax.tree.leaves(
                        v, is_leaf=lambda x: isinstance(
                            x, (Jaxpr, ClosedJaxpr))):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)
    walk(jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr)
    return found


def test_detector_flags_the_rederive_path():
    """Positive control: the standalone pre-pass IS the signature."""
    s = _clustered(jax.random.PRNGKey(10), 256, 128)
    w = jax.random.normal(jax.random.PRNGKey(11), (128, 64))
    jx = jax.make_jaxpr(lambda sv: ops.spike_matmul(sv, w))(s)
    assert len(_dense_occ_reductions(jx)) >= 1


def _fused_overrides():
    return (dispatch.use_backend("pallas-interpret", op="lif_scan_occ"),
            dispatch.use_backend("pallas-csr-interpret", op="spike_matmul"),
            dispatch.use_backend("pallas-csr-interpret", op="econv"))


def test_fused_spikingformer_forward_has_zero_dense_occ_reductions():
    """The tentpole's proof: with the event backends live, a whole-network
    spikingformer trace re-derives occupancy from a dense activation
    exactly zero times — every consumer runs off carried/propagated maps
    emitted by the fused LIF. Asserted at BOTH levels from one trace:
    the jaxpr contains no dense-reduction signature, and the trace-time
    watcher recorded zero `tile_occupancy` calls."""
    from repro.configs.base import SpikingConfig
    from repro.models import spikingformer
    params = spikingformer.spikingformer_init(jax.random.PRNGKey(0),
                                              depth=1, dim=32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3))
    o1, o2, o3 = _fused_overrides()
    with warnings.catch_warnings(), o1, o2, o3:
        warnings.simplefilter("ignore", RuntimeWarning)
        with spikes_mod.watch_occupancy_prepasses() as rec:
            jx = jax.make_jaxpr(lambda xx: spikingformer.spikingformer_apply(
                params, xx, n_heads=4,
                spiking_cfg=SpikingConfig(t_steps=2)))(x)
    flagged = _dense_occ_reductions(jx)
    assert flagged == [], flagged
    assert rec["calls"] == 0, rec


def test_fused_vgg11_forward_rederives_only_at_the_coded_input():
    """CNN family: every spike-fed conv consumes a carried/propagated map.
    The single allowed re-derivation is the direct-coded INPUT conv
    (OPT1): its drive is multi-bit, produced by no spiking layer — i.e.
    zero standalone reductions BETWEEN spiking layers."""
    from repro.configs.base import CNNConfig, SpikingConfig
    from repro.models import cnn
    cfg = CNNConfig(name="vgg11", layers=cnn.VGG11_LAYERS,
                    spiking=SpikingConfig(t_steps=1))
    p = cnn.vgg11_init(cfg, jax.random.PRNGKey(0))
    # batch 2: every layer's B*H*W fills 8-row chunks (down to the 2x2
    # tail convs), so the fused emission holds end to end — at batch 1
    # the tail layers' producers fall back to ref emission, the
    # documented lif_scan_occ degrade.
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    o1, o2, o3 = _fused_overrides()
    with warnings.catch_warnings(), o1, o2, o3:
        warnings.simplefilter("ignore", RuntimeWarning)
        jx = jax.make_jaxpr(
            lambda xx: cnn.vgg11_apply(cfg, p, xx))(x)
    flagged = _dense_occ_reductions(jx)
    assert len(flagged) <= 1, flagged


# ----------------------------------------------------- sharded EventTensor
def test_event_tensor_sharded_parity(multidevice_run):
    """8-way shard_map parity vs single device at 1e-5, carried-occupancy
    routing asserted — runs in the shared multi-device subprocess."""
    multidevice_run.check("EVENT_TENSOR")

"""Packed uint32 spike payload: round-trip properties (arbitrary trailing
axes, incl. non-multiples of 32), packed-popcount occupancy == the dense
pre-pass exactly, loud wrong-width rejection, routing/attribution of
packed EventTensors (packed-csr pin, explicit unpack shim, dense calls
never drifting onto packed backends), pack survival through pooling,
whole-model packed-forward parity, the committed bytes-moved ledger
(BENCH_PR7.json provenance pin), and the jaxpr proof that packed mode
materializes no f32 spike tensor between spiking layers.
"""
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from hypothesis_compat import HAVE_HYPOTHESIS, given, st  # noqa: E402

from repro.core import costmodel  # noqa: E402
from repro.core.events import EventTensor, max_pool_events  # noqa: E402
from repro.core.lif import LIFConfig  # noqa: E402
from repro.core.spikes import (PACK, pack_spikes, pack_spikes_padded,  # noqa: E402
                               packed_tile_occupancy, packed_width,
                               tile_occupancy, unpack_spikes)
from repro.kernels import dispatch, ops  # noqa: E402
from repro.models.layers import lif_fire_events  # noqa: E402

REPO = Path(__file__).parent.parent


def _spikes(shape, seed, p=0.3):
    rng = np.random.RandomState(seed)
    return jnp.asarray((rng.rand(*shape) < p).astype(np.float32))


# ------------------------------------------------------------ round trip
def _assert_roundtrip(t, m, k, seed):
    s = _spikes((t, m, k), seed)
    p = pack_spikes_padded(s)
    assert p.dtype == jnp.uint32
    assert p.shape == (t, m, packed_width(k))
    full = unpack_spikes(p)
    np.testing.assert_array_equal(np.asarray(full[..., :k]), np.asarray(s))
    # pad bits are guaranteed-zero — they must never reappear as events
    np.testing.assert_array_equal(np.asarray(full[..., k:]), 0.0)
    assert int(jax.lax.population_count(p).sum()) == int(s.sum())


@pytest.mark.parametrize("k", [1, 31, 32, 33, 64, 97, 128])
def test_pack_unpack_roundtrip_fixed(k):
    _assert_roundtrip(2, 5, k, seed=k)


@given(st.integers(1, 4), st.integers(1, 9), st.integers(1, 130),
       st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip_property(t, m, k, seed):
    _assert_roundtrip(t, m, k, seed)


def test_pack_spikes_rejects_non_multiple_of_32():
    with pytest.raises(ValueError, match="not a multiple"):
        pack_spikes(_spikes((4, 33), 0))


# ------------------------------------------- packed occupancy == dense
@pytest.mark.parametrize("m,k,tm,tk", [(256, 256, 128, 128),
                                       (16, 64, 8, 32),
                                       (24, 96, 8, 32)])
def test_packed_popcount_occupancy_equals_dense_prepass(m, k, tm, tk):
    s = _spikes((m, k), seed=m + k)
    got = packed_tile_occupancy(pack_spikes(s), tm, tk, k=k)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(tile_occupancy(s, tm, tk)))


def test_packed_occupancy_pad_bits_never_inflate_counts():
    # non-multiple-of-32 channels: the padded words' high bits are zero,
    # so the packed map equals the dense map of the zero-padded tensor
    k = 100
    s = _spikes((16, k), seed=7)
    p = pack_spikes_padded(s)
    dense_padded = jnp.pad(s, ((0, 0), (0, packed_width(k) * PACK - k)))
    np.testing.assert_array_equal(
        np.asarray(packed_tile_occupancy(p, 8, 32)),
        np.asarray(tile_occupancy(dense_padded, 8, 32)))


# ---------------------------------------------- loud wrong-width rejection
def test_packed_occupancy_rejects_wrong_width():
    p = pack_spikes(_spikes((16, 64), 1))           # 2 words
    with pytest.raises(ValueError, match="does not cover"):
        packed_tile_occupancy(p, 8, 32, k=128)      # claims 4 words
    with pytest.raises(ValueError, match="not a multiple"):
        packed_tile_occupancy(p, 8, 48)             # tile_k % 32 != 0


def test_event_tensor_rejects_wrong_width_payload():
    p = pack_spikes(_spikes((16, 64), 2))
    with pytest.raises(ValueError, match="does not cover"):
        EventTensor(None, None, packed=p, feature_size=128)
    with pytest.raises(ValueError, match="uint32"):
        EventTensor(None, None, packed=p.astype(jnp.int32), feature_size=64)


def test_packed_matmul_rejects_wrong_width_operand():
    p = pack_spikes(_spikes((16, 64), 3))
    w = jnp.ones((128, 8), jnp.float32)
    with pytest.raises(ValueError, match="does not cover"):
        ops.spike_matmul_packed(p, w, packed_k=128)


# -------------------------------------------- routing and attribution
def _packed_probe(seed=0, n=24):
    drive = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, 64)) * 2.0
    et = lif_fire_events(drive, LIFConfig(), packed=True)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (64, n))
    return drive, et, w


def test_lif_fire_events_packed_carries_no_dense_spikes():
    drive, et, _ = _packed_probe()
    assert et.is_packed and et.spikes is None
    assert et.packed.dtype == jnp.uint32
    assert et.shape == drive.shape
    dense_et = lif_fire_events(drive, LIFConfig(), packed=False)
    np.testing.assert_array_equal(np.asarray(et.dense()),
                                  np.asarray(dense_et.spikes))
    np.testing.assert_array_equal(np.asarray(et.occupancy),
                                  np.asarray(dense_et.occupancy))


def test_packed_event_tensor_routes_to_packed_csr_and_matches_oracle():
    drive, et, w = _packed_probe()
    expect = jnp.matmul(et.dense(), w)
    with dispatch.use_backend("packed-csr-interpret", op="spike_matmul"):
        with dispatch.watch_resolutions() as rec:
            got = dispatch.spike_matmul(et, w)
    routes = {r["backend"] for r in rec if r["op"] == "spike_matmul"}
    assert routes == {"packed-csr-interpret"}, routes
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-5)


def test_packed_apec_and_econv_match_dense_under_packed_pin():
    drive, et, w = _packed_probe(seed=4)
    with dispatch.use_backend("packed-csr-interpret", op="apec_matmul"):
        got = dispatch.apec_matmul(et, w, g=2)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.matmul(et.dense(), w)),
                               atol=1e-5)
    conv_drive = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 8, 32)) * 2
    cet = lif_fire_events(conv_drive, LIFConfig(), packed=True)
    wc = jax.random.normal(jax.random.PRNGKey(7), (3, 3, 32, 8))
    expect = dispatch.call_backend("econv", dispatch.REF, cet.dense(), wc,
                                   stride=1, padding="SAME")
    with dispatch.use_backend("packed-csr-interpret", op="econv"):
        got = dispatch.econv(cet, wc, stride=1, padding="SAME")
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=1e-4)


def test_packed_call_off_family_takes_explicit_unpack_shim():
    """A packed call pinned to a dense-only backend must go through the
    explicit unpack shim — warned, attributed ``+unpack`` — and still
    produce the oracle values. Never a silent reinterpret or densify."""
    _, et, w = _packed_probe(seed=8)
    dispatch.reset_fallback_warnings()
    with dispatch.use_backend(dispatch.REF, op="spike_matmul"):
        with pytest.warns(RuntimeWarning, match="unpack"):
            with dispatch.watch_resolutions() as rec:
                got = dispatch.spike_matmul(et, w)
    routes = {r["backend"] for r in rec if r["op"] == "spike_matmul"}
    assert routes == {"ref+unpack"}, routes
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.matmul(et.dense(), w)),
                               atol=1e-5)


def test_dense_calls_never_auto_select_packed_backends():
    args, kwargs = dispatch.example_inputs("spike_matmul",
                                           jax.random.PRNGKey(0))
    assert "packed" not in dispatch.resolve_name("spike_matmul", *args,
                                                 **kwargs)


def test_packed_family_is_registered_for_the_interpreter_only():
    """No TPU backend consumes packed words (their (128, 4) uint32 blocks
    break Mosaic's (8, 128) block rule), so the CPU interpreter is the
    only platform where packed emission is allowed."""
    for op in ("spike_matmul", "apec_matmul", "econv"):
        packed = [dispatch.get_backend(op, n)
                  for n in dispatch.backend_names(op)
                  if "packed" in dispatch.get_backend(op, n).payload]
        assert packed and all(b.platforms == ("cpu",) for b in packed), op
    assert dispatch.packed_kernels_available()


def test_packed_emission_raises_where_no_packed_kernels(monkeypatch):
    """On a platform without packed kernels (the TPU), packed emission
    must refuse instead of densifying every consumer via the shim."""
    monkeypatch.setattr(dispatch, "packed_kernels_available", lambda: False)
    drive = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64)) * 2
    with pytest.raises(NotImplementedError, match="packed"):
        lif_fire_events(drive, LIFConfig(), packed=True)
    assert lif_fire_events(drive, LIFConfig(), packed=False).spikes \
        is not None


# ----------------------------------------------- pack survival: pooling
def test_max_pool_packed_is_bitwise_or_of_lanes():
    s = _spikes((2, 8, 8, 64), seed=11, p=0.4)
    et = EventTensor.from_spikes(s.reshape(-1, 64), pack=True)
    spatial = EventTensor(None, None, packed=et.packed.reshape(2, 8, 8, 2),
                          feature_size=64)
    pooled = max_pool_events(spatial, 2)
    assert pooled.is_packed
    expect = jax.lax.reduce_window(s, -jnp.inf, jax.lax.max,
                                   (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    np.testing.assert_array_equal(np.asarray(pooled.dense()),
                                  np.asarray(expect))


def test_packed_only_reshape_guards_trailing_axis():
    _, et, _ = _packed_probe(seed=12)
    folded = et.reshape(-1, et.shape[-1])
    assert folded.is_packed and folded.shape == (32, 64)
    with pytest.raises(ValueError, match="explicit unpack"):
        et.reshape(2, 16 * 64)


# ---------------------------------------------- whole-model packed parity
@pytest.mark.slow
def test_spikingformer_forward_packed_matches_dense():
    from repro.configs.base import SpikingConfig
    from repro.models import spikingformer
    params = spikingformer.spikingformer_init(jax.random.PRNGKey(0),
                                              depth=1, dim=32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))

    def logits(packed):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            return np.asarray(spikingformer.spikingformer_apply(
                params, x, n_heads=4,
                spiking_cfg=SpikingConfig(t_steps=2, packed=packed)))

    np.testing.assert_allclose(logits(True), logits(False), atol=1e-4)


# ------------------------------------------------- bytes-moved cost model
def test_bytes_moved_packed_shrinks_spike_stream_32x_only():
    occ = np.array([[3, 0, 1], [0, 5, 0]], np.int32)
    dense = costmodel.matmul_bytes_moved(occ, 256, backend="pallas-csr")
    packed = costmodel.matmul_bytes_moved(occ, 256, backend="packed-csr")
    # same trimmed tile grid — only the spike payload narrows (4B -> 1b)
    assert packed.spike_hbm * 32 == dense.spike_hbm
    assert packed.weight_hbm == dense.weight_hbm
    assert packed.out_hbm == dense.out_hbm
    assert packed.total < dense.total
    assert packed.payload == "packed" and dense.payload == "dense"


def test_spike_tile_bytes_rejects_untileable_packed_width():
    with pytest.raises(ValueError):
        costmodel.spike_tile_bytes(128, 48, payload="packed")


@pytest.mark.parametrize("family", sorted(costmodel.PACKED_BYTES_POINTS))
def test_packed_bytes_points_match_committed_bench(family):
    """Provenance pin: the constants embedded in the cost model must be
    exactly the bytes-ledger rows of the committed BENCH_PR7.json, and
    the packed event stream must clear the 4x reduction floor at the
    high-sparsity points (it is 32x by construction)."""
    pts = costmodel.packed_bytes_points_from_bench(
        str(REPO / "BENCH_PR7.json"), family)
    assert pts == costmodel.PACKED_BYTES_POINTS[family]
    reduction = {pct: f32 / packed for pct, f32, packed in pts}
    for pct in (90, 97):
        assert reduction[pct] >= 4.0, (family, pct, reduction[pct])


# --------------------------------------- no f32 spikes between layers
def _sub_jaxprs(p):
    if hasattr(p, "jaxpr"):
        yield p.jaxpr
    elif hasattr(p, "eqns"):
        yield p
    elif isinstance(p, (list, tuple)):
        for x in p:
            yield from _sub_jaxprs(x)


def _f32_avals_of_shape(jaxpr, shape, hits):
    """Count eqn outputs materialized at `shape` in f32 — descending into
    sub-jaxprs (pjit/scan/custom_vjp bodies run at HBM granularity) but
    NOT into pallas_call kernels, whose internals live in VMEM; a
    pallas_call's own OUTvars do count (they land in HBM)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = v.aval
            if (getattr(aval, "shape", None) == shape
                    and getattr(aval, "dtype", None) == jnp.float32):
                hits.append(str(eqn.primitive))
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                _f32_avals_of_shape(sub, shape, hits)


@pytest.mark.slow
def test_packed_chain_materializes_no_f32_spike_tensor():
    """The tentpole's fusion proof: under packed mode, the jaxpr of a
    fire -> matmul chain (fused Pallas emission pinned, packed-csr
    consumer pinned) contains NO f32 value of the spike shape — the
    uint32 words are the only event payload crossing HBM. The identical
    dense-pinned chain materializes the f32 spikes, validating that the
    walker actually sees them."""
    lif = LIFConfig()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64)) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48))  # N != K

    def chain(packed, consumer):
        def f(x, w):
            et = lif_fire_events(x, lif, packed=packed)
            return dispatch.spike_matmul(et, w)
        with dispatch.use_backend("pallas-interpret", op="lif_scan_occ"), \
                dispatch.use_backend(consumer, op="spike_matmul"):
            return jax.make_jaxpr(f)(x, w)

    spike_shape = x.shape
    hits_packed: list = []
    _f32_avals_of_shape(chain(True, "packed-csr-interpret").jaxpr,
                        spike_shape, hits_packed)
    assert hits_packed == [], \
        f"packed chain materialized f32 spike tensors via {hits_packed}"
    hits_dense: list = []
    _f32_avals_of_shape(chain(False, "pallas-csr-interpret").jaxpr,
                        spike_shape, hits_dense)
    assert hits_dense, "walker found no f32 spikes even on the dense chain"

"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spikes import pack_spikes, unpack_spikes
from repro.kernels import ops, ref
from repro.kernels.lif_scan import lif_scan_pallas
from repro.kernels.sdsa_kernel import (sdsa_apply_pallas, sdsa_packed,
                                       sdsa_status_pallas)
from repro.kernels.spike_matmul import spike_matmul_pallas


# ---------------------------------------------------------------- lif_scan
@pytest.mark.parametrize("t,m,n", [(1, 8, 128), (4, 16, 256), (8, 8, 384),
                                   (2, 32, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_scan_kernel_matches_ref(t, m, n, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (t, m, n)) * 2).astype(dtype)
    out = lif_scan_pallas(x, interpret=True)
    expect = ref.lif_scan_ref(x)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0)


@pytest.mark.parametrize("soft_reset", [True, False])
@pytest.mark.parametrize("decay,v_th", [(0.5, 1.0), (0.9, 0.5), (0.0, 1.0)])
def test_lif_scan_kernel_params(decay, v_th, soft_reset):
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 128)) * 2
    out = lif_scan_pallas(x, decay=decay, v_th=v_th, soft_reset=soft_reset,
                          interpret=True)
    expect = ref.lif_scan_ref(x, decay=decay, v_th=v_th,
                              soft_reset=soft_reset)
    np.testing.assert_allclose(out, expect, atol=0)


def test_lif_wrapper_arbitrary_shape():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 7, 11)) * 2
    out = ops.lif(x)
    expect = ref.lif_scan_ref(x)
    np.testing.assert_allclose(out, expect, atol=0)


@pytest.mark.parametrize("packed", [False, True])
def test_lif_occ_row_split_matches_one_call(monkeypatch, packed):
    """Rows split across calls when one SMEM count table would overflow:
    spikes/words and counts must equal the single-call result."""
    from repro.kernels import lif_scan
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 256)) * 2

    def run():
        if packed:
            return lif_scan.lif_scan_occ_packed_pallas(x, interpret=True)
        return lif_scan._lif_occ_pallas(
            x, decay=0.5, v_th=1.0, soft_reset=True, block_m=8,
            block_n=128, emit_vres=True, interpret=True)
    whole = run()
    monkeypatch.setattr(lif_scan, "_COUNT_WORDS", 8)   # 16 rows per call
    split = run()
    assert len(split) == len(whole)
    for a, b in zip(split, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spikes = whole[0] if not packed else unpack_spikes(whole[0], axis=-1)
    counts = spikes.reshape(2, 8, 8, 2, 128).sum(axis=(2, 4))
    np.testing.assert_array_equal(np.asarray(whole[1]), np.asarray(counts))


# -------------------------------------------------------------------- sdsa
@pytest.mark.parametrize("bh,n,dw", [(2, 16, 2), (4, 256, 4), (1, 512, 1),
                                     (8, 64, 8)])
def test_sdsa_status_kernel_sweep(bh, n, dw):
    k = jax.random.bits(jax.random.PRNGKey(0), (bh, n, dw), jnp.uint32)
    v = jax.random.bits(jax.random.PRNGKey(1), (bh, n, dw), jnp.uint32)
    out = sdsa_status_pallas(k, v, block_n=min(256, n), interpret=True)
    np.testing.assert_array_equal(out, ref.sdsa_status_ref(k, v))


@pytest.mark.parametrize("bh,n,dw", [(2, 64, 4), (3, 128, 2)])
def test_sdsa_full_packed_kernel(bh, n, dw):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.bits(kk, (bh, n, dw), jnp.uint32) for kk in ks)
    out = sdsa_packed(q, k, v, block_n=64, interpret=True)
    np.testing.assert_array_equal(out, ref.sdsa_packed_ref(q, k, v))


@pytest.mark.parametrize("d", [32, 64, 70, 128])
def test_sdsa_wrapper_matches_dense_core(d):
    shape = (2, 3, 24, d)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = ((jax.random.uniform(kk, shape) < 0.4).astype(jnp.float32)
               for kk in ks)
    out = ops.sdsa_or(q, k, v)
    np.testing.assert_array_equal(out, ref.sdsa_unpacked_ref(q, k, v))


def test_packed_roundtrip_property():
    s = (jax.random.uniform(jax.random.PRNGKey(4), (5, 96)) < 0.5
         ).astype(jnp.float32)
    np.testing.assert_array_equal(unpack_spikes(pack_spikes(s)), s)


# ------------------------------------------------------------ spike_matmul
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256)])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_spike_matmul_kernel_sweep(m, k, n, density):
    s = (jax.random.uniform(jax.random.PRNGKey(0), (m, k)) < density
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    out = spike_matmul_pallas(s, w, interpret=True)
    np.testing.assert_allclose(out, ref.spike_matmul_ref(s, w),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spike_matmul_dtypes(dtype):
    s = (jax.random.uniform(jax.random.PRNGKey(2), (128, 256)) < 0.2
         ).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 128)).astype(dtype)
    out = spike_matmul_pallas(s, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.spike_matmul_ref(s, w),
                                                np.float32),
        atol=2e-2, rtol=2e-2)


def test_spike_matmul_skips_empty_tiles_exactly():
    """Zero tiles contribute exactly zero — skipping is lossless."""
    s = jnp.zeros((256, 256), jnp.float32).at[:128, :128].set(
        (jax.random.uniform(jax.random.PRNGKey(4), (128, 128)) < 0.3
         ).astype(jnp.float32))
    w = jax.random.normal(jax.random.PRNGKey(5), (256, 128))
    out = spike_matmul_pallas(s, w, interpret=True)
    np.testing.assert_allclose(out, ref.spike_matmul_ref(s, w), atol=1e-4)


def test_spike_matmul_wrapper_padding():
    s = (jax.random.uniform(jax.random.PRNGKey(6), (100, 200)) < 0.2
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), (200, 60))
    out = ops.spike_matmul(s, w)
    np.testing.assert_allclose(out, ref.spike_matmul_ref(s, w), atol=1e-4,
                               rtol=1e-4)

"""Main-path Pallas kernels compile for a TPU v5e at real widths.

The topology is only described (no chip is attached): Mosaic, the TPU
kernel compiler, runs exactly as it would for the chip and refuses what
the chip would refuse — block shapes off the (8, 128) tiling, lowerings
it lacks, scoped-memory overruns — none of which interpret mode sees.
Every case compiles with ``interpret=False`` and asserts a
``tpu_custom_call`` in the compiled program, so a kernel that quietly
lowered as interpreted HLO cannot pass.

The packed-csr family is absent on purpose: its (block_m, block_k/32)
word blocks break the (8, 128) rule, so it is registered for the CPU
interpreter only (see `kernels.dispatch`).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spikes import occupancy_to_csr, tile_occupancy
from repro.kernels import apec_kernel, lif_scan, sdsa_kernel, spike_matmul

M = K = N = 1024          # matmul widths
T = 4                     # timesteps (the paper's CNNs and SpikingFormer)
SF_BH, SF_TOKENS, SF_WORDS = T * 32 * 8, 64, 1   # SpikingFormer-4-256 SDSA
LM_BH, LM_TOKENS, LM_WORDS = 2 * 32, 256, 2      # causal, head_dim 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _csr(s):
    return occupancy_to_csr(tile_occupancy(s, 128, 128), tiling=(128, 128))


def _apec(pipeline):
    def fn(res, ov, w):
        occ_res = tile_occupancy(res, 128, 128)
        occ_ov = tile_occupancy(ov, 64, 128)
        csr = occupancy_to_csr(occ_res + occ_ov, tiling=(128, 128))
        steps = (csr.tile_m_idx, csr.tile_k_idx)
        return spike_matmul.apec_matmul_csr_pallas(
            res, ov, w, 2, csr, (occ_res[steps] * csr.valid).astype(jnp.int32),
            (occ_ov[steps] * csr.valid).astype(jnp.int32),
            interpret=False, pipeline=pipeline)
    return fn


def _lif_occ(x):
    return lif_scan._lif_occ_pallas(x, decay=0.5, v_th=1.0, soft_reset=True,
                                    block_m=8, block_n=128, emit_vres=False,
                                    interpret=False)


def _lif_grad(x):
    return jax.grad(lambda y: lif_scan.lif_scan_pallas_sg(
        y, 0.5, 1.0, True, 2.0, 8, 128, False).sum())(x)


def _lif_occ_grad(x):
    return jax.grad(lambda y: lif_scan.lif_scan_occ_pallas_sg(
        y, 0.5, 1.0, True, 2.0, 8, 128, False)[0].sum())(x)


f32, u32 = jnp.float32, jnp.uint32
CASES = {
    "lif_scan": (lambda x: lif_scan.lif_scan_pallas(x, interpret=False),
                 [((T, 256, 512), f32)]),
    "lif_scan_surrogate_grad": (_lif_grad, [((T, 256, 512), f32)]),
    "lif_scan_occ": (_lif_occ, [((T, 2048, 256), f32)]),
    "lif_scan_occ_surrogate_grad": (_lif_occ_grad, [((T, 2048, 256), f32)]),
    "spike_matmul_pallas": (
        lambda s, w: spike_matmul.spike_matmul_pallas(
            s, w, tile_occupancy(s, 128, 128), interpret=False),
        [((M, K), f32), ((K, N), f32)]),
    "spike_matmul_pallas_csr": (
        lambda s, w: spike_matmul.spike_matmul_csr_pallas(
            s, w, _csr(s), interpret=False),
        [((M, K), f32), ((K, N), f32)]),
    "spike_matmul_pallas_csr_pipe": (
        lambda s, w: spike_matmul.spike_matmul_csr_pallas(
            s, w, _csr(s), interpret=False, pipeline=True),
        [((M, K), f32), ((K, N), f32)]),
    "apec_matmul_csr": (_apec(False),
                        [((M, K), f32), ((M // 2, K), f32), ((K, N), f32)]),
    "apec_matmul_csr_pipe": (_apec(True), [((M, K), f32),
                                           ((M // 2, K), f32),
                                           ((K, N), f32)]),
    "apec_decompose_packed": (
        lambda p: apec_kernel.apec_decompose_packed(p, 2, interpret=False),
        [((M, K // 32), u32)]),
    "sdsa": (lambda q, k, v: sdsa_kernel.sdsa_packed(
        q, k, v, block_n=SF_TOKENS, interpret=False),
        [((SF_BH, SF_TOKENS, SF_WORDS), u32)] * 3),
    "sdsa_ragged_tokens": (lambda k, v: sdsa_kernel.sdsa_status_pallas(
        k, v, block_n=200, interpret=False),
        [((SF_BH, 200, SF_WORDS), u32)] * 2),
    "causal_sdsa": (lambda kv: sdsa_kernel.sdsa_causal_status_pallas(
        kv, interpret=False), [((LM_BH, LM_TOKENS, LM_WORDS), u32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()

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

The chip benchmark's timed programs (`bench/configs/<config>.py`) compile
here too, at batch 1, to hold the names a profile reads them by: every
kernel's `pallas_call(name=...)`, the per-layer metrics' patterns, and a
layer `named_scope` over every instruction.
"""
import collections
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spikes import occupancy_to_csr, tile_occupancy
from repro.kernels import (apec_kernel, dispatch, lif_scan, sdsa_kernel,
                           spike_matmul)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import BENCH, load_module  # noqa: E402
from bench import rawtrace  # noqa: E402

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


# ------------------------------------------- the benchmark's programs
# Every pallas_call's name; the per-layer metrics find kernels by them.
KERNEL_NAMES = {
    "lif", "lif_occ", "lif_occ_packed", "grad_lif_fwd", "grad_lif_bwd",
    "grad_lif_occ_fwd", "_spike_matmul_predicated", "_spike_matmul_csr_core",
    "_spike_matmul_packed_csr", "apec_matmul_csr", "apec_matmul_packed_csr",
    "apec_decompose", "sdsa_status", "sdsa_apply", "causal_sdsa_status"}
# The registry ops whose Pallas routes run each reader's kernels.
FAMILY_OPS = {"roofline.event_matmul": ("econv", "spike_matmul"),
              "roofline.lif": ("lif_scan", "lif_scan_occ")}
LAYER_SCOPES = {
    "spikingformer-4-256": r"encode|sps\.\d+|block\.\d+\.(attn|ffn)|head",
    "vgg11": r"encode|conv\.\d+|pool\.\d+|head"}
CUSTOM_CALL = re.compile(
    r'^%([\w.\-]+) = .*custom_call_target="tpu_custom_call"')
HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
# an op_name that is a path: the traced program's, not an argument's
HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*/[^"]*)"')


def _instructions(hlo_text):
    """Each instruction's text, without indent or ROOT, leaving out
    reducer bodies (`to_apply`), which run as part of their caller."""
    reducers = set(re.findall(r"to_apply=%([\w.\-]+)", hlo_text))
    out, computation = [], None
    for line in hlo_text.splitlines():
        head = HLO_COMPUTATION.match(line)
        if head:
            computation = head.group(1)
        elif HLO_INSTRUCTION.match(line) \
                and computation not in reducers:
            out.append(re.sub(r"^\s+(ROOT )?", "", line))
    return out


@pytest.fixture(scope="module")
def bench_programs(one_chip):
    """{config: (compiled HLO text, resolutions)} of the benchmark's timed
    programs at batch 1, lowered as the harness lowers them, with dispatch
    steered to the TPU routes. The jit caches are cleared on both sides,
    so that no CPU trace is reused here and no TPU trace leaks out."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        try:
            for config in LAYER_SCOPES:
                base = os.path.join(BENCH, "configs", config)
                model = load_module(base + ".py")
                with open(base + ".json") as f:
                    cfg = json.load(f)
                params = jax.eval_shape(functools.partial(model.init, cfg),
                                        jax.random.key(0))
                params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=one_chip), params)
                x = jax.ShapeDtypeStruct((1, cfg["img"], cfg["img"],
                                          cfg["in_ch"]), jnp.float32,
                                         sharding=one_chip)
                with jax.default_matmul_precision(cfg["matmul_precision"]), \
                        dispatch.watch_resolutions() as rec:
                    lowered = jax.jit(model.program(cfg)).lower(params, x)
                out[config] = (lowered.compile().as_text(), list(rec))
        finally:
            jax.clear_caches()
    return out


@pytest.mark.parametrize("config", sorted(LAYER_SCOPES))
def test_bench_program_kernels_are_named(config, bench_programs):
    hlo, _ = bench_programs[config]
    kernels = [m.group(1) for text in _instructions(hlo)
               for m in [CUSTOM_CALL.match(text)] if m]
    assert kernels
    assert {re.sub(r"\.\d+$", "", k) for k in kernels} <= KERNEL_NAMES


@pytest.mark.parametrize("config", sorted(LAYER_SCOPES))
@pytest.mark.parametrize("metric", sorted(FAMILY_OPS))
def test_bench_reader_patterns_count_pallas_routes(config, metric,
                                                   bench_programs):
    hlo, resolutions = bench_programs[config]
    patterns = load_module(os.path.join(BENCH, "metrics",
                                        metric + ".py")).PATTERNS
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    matched = sum(1 for text in _instructions(hlo) if rx.search(text))
    routes = sum(1 for r in resolutions if r["op"] in FAMILY_OPS[metric]
                 and r["backend"].startswith("pallas"))
    assert routes > 0
    assert matched == routes


@pytest.mark.parametrize("config", sorted(LAYER_SCOPES))
def test_bench_program_layers_are_scoped(config, bench_programs):
    hlo, _ = bench_programs[config]
    scope = re.compile(rf"^({LAYER_SCOPES[config]})$")
    layers = collections.Counter()
    for text in _instructions(hlo):
        op = HLO_OP_NAME.search(text)
        if op:
            layers[rawtrace.layer_of(op.group(1))] += 1
    assert layers
    assert all(scope.match(layer) for layer in layers), layers


@pytest.mark.parametrize("config", sorted(LAYER_SCOPES))
def test_bench_program_has_im2col(config, bench_programs):
    hlo, _ = bench_programs[config]
    assert any(re.search(r"(^|/)im2col/", op.group(1))
               for text in _instructions(hlo)
               for op in [HLO_OP_NAME.search(text)] if op)

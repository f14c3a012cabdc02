"""Registry-driven parity harness: every (op x backend) pair vs the `ref`
oracle, plus override/fallback semantics and an end-to-end model smoke.

Any future kernel becomes parity-tested the moment it registers — the
parametrization below enumerates the live registry, not a hand-kept list.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch

ATOL = 1e-5


@pytest.fixture(autouse=True)
def _no_ambient_backend_override(monkeypatch):
    """These tests pin resolution explicitly; a developer's exported
    EXSPIKE_BACKEND must not leak in and flip expected defaults. Fallback
    warnings dedup per (op, from, to) per process, so each test re-arms
    them to assert its own warning independently."""
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    dispatch.reset_fallback_warnings()

# Every pair runnable on this test platform (CPU). TPU-only backends are
# exercised by the same harness when the suite runs on TPU.
PAIRS = [
    (op, be)
    for op in dispatch.op_names()
    for be in dispatch.backend_names(op)
    if jax.default_backend() in dispatch.get_backend(op, be).platforms
]


def _assert_tree_close(got, expect, atol):
    """Leaf-wise comparison — ops may return pytrees (e.g. `lif_scan_occ`
    returns (spikes, occupancy))."""
    g_leaves = jax.tree.leaves(got)
    e_leaves = jax.tree.leaves(expect)
    assert len(g_leaves) == len(e_leaves)
    for g, e in zip(g_leaves, e_leaves):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(e, np.float32), atol=atol)


@pytest.mark.parametrize("op,backend", PAIRS,
                         ids=[f"{o}-{b}" for o, b in PAIRS])
def test_backend_matches_ref_oracle(op, backend):
    args, kwargs = dispatch.example_inputs(op, jax.random.PRNGKey(0))
    expect = dispatch.call_backend(op, dispatch.REF, *args, **kwargs)
    got = dispatch.call_backend(op, backend, *args, **kwargs)
    _assert_tree_close(got, expect, ATOL)


# -------------------------------------------------------- gradient parity
# Every (op x backend) pair that declares the gradient contract
# (differentiable=True or a vjp= registration): jax.grad of a fixed probe
# loss must match the ref oracle's surrogate gradients. Enumerated from
# the live registry, like the forward pass above.
DIFF_PAIRS = [
    (op, be)
    for op in dispatch.op_names()
    for be in dispatch.differentiable_backend_names(op)
    if jax.default_backend() in dispatch.get_backend(op, be).platforms
]

# Surrogate gradients are exact closed forms (ATan / transpose rules /
# ref-replay), so the only slack needed is f32 association-order drift.
GRAD_ATOL = 1e-4


def _make_probe(out_ref):
    """One fixed probe per output leaf (int leaves — non-differentiated
    aux like the `lif_scan_occ` map — probe to a constant-zero term)."""
    leaves, treedef = jax.tree.flatten(out_ref)
    probes = [jax.random.normal(jax.random.PRNGKey(42 + i), l.shape,
                                jnp.float32) for i, l in enumerate(leaves)]
    return jax.tree.unflatten(treedef, probes)


def _probe_loss(op, backend, kwargs, probe):
    def loss(args):
        out = dispatch.call_backend(op, backend, *args, **kwargs)
        terms = jax.tree.map(
            lambda o, pr: jnp.sum(o.astype(jnp.float32) * pr), out, probe)
        return sum(jax.tree.leaves(terms))
    return loss


@pytest.mark.parametrize("op,backend", DIFF_PAIRS,
                         ids=[f"{o}-{b}" for o, b in DIFF_PAIRS])
def test_grad_matches_ref_oracle(op, backend):
    args, kwargs = dispatch.example_inputs(op, jax.random.PRNGKey(0))
    out_ref = dispatch.call_backend(op, dispatch.REF, *args, **kwargs)
    probe = _make_probe(out_ref)
    g_ref = jax.grad(_probe_loss(op, dispatch.REF, kwargs, probe))(args)
    g = jax.grad(_probe_loss(op, backend, kwargs, probe))(args)
    assert len(g) == len(g_ref)
    for got, expect in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(expect, np.float32),
                                   atol=GRAD_ATOL)


# ---------------------------------- EventTensor-carried forward parity
# The full-event pipeline's gradient contract: a forward whose consumer
# receives the producer's carried occupancy (stop-gradient aux) must
# match the dense-spike forward — values AND jax.grad — for every
# differentiable backend of every map-consuming op. Enumerated from the
# live registry like everything else.
EVENT_CONSUMER_OPS = ("spike_matmul", "apec_matmul", "econv")
EVENT_PAIRS = [
    (op, be)
    for op in EVENT_CONSUMER_OPS
    for be in dispatch.differentiable_backend_names(op)
    if jax.default_backend() in dispatch.get_backend(op, be).platforms
]


def _event_probe_setup(op):
    if op == "econv":
        drive = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 8, 8)) * 2
        w = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 8, 6))
        return drive, w, {"stride": 1, "padding": "SAME"}
    drive = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 48)) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(9), (48, 24))
    return drive, w, ({"g": 2} if op == "apec_matmul" else {})


@pytest.mark.parametrize("op,backend", EVENT_PAIRS,
                         ids=[f"{o}-{b}" for o, b in EVENT_PAIRS])
def test_grad_through_event_tensor_forward_matches_dense(op, backend):
    from repro.core.events import conv_patch_occupancy
    from repro.core.lif import LIFConfig
    from repro.models.layers import lif_fire_events
    drive, w, kwargs = _event_probe_setup(op)
    lif = LIFConfig()

    def forward(x, carried):
        et = lif_fire_events(x, lif)           # fused producer (ref on CPU)
        kw = dict(kwargs)
        if op == "econv":
            et = et.reshape((-1,) + et.shape[2:])     # T*B fold keeps map
            if carried:
                kw["occupancy"] = conv_patch_occupancy(et, w.shape, 1,
                                                       "SAME")
        elif carried:
            kw["occupancy"] = et.occupancy_for(128, 128)
        return dispatch.call_backend(op, backend, et.spikes, w, **kw)

    out_carried = forward(drive, True)
    out_dense = forward(drive, False)
    np.testing.assert_allclose(np.asarray(out_carried),
                               np.asarray(out_dense), atol=1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(42), out_dense.shape)

    def loss(carried):
        return lambda x: jnp.sum(forward(x, carried).astype(jnp.float32)
                                 * probe)

    g_carried = jax.grad(loss(True))(drive)
    g_dense = jax.grad(loss(False))(drive)
    assert bool(jnp.any(g_dense != 0))
    np.testing.assert_allclose(np.asarray(g_carried), np.asarray(g_dense),
                               atol=1e-5)


def test_every_backend_declares_gradient_contract():
    """Training resolves backends exactly like inference, so a forward-only
    registration would be a landmine: any op x backend the resolver can
    pick must be differentiable."""
    for op in dispatch.op_names():
        diff = set(dispatch.differentiable_backend_names(op))
        assert set(dispatch.backend_names(op)) == diff, \
            f"{op}: non-differentiable backends {set(dispatch.backend_names(op)) - diff}"


def test_grad_through_dispatch_resolution():
    """jax.grad through the dispatch() entry point itself (auto resolution,
    no call_backend pinning) — the path the train loop takes."""
    args, kwargs = dispatch.example_inputs("lif_scan", jax.random.PRNGKey(3))
    (x,), _ = args, kwargs

    def loss(x):
        s = dispatch.lif_scan(x, **kwargs)
        return jnp.sum(s * s.shape[-1])
    g = jax.grad(loss)(x)
    assert g.shape == x.shape
    assert bool(jnp.any(g != 0))
    assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("soft_reset,alpha", [(False, 2.0), (True, 3.0),
                                              (False, 4.0)])
def test_grad_parity_lif_hard_reset_and_alpha(soft_reset, alpha):
    """The backward kernel's hard-reset branch ((1-S) - V*sg) and the
    surrogate_alpha plumbing — neither is reachable from the canonical
    example (soft reset, alpha=2), so cover them explicitly."""
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 3, 40)) * 2.0
    probe = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    kwargs = dict(decay=0.6, v_th=0.8, soft_reset=soft_reset,
                  surrogate_alpha=alpha)

    def loss(backend):
        def f(x):
            out = dispatch.call_backend("lif_scan", backend, x, **kwargs)
            return jnp.sum(out * probe)
        return f

    g_ref = jax.grad(loss(dispatch.REF))(x)
    g_pal = jax.grad(loss("pallas-interpret"))(x)
    assert bool(jnp.any(g_ref != 0))
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=GRAD_ATOL)


@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.int8],
                         ids=["bool", "int8"])
@pytest.mark.parametrize("op", ["spike_matmul", "apec_matmul", "econv",
                                "tconv"])
def test_spike_ops_preserve_narrow_input_dtypes(op, dtype):
    """Binary event maps arrive as bool/int8 from quantized producers;
    dispatch entry must NOT silently upcast them (any promotion happens
    inside the op that needs it) and the activation output must come
    back in the weight dtype regardless of the spike storage dtype."""
    args, kwargs = dispatch.example_inputs(op, jax.random.PRNGKey(0))
    s, w = args[0], args[1]
    expect = dispatch.dispatch(op, *args, **kwargs)
    got = dispatch.dispatch(op, s.astype(dtype), *args[1:], **kwargs)
    assert got.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=ATOL)


# ---------------------------------------- im2col by static shifted slices
# Every channel count the benchmark's convolutions take, strides 1 and 2,
# SAME/VALID/explicit padding at 3x3, plus a 1x1 and an even 2x2 kernel.
IM2COL_CASES = (
    [(ci, 3, stride, pad) for ci in (3, 32, 64, 128, 256)
     for stride in (1, 2) for pad in ("SAME", "VALID", ((1, 0), (0, 2)))]
    + [(32, 1, 1, "SAME"), (64, 2, 2, "SAME"), (64, 2, 1, "VALID")])


def _assert_im2col_rows_exact(x, k, stride, padding):
    """`ops.im2col_rows` is lax's patch extraction with the features in
    (kh, kw, C) order, bit for bit, and zeros up to the 128-lane tile.
    Returns (Ho, Wo)."""
    from repro.kernels import ops
    ci = x.shape[-1]
    with jax.default_matmul_precision("highest"):
        lax_patches = jax.lax.conv_general_dilated_patches(
            x, (k, k), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, ho, wo, _ = lax_patches.shape
    expect = np.asarray(lax_patches).reshape(n, ho, wo, ci, k, k)
    expect = expect.transpose(0, 1, 2, 4, 5, 3).reshape(n * ho * wo, -1)
    rows, hw = ops.im2col_rows(x, k, k, stride, padding)
    assert hw == (ho, wo)
    np.testing.assert_array_equal(np.asarray(rows), expect)
    rows, _ = ops.im2col_rows(x, k, k, stride, padding, lanes=128)
    assert rows.shape[1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(rows[:, :expect.shape[1]]),
                                  expect)
    assert not np.any(np.asarray(rows[:, expect.shape[1]:]))
    return ho, wo


@pytest.mark.parametrize(
    "ci,k,stride,padding", IM2COL_CASES,
    ids=[f"ci{c}-k{k}-s{s}-{p if isinstance(p, str) else 'explicit'}"
         for c, k, s, p in IM2COL_CASES])
def test_im2col_rows_exact_and_econv_matches_ref(ci, k, stride, padding):
    """Patch rows equal lax's (values that are no spikes too: VGG's first
    conv takes the direct-coded image); econv's Pallas routes built on
    them match the TConv oracle with and without a carried map."""
    from repro.core.events import EventTensor, conv_patch_occupancy
    kx, ks, kw_ = jax.random.split(jax.random.PRNGKey(ci * 10 + k), 3)
    _assert_im2col_rows_exact(jax.random.normal(kx, (2, 12, 12, ci)), k,
                              stride, padding)

    # image 0 silent, so the carried map has empty row tiles to skip
    s = (jax.random.uniform(ks, (2, 12, 12, ci)) < 0.3).astype(jnp.float32)
    s = s.at[0].set(0.0)
    w = jax.random.normal(kw_, (k, k, ci, 8)) * (k * k * ci) ** -0.5
    expect = dispatch.call_backend("econv", dispatch.REF, s, w,
                                   stride=stride, padding=padding)
    occ = conv_patch_occupancy(EventTensor.from_spikes(s), w.shape, stride,
                               padding) if isinstance(padding, str) else None
    for backend in ("pallas-interpret", "pallas-csr-interpret"):
        for occupancy in ((None, occ) if occ is not None else (None,)):
            got = dispatch.call_backend("econv", backend, s, w,
                                        stride=stride, padding=padding,
                                        occupancy=occupancy)
            np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                       atol=ATOL)


@pytest.mark.parametrize("n,hw,ci,k,stride,padding", [
    (2, 64, 3, 3, 1, "SAME"), (1, 96, 64, 3, 2, "SAME"),
    (1, 160, 16, 2, 2, ((1, 0), (0, 2)))])
def test_im2col_rows_exact_in_bands_of_large_images(n, hw, ci, k, stride,
                                                    padding):
    """An image too large for one kernel step is written in bands of
    output rows, each reading its overlapping input rows: the rows are
    still exact."""
    from repro.kernels import im2col
    x = jax.random.normal(jax.random.PRNGKey(hw), (n, hw, hw, ci))
    ho, wo = _assert_im2col_rows_exact(x, k, stride, padding)
    kk = k * k * ci
    assert im2col._step_shape(n, ho, wo, ci, kk + (-kk % 128), k * k,
                              4)[1] < ho


@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_tconv_pallas_patch_rows_match_ref(padding):
    """The transposed conv's Pallas route builds its patches with the same
    helper and the HWIO weights unpermuted: equal to the oracle at
    stride 2."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    s = (jax.random.uniform(k1, (2, 7, 7, 32)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(k2, (3, 3, 32, 16)) * (9 * 32) ** -0.5
    expect = dispatch.call_backend("tconv", dispatch.REF, s, w, stride=2,
                                   padding=padding)
    got = dispatch.call_backend("tconv", "pallas-interpret", s, w, stride=2,
                                padding=padding)
    assert got.shape == expect.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=ATOL)


def test_sdsa_ops_handle_non_tile_multiple_token_counts():
    """Token counts whose sublane padding is not a block_n multiple
    (e.g. 384 > 256) must still run on the packed kernels — the wrappers
    pick a dividing block size instead of erroring at trace time."""
    for n in (300, 384):
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        q, k, v = ((jax.random.uniform(kk, (2, 1, 2, n, 40)) < 0.3)
                   .astype(jnp.float32) for kk in ks)
        expect = dispatch.call_backend("causal_sdsa", dispatch.REF, q, k, v)
        got = dispatch.call_backend("causal_sdsa", "pallas-interpret",
                                    q, k, v)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
        expect = dispatch.call_backend("sdsa", dispatch.REF, q[0], k[0], v[0])
        got = dispatch.call_backend("sdsa", "pallas-interpret",
                                    q[0], k[0], v[0])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@pytest.mark.slow
def test_grad_parity_large_lif_multi_tile():
    """Fused LIF backward across multiple (bm, bn) grid tiles and a padded
    remainder — exercises the VMEM-carry reversal beyond one program."""
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 4, 2100)) * 2.0
    probe = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def loss(backend):
        def f(x):
            out = dispatch.call_backend("lif_scan", backend, x,
                                        decay=0.5, v_th=1.0)
            return jnp.sum(out * probe)
        return f

    g_ref = jax.grad(loss(dispatch.REF))(x)
    g_pal = jax.grad(loss("pallas-interpret"))(x)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=GRAD_ATOL)


@pytest.mark.parametrize("op", dispatch.op_names())
def test_example_inputs_are_deterministic(op):
    a1, k1 = dispatch.example_inputs(op, jax.random.PRNGKey(7))
    a2, k2 = dispatch.example_inputs(op, jax.random.PRNGKey(7))
    assert k1 == k2
    for x, y in zip(a1, a2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------ override semantics
def test_use_backend_overrides_resolution():
    args, kwargs = dispatch.example_inputs("sdsa", jax.random.PRNGKey(1))
    with dispatch.use_backend("pallas-interpret", op="sdsa"):
        assert dispatch.resolve_name("sdsa", *args, **kwargs) \
            == "pallas-interpret"
    assert dispatch.resolve_name("sdsa", *args, **kwargs) == dispatch.REF


def test_global_override_applies_to_all_ops():
    with dispatch.use_backend(dispatch.REF):
        for op in dispatch.op_names():
            args, kwargs = dispatch.example_inputs(op, jax.random.PRNGKey(2))
            assert dispatch.resolve_name(op, *args, **kwargs) == dispatch.REF


def test_env_var_override(monkeypatch):
    args, kwargs = dispatch.example_inputs("apec_matmul",
                                           jax.random.PRNGKey(3))
    assert dispatch.resolve_name("apec_matmul", *args, **kwargs) == "jnp"
    monkeypatch.setenv(dispatch.ENV_VAR, "apec_matmul=ref")
    assert dispatch.resolve_name("apec_matmul", *args, **kwargs) \
        == dispatch.REF
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
    assert dispatch.resolve_name("apec_matmul", *args, **kwargs) \
        == "pallas-interpret"


def test_unmet_constraint_falls_back_to_ref_with_warning():
    # g does not divide P: the packed APEC kernel must refuse and the call
    # must still produce the exact dense result via ref.
    s = (jax.random.uniform(jax.random.PRNGKey(4), (10, 32)) < 0.5
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (32, 8))
    with dispatch.use_backend("pallas-interpret", op="apec_matmul"):
        with pytest.warns(RuntimeWarning, match="falling back"):
            out = dispatch.apec_matmul(s, w, g=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=ATOL)


def test_auto_resolution_warns_on_capability_fallback():
    """No override at all: when the preferred auto backend refuses the
    inputs (g does not divide P), the silent-looking default path must
    still surface a RuntimeWarning, not quietly lose APEC compression."""
    s = (jax.random.uniform(jax.random.PRNGKey(10), (10, 32)) < 0.5
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), (32, 8))
    with pytest.warns(RuntimeWarning, match="not divisible"):
        out = dispatch.apec_matmul(s, w, g=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=ATOL)


def test_csr_constraint_degrades_to_pallas_not_ref():
    """pallas-csr's declared fallback chain: a CSR-only constraint failure
    (g=3 does not divide the 128-row tile) must degrade to the predicated
    pallas kernel — same family, comparable sweep — never straight to ref.
    """
    s = (jax.random.uniform(jax.random.PRNGKey(20), (12, 32)) < 0.5
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(21), (32, 8))
    with dispatch.use_backend("pallas-csr-interpret", op="apec_matmul"):
        with pytest.warns(RuntimeWarning, match="degrading to "
                          "'pallas-interpret'"):
            assert dispatch.resolve_name("apec_matmul", s, w, g=3) \
                == "pallas-interpret"
        # the same degrade edge is deduped per process — re-arm so the
        # dispatch below demonstrably warns again on its own
        dispatch.reset_fallback_warnings()
        with pytest.warns(RuntimeWarning):
            out = dispatch.apec_matmul(s, w, g=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=ATOL)


def test_csr_fallback_chain_ends_at_ref_when_whole_family_refuses():
    """When the chained backend can't take the inputs either (P % g fails
    for every packed path), the walk must still terminate at ref."""
    s = (jax.random.uniform(jax.random.PRNGKey(22), (10, 32)) < 0.5
         ).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(23), (32, 8))
    with dispatch.use_backend("pallas-csr-interpret", op="apec_matmul"):
        with pytest.warns(RuntimeWarning, match="falling back"):
            out = dispatch.apec_matmul(s, w, g=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(s @ w), atol=ATOL)


def test_unknown_backend_falls_back_to_ref_with_warning():
    args, kwargs = dispatch.example_inputs("sdsa", jax.random.PRNGKey(6))
    with dispatch.use_backend("no-such-backend", op="sdsa"):
        with pytest.warns(RuntimeWarning, match="not registered"):
            out = dispatch.dispatch("sdsa", *args, **kwargs)
    expect = dispatch.call_backend("sdsa", dispatch.REF, *args, **kwargs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_call_backend_raises_instead_of_falling_back():
    args, kwargs = dispatch.example_inputs("sdsa", jax.random.PRNGKey(8))
    kwargs["mode"] = "sum"
    with pytest.raises(ValueError, match="mode"):
        dispatch.call_backend("sdsa", "pallas-interpret", *args, **kwargs)


def test_sdsa_sum_mode_auto_falls_back_under_packed_override():
    """mode='sum' can't run on the bitwise path: override must fall back
    to ref, matching the dense result."""
    args, kwargs = dispatch.example_inputs("sdsa", jax.random.PRNGKey(9))
    kwargs["mode"] = "sum"
    expect = dispatch.call_backend("sdsa", dispatch.REF, *args, **kwargs)
    with dispatch.use_backend("pallas-interpret", op="sdsa"):
        with pytest.warns(RuntimeWarning):
            out = dispatch.dispatch("sdsa", *args, **kwargs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ------------------------------------------------------- end-to-end smoke
def _tiny_spikingformer_logits():
    from repro.configs.base import SpikingConfig
    from repro.models import spikingformer
    params = spikingformer.spikingformer_init(jax.random.PRNGKey(0),
                                              depth=1, dim=32)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    return spikingformer.spikingformer_apply(
        params, x, n_heads=4, spiking_cfg=SpikingConfig(t_steps=2))


@pytest.fixture(scope="module")
def default_logits():
    """Default-resolution logits, computed once for the smoke tests."""
    return np.asarray(_tiny_spikingformer_logits())


def test_model_outputs_identical_ref_vs_default(default_logits):
    """EXSPIKE_BACKEND=ref vs default resolution: identical logits (on CPU
    both resolve to jnp paths; apec/econv/sdsa routing must not drift)."""
    with dispatch.use_backend(dispatch.REF):
        ref_logits = np.asarray(_tiny_spikingformer_logits())
    np.testing.assert_allclose(default_logits, ref_logits, atol=ATOL)


def test_model_outputs_match_under_kernel_backends(default_logits):
    """Whole-model parity with the Pallas (interpret) kernels driving the
    attention core and conv stem — the acceptance gate for swapping real
    TPU kernels in later."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with dispatch.use_backend("pallas-interpret", op="sdsa"), \
                dispatch.use_backend("pallas-interpret", op="econv"):
            kernel_logits = np.asarray(_tiny_spikingformer_logits())
    np.testing.assert_allclose(kernel_logits, default_logits, atol=1e-4)


def test_env_ref_subprocess_like(default_logits, monkeypatch):
    """The documented env knob end to end: set EXSPIKE_BACKEND=ref in this
    process and check the model still produces the same logits."""
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert os.environ[dispatch.ENV_VAR] == "ref"
    np.testing.assert_allclose(np.asarray(_tiny_spikingformer_logits()),
                               default_logits, atol=ATOL)

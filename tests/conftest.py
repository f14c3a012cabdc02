import os
import subprocess
import sys
import textwrap

import pytest

# Tests must see the single real CPU device (the dry-run sets its own
# device-count flag in its own process; never here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Test models are tiny: XLA compile time dominates wall clock, so skip the
# backend optimization pipeline (~30% faster suite; export XLA_FLAGS to
# override).
os.environ.setdefault("XLA_FLAGS", "--xla_backend_optimization_level=0")

try:
    from hypothesis import settings
except ImportError:
    settings = None

if settings is not None:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")


# ---------------------------------------------------------------------------
# Shared 8-host-device subprocess: every multi-device test payload runs in
# ONE child process (one interpreter + jax import + compile session instead
# of one per test module). Payloads are independent try/except sections, so
# one failure doesn't mask the others; each test asserts its own marker.
# ---------------------------------------------------------------------------
MULTIDEVICE_SCRIPT = textwrap.dedent("""
    import os
    # 8 *host* (CPU) devices; pin the platform so jax never probes the TPU
    # runtime — on TPU-toolchain images without a TPU attached, that probe
    # blocks for minutes in libtpu initialization timeouts.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               "--xla_backend_optimization_level=0")
    import tempfile
    import traceback

    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    def section(name, fn):
        try:
            fn()
        except Exception:
            print(name + "_FAIL", flush=True)
            traceback.print_exc()
        else:
            print(name + "_OK", flush=True)

    def ckpt_elastic():
        from repro.checkpoint import checkpointer
        with tempfile.TemporaryDirectory() as d:
            # save on a (4, 2) mesh
            mesh_a = make_mesh((4, 2), ("data", "model"))
            x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
            xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
            checkpointer.save(d, 1, {"x": xa})
            # restore onto a (2, 2) mesh — elastic shrink (data axis halved)
            mesh_b = make_mesh((2, 2), ("data", "model"),
                               devices=jax.devices()[:4])
            sh = {"x": NamedSharding(mesh_b, P("data", "model"))}
            out = checkpointer.restore(d + "/step_000000001", {"x": x}, sh)
            assert out["x"].sharding.mesh.shape["data"] == 2
            np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))

    def elastic_e2e():
        from repro.configs.base import LMConfig, SpikingConfig
        from repro.launch.train import train_loop
        from repro.runtime.elastic import shrunk_mesh
        cfg = LMConfig(name="elastic", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=64, spiking=SpikingConfig(t_steps=1),
                       remat="none", loss_chunk=16)
        with tempfile.TemporaryDirectory() as d:
            mesh_a = make_mesh((4, 2), ("data", "model"))
            out1 = train_loop(cfg, steps=6, batch=8, seq=16, ckpt_dir=d,
                              save_every=3, mesh=mesh_a, log_every=100)
            # 2 of 4 data groups "fail": plan the shrink, rebuild, resume.
            plan = shrunk_mesh((4, 2), ("data", "model"),
                               n_failed_data_groups=2)
            assert plan.mesh_shape == (2, 2) and plan.microbatch_scale == 2
            mesh_b = make_mesh(plan.mesh_shape, plan.axis_names,
                               devices=jax.devices()[:4])
            out2 = train_loop(cfg, steps=10, batch=8, seq=16, ckpt_dir=d,
                              save_every=3, resume=True, mesh=mesh_b,
                              log_every=100)
            assert len(out2["losses"]) == 4            # resumed at step 6
            assert np.isfinite(out2["final_loss"])

    # One jitted train step per (cfg, mesh, spiking): the drill sections
    # replay the same step across healthy/failure/resumed phases, so the
    # jit wrapper must be shared or every phase pays a recompile.
    _DRILL_STEPS = {}

    def _drill_step_fn(cfg, mesh, spiking):
        import functools
        from repro.launch import steps as steps_mod
        from repro.optim import adamw, schedule as sched
        key = (cfg.name, id(mesh), spiking)
        if key not in _DRILL_STEPS:
            schedule_fn = functools.partial(
                sched.warmup_cosine, warmup_steps=2, total_steps=10)
            _DRILL_STEPS[key] = jax.jit(steps_mod.make_train_step(
                cfg, adamw.AdamWConfig(lr=1e-2), schedule_fn,
                spiking=spiking, mesh=mesh))
        return _DRILL_STEPS[key]

    def _drill_loop(cfg, mesh, params, opt_state, batches, start, stop,
                    mgr=None, spiking=False):
        # Feed IDENTICAL global batches regardless of mesh shape (unlike
        # train_loop, which feeds shard 0 local rows — that would give the
        # shrunk mesh different data and no comparable loss trajectory).
        from repro.optim import adamw
        from repro.runtime import sharding
        p_sh = sharding.named(mesh, sharding.param_specs(cfg, params, mesh))
        o_sh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                                mu=p_sh, nu=p_sh)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        step_fn = _drill_step_fn(cfg, mesh, spiking)
        losses = []
        for t in range(start, stop):
            dev = {k: jnp.asarray(v) for k, v in batches[t].items()}
            params, opt_state, metrics = step_fn(params, opt_state, dev)
            losses.append(float(metrics["loss"]))
            if mgr and mgr.should_save(t + 1):
                mgr.save(t + 1, (params, opt_state))
        if mgr:
            mgr.wait()
        return params, opt_state, losses

    def elastic_drill():
        # Recovery drill: mid-training shard loss AND a torn newest
        # checkpoint. restore_latest must walk back to the newest VALID
        # snapshot, reshard_restore must load it onto the shrunk mesh, and
        # the resumed loss trajectory must track the healthy run (same
        # global batches; only fp reduction order differs across meshes).
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs.base import LMConfig, SpikingConfig
        from repro.data import synthetic
        from repro.models import lm
        from repro.optim import adamw
        from repro.runtime import faults
        from repro.runtime.elastic import shrunk_mesh, reshard_restore
        cfg = LMConfig(name="drill", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=64, spiking=SpikingConfig(t_steps=1),
                       remat="none", loss_chunk=16)
        batches = [synthetic.lm_batch(0, 0, t, 8, 16, cfg.vocab)
                   for t in range(10)]
        params0 = lm.init_params(cfg, jax.random.PRNGKey(0))
        opt0 = adamw.init(params0, adamw.AdamWConfig(lr=1e-2))
        mesh_a = make_mesh((4, 2), ("data", "model"))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, save_every=3)
            *_, healthy = _drill_loop(cfg, mesh_a, params0, opt0, batches,
                                      0, 10, mgr=mgr)     # saves 3, 6, 9
            # 2 of 4 data groups die; the newest checkpoint is also torn
            # (writer died with the shard) — recovery must not trust it.
            faults.truncate_checkpoint(os.path.join(d, "step_000000009"))
            plan = shrunk_mesh((4, 2), ("data", "model"),
                               n_failed_data_groups=2)
            assert plan.mesh_shape == (2, 2)
            mesh_b = make_mesh(plan.mesh_shape, plan.axis_names,
                               devices=jax.devices()[:4])
            step, (p, o) = reshard_restore(cfg, mgr, (params0, opt0),
                                           mesh_b)
            assert step == 6, step   # walked back past the torn snapshot
            *_, resumed = _drill_loop(cfg, mesh_b, p, o, batches, 6, 10)
        assert all(np.isfinite(resumed))
        np.testing.assert_allclose(resumed, healthy[6:10],
                                   rtol=0.05, atol=0.05)

    def elastic_packed():
        # The packed-payload config must survive the same elastic
        # roundtrip: checkpoint a SpikingConfig(packed=True) run, restore
        # onto the shrunk mesh, and replay one step — under guard audit —
        # with loss parity vs the pre-failure trajectory.
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs.base import LMConfig, SpikingConfig
        from repro.data import synthetic
        from repro.kernels import dispatch
        from repro.models import lm
        from repro.optim import adamw
        from repro.runtime.elastic import shrunk_mesh, reshard_restore
        cfg = LMConfig(name="drill-packed", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                       vocab=64,
                       spiking=SpikingConfig(t_steps=1, packed=True),
                       remat="none", loss_chunk=16)
        batches = [synthetic.lm_batch(1, 0, t, 8, 16, cfg.vocab)
                   for t in range(5)]
        params0 = lm.init_params(cfg, jax.random.PRNGKey(1))
        opt0 = adamw.init(params0, adamw.AdamWConfig(lr=1e-2))
        mesh_a = make_mesh((4, 2), ("data", "model"))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, save_every=4)
            *_, pre = _drill_loop(cfg, mesh_a, params0, opt0, batches,
                                  0, 5, mgr=mgr, spiking=True)  # saves @4
            plan = shrunk_mesh((4, 2), ("data", "model"),
                               n_failed_data_groups=2)
            mesh_b = make_mesh(plan.mesh_shape, plan.axis_names,
                               devices=jax.devices()[:4])
            step, (p, o) = reshard_restore(cfg, mgr, (params0, opt0),
                                           mesh_b)
            assert step == 4, step
            with dispatch.use_guard("audit"):   # no false positives under
                *_, replay = _drill_loop(cfg, mesh_b, p, o, batches,  # jit
                                         4, 5, spiking=True)
        np.testing.assert_allclose(replay[0], pre[4], rtol=0.05, atol=0.05)

    def shard_map_moe():
        from repro.models import moe
        mesh = make_mesh((2, 4), ("data", "model"))
        p = moe.moe_init(jax.random.PRNGKey(0), 32, 16, n_experts=8)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32),
                              jnp.float32)
        ref = moe.moe_apply(p, x, top_k=2, capacity_factor=8.0)
        with mesh, jax.sharding.set_mesh(mesh):
            p_sh = jax.device_put(p, {
                "router": NamedSharding(mesh, P(None, None)),
                "w_gate": NamedSharding(mesh, P("model", None, None)),
                "w_up": NamedSharding(mesh, P("model", None, None)),
                "w_down": NamedSharding(mesh, P("model", None, None)),
            })
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            out = jax.jit(lambda pp, xx: moe.moe_apply_shard_map(
                pp, xx, top_k=2, capacity_factor=8.0))(p_sh, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def mesh_dispatch():
        import warnings
        from repro.core.spikes import (shard_occupancy_to_csr,
                                       stack_shard_csrs)
        from repro.kernels import dispatch, ops
        from repro.runtime import sharding
        mesh8 = make_mesh((8, 1), ("data", "model"))
        # 1024 rows / 8 shards = 128: per-shard tile grids divide cleanly,
        # so mesh-aware resolution must KEEP the csr family per shard.
        s = (jax.random.uniform(jax.random.PRNGKey(0), (1024, 128)) < 0.05
             ).astype(jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (128, 64), jnp.float32)
        ref = np.asarray(jnp.dot(s, w))        # single-device oracle
        g_ref = np.asarray(jax.grad(lambda ww: jnp.sum(s @ ww))(w))
        with dispatch.use_backend("pallas-csr-interpret", op="spike_matmul"):
            out, rep = sharding.event_op_sharded(
                mesh8, "spike_matmul", s, w, with_report=True)
            assert rep["backend"] == "pallas-csr-interpret", rep
            assert rep["attribution"] == "pallas-csr-interpret", rep
            assert rep["n_shards"] == 8 and rep["occupancy"].imbalance >= 1.0
            np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
            g = jax.grad(lambda ww: jnp.sum(sharding.event_op_sharded(
                mesh8, "spike_matmul", s, ww)))(w)
            np.testing.assert_allclose(np.asarray(g), g_ref, atol=1e-5)
            # per-shard eager work lists (no global-occupancy gather),
            # differentiable like the registry backend (custom transpose)
            stack = stack_shard_csrs(shard_occupancy_to_csr(
                ops.padded_occupancy(s), 8, tiling=(128, 128)))
            out2 = sharding.event_op_sharded(mesh8, "spike_matmul", s, w,
                                             csr_stack=stack)
            np.testing.assert_allclose(np.asarray(out2), ref, atol=1e-5)
            g2 = jax.grad(lambda ww: jnp.sum(sharding.event_op_sharded(
                mesh8, "spike_matmul", s, ww, csr_stack=stack)))(w)
            np.testing.assert_allclose(np.asarray(g2), g_ref, atol=1e-5)
        with dispatch.use_backend("pallas-csr-interpret", op="apec_matmul"):
            out3, rep3 = sharding.event_op_sharded(
                mesh8, "apec_matmul", s, w, g=2, with_report=True)
            assert rep3["attribution"] == "pallas-csr-interpret", rep3
            np.testing.assert_allclose(np.asarray(out3), ref, atol=1e-5)
            g3 = jax.grad(lambda ww: jnp.sum(sharding.event_op_sharded(
                mesh8, "apec_matmul", s, ww, g=2)))(w)
            np.testing.assert_allclose(np.asarray(g3), g_ref, atol=1e-5)
        # 512 rows / 8 shards = 64: ragged per-shard tile grid, so the
        # mesh gate must walk the declared chain — and say so in the
        # attribution — while output parity still holds.
        with dispatch.use_backend("pallas-csr-interpret", op="spike_matmul"):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                out4, rep4 = sharding.event_op_sharded(
                    mesh8, "spike_matmul", s[:512], w, with_report=True)
                rb = dispatch.resolved_backends(mesh=mesh8)
            assert rep4["attribution"] \
                == "pallas-interpret<-pallas-csr-interpret", rep4
            np.testing.assert_allclose(np.asarray(out4), ref[:512],
                                       atol=1e-5)
            # canonical example shapes never fill a per-shard tile, so
            # the mesh-aware resolved_backends map shows the degrade too
            assert rb["spike_matmul"] \
                == "pallas-interpret<-pallas-csr-interpret", rb

    def event_tensor():
        from repro.core.events import EventTensor
        from repro.kernels import dispatch
        from repro.runtime import sharding
        mesh8 = make_mesh((8, 1), ("data", "model"))
        s = (jax.random.uniform(jax.random.PRNGKey(5), (1024, 128)) < 0.05
             ).astype(jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(6), (128, 64), jnp.float32)
        et = EventTensor.from_spikes(s)
        ref = np.asarray(jnp.dot(s, w))
        g_ref = np.asarray(jax.grad(lambda ww: jnp.sum(s @ ww))(w))
        with dispatch.use_backend("pallas-csr-interpret", op="spike_matmul"):
            # concrete carried map -> per-shard TRIMMED work lists built
            # from the tiny map (occupancy_source must say so: the
            # sharded path reuses the producer's emission, it does not
            # rebuild local lists from resident spikes)
            out, rep = sharding.event_op_sharded(
                mesh8, "spike_matmul", et, w, with_report=True)
            assert rep["occupancy_source"] == "carried", rep
            assert rep["attribution"] == "pallas-csr-interpret", rep
            np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
            # traced carried map: sharded occupancy operand inside the
            # shard_map body, fwd AND bwd parity vs single device
            f = jax.jit(lambda ov, ww: sharding.event_op_sharded(
                mesh8, "spike_matmul", s, ww, occupancy=ov))
            np.testing.assert_allclose(np.asarray(f(et.occupancy, w)), ref,
                                       atol=1e-5)
            g = jax.grad(lambda ww: jnp.sum(sharding.event_op_sharded(
                mesh8, "spike_matmul", et, ww)))(w)
            np.testing.assert_allclose(np.asarray(g), g_ref, atol=1e-5)
            g2 = jax.jit(jax.grad(lambda ww: jnp.sum(f(et.occupancy, ww))))(w)
            np.testing.assert_allclose(np.asarray(g2), g_ref, atol=1e-5)
        with dispatch.use_backend("pallas-csr-interpret", op="apec_matmul"):
            out3, rep3 = sharding.event_op_sharded(
                mesh8, "apec_matmul", et, w, g=2, with_report=True)
            assert rep3["occupancy_source"] == "carried", rep3
            np.testing.assert_allclose(np.asarray(out3), ref, atol=1e-5)

    def rebalance_pipe():
        from repro.core.spikes import rebalance_shard_plan
        from repro.kernels import dispatch, ops
        from repro.runtime import sharding
        mesh8 = make_mesh((8, 1), ("data", "model"))
        # Hotspot band: every event in the first quarter of the rows, so
        # the static row-contiguous split piles all occupied tiles onto
        # two shards while 16 tile rows / 8 shards = 2 leaves the
        # occupancy-weighted plan room to move whole tile rows. K = 128
        # (one k-tile) like the other sections, so the per-tile partial
        # sums keep the dense oracle's reduction order at atol=1e-5.
        s_np = np.zeros((2048, 128), np.float32)
        s_np[:512] = (np.random.default_rng(0).random((512, 128)) < 0.3
                      ).astype(np.float32)
        s = jnp.asarray(s_np)
        w = jax.random.normal(jax.random.PRNGKey(1), (128, 64), jnp.float32)
        occ = np.asarray(ops.padded_occupancy(s))
        plan = rebalance_shard_plan(occ, 8)
        assert sorted(plan.perm.tolist()) == list(range(16)), plan
        assert not plan.identity and plan.improves, plan
        ref = np.asarray(s @ w)
        g_ref = np.asarray(jax.grad(lambda ww: jnp.sum(s @ ww))(w))
        gs_ref = np.asarray(jax.grad(lambda ss: jnp.sum(ss @ w))(s))
        # Pipelined backend + rebalanced split composed: the pipe kernel
        # consumes the occupancy-weighted per-shard work lists, outputs
        # permute back, fwd AND both grads match the dense oracle.
        with dispatch.use_backend("pallas-csr-pipe-interpret",
                                  op="spike_matmul"):
            out, rep = sharding.event_op_sharded(
                mesh8, "spike_matmul", s, w, occupancy=occ,
                with_report=True)
            assert rep["attribution"] == "pallas-csr-pipe-interpret", rep
            imb = rep["occupancy"]
            assert imb.pre_per_shard and \
                imb.imbalance < imb.pre_imbalance, imb
            np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
            out_st = sharding.event_op_sharded(
                mesh8, "spike_matmul", s, w, occupancy=occ,
                rebalance=False)
            np.testing.assert_allclose(np.asarray(out_st), ref, atol=1e-5)
            g = jax.grad(lambda ww: jnp.sum(sharding.event_op_sharded(
                mesh8, "spike_matmul", s, ww, occupancy=occ)))(w)
            np.testing.assert_allclose(np.asarray(g), g_ref, atol=1e-5)
            gs = jax.grad(lambda ss: jnp.sum(sharding.event_op_sharded(
                mesh8, "spike_matmul", ss, w, occupancy=occ)))(s)
            np.testing.assert_allclose(np.asarray(gs), gs_ref, atol=1e-5)

    def gspmd_shard():
        # A `per_data_shard` backend (the TPU lif_scan/causal_sdsa
        # kernels, which GSPMD cannot partition) runs per data shard in a
        # shard_map only where a step asks for it (the sharded train
        # step); the interpret twin stands in for the compiled kernel.
        import dataclasses
        from repro.kernels import dispatch
        mesh = make_mesh((4, 2), ("data", "model"))
        kw = dict(decay=0.5, v_th=1.0, soft_reset=True)
        be = dataclasses.replace(
            dispatch.get_backend("lif_scan", "pallas-interpret"),
            per_data_shard=True)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16, 128)) * 2
        x1 = x[:, :1]                           # batch 1, on no mesh
        assert dispatch._gspmd_shard_wrap(be, (x,)) is be   # no mesh
        # A serve step under the mesh (unplaced params/state): run whole.
        with dispatch.use_mesh(mesh):
            assert dispatch._gspmd_shard_wrap(be, (x,)) is be
            assert dispatch._gspmd_shard_wrap(be, (x1,)) is be
        with dispatch.use_mesh(mesh, split_kernels=True):
            wrapped = dispatch._gspmd_shard_wrap(be, (x,))
            whole = dispatch._gspmd_shard_wrap(be, (x1,))
        ref1 = dispatch.call_backend("lif_scan", "ref", x1, **kw)
        for w_ in (be, whole):                  # batch 1 the mesh can't split
            out1 = jax.jit(lambda y, _w=w_: _w.fn(y, **kw))(x1)
            np.testing.assert_array_equal(np.asarray(out1), np.asarray(ref1))
        xs = jax.device_put(x, NamedSharding(mesh, P(None, "data")))
        f = jax.jit(lambda y: wrapped.fn(y, **kw))
        assert "shard_map" in str(jax.make_jaxpr(f)(xs))
        out = f(xs)
        ref = dispatch.call_backend("lif_scan", "ref", x, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert len(out.sharding.device_set) == 8
        g = jax.jit(jax.grad(lambda y: jnp.sum(wrapped.fn(y, **kw))))(xs)
        g_ref = jax.grad(lambda y: jnp.sum(
            dispatch.call_backend("lif_scan", "ref", y, **kw)))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-5)

    section("CKPT_ELASTIC", ckpt_elastic)
    section("ELASTIC_E2E", elastic_e2e)
    section("ELASTIC_DRILL", elastic_drill)
    section("ELASTIC_PACKED", elastic_packed)
    section("SHARD_MAP", shard_map_moe)
    section("MESH_DISPATCH", mesh_dispatch)
    section("EVENT_TENSOR", event_tensor)
    section("REBALANCE_PIPE", rebalance_pipe)
    section("GSPMD_SHARD", gspmd_shard)
""")


class MultideviceRun:
    def __init__(self, stdout: str, stderr: str):
        self.stdout = stdout
        self.stderr = stderr

    def check(self, name: str):
        assert f"{name}_OK" in self.stdout, (
            f"{name} section did not pass in the shared multi-device "
            f"subprocess.\nstdout: {self.stdout[-1000:]}\n"
            f"stderr: {self.stderr[-3000:]}")


_MULTIDEV_PROC = None


def _spawn_multidevice() -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.Popen([sys.executable, "-c", MULTIDEVICE_SCRIPT],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env, cwd=root)


def _uses_multidevice(item) -> bool:
    return "multidevice_run" in getattr(item, "fixturenames", ())


def pytest_collection_modifyitems(session, config, items):
    """Push the multi-device tests to the end of the run so the shared
    subprocess overlaps with the single-process tests ahead of them."""
    items.sort(key=_uses_multidevice)   # stable: only moves consumers last


def pytest_collection_finish(session):
    """Start the shared multi-device subprocess as soon as we know a
    selected test will consume it. Runs after -k/-m deselection, so
    filtered runs don't pay for an unused 8-device child."""
    global _MULTIDEV_PROC
    if _MULTIDEV_PROC is None and any(
            _uses_multidevice(i) for i in session.items):
        _MULTIDEV_PROC = _spawn_multidevice()


@pytest.fixture(scope="session")
def multidevice_run():
    global _MULTIDEV_PROC
    if _MULTIDEV_PROC is None:       # e.g. fixture requested interactively
        _MULTIDEV_PROC = _spawn_multidevice()
    out, err = _MULTIDEV_PROC.communicate(timeout=600)
    return MultideviceRun(out, err)


def pytest_sessionfinish(session, exitstatus):
    """Don't orphan the shared subprocess when a run aborts (-x) before
    any multi-device test consumed the fixture."""
    if _MULTIDEV_PROC is not None and _MULTIDEV_PROC.poll() is None:
        _MULTIDEV_PROC.kill()

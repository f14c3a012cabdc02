"""Run the full-event path and the LM server once on a TPU, and check them.

    python chip_smoke.py [--seed N]     one chip: kernels, paper models, server
    python chip_smoke.py --chips 4      four chips: sharded event ops and
                                        sharded training, nothing else

Every phase goes through the library's own entry points with automatic
backend resolution, jitted, on weights and inputs made from `--seed`.
Each phase is watched with `dispatch.watch_resolutions()`: an op that
resolves to the `ref`/`jnp` oracles, to a `+unpack`/`+repaired` shim or
along a `<-` degrade fails the run, and dispatch RuntimeWarnings are
errors. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; the script
exits non-zero without it when JAX finds no TPU, when a phase raises or
when a check fails. It never sets JAX_PLATFORMS or LIBTPU_INIT_ARGS and
starts no subprocess: one process holds the chip.

The printed times are set-up figures of one run (compile included), not
measurements of speed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# Parity bounds. Kernel parity (phase a) runs on inputs whose arithmetic
# is exact in f32 (dyadic drives, 8-fractional-bit weights), so any
# kernel/oracle difference is a fault; the model parity (phase b) runs
# random f32 weights, where summation order moves a membrane by ~1e-6
# and can flip the rare spike that sits that close to threshold (one in
# ~4e6 on a v5e). A lower-precision matmul route flips far more.
KERNEL_ATOL = 1e-5
MODEL_FLIP_FRACTION = 1e-5      # per layer, kernels vs ref, highest precision
MODEL_LOGIT_ATOL = 1e-3         # max |logit| difference, same pair


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def bad_attribution(attribution: str) -> bool:
    """True for a resolution that is not a compiled kernel of the chip:
    the oracles, an explicit densify/repair shim, or a degrade."""
    base = attribution.split("<-")[0].split("+")[0]
    return (base in ("ref", "jnp") or "<-" in attribution
            or "+unpack" in attribution or "+repaired" in attribution)


def attributions(records) -> dict:
    out: dict = {}
    for r in records:
        out.setdefault(r["op"], set()).add(r["attribution"])
    return {op: sorted(a) for op, a in sorted(out.items())}


def check_attributions(phase: str, records) -> dict:
    seen = attributions(records)
    log(f"[{phase}] attribution: {seen}")
    bad = {op: a for op, a in seen.items() if any(map(bad_attribution, a))}
    check(not bad, f"[{phase}] off-kernel resolution: {bad}")
    return seen


def _quantized(key, shape, bits: int = 8, bound: float = 4.0):
    """Normal samples rounded to `bits` fractional bits: products with
    binary spikes and their sums stay exact in f32 in any order."""
    import jax
    import jax.numpy as jnp
    x = jnp.clip(jax.random.normal(key, shape), -bound, bound)
    return jnp.round(x * 2 ** bits) / 2 ** bits


def _clustered(key, m: int, k: int, live: float = 0.4, density: float = 0.3):
    """Binary (m, k) spikes whose 128x128 tiles are live with probability
    `live` and fire at `density` inside: the CSR kernels skip tiles."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(key)
    tiles = jax.random.uniform(k1, (m // 128, 1, k // 128, 1)) < live
    fire = jax.random.uniform(k2, (m // 128, 128, k // 128, 128)) < density
    return (tiles & fire).astype(jnp.float32).reshape(m, k)


# ------------------------------------------------------------ phase (a)
def kernel_cases(seed: int, width: int = 1024, t: int = 4, suffix: str = ""):
    """(op, backend, args, kwargs) for every main-path TPU registration,
    at real widths: M=K=N=`width`, T=`t`, SpikingFormer-4-256's attention
    shape and a TinyLlama-width causal attention shape."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    lif_x = jnp.round(jax.random.normal(next(ks), (t, 32, 64, 256)) * 128) / 64
    s = _clustered(next(ks), width, width)
    w = _quantized(next(ks), (width, width))
    occ = ops.padded_occupancy(s)

    def binary(shape, density=0.3):
        return (jax.random.uniform(next(ks), shape) < density).astype(
            jnp.float32)
    sf_attn = tuple(binary((t, 8, 8, 64, 32)) for _ in range(3))
    lm_attn = tuple(binary((2, 2, 32, 128, 64)) for _ in range(3))
    lif_kw = {"decay": 0.5, "v_th": 1.0, "soft_reset": True}
    cases = [("lif_scan", "pallas", (lif_x,), lif_kw),
             ("lif_scan_occ", "pallas", (lif_x,), lif_kw)]
    for be in ("pallas", "pallas-csr", "pallas-csr-pipe"):
        cases.append(("spike_matmul", be, (s, w), {"occupancy": occ}))
    for be in ("pallas-csr", "pallas-csr-pipe"):
        cases.append(("apec_matmul", be, (s, w), {"g": 2}))
    cases.append(("sdsa", "pallas", sf_attn, {"mode": "or"}))
    cases.append(("causal_sdsa", "pallas", lm_attn, {"mode": "or"}))
    return [(op, be + suffix, a, kw) for op, be, a, kw in cases]


def phase_kernels(cases) -> None:
    """Each registration jitted on the chip against its `ref` oracle on
    identical inputs, under highest matmul precision."""
    import jax
    import numpy as np

    from repro.kernels import dispatch
    with jax.default_matmul_precision("highest"):
        for op, be, args, kw in cases:
            def run(*a, _be=be, _op=op, _kw=kw):
                return dispatch.call_backend(_op, _be, *a, **_kw)

            def oracle(*a, _op=op, _kw=kw):
                return dispatch.call_backend(_op, "ref", *a, **_kw)
            t0 = time.perf_counter()
            compiled = jax.jit(run).lower(*args).compile()
            compile_s = time.perf_counter() - t0
            n_kernels = compiled.as_text().count("tpu_custom_call")
            if jax.default_backend() == "tpu":
                check(n_kernels > 0, f"{op}/{be}: no Mosaic kernel in HLO")
            got = jax.block_until_ready(compiled(*args))
            want = jax.jit(oracle)(*args)
            err = 0.0
            for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                g, e = np.asarray(g, np.float64), np.asarray(e, np.float64)
                check(g.shape == e.shape, f"{op}/{be}: {g.shape} vs {e.shape}")
                check(np.isfinite(g).all(), f"{op}/{be}: non-finite output")
                err = max(err, float(np.max(np.abs(g - e))) if g.size else 0)
            log(f"[kernels] {op}/{be}: tpu_custom_call={n_kernels} "
                f"max_abs_err={err:.3e} compile_s={compile_s:.2f}")
            check(err <= KERNEL_ATOL,
                  f"{op}/{be}: max |kernel - ref| {err} > {KERNEL_ATOL}")


# ------------------------------------------------------------ phase (b)
def paper_models(seed: int):
    """(name, apply(params, x, collect_stats), params) for the paper's
    SpikingFormer-4-256 and VGG11 at their published shapes."""
    import jax

    from repro.configs import registry
    from repro.configs.base import SpikingConfig
    from repro.models import cnn, spikingformer as sf
    key = jax.random.PRNGKey(seed)
    kw = registry.PAPER_TRANSFORMERS["spikingformer-4-256"]
    sf_params = sf.spikingformer_init(key, **kw)

    def sf_apply(p, x, collect_stats=False):
        return sf.spikingformer_apply(p, x, spiking_cfg=SpikingConfig(
            t_steps=4), collect_stats=collect_stats)
    vgg_cfg = registry.paper_cnn_configs()["vgg11"]
    vgg_params = cnn.vgg11_init(vgg_cfg, key)

    def vgg_apply(p, x, collect_stats=False):
        return cnn.vgg11_apply(vgg_cfg, p, x, collect_stats=collect_stats)
    return [("spikingformer-4-256", sf_apply, sf_params),
            ("vgg11", vgg_apply, vgg_params)]


def phase_models(seed: int, batch: int = 32, img: int = 32) -> None:
    import jax
    import numpy as np

    from repro.kernels import dispatch
    x = jax.random.uniform(jax.random.PRNGKey(seed + 1), (batch, img, img, 3))
    for name, apply, params in paper_models(seed):
        with dispatch.watch_resolutions() as rec:
            t0 = time.perf_counter()
            compiled = jax.jit(apply).lower(params, x).compile()
            compile_s = time.perf_counter() - t0
        logits = np.asarray(compiled(params, x))
        check_attributions(f"models/{name}", rec)
        finite = bool(np.isfinite(logits).all())
        log(f"[models/{name}] input={tuple(x.shape)} T=4 "
            f"logits={logits.shape} finite={finite} "
            f"compile_s={compile_s:.2f}")
        check(finite, f"{name}: non-finite logits")

        # The compared program is traced again (stats on, highest
        # precision), so its own resolutions are checked too.
        stats_fn = jax.jit(lambda p, x_, _a=apply: _a(p, x_, True))
        with jax.default_matmul_precision("highest"):
            with dispatch.watch_resolutions() as rec_stats:
                got, got_stats = stats_fn(params, x)
            check_attributions(f"models/{name}/compared", rec_stats)
            with dispatch.use_backend(dispatch.REF):
                want, want_stats = jax.jit(
                    lambda p, x_, _a=apply: _a(p, x_, True))(params, x)
        logit_err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        flips = [float(np.mean(np.asarray(g) != np.asarray(e)))
                 for g, e in zip(got_stats, want_stats)]
        log(f"[models/{name}] vs ref (highest precision): "
            f"max_logit_err={logit_err:.3e} "
            f"flipped_fraction_per_layer="
            f"{[float(f'{f:.3e}') for f in flips]} "
            f"(bounds: logits {MODEL_LOGIT_ATOL}, "
            f"flips {MODEL_FLIP_FRACTION} per layer)")
        check(logit_err <= MODEL_LOGIT_ATOL,
              f"{name}: logit error {logit_err} > {MODEL_LOGIT_ATOL}")
        check(max(flips) <= MODEL_FLIP_FRACTION,
              f"{name}: flipped spikes {max(flips)} > {MODEL_FLIP_FRACTION}")


# ------------------------------------------------------------ phase (c)
def phase_server(cfg, seed: int, n_requests: int = 4,
                 max_new: int = 8) -> None:
    """`launch.serve.Server` answering `n_requests` requests of `max_new`
    tokens, spiking and dense."""
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.serve import Request, Server
    for spiking in (True, False):
        mode = "spiking" if spiking else "dense"
        rng = np.random.default_rng(seed)
        with dispatch.watch_resolutions() as rec:
            t0 = time.perf_counter()
            server = Server(cfg, n_slots=n_requests, spiking=spiking,
                            seed=seed)
            reqs = [Request(rid=i, prompt=[int(t) for t in
                                           rng.integers(0, cfg.vocab, 8)],
                            max_new=max_new) for i in range(n_requests)]
            for r in reqs:
                server.submit(r)
            server.run_until_drained(max_steps=16 * max_new)
            wall_s = time.perf_counter() - t0
        check_attributions(f"server/{mode}", rec)
        states = [r.state for r in reqs]
        log(f"[server/{mode}] {cfg.name} d_model={cfg.d_model} "
            f"layers={cfg.n_layers} vocab={cfg.vocab}: states={states} "
            f"tokens={[len(r.generated) for r in reqs]} "
            f"steps={server.steps_executed} "
            f"prefills={server.prefills_executed} "
            f"wall_s={wall_s:.2f} (compile included)")
        check(all(r.state == "done" for r in reqs),
              f"server/{mode}: {[(r.state, r.failure_cause) for r in reqs]}")
        check(all(len(r.generated) == max_new for r in reqs),
              f"server/{mode}: wrong token counts")
        del server


# ------------------------------------------------------- four chips
def phase_sharded_ops(mesh, seed: int, m: int = 4096) -> None:
    """`event_op_sharded` for spike_matmul and apec_matmul, rebalance on,
    on hotspot-band spikes (one contiguous active band), against the
    single-device kernel."""
    import jax
    import numpy as np

    from benchmarks.sparsity_sweep import K, N, REBAL_SPARSITIES, \
        hotspot_spikes
    from repro.kernels import dispatch, ops
    from repro.runtime import sharding
    n_dev = mesh.devices.size
    w = _quantized(jax.random.PRNGKey(seed + 2), (K, N))
    with jax.default_matmul_precision("highest"):
        for op, kw in (("spike_matmul", {}), ("apec_matmul", {"g": 2})):
            for sparsity in REBAL_SPARSITIES:
                s = hotspot_spikes(jax.random.PRNGKey(
                    seed + int(sparsity * 1000)), m, K, sparsity)
                occ = np.asarray(ops.padded_occupancy(s))
                out, rep = sharding.event_op_sharded(
                    mesh, op, s, w, occupancy=occ, rebalance=True,
                    with_report=True, **kw)
                with dispatch.watch_resolutions() as rec:
                    single = jax.jit(lambda s_, w_, _op=op, _kw=kw: dispatch.
                                     dispatch(_op, s_, w_, **_kw))(s, w)
                check_attributions(f"mesh/{op}/single", rec)
                check(not bad_attribution(rep["attribution"]),
                      f"mesh/{op}: sharded resolution {rep['attribution']}")
                devices = out.sharding.device_set
                err = float(np.max(np.abs(np.asarray(out)
                                          - np.asarray(single))))
                log(f"[mesh/{op}] s{int(sparsity * 100)} rows={m}: "
                    f"backend={rep['attribution']} "
                    f"source={rep['occupancy_source']} "
                    f"{rep['occupancy'].as_fields()} "
                    f"devices={len(devices)} max_abs_err_vs_single={err:.3e}")
                check(len(devices) == n_dev,
                      f"mesh/{op}: output on {len(devices)} devices")
                check(err <= KERNEL_ATOL,
                      f"mesh/{op}: sharded vs single {err} > {KERNEL_ATOL}")


def phase_sharded_train(mesh, cfg, steps: int = 3, batch: int = 16,
                        seq: int = 256) -> None:
    """`launch.train.train_loop` on a (n, 1) data mesh."""
    import jax
    import numpy as np

    from repro.kernels import dispatch
    from repro.launch.train import train_loop
    n_dev = mesh.devices.size
    with dispatch.watch_resolutions() as rec:
        t0 = time.perf_counter()
        out = train_loop(cfg, steps=steps, batch=batch, seq=seq, mesh=mesh,
                         log_every=1)
        wall_s = time.perf_counter() - t0
    # `train_loop` also resolves every op on its canonical example shapes
    # for its log line; the LM's own ops are the ones checked.
    seen = attributions(rec)
    log(f"[mesh/train] attribution: {seen}")
    for op in ("lif_scan", "causal_sdsa"):
        check(op in seen and not any(map(bad_attribution, seen[op])),
              f"mesh/train: {op} resolved to {seen.get(op)}")
    placed = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(out["params"])}
    losses = out["losses"]
    log(f"[mesh/train] {cfg.name} d_model={cfg.d_model} "
        f"layers={cfg.n_layers} batch={batch} seq={seq}: losses={losses} "
        f"param_device_counts={sorted(placed)} wall_s={wall_s:.2f} "
        f"(compile included)")
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"mesh/train: losses {losses}")
    check(placed == {n_dev}, f"mesh/train: params on {placed} devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded event ops and sharded "
                         "training, on a 4-way data mesh")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    warnings.filterwarnings("error", message="exspike dispatch",
                            category=RuntimeWarning)
    log(f"[device] {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 1:
        phase_kernels(kernel_cases(args.seed))
        phase_models(args.seed)
        phase_server(registry.get_config("tinyllama-1.1b"), args.seed)
    else:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.chips, 1), ("data", "model"),
                         devices=devices[:args.chips])
        phase_sharded_ops(mesh, args.seed)
        # Full TinyLlama width, depth cut to 4 layers: its AdamW state
        # fits a v5e chip's 16 GB per the compiled memory analysis.
        phase_sharded_train(mesh, registry.get_config(
            "tinyllama-1.1b").replace(n_layers=4))
    entries = (sum(len(f) for _, _, f in os.walk(cache_dir))
               if os.path.isdir(cache_dir) else 0)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s; "
        f"compile cache {cache_dir} holds {entries} files")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

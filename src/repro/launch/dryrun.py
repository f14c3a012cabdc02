import os
# 512 *host* (CPU) devices; pin the platform so jax never probes the TPU
# runtime (a multi-minute libtpu timeout on TPU-toolchain images with no
# TPU attached — the dry-run is a CPU-side compile study by design).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (arch x shape) cell, lower + compile the real step function on
the production mesh — single-pod (16, 16) and multi-pod (2, 16, 16) — with
ShapeDtypeStruct inputs (no allocation), then record:

  * memory_analysis()      — proves the cell fits per-device HBM,
  * cost_analysis()        — raw HLO FLOPs/bytes (loop bodies counted once),
  * collective bytes       — HLO-parsed, while-trip-count scaled,
  * analytic step cost     — trip-count-aware FLOPs/bytes (launch.flops),

into results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--cells a:s,a:s,...]
"""
import argparse
import functools
import json
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.launch import flops as flops_mod
from repro.launch import hlo_analysis, specs, steps
from repro.launch.mesh import make_production_mesh, chips
from repro.models import lm
from repro.optim import adamw
from repro.runtime import sharding


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _mem_report(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception as e:        # backend without memory analysis
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    return out


def _cost_report(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    ca = ca[0] if isinstance(ca, list) else ca
    if ca is None:
        return {}
    keep = {}
    for k in ("flops", "bytes accessed", "transcendentals", "utilization"):
        if k in ca:
            keep[k.replace(" ", "_")] = float(ca[k])
    return keep


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               mode: str | None = None, cfg_override=None):
    """Returns (record dict, lowered, compiled)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override or registry.get_config(arch)
    shape = registry.get_shape(shape_name)
    spiking = specs.spiking_for_shape(shape) if mode is None \
        else (mode == "spiking")

    params_abs = specs.abstract_params(cfg)
    pspecs = sharding.param_specs(cfg, params_abs, mesh)
    problems = sharding.validate_specs(params_abs, pspecs, mesh)
    if problems:
        raise ValueError(f"sharding divisibility problems: {problems[:5]}")
    p_sh = _named(mesh, pspecs)
    repl = NamedSharding(mesh, P())

    t0 = time.time()
    with mesh, jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            opt_abs = jax.eval_shape(functools.partial(
                adamw.init, cfg=adamw.AdamWConfig(
                    state_dtype=cfg.opt_state_dtype)), params_abs)
            o_sh = adamw.AdamWState(
                step=repl, mu=_named(mesh, pspecs), nu=_named(mesh, pspecs))
            batch_abs = specs.train_batch_spec(cfg, shape)
            b_sh = _named(mesh, sharding.batch_specs(cfg, batch_abs, mesh))
            fn = steps.make_train_step(cfg, spiking=spiking, mesh=mesh)
            metrics_sh = {"loss": repl, "grad_norm": repl}
            lowered = jax.jit(
                fn, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, metrics_sh),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            batch_abs = specs.prefill_spec(cfg, shape)
            b_sh = _named(mesh, sharding.batch_specs(cfg, batch_abs, mesh))
            fn = steps.make_prefill(cfg, spiking, mesh=mesh)
            bs = sharding.batch_axes(mesh, shape.global_batch) or None
            out_sh = NamedSharding(mesh, P(
                bs, "model" if cfg.vocab % mesh.shape["model"] == 0
                else None))
            lowered = jax.jit(
                fn, in_shardings=(p_sh, b_sh), out_shardings=out_sh,
            ).lower(params_abs, batch_abs)
        else:  # decode / long_decode
            state_abs, tok_abs, pos_abs = specs.decode_specs(
                cfg, shape, spiking)
            s_specs = sharding.decode_state_specs(cfg, state_abs, mesh)
            s_sh = _named(mesh, s_specs)
            bs = None if cfg.tp2d else \
                (sharding.batch_axes(mesh, shape.global_batch) or None)
            tok_sh = NamedSharding(mesh, P(bs))
            logits_sh = NamedSharding(mesh, P(
                bs, "model" if cfg.vocab % mesh.shape["model"] == 0
                else None))
            fn = steps.make_serve_step(cfg, spiking, mesh=mesh)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, s_sh, tok_sh, repl),
                out_shardings=(logits_sh, s_sh), donate_argnums=(1,),
            ).lower(params_abs, state_abs, tok_abs, pos_abs)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    coll_raw = hlo_analysis.collective_bytes_unscaled(hlo)
    analytic = flops_mod.step_cost(cfg, shape, spiking)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips(make_production_mesh(multi_pod=multi_pod)),
        "mode": "spiking" if spiking else "dense",
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": _mem_report(compiled),
        "cost_analysis_raw": _cost_report(compiled),
        "collective_bytes": coll,
        "collective_bytes_unscaled": coll_raw,
        "analytic": analytic.asdict(),
        "hlo_chars": len(hlo),
    }
    return record, lowered, compiled


def run_cell(arch, shape_name, multi_pod, out_dir, mode=None,
             cfg_override=None, suffix=""):
    name = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    if mode:
        name += f"__{mode}"
    if suffix:
        name += f"__{suffix}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name + ".json")
    try:
        record, _, _ = lower_cell(arch, shape_name, multi_pod, mode,
                                  cfg_override=cfg_override)
        record["variant"] = suffix or "baseline"
    except Exception as e:
        record = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "ok": False, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    status = "OK" if record.get("ok") else f"FAIL ({record.get('error')})"
    print(f"[dryrun] {name}: {status}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cells", default=None,
                    help="comma list of arch:shape pairs")
    ap.add_argument("--mode", default=None, choices=["spiking", "dense"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s) for a, s in registry.all_cells()]
    elif args.cells:
        cells = [tuple(c.split(":")) for c in args.cells.split(",")]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    n_ok = 0
    for arch, shape_name in cells:
        rec = run_cell(arch, shape_name, args.multi_pod, args.out, args.mode)
        n_ok += bool(rec.get("ok"))
    print(f"[dryrun] {n_ok}/{len(cells)} cells OK")
    if n_ok < len(cells):
        raise SystemExit(1)


if __name__ == "__main__":
    main()

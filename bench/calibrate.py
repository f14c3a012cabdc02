"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <name> --seeds 12 --control 3 \
        --seconds 2 [--first-seed N] [--out FILE]

In one process on the chip, for each of `--seeds` seeds: make the weights
and the pool, drive the cell's compiled program for `--seconds` at the
cell's own load and compare its sampled answers with the reference, as a
run does. On the first `--control` seeds, also put the control in the
program's place: the reference one precision step below the
configuration's (`refops` "high", three bfloat16 passes, for float32 at
"highest"), on the same requests. The planted faults are read from the
same answers: half of each request's images answered with the other
half's logits, and one answer per request with its classes rotated.
Each reading is one JSON line on standard output; `--out` also writes
them all to a file.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def faults(got):
    """Planted faults on the program's answers (R, B, classes)."""
    out = {}
    if got.shape[1] >= 2:
        half = got.copy()
        h = got.shape[1] // 2
        half[:, h:2 * h] = got[:, :h]
        out["half_batch"] = half
    altered = got.copy()
    altered[:, 0] = got[:, 0][:, ::-1]
    out["answer_altered"] = altered
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import numpy as np
    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    cell = harness.load_cell(args.workload)
    runner = harness.Runner(cell)
    precision = cell.cfg["matmul_precision"]
    lines = []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        runner.prepare(seed)
        if runner.compiled is None:
            runner.compile()
            harness.log(f"[calibrate] resolutions {runner.resolutions}")
        runner.warm_up()
        window = runner.drive(args.seconds)
        picked = runner.sample(window, seed)
        pool_idx = np.array([window.requests[i][0] for i in picked])
        want, _ = runner.reference(pool_idx, precision)
        got = np.stack([window.outputs[i] for i in picked])
        rows = [("program", harness.compare(got, want))]
        rows += [(f"fault.{k}", harness.compare(v, want))
                 for k, v in faults(got).items()]
        if n < args.control:
            control, _ = runner.reference(pool_idx, "high")
            rows.append(("control", harness.compare(control, want)))
        for kind, readings in rows:
            line = {"workload": args.workload, "seed": seed, "kind": kind,
                    "requests": len(window.requests),
                    "compared": int(len(picked)), **readings,
                    "at": time.time()}
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())

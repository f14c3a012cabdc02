"""The command refuses to run, and prints no result, where it must."""
import os
import shutil
import subprocess
import sys

from bench import harness

CMD = [sys.executable, "bench/run.py", "--workload", "vgg11.cifar.b1",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_large_seeds_give_distinct_keys():
    import jax
    keys = [jax.random.key_data(harness.seed_key(s)).tolist()
            for s in (1, 2**32 + 1, 2**33 + 1)]
    assert len({tuple(k) for k in keys}) == 3

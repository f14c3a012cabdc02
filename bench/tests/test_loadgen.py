"""The traffic generator."""
import glob
import json
import os
import time

import jax.numpy as jnp
import numpy as np

from bench import BENCH, harness, loadgen

MIXES = sorted(os.path.basename(p)[:-len(".json")]
               for p in glob.glob(os.path.join(BENCH, "traffic", "*.json")))


def test_pool_is_seeded_and_shaped():
    mix = loadgen.load("cifar.b128")
    mix.update(pool_requests=2, batch=3)
    a = loadgen.make_pool(mix, 2**33 + 1)
    assert a.shape == (2, 3, 32, 32, 3) and a.dtype == np.float32
    assert 0.0 <= a.min() and a.max() <= 1.0
    np.testing.assert_array_equal(a, loadgen.make_pool(mix, 2**33 + 1))
    assert not np.array_equal(a, loadgen.make_pool(mix, 2**33 + 2))


def test_cifar_pool_is_pinned():
    # The traffic must not move: these are the generator's numbers.
    mix = loadgen.load("cifar.b1")
    mix.update(pool_requests=2)
    pool = loadgen.make_pool(mix, 2**33 + 5)
    assert float(pool.sum(dtype=np.float64)) == 2464.20322302121
    np.testing.assert_array_equal(
        pool[1, 0, 5, 7], np.float32([0.049390412867069244,
                                      0.16204382479190826,
                                      0.20978648960590363]))


def test_every_mix_loads():
    assert MIXES
    for name in MIXES:
        mix = loadgen.load(name)
        assert mix["in_flight"] >= 1 and mix["check_requests"] >= 1
        assert os.path.exists(os.path.join(
            BENCH, "images", mix["images"]["source"] + ".py"))


def test_new_source_is_a_file(tmp_path, monkeypatch):
    """A mix with inputs of another shape and type needs only new files."""
    (tmp_path / "images").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "images" / "frames.py").write_text(
        "import numpy as np\n"
        "def make(seed, n, side, polarities):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    return rng.integers(0, 4, (n, side, side, polarities), "
        "np.uint8)\n")
    (tmp_path / "traffic" / "frames.b2.json").write_text(json.dumps({
        "images": {"source": "frames", "side": 8, "polarities": 2},
        "batch": 2, "in_flight": 1, "pool_requests": 3,
        "check_requests": 1}))
    monkeypatch.setattr(loadgen, "BENCH", str(tmp_path))
    pool = loadgen.make_pool(loadgen.load("frames.b2"), 2**33)
    assert pool.shape == (3, 2, 8, 8, 2) and pool.dtype == np.uint8


def test_open_loop_sends_at_the_rate(monkeypatch):
    cell = harness.load_cell("vgg11.cifar.b1")
    cell.mix.update(pool_requests=4, rate_per_s=40.0)
    runner = harness.Runner(cell, program=lambda cfg: (
        lambda params, x: jnp.broadcast_to(x.sum((1, 2, 3))[:, None],
                                           (x.shape[0], 10))))
    runner.prepare(2**32 + 9)
    runner.compile()
    t = time.perf_counter()
    window = runner._loop(seconds=0.5)
    assert time.perf_counter() - t >= 0.5
    holds = [t_hold for _, t_hold, _ in window.requests]
    assert len(holds) == 20
    np.testing.assert_allclose(np.diff(holds), 1 / 40.0, rtol=1e-6)
    assert all(done >= hold for _, hold, done in window.requests)

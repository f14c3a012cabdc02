"""Substrate: optimizer, schedules, grad compression, data, stragglers."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis_compat import given, st

from repro.data import pipeline, synthetic
from repro.optim import adamw, grad_compress, schedule
from repro.runtime.straggler import StragglerConfig, StragglerMonitor


# ------------------------------------------------------------------ AdamW
def test_adamw_minimizes_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0)
    state = adamw.init(params, cfg)
    for _ in range(200):
        grads = jax.tree.map(lambda w: 2 * w, params)
        params, state = adamw.update(grads, state, params, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_adamw_bf16_state_dtype():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    cfg = adamw.AdamWConfig(state_dtype="bfloat16")
    state = adamw.init(params, cfg)
    assert state.mu["w"].dtype == jnp.bfloat16
    _, state2 = adamw.update({"w": jnp.ones(4)}, state, params, cfg)
    assert state2.mu["w"].dtype == jnp.bfloat16


def test_adamw_clip_norm():
    params = {"w": jnp.zeros(3)}
    cfg = adamw.AdamWConfig(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    state = adamw.init(params, cfg)
    big = {"w": jnp.full(3, 1e6)}
    new_params, _ = adamw.update(big, state, params, cfg)
    assert float(jnp.max(jnp.abs(new_params["w"]))) < 10.0


def test_schedule_warmup_cosine():
    s = schedule.warmup_cosine(0, warmup_steps=10, total_steps=100)
    assert float(s) == 0.0
    assert float(schedule.warmup_cosine(10, warmup_steps=10,
                                        total_steps=100)) > 0.9
    end = schedule.warmup_cosine(100, warmup_steps=10, total_steps=100,
                                 min_ratio=0.1)
    np.testing.assert_allclose(float(end), 0.1, atol=1e-5)


# --------------------------------------------------------- grad compression
@given(seed=st.integers(0, 2**16))
def test_compress_decompress_bounded_error(seed):
    g = {"w": jax.random.normal(jax.random.PRNGKey(seed), (64,))}
    ef = grad_compress.init(g)
    wire, scales, ef2 = grad_compress.compress(g, ef)
    back = grad_compress.decompress(wire, scales)
    max_err = float(jnp.max(jnp.abs(back["w"] - g["w"])))
    scale = float(scales["w"])
    assert max_err <= scale * 0.51 + 1e-6     # half-ulp of int8 grid
    # error feedback holds the residual
    np.testing.assert_allclose(np.asarray(ef2.error["w"], np.float32),
                               np.asarray(g["w"] - back["w"]), atol=2e-2)


def test_error_feedback_preserves_signal_over_steps():
    """A constant tiny gradient below one quantization step must still get
    through within a few iterations thanks to error feedback."""
    g = {"w": jnp.full((8,), 1e-3)}
    big = {"w": jnp.zeros(8).at[0].set(1.0)}   # sets scale = 1/127
    ef = grad_compress.init(g)
    acc = jnp.zeros(8)
    for _ in range(20):
        mixed = {"w": g["w"] + big["w"] * 0}
        # keep scale dominated by a separate large entry
        mixed["w"] = mixed["w"].at[0].set(1.0)
        wire, scales, ef = grad_compress.compress(mixed, ef)
        acc = acc + grad_compress.decompress(wire, scales)["w"]
    # entry 1..7 each delivered ~20*1e-3 total despite quant step ~7.9e-3
    np.testing.assert_allclose(acc[1:], 20e-3, rtol=0.2)


def test_wire_dtype_halves_bytes():
    g = {"w": jnp.zeros((128,), jnp.float32)}
    wire, _, _ = grad_compress.compress(g, grad_compress.init(g))
    assert wire["w"].dtype == jnp.bfloat16    # 2B vs 4B on the wire


# ------------------------------------------------------------------- data
def test_synthetic_determinism():
    a = synthetic.lm_batch(0, 3, 7, 4, 16, 100)
    b = synthetic.lm_batch(0, 3, 7, 4, 16, 100)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = synthetic.lm_batch(0, 4, 7, 4, 16, 100)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_markov_structure_learnable():
    batch = synthetic.markov_tokens(0, 0, 0, 8, 256, 64)
    a = 6364136223846793005 % 64
    follows = np.mean(batch[:, 1:] == (a * batch[:, :-1]) % 64)
    assert follows > 0.6                     # 80% greedy transitions


def test_pipeline_prefetch_and_restore():
    mk = lambda shard, step: synthetic.lm_batch(0, shard, step, 2, 8, 50)
    pipe = pipeline.ShardedPipeline(mk, n_shards=2, shard=1).start()
    it = iter(pipe)
    b0, b1 = next(it), next(it)
    state = pipe.state_dict()
    pipe.stop()
    assert state["step"] == 2
    pipe2 = pipeline.ShardedPipeline.restore(mk, state).start()
    b2 = next(iter(pipe2))
    pipe2.stop()
    expect = synthetic.lm_batch(0, 1, 2, 2, 8, 50)
    np.testing.assert_array_equal(b2["tokens"], expect["tokens"])


def test_pipeline_elastic_reshard():
    mk = lambda shard, step: synthetic.lm_batch(0, shard, step, 2, 8, 50)
    pipe = pipeline.ShardedPipeline(mk, n_shards=4, shard=3).start()
    next(iter(pipe))
    state = pipe.state_dict()
    pipe.stop()
    pipe2 = pipeline.ShardedPipeline.restore(mk, state, n_shards=2, shard=1)
    assert pipe2.n_shards == 2 and pipe2.shard == 1 and pipe2.step == 1


# -------------------------------------------------------------- stragglers
def test_straggler_monitor_flags_outliers(monkeypatch):
    """Deterministic: drive the monitor with an injected clock (wall-clock
    sleeps flake under load)."""
    import repro.runtime.straggler as strag
    now = [0.0]
    monkeypatch.setattr(strag.time, "perf_counter", lambda: now[0])
    mon = StragglerMonitor(StragglerConfig(warmup_steps=0, threshold=1.5,
                                           patience=2))
    durations = [0.01, 0.01, 0.01, 0.01, 0.5, 0.5]  # steps 5,6 straggle
    for dt in durations:
        mon.step_start()
        now[0] += dt
        r = mon.step_end()
    assert r["flagged"]
    assert r["exclude_vote"]                  # 2 consecutive -> vote
    assert mon.flagged_steps == [5, 6]


# ------------------------------------------------------ compile cache
def test_compile_cache_defers_to_env_dir(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert calls == []


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    from pathlib import Path

    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    want = str(repo / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()

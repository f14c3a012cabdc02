"""Beyond-paper optimization paths (§Perf): spec validity + equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.models import lm, moe
from repro.runtime import sharding


def _mesh():
    return AbstractMesh((16, 16), ("data", "model"))


def test_tp2d_param_specs_valid():
    cfg = registry.get_config("mistral-large-123b").replace(tp2d=True)
    abs_params = lm.abstract_params(cfg)
    specs = sharding.param_specs(cfg, abs_params, _mesh())
    assert not sharding.validate_specs(abs_params, specs, _mesh())
    # tp2d shards over both axes where divisible (weights resident)
    assert specs["lm_head"] == P(None, ("data", "model"))


def test_pure_fsdp_param_specs_valid():
    cfg = registry.get_config("qwen3-4b").replace(pure_fsdp=True)
    abs_params = lm.abstract_params(cfg)
    specs = sharding.param_specs(cfg, abs_params, _mesh())
    assert not sharding.validate_specs(abs_params, specs, _mesh())
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    # no pure-TP col/row specs remain: at most one sharded dim per leaf
    for s in flat:
        assert sum(ax is not None for ax in s) <= 1


def test_padded_expert_bank_routes_only_real_experts():
    p = moe.moe_init(jax.random.PRNGKey(0), 32, 16, n_experts=6,
                     bank_size=8)
    assert p["w_gate"].shape[0] == 8 and p["router"].shape[-1] == 6
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    out = moe.moe_apply(p, x, top_k=2, capacity_factor=8.0)
    # same routing math as an unpadded bank with identical weights
    p6 = {k: (v[:6] if k in ("w_gate", "w_up", "w_down") else v)
          for k, v in p.items()}
    out6 = moe.moe_apply(p6, x, top_k=2, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out6, np.float32), atol=1e-5)


def test_decode_dus_and_masked_update_agree():
    from repro.models import transformer as tfm
    p = tfm.attn_init(jax.random.PRNGKey(0), 64, 4, 2, 16)
    cache = tfm.kv_cache_init(2, 8, 2, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64), jnp.bfloat16)
    kw = dict(n_heads=4, n_kv=2, d_head=16)
    o1, c1 = tfm.attention_dense_decode(p, x, cache, jnp.int32(3),
                                        masked_cache_update=True, **kw)
    o2, c2 = tfm.attention_dense_decode(p, x, cache, jnp.int32(3),
                                        masked_cache_update=False, **kw)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-2)
    np.testing.assert_array_equal(np.asarray(c1.k, np.float32),
                                  np.asarray(c2.k, np.float32))


@pytest.mark.slow
def test_moe_shard_map_equivalence_multidevice(multidevice_run):
    """Manual-EP shard_map MoE == GSPMD moe_apply on a real 2x4 mesh
    (shared 8-host-device subprocess; see conftest.multidevice_run)."""
    multidevice_run.check("SHARD_MAP")


def test_moe_shard_map_falls_back_without_mesh():
    p = moe.moe_init(jax.random.PRNGKey(0), 32, 16, n_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    out = moe.moe_apply_shard_map(p, x, top_k=2, capacity_factor=8.0)
    ref = moe.moe_apply(p, x, top_k=2, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

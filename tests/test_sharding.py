"""Sharding rules validated against the production mesh shapes for every
assigned arch (AbstractMesh — no devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.models import lm
from repro.runtime import sharding


def _mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divisible(arch, multi_pod):
    cfg = registry.get_config(arch)
    mesh = _mesh(multi_pod)
    abs_params = lm.abstract_params(cfg)
    specs = sharding.param_specs(cfg, abs_params, mesh)
    problems = sharding.validate_specs(abs_params, specs, mesh)
    assert not problems, problems[:5]


@pytest.mark.parametrize("arch", ["mistral-large-123b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b"])
def test_big_arch_params_are_model_sharded(arch):
    """The big archs must not replicate their matrices (HBM would blow)."""
    cfg = registry.get_config(arch)
    mesh = _mesh()
    abs_params = lm.abstract_params(cfg)
    specs = sharding.param_specs(cfg, abs_params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(abs_params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    replicated_big = [
        (p, l.shape) for (p, l), s in zip(flat, flat_s)
        if l.size > 64 * 1024 * 1024 and all(ax is None for ax in s)]
    assert not replicated_big, replicated_big[:5]


def test_batch_axes_divisibility():
    mesh = _mesh(multi_pod=True)
    assert sharding.batch_axes(mesh, 256) == ("pod", "data")
    assert sharding.batch_axes(mesh, 32) == ("pod", "data")
    assert sharding.batch_axes(mesh, 2) == ("pod",)
    assert sharding.batch_axes(mesh, 1) == ()
    single = _mesh()
    assert sharding.batch_axes(single, 128) == ("data",)
    assert sharding.batch_axes(single, 8) == ()   # 8 % 16 != 0


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_decode_state_specs_build(arch):
    cfg = registry.get_config(arch)
    mesh = _mesh()
    import functools
    state_abs = jax.eval_shape(functools.partial(
        lm.init_decode_state, cfg, 128, 1024, False))
    specs = sharding.decode_state_specs(cfg, state_abs, mesh)
    problems = sharding.validate_specs(state_abs, specs, mesh)
    assert not problems, problems[:5]


def test_fsdp_shards_optimizer_dim():
    cfg = registry.get_config("mistral-large-123b")
    assert cfg.fsdp
    mesh = _mesh()
    abs_params = lm.abstract_params(cfg)
    specs = sharding.param_specs(cfg, abs_params, mesh)
    # embed spec should carry the data axis for FSDP
    assert specs["embed"] == P("model", "data")

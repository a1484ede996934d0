"""Sharding rules, state-sharding trees, and a miniature dry-run: lower and
compile real step functions on a small forced-host-device mesh."""

import os

import pytest

# must be set before jax initializes devices in this test process; harmless
# if another test already initialized (we then skip the mesh-size asserts)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig, ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import DEFAULT_RULES, spec_for, use_mesh
from repro.parallel.state_sharding import (
    abstract_caches,
    abstract_train_state,
    batch_sharding,
    cache_sharding,
    train_state_sharding,
    with_sharding,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 host devices (XLA_FLAGS set too late)"
)


def _mesh():
    return make_mesh((2, 4), ("data", "model"))


def test_spec_for_divisibility_and_dedup():
    with use_mesh(_mesh()):
        # divisible: sharded
        assert spec_for(("embed", "mlp"), (64, 64)) == jax.sharding.PartitionSpec("data", "model")
        # non-divisible dim is dropped
        assert spec_for(("embed", "mlp"), (63, 64)) == jax.sharding.PartitionSpec(None, "model")
        # duplicate mesh axis: first logical axis wins
        s = spec_for(("experts", "embed", "mlp"), (8, 64, 64))
        assert s == jax.sharding.PartitionSpec("model", "data", None)


def test_state_sharding_covers_every_leaf():
    cfg = get_config("qwen3-0.6b_smoke")
    rc = RunConfig(moments_dtype="int8")
    with use_mesh(_mesh()):
        state = abstract_train_state(cfg, rc)
        sh = train_state_sharding(cfg, rc, state)
        leaves_s = jax.tree.leaves(sh)
        leaves_a = jax.tree.leaves(state)
        assert len(leaves_s) == len(leaves_a)
        assert all(s is not None for s in leaves_s)
        # at least the embedding must actually be sharded
        flat, _ = jax.tree_util.tree_flatten_with_path(sh)
        emb = [s for p, s in flat if "embedding" in str(p)]
        assert any(s.spec != jax.sharding.PartitionSpec(None, None) for s in emb)


@pytest.mark.parametrize("arch", ["qwen3-0.6b_smoke", "deepseek-v2-lite-16b_smoke", "falcon-mamba-7b_smoke"])
def test_mini_dryrun_train(arch):
    """lower+compile a real train_step on the 2x4 mesh (reduced config)."""
    from repro.models.model import input_specs
    from repro.train.train_step import build_train_step

    cfg = get_config(arch)
    rc = RunConfig(dtype="float32", param_dtype="float32", remat="block")
    shape = ShapeConfig("t", 16, 4, "train")
    with use_mesh(_mesh()):
        state = abstract_train_state(cfg, rc)
        state_sh = with_sharding(state, train_state_sharding(cfg, rc, state))
        specs = input_specs(cfg, shape)
        batch_sh = with_sharding(specs, batch_sharding(specs))
        compiled = jax.jit(build_train_step(cfg, rc)).lower(state_sh, batch_sh).compile()
        assert compiled.cost_analysis() is not None


def test_mini_dryrun_decode():
    from repro.serve import build_decode

    cfg = get_config("qwen3-0.6b_smoke")
    rc = RunConfig(dtype="float32", param_dtype="float32", remat="none")
    with use_mesh(_mesh()):
        from repro.models import param_sharding
        from repro.parallel.sharding import shape_structs
        from repro.models import model_spec

        params = shape_structs(model_spec(cfg), jnp.float32)
        params_sh = with_sharding(params, param_sharding(cfg, rc))
        caches = abstract_caches(cfg, rc, 4, 32)
        caches_sh = with_sharding(caches, cache_sharding(cfg, rc, caches))
        toks = jax.ShapeDtypeStruct((4, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = (
            jax.jit(build_decode(cfg, rc)).lower(params_sh, caches_sh, toks, pos).compile()
        )
        assert compiled is not None


def test_rules_have_no_unknown_axes():
    mesh_axes = {"pod", "data", "model", None}
    for logical, mesh_ax in DEFAULT_RULES.items():
        if isinstance(mesh_ax, tuple):
            assert all(a in mesh_axes for a in mesh_ax), logical
        else:
            assert mesh_ax in mesh_axes, logical

"""FSDP/ZeRO sharding-rule tests (reference: tests/fsdp/test_fsdp.py strategy matrix,
tests/deepspeed/test_deepspeed.py stage mapping — here as pure placement checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin, ZeroPlugin
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.parallel.sharding import fsdp_partition_spec, supports_host_offload
from accelerate_tpu.utils import ShardingStrategy


class TestFsdpPartitionSpec:
    def test_shards_largest_divisible_dim(self):
        assert fsdp_partition_spec((128, 64), 8, 0) == PartitionSpec("fsdp", None)
        assert fsdp_partition_spec((64, 128), 8, 0) == PartitionSpec(None, "fsdp")

    def test_small_params_replicated(self):
        assert fsdp_partition_spec((4, 4), 8, min_weight_size=2**12) == PartitionSpec()

    def test_indivisible_falls_back_to_next_dim(self):
        # 10 not divisible by 8, 64 is
        assert fsdp_partition_spec((10, 64), 8, 0) == PartitionSpec(None, "fsdp")

    def test_nothing_divisible_replicates(self):
        assert fsdp_partition_spec((7, 9), 8, 0) == PartitionSpec()

    def test_fsdp_size_one_replicates(self):
        assert fsdp_partition_spec((128, 64), 1, 0) == PartitionSpec()


def _state_for(strategy):
    acc = Accelerator(
        fsdp_plugin=FullyShardedDataParallelPlugin(sharding_strategy=strategy, min_weight_size=8)
    )
    params = {"w": jnp.ones((16, 8)), "tiny": jnp.ones((2,))}
    return acc.create_train_state(params=params, tx=optax.adamw(1e-3))


class TestStrategies:
    def test_full_shard(self):
        state = _state_for(ShardingStrategy.FULL_SHARD)
        assert "fsdp" in str(state.params["w"].sharding.spec)
        mu_specs = [
            str(x.sharding.spec)
            for x in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(x, "sharding") and x.shape == (16, 8)
        ]
        assert all("fsdp" in s for s in mu_specs)

    def test_shard_grad_op_params_replicated(self):
        state = _state_for(ShardingStrategy.SHARD_GRAD_OP)
        assert str(state.params["w"].sharding.spec) == "PartitionSpec()"
        mu_specs = [
            str(x.sharding.spec)
            for x in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(x, "sharding") and x.shape == (16, 8)
        ]
        assert all("fsdp" in s for s in mu_specs)

    def test_no_shard_all_replicated(self):
        state = _state_for(ShardingStrategy.NO_SHARD)
        specs = {
            str(x.sharding.spec)
            for x in jax.tree_util.tree_leaves((state.params, state.opt_state))
            if hasattr(x, "sharding")
        }
        assert specs == {"PartitionSpec()"}

    def test_small_params_replicated_under_full_shard(self):
        state = _state_for(ShardingStrategy.FULL_SHARD)
        assert str(state.params["tiny"].sharding.spec) == "PartitionSpec()"


class TestStepKeepsPlacement:
    def test_state_keeps_its_placement_and_the_step_compiles_once(self):
        """The compiled step must hand the state back placed as it came in.
        Left to XLA's sharding propagation a vector the policy keeps
        replicated (under ``min_weight_size``) returns sharded over ``fsdp``
        — and a placement spelled ``P("fsdp", None)`` returns as
        ``P("fsdp")`` — so the second step saw "new" input shardings and
        compiled again."""
        from accelerate_tpu.utils.jax_compat import jit_cache_size

        acc = Accelerator(
            mesh={"fsdp": 4},
            fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=2**12),
        )
        params = {"w": jnp.ones((256, 64)) * 0.01, "scale": jnp.ones((64,))}
        state = acc.create_train_state(params=params, tx=optax.adamw(1e-2))
        placed = jax.tree_util.tree_map(lambda x: x.sharding, state)
        assert "fsdp" in str(placed.params["w"].spec)
        assert placed.params["scale"].spec == PartitionSpec()

        def loss_fn(p, batch, rng=None):
            return jnp.mean((batch["x"] @ p["w"] * p["scale"]) ** 2)

        step = acc.compile_train_step(loss_fn)
        batch = {"x": jnp.ones((8, 256))}
        for _ in range(3):
            state, _ = step(state, batch)
        assert jax.tree_util.tree_map(lambda x: x.sharding, state) == placed
        assert jit_cache_size(step._jitted) == 1


class TestZeroMapping:
    @pytest.mark.parametrize(
        "stage,shards_params,shards_opt",
        [(0, False, False), (1, False, True), (2, False, True), (3, True, True)],
    )
    def test_stage_mapping(self, stage, shards_params, shards_opt):
        fsdp = ZeroPlugin(zero_stage=stage).to_fsdp_plugin()
        assert fsdp.shards_params == shards_params
        assert fsdp.shards_opt_state == shards_opt

    def test_invalid_stage(self):
        with pytest.raises(ValueError):
            ZeroPlugin(zero_stage=5)

    @pytest.mark.parametrize("stage,shards_grads", [(0, False), (1, False), (2, True), (3, True)])
    def test_stage_gradient_sharding(self, stage, shards_grads):
        # ZeRO-1 shards only opt state (grads all-reduced); ZeRO-2 also shards
        # the gradient buffer (reduce-scatter comm pattern).
        fsdp = ZeroPlugin(zero_stage=stage).to_fsdp_plugin()
        assert fsdp.shards_grads == shards_grads

    @pytest.mark.parametrize("stage", [1, 2])
    def test_grad_accum_buffer_sharding_differs_by_stage(self, stage):
        from accelerate_tpu.state import AcceleratorState, GradientState

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        acc = Accelerator(
            deepspeed_plugin=ZeroPlugin(zero_stage=stage),
            gradient_accumulation_steps=2,
        )
        state = acc.create_train_state(params={"w": jnp.ones((128, 64))}, tx=optax.adamw(1e-3))
        spec = str(state.grad_accum["w"].sharding.spec)
        if stage == 1:
            assert "fsdp" not in spec, f"stage 1 grads must stay replicated, got {spec}"
        else:
            assert "fsdp" in spec, f"stage 2 grads must shard over fsdp, got {spec}"
        # opt state shards either way
        mu_specs = [
            str(x.sharding.spec)
            for x in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(x, "sharding") and x.shape == (128, 64)
        ]
        assert all("fsdp" in s for s in mu_specs)

    def test_stage1_and_stage2_numerics_match(self):
        from accelerate_tpu.models.transformer import Transformer, TransformerConfig, lm_loss_fn
        from accelerate_tpu.state import AcceleratorState, GradientState

        cfg = TransformerConfig.tiny()
        model = Transformer(cfg)
        batch = {
            "input_ids": np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
        }
        losses = []
        for stage in (1, 2):
            GradientState._reset_state()
            AcceleratorState._reset_state(reset_partial_state=True)
            acc = Accelerator(
                deepspeed_plugin=ZeroPlugin(zero_stage=stage), gradient_accumulation_steps=2
            )
            params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 16), jnp.int32))["params"]
            state = acc.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
            step = acc.compile_train_step(lm_loss_fn(model))
            for _ in range(4):
                state, metrics = step(state, batch)
            losses.append(float(jax.device_get(metrics["loss"])))
        np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


class TestHybridMesh:
    def test_hybrid_mesh_builds(self):
        mesh = build_mesh({"dp": 2, "fsdp": 4}, dcn_axes={"dp": 2})
        assert dict(mesh.shape) == {"dp": 2, "fsdp": 4}

    def test_hybrid_mesh_rejects_non_dividing_dcn(self):
        with pytest.raises(ValueError, match="must divide"):
            build_mesh({"dp": 2, "fsdp": 4}, dcn_axes={"dp": 4})

    def test_hybrid_mesh_rejects_unknown_dcn_axis(self):
        with pytest.raises(ValueError, match="not present"):
            build_mesh({"dp": 2, "fsdp": 4}, dcn_axes={"pp": 2})

    def test_offload_not_supported_on_cpu(self):
        mesh = build_mesh({"dp": 8})
        assert not supports_host_offload(mesh)

    def test_offload_falls_back_with_warning(self):
        acc = Accelerator(
            deepspeed_plugin=ZeroPlugin(zero_stage=2, offload_optimizer_device="cpu")
        )
        state = acc.create_train_state(params={"w": jnp.ones((16, 8))}, tx=optax.adamw(1e-3))
        kinds = {
            x.sharding.memory_kind
            for x in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(x, "sharding")
        }
        # fallback on the CPU backend: everything stays in the backend's
        # default memory
        assert kinds == {jax.devices()[0].default_memory().kind}
        with pytest.warns(UserWarning, match="TPU runtime"):
            acc.compile_train_step(lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2))

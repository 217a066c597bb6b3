"""PowerSGD gradient compression (reference DDPCommunicationHookType.POWER_SGD,
utils/dataclasses.py:105-199; TPU design in parallel/compression.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.parallel.compression import (
    compressed_pmean,
    compression_stats,
    is_compressible,
    powersgd_init,
)
from accelerate_tpu.parallel.mesh import shard_map
from accelerate_tpu.utils.dataclasses import CollectiveKwargs


def _pmean_harness(grads, state, dp=4):
    """Run compressed_pmean under shard_map on a dp mesh: grads have a leading
    replica axis (dp, ...); state errors likewise."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:dp]), ("dp",))

    g_specs = jax.tree_util.tree_map(lambda _: P("dp"), grads)
    s_specs = jax.tree_util.tree_map(
        lambda x: None if x is None else {"q": P(), "error": P("dp")},
        state,
        is_leaf=lambda x: x is None or (isinstance(x, dict) and "q" in x),
    )

    def run(g, s):
        g = jax.tree_util.tree_map(lambda x: x[0], g)
        s = jax.tree_util.tree_map(
            lambda e: None if e is None else {"q": e["q"], "error": e["error"][0]},
            s,
            is_leaf=lambda x: x is None or (isinstance(x, dict) and "q" in x),
        )
        ghat, ns = compressed_pmean(g, s, "dp")
        ns = jax.tree_util.tree_map(
            lambda e: None if e is None else {"q": e["q"], "error": e["error"][None]},
            ns,
            is_leaf=lambda x: x is None or (isinstance(x, dict) and "q" in x),
        )
        return ghat, ns

    return jax.jit(
        shard_map(
            run, mesh=mesh,
            in_specs=(g_specs, s_specs),
            out_specs=(jax.tree_util.tree_map(lambda _: P(), grads), s_specs),
            check_vma=False,
        )
    )(grads, state)


class TestCompressionCore:
    def test_is_compressible(self):
        assert is_compressible((64, 64), rank=2, min_size=16)
        assert not is_compressible((64,), rank=2, min_size=16)          # 1-D
        assert not is_compressible((4, 4), rank=2, min_size=4096)       # too small

    def test_full_rank_is_exact_mean(self):
        # r >= min(m, n): P spans col(G), so PQ'^T reconstructs the mean exactly.
        dp, m, n = 4, 12, 8
        key = jax.random.PRNGKey(1)
        grads = {"w": jax.random.normal(key, (dp, m, n))}
        params = {"w": jnp.zeros((m, n))}
        state = powersgd_init(params, rank=n, min_compression_size=1, replicas=dp)
        ghat, _ = _pmean_harness(grads, state, dp=dp)
        np.testing.assert_allclose(ghat["w"], grads["w"].mean(0), rtol=1e-4, atol=1e-5)

    def test_error_feedback_accumulates_residual(self):
        # After one round: error == (local grad) - (rank-r approx); the approx
        # is the same on every replica while errors differ.
        dp, m, n = 4, 16, 16
        grads = {"w": jax.random.normal(jax.random.PRNGKey(2), (dp, m, n))}
        params = {"w": jnp.zeros((m, n))}
        state = powersgd_init(params, rank=2, min_compression_size=1, replicas=dp)
        ghat, ns = _pmean_harness(grads, state, dp=dp)
        err = np.asarray(ns["w"]["error"])
        for r in range(dp):
            np.testing.assert_allclose(
                err[r], np.asarray(grads["w"][r] - ghat["w"]), rtol=1e-4, atol=1e-5
            )

    def test_uncompressible_leaves_plain_pmean(self):
        dp = 4
        grads = {"b": jax.random.normal(jax.random.PRNGKey(3), (dp, 32))}
        params = {"b": jnp.zeros((32,))}
        state = powersgd_init(params, rank=2, min_compression_size=1, replicas=dp)
        assert state["b"] is None
        ghat, _ = _pmean_harness(grads, state, dp=dp)
        np.testing.assert_allclose(ghat["b"], grads["b"].mean(0), rtol=1e-5)

    def test_compression_stats(self):
        params = {"w": jnp.zeros((256, 256)), "b": jnp.zeros((256,))}
        state = powersgd_init(params, rank=4, min_compression_size=1)
        stats = compression_stats(params, state)
        assert stats["floats_uncompressed"] == 256 * 256 + 256
        assert stats["floats_compressed"] == 4 * (256 + 256) + 256
        assert stats["compression_ratio"] > 20


def _quadratic_setup(accelerator, rank=None, seed=0):
    """Tiny least-squares model; big enough matrices to engage compression."""
    key = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(key, (32, 16)) * 0.1, "b": jnp.zeros((16,))}

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    tx = optax.sgd(0.1)
    state = accelerator.create_train_state(params=params, tx=tx)
    step = accelerator.compile_train_step(loss_fn)
    return state, step, loss_fn


def _batch(n=32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n, 32))
    w_true = jax.random.normal(k2, (32, 16)) * 0.5
    return {"x": x, "y": x @ w_true}


class TestPowerSGDTrainStep:
    def test_full_rank_matches_uncompressed(self):
        # rank >= min(m, n) makes PowerSGD an exact mean -> identical training.
        base = Accelerator(mesh={"dp": 4})
        state_u, step_u, _ = _quadratic_setup(base)
        from accelerate_tpu.state import AcceleratorState, GradientState

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        acc_c = Accelerator(
            mesh={"dp": 4},
            kwargs_handlers=[
                CollectiveKwargs(comm_hook="powersgd", powersgd_rank=16, comm_hook_min_size=1)
            ],
        )
        state_c, step_c, _ = _quadratic_setup(acc_c)
        batch = _batch()
        for i in range(3):
            state_u, mu = step_u(state_u, batch)
            state_c, mc = step_c(state_c, batch)
        np.testing.assert_allclose(
            np.asarray(state_u.params["w"]), np.asarray(state_c.params["w"]),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(float(mu["loss"]), float(mc["loss"]), rtol=1e-4)

    def test_low_rank_converges(self):
        acc = Accelerator(
            mesh={"dp": 4},
            kwargs_handlers=[
                CollectiveKwargs(comm_hook="powersgd", powersgd_rank=2, comm_hook_min_size=1)
            ],
        )
        state, step, loss_fn = _quadratic_setup(acc)
        batch = _batch()
        first = float(loss_fn(state.params, batch))
        for i in range(100):
            state, metrics = step(state, batch)
        assert float(metrics["loss"]) < first * 0.1
        # error feedback is per-replica: leading axis == dp
        assert state.comm_state["w"]["error"].shape[0] == 4

    def test_powersgd_composes_with_fsdp(self):
        """HYBRID_SHARD composition (partial-auto shard_map): a dp2 x fsdp2
        run must train IDENTICALLY to a dp2-only run on the same global
        batches — fsdp is placement, not a different computation — and the
        params must actually shard over fsdp."""
        from accelerate_tpu import FullyShardedDataParallelPlugin
        from accelerate_tpu.state import AcceleratorState, GradientState

        hook = [CollectiveKwargs(comm_hook="powersgd", powersgd_rank=2, comm_hook_min_size=1)]
        acc_dp = Accelerator(mesh={"dp": 2}, kwargs_handlers=hook)
        state_dp, step_dp, _ = _quadratic_setup(acc_dp)
        batch = _batch()
        for _ in range(4):
            state_dp, m_dp = step_dp(state_dp, batch)

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        acc_h = Accelerator(
            mesh={"dp": 2, "fsdp": 2},
            fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=0),
            kwargs_handlers=hook,
        )
        state_h, step_h, _ = _quadratic_setup(acc_h)
        specs = {str(x.sharding.spec) for x in jax.tree_util.tree_leaves(state_h.params)}
        assert any("fsdp" in s for s in specs), specs
        for _ in range(4):
            state_h, m_h = step_h(state_h, batch)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(state_h.params["w"])),
            np.asarray(jax.device_get(state_dp.params["w"])),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(float(m_h["loss"]), float(m_dp["loss"]), rtol=1e-4)

    def test_powersgd_rejects_model_parallel_mesh(self):
        acc = Accelerator(
            mesh={"dp": 2, "tp": 2},
            kwargs_handlers=[CollectiveKwargs(comm_hook="powersgd")],
        )
        params = {"w": jnp.zeros((32, 16))}
        with pytest.raises(ValueError, match="dp/fsdp"):
            acc.create_train_state(params=params, tx=optax.sgd(0.1))

    def test_powersgd_rejects_fp16(self):
        acc = Accelerator(
            mixed_precision="fp16",
            mesh={"dp": 4},
            kwargs_handlers=[CollectiveKwargs(comm_hook="powersgd")],
        )
        params = {"w": jnp.zeros((32, 16))}
        with pytest.raises(ValueError, match="loss scaling"):
            acc.create_train_state(params=params, tx=optax.sgd(0.1))

    def test_unknown_hook_rejected(self):
        acc = Accelerator(
            mesh={"dp": 4},
            kwargs_handlers=[CollectiveKwargs(comm_hook="topk")],
        )
        params = {"w": jnp.zeros((32, 16))}
        with pytest.raises(ValueError, match="Unknown"):
            acc.create_train_state(params=params, tx=optax.sgd(0.1))

    def test_scalar_batch_leaf_replicates(self):
        # rank-0 batch leaves can't shard over dp; they must replicate (the
        # SPMD path's _constrain_batch behavior).
        acc = Accelerator(
            mesh={"dp": 4},
            kwargs_handlers=[
                CollectiveKwargs(comm_hook="powersgd", powersgd_rank=2, comm_hook_min_size=1)
            ],
        )
        params = {"w": jnp.zeros((32, 16))}

        def loss_fn(p, batch):
            pred = batch["x"] @ p["w"]
            return batch["coef"] * jnp.mean((pred - batch["y"]) ** 2)

        state = acc.create_train_state(params=params, tx=optax.sgd(0.1))
        step = acc.compile_train_step(loss_fn)
        b = _batch()
        b["coef"] = jnp.float32(2.0)
        state, metrics = step(state, b)
        assert np.isfinite(float(metrics["loss"]))

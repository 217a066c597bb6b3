"""Chunked host-offloaded optimizer updates (utils/chunked_update.py — the
DeepSpeedCPUAdam/ZeRO-Offload parity piece; reference DeepSpeedPlugin
offload_optimizer_device="cpu")."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.utils.chunked_update import build_chunked_tx, partition_leaves
from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin


def _params(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    # two leaves > 1MB/12 elements each -> 1MB chunking yields multiple groups
    return {
        "w1": jax.random.normal(k1, (300, 300)) * 0.05,
        "w2": jax.random.normal(k2, (300, 300)) * 0.05,
        "b": jnp.zeros((300,)),
    }


def _loss_fn(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"])
    pred = h @ p["w2"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _batch(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (16, 300))
    return {"x": x, "y": jax.random.normal(k2, (16, 300))}


class TestPartition:
    def test_partition_respects_budget(self):
        params = _params()
        groups = partition_leaves(params, 300 * 300 * 12 + 1)
        # each big leaf alone busts the next add -> w1 | w2+b or similar split
        assert len(groups) >= 2
        flat = [i for g in groups for i in g]
        assert sorted(flat) == list(range(3))  # every leaf exactly once

    def test_single_group_returns_original_tx(self):
        tx = optax.adamw(1e-3)
        out_tx, info = build_chunked_tx(tx, _params(), 10**12)
        assert out_tx is tx and info is None

    def test_chained_tx_math_matches_plain(self):
        params = _params()
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        plain = optax.adamw(1e-3)
        chained, info = build_chunked_tx(plain, params, 300 * 300 * 12 + 1)
        assert info is not None and len(info["groups"]) >= 2
        s0, s1 = plain.init(params), chained.init(params)
        u0, _ = plain.update(grads, s0, params)
        u1, _ = chained.update(grads, s1, params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), u0, u1
        )

    def test_sliced_view_math_matches_plain(self):
        # ONE leaf far bigger than the budget: must slice along axis 0 (the
        # scan-stacked-layers case) and still match the plain transform.
        params = {"stack": jax.random.normal(jax.random.PRNGKey(0), (48, 64, 64)) * 0.1}
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        plain = optax.adamw(1e-3)
        chunk_bytes = 8 * 64 * 64 * 12  # ~8 rows per slice
        chained, info = build_chunked_tx(plain, params, chunk_bytes)
        assert info is not None
        assert len(info["spec"][0]) >= 6      # the leaf was sliced
        assert len(info["groups"]) >= 6
        s0, s1 = plain.init(params), chained.init(params)
        u0, _ = plain.update(grads, s0, params)
        u1, _ = chained.update(grads, s1, params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8), u0, u1
        )


class TestChunkedTraining:
    def _train(self, accelerator, steps=5):
        params = _params()
        state = accelerator.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        step = accelerator.compile_train_step(_loss_fn, max_grad_norm=1.0)
        batch = _batch()
        for _ in range(steps):
            state, metrics = step(state, batch)
        return state, metrics

    def test_matches_unchunked_training(self):
        from accelerate_tpu.state import AcceleratorState, GradientState

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # offload-unsupported fallback on CPU
            acc_c = Accelerator(
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=1,
                )
            )
            assert acc_c is not None
            state_c, metrics_c = self._train(acc_c)
            assert acc_c._chunk_info is not None and len(acc_c._chunk_info["groups"]) >= 2

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        acc_p = Accelerator()
        state_p, metrics_p = self._train(acc_p)

        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
            ),
            state_c.params,
            state_p.params,
        )
        assert int(state_c.step) == int(state_p.step) == 5

    def test_with_gradient_accumulation(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                gradient_accumulation_steps=2,
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=1,
                ),
            )
        params = _params()
        state = acc.create_train_state(params=params, tx=optax.sgd(0.1), seed=0)
        step = acc.compile_train_step(_loss_fn)
        batch = _batch()
        p0 = np.asarray(state.params["w1"])
        state, m1 = step(state, batch)          # micro-step: no update
        np.testing.assert_array_equal(np.asarray(state.params["w1"]), p0)
        assert int(state.step) == 0
        state, m2 = step(state, batch)          # sync: chunked update applies
        assert int(state.step) == 1
        assert not np.array_equal(np.asarray(state.params["w1"]), p0)

    def test_checkpoint_roundtrip(self, tmp_path):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=1,
                )
            )
        state, _ = self._train(acc, steps=2)
        acc.save_state(str(tmp_path / "ck"), state=state)
        restored = acc.load_state(str(tmp_path / "ck"), state=state)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            state.opt_state,
            restored.opt_state,
        )


class TestOverlapWindow:
    """Double-buffered chunk dispatch (offload_update_overlap): numerics must
    be identical to the fully serialized window — the window only changes
    when the host barrier lands, never what is computed."""

    def _train(self, overlap, steps=4):
        from accelerate_tpu.state import AcceleratorState, GradientState

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=1,
                    offload_update_overlap=overlap,
                )
            )
        params = _params()
        state = acc.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        assert acc._chunk_info is not None
        assert acc._chunk_info["overlap"] == overlap
        step = acc.compile_train_step(_loss_fn, max_grad_norm=1.0)
        batch = _batch()
        for _ in range(steps):
            state, metrics = step(state, batch)
        return state

    def test_overlap_matches_serialized(self):
        s1 = self._train(overlap=1)
        s2 = self._train(overlap=2)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            s1.params, s2.params,
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            s1.opt_state, s2.opt_state,
        )


class TestAutoChunkBytes:
    def test_fills_headroom(self):
        from accelerate_tpu.utils.chunked_update import auto_chunk_bytes

        # 2.13B-param bf16-working/bf16-grad config on a 16 GB chip (the zero3
        # bench shape): resident ~8.5 GB, margin 1.6 GB -> ~5.9 GB free over
        # a serialized window at the swept 6x budget => ~1 GB chunks (the
        # size an earlier round's sweep measured best).
        params = {"w": jax.ShapeDtypeStruct((2_130_000, 1000), jnp.float32)}
        chunk = auto_chunk_bytes(
            params,
            working_bytes_per_element=2,
            grad_bytes_per_element=2,
            shard_degree=1,
            overlap=1,
            hbm_bytes=16 << 30,
        )
        assert (700 << 20) < chunk < (1200 << 20)

    def test_sharding_scales_global_chunk(self):
        from accelerate_tpu.utils.chunked_update import auto_chunk_bytes

        params = {"w": jax.ShapeDtypeStruct((2_130_000, 1000), jnp.float32)}
        c1 = auto_chunk_bytes(
            params, working_bytes_per_element=2, grad_bytes_per_element=2,
            shard_degree=1, overlap=2, hbm_bytes=16 << 30,
        )
        c4 = auto_chunk_bytes(
            params, working_bytes_per_element=2, grad_bytes_per_element=2,
            shard_degree=4, overlap=2, hbm_bytes=16 << 30,
        )
        # 4-way sharding quarters the resident set AND multiplies the global
        # chunk by the shard degree (each device streams only its shard)
        assert c4 > 2 * c1

    def test_clamps_to_floor_when_no_headroom(self):
        from accelerate_tpu.utils.chunked_update import auto_chunk_bytes

        params = {"w": jax.ShapeDtypeStruct((8_000_000, 1000), jnp.float32)}
        chunk = auto_chunk_bytes(
            params, working_bytes_per_element=2, grad_bytes_per_element=2,
            overlap=2, hbm_bytes=16 << 30,
        )
        assert chunk == 64 << 20

    def test_detect_hbm_cpu_standin_and_unknown_device_raises(self):
        from accelerate_tpu.utils.chunked_update import detect_hbm_bytes

        # the CPU rig reports no memory_stats: a labelled one-v5e stand-in
        assert detect_hbm_bytes() == 16 << 30

        class Reporting:
            platform, device_kind = "tpu", "TPU v5 lite"
            def memory_stats(self):
                return {"bytes_limit": 15 << 30}

        class Silent(Reporting):
            def memory_stats(self):
                return None

        assert detect_hbm_bytes(Reporting()) == 15 << 30
        with pytest.raises(ValueError, match="bytes_limit"):
            detect_hbm_bytes(Silent())

    def test_accelerator_resolves_auto(self):
        from accelerate_tpu.state import AcceleratorState, GradientState

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=-1,
                )
            )
        params = _params()
        state = acc.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        # tiny params on a >=16 GB budget: auto picks a chunk far bigger than
        # the whole state -> single group -> chunking dissolves
        assert acc._chunk_info is None
        assert state is not None


class TestNvmeTier:
    """Disk-backed optimizer state (ZeroPlugin offload_optimizer_device="nvme"
    + nvme_path — reference DeepSpeedPlugin nvme knobs,
    /root/reference/src/accelerate/utils/dataclasses.py:806-834).  Numerics
    must match the in-memory path exactly; the state must actually live in
    .dat files and come back as mmaps."""

    def _train(self, accelerator, steps=4):
        params = _params()
        state = accelerator.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        step = accelerator.compile_train_step(_loss_fn, max_grad_norm=1.0)
        batch = _batch()
        for _ in range(steps):
            state, metrics = step(state, batch)
        return state, metrics

    def _reset(self):
        from accelerate_tpu.state import AcceleratorState, GradientState

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)

    def test_matches_in_memory_training(self, tmp_path):
        import os

        from accelerate_tpu.utils.dataclasses import ZeroPlugin

        self._reset()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc_d = Accelerator(
                deepspeed_plugin=ZeroPlugin(
                    zero_stage=2,
                    offload_optimizer_device="nvme",
                    nvme_path=str(tmp_path / "opt"),
                    offload_update_chunk_mb=1,
                )
            )
        state_d, _ = self._train(acc_d)
        assert acc_d._chunk_info is not None
        assert acc_d._chunk_info.get("disk_store") is not None
        # the state's opt leaves are disk-backed mmaps, and .dat files exist
        arrs = [
            x for x in jax.tree_util.tree_leaves(state_d.opt_state)
            if hasattr(x, "dtype") and not isinstance(x, jax.Array)
        ]
        assert arrs, "no disk-backed optimizer leaves"
        assert any(isinstance(x, np.memmap) for x in arrs)
        dats = [
            f for root, _, files in os.walk(tmp_path / "opt") for f in files
            if f.endswith(".dat")
        ]
        assert dats, "no .dat chunk files written"

        self._reset()
        acc_p = Accelerator()
        state_p, _ = self._train(acc_p)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
            ),
            state_d.params, state_p.params,
        )

    def test_rejects_unchunkable_state(self, tmp_path):
        from accelerate_tpu.utils.dataclasses import ZeroPlugin

        self._reset()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                deepspeed_plugin=ZeroPlugin(
                    zero_stage=2,
                    offload_optimizer_device="nvme",
                    nvme_path=str(tmp_path / "opt"),
                    offload_update_chunk_mb=1024,  # whole tiny state fits one chunk
                )
            )
        with pytest.raises(ValueError, match="single chunk"):
            acc.create_train_state(params=_params(), tx=optax.adamw(1e-2), seed=0)

    def test_gradient_accumulation_on_disk(self, tmp_path):
        from accelerate_tpu.utils.dataclasses import ZeroPlugin

        self._reset()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                gradient_accumulation_steps=2,
                deepspeed_plugin=ZeroPlugin(
                    zero_stage=2,
                    offload_optimizer_device="nvme",
                    nvme_path=str(tmp_path / "opt"),
                    offload_update_chunk_mb=1,
                ),
            )
        params = _params()
        state = acc.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        step = acc.compile_train_step(_loss_fn)
        batch = _batch()
        p0 = np.asarray(state.params["w1"])
        state, _ = step(state, batch)
        np.testing.assert_array_equal(np.asarray(state.params["w1"]), p0)
        state, _ = step(state, batch)
        assert int(state.step) == 1
        assert not np.array_equal(np.asarray(state.params["w1"]), p0)


class TestMasterWeights:
    """ZeRO-Offload weight split (utils/chunked_update.with_master_weights):
    fp32 masters inside the (offloaded) optimizer state, compute-dtype params."""

    def test_fp32_wrapper_matches_plain(self):
        from accelerate_tpu.utils.chunked_update import with_master_weights

        params = _params()
        grads = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.01, params)
        plain = optax.adamw(1e-3)
        wrapped = with_master_weights(plain)
        sp, sw = plain.init(params), wrapped.init(params)
        p_plain, p_wrap = params, params
        for _ in range(3):
            u, sp = plain.update(grads, sp, p_plain)
            p_plain = optax.apply_updates(p_plain, u)
            u, sw = wrapped.update(grads, sw, p_wrap)
            p_wrap = optax.apply_updates(p_wrap, u)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
            p_plain, p_wrap,
        )

    def test_bf16_training_with_masters(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            acc = Accelerator(
                mixed_precision="bf16",
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    sharding_strategy="NO_SHARD",
                    offload_optimizer=True,
                    offload_update_chunk_mb=1,
                ),
            )
        params = _params()
        state = acc.create_train_state(params=params, tx=optax.adamw(1e-2), seed=0)
        # device params are compute-dtype; fp32 masters live in the opt state
        assert state.params["w1"].dtype == jnp.bfloat16
        masters = [
            s.inner_state["master"]
            for s in state.opt_state
            if hasattr(s, "inner_state") and isinstance(s.inner_state, dict)
        ]
        assert masters and all(
            jax.tree_util.tree_leaves(m)[0].dtype == jnp.float32 for m in masters
        )
        step = acc.compile_train_step(_loss_fn, max_grad_norm=1.0)
        batch = _batch()
        first = None
        for _ in range(30):
            state, metrics = step(state, batch)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first * 0.7
        # params track cast(master) each applied step; the bias leaf is small
        # enough to live whole in one chunk's master subtree
        m_b = next(
            s.inner_state["master"]["b"]
            for s in state.opt_state
            if hasattr(s, "inner_state") and isinstance(s.inner_state, dict)
            and hasattr(s.inner_state["master"].get("b"), "astype")
        )
        # params track cast(master) to within bf16 rounding of the delta add
        np.testing.assert_allclose(
            np.asarray(state.params["b"], np.float32),
            np.asarray(m_b.astype(jnp.bfloat16), np.float32),
            rtol=2e-2, atol=1e-3,
        )

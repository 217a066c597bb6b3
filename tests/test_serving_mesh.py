"""Multi-chip serving: tensor-parallel engines + prefix-affinity replicas.

Two contracts under test.  Tensor parallel: ``ServingEngine(mesh=...)`` must
shard the KV pool on the head axis (per-device bytes = total / tp) and the
params column-parallel (``SERVING_TP_RULES``) while staying TOKEN-IDENTICAL
to tp=1 — greedy, sampled, speculative, and quantized-KV alike — within the
same compiled-executable budget.  Replicas: ``ReplicaRouter`` must place
requests where their prefix KV already lives, fall back to least-loaded,
fail over when a replica refuses, and aggregate stats across engines.

Identity tests run float32 for the same reason ``test_serving.py`` does:
token-exactness needs full-precision argmax margins, not bf16 ties.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.parallel.mesh import build_mesh, replica_meshes
from accelerate_tpu.serving import PagedKVPool, ReplicaRouter, ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry


def _tiny_model(seed=0, **kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _mesh_tp2():
    return build_mesh({"tp": 2}, devices=jax.devices()[:2])


def _engine(model, params, **kw):
    defaults = dict(num_slots=4, max_len=64, prefill_buckets=(8, 16),
                    decode_window=4, registry=MetricsRegistry())
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


def _prompts(seed, lengths, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]


class TestShardedPoolGeometry:
    def test_paged_pool_head_sharded(self):
        mesh = _mesh_tp2()
        cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
        pool = PagedKVPool(cfg, num_slots=2, max_len=64, page_size=8,
                           num_pages=17, mesh=mesh)
        spec = pool.pages_k.sharding.spec
        assert tuple(spec) == (None, None, "tp", None, None)
        assert pool.pages_v.sharding.spec == spec
        assert pool.tp_degree == 2
        assert pool.kv_bytes_per_device() == pool.kv_bytes() // 2

    def test_engine_reports_per_device_bytes(self):
        model, params = _tiny_model()
        e1 = _engine(model, params)
        e2 = _engine(model, params, mesh=_mesh_tp2())
        assert e2.tp_degree == 2
        assert e2.kv_pool_bytes() * 2 == e1.kv_pool_bytes()

    def test_indivisible_heads_rejected(self):
        model, params = _tiny_model(hidden_size=48, num_heads=6, num_kv_heads=3)
        with pytest.raises(ValueError, match="tp=2"):
            _engine(model, params, mesh=_mesh_tp2())

    def test_tp_degree_gauge_and_serving_rules(self):
        from accelerate_tpu.parallel.tensor_parallel import path_to_str

        model, params = _tiny_model()
        reg = MetricsRegistry()
        eng = _engine(model, params, mesh=_mesh_tp2(), registry=reg)
        assert reg.gauge("serve/tp_degree").value == 2.0
        # column-parallel only: o_proj/down_proj replicated (token identity)
        sharded = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(eng.params)[0]:
            axes = [a for a in leaf.sharding.spec if a is not None] \
                if hasattr(leaf.sharding, "spec") else []
            sharded[path_to_str(path)] = bool(axes)
        assert any(v for k, v in sharded.items() if "q_proj" in k)
        assert any(v for k, v in sharded.items() if "lm_head" in k)
        assert not any(v for k, v in sharded.items() if "o_proj" in k)
        assert not any(v for k, v in sharded.items() if "down_proj" in k)

    def test_pallas_kernel_refused_under_tp(self):
        from accelerate_tpu.ops.paged_attention import resolve_paged_kernel

        mesh = _mesh_tp2()
        with pytest.raises(ValueError, match="single-chip"):
            resolve_paged_kernel("pallas", mesh)
        model, params = _tiny_model()
        with pytest.raises(ValueError, match="single-chip"):
            _engine(model, params, mesh=mesh, decode_kernel="pallas")
        assert resolve_paged_kernel("pallas", None) == "pallas"
        assert resolve_paged_kernel("xla", mesh) == "xla"
        dp = build_mesh({"dp": 2}, devices=jax.devices()[:2])
        assert resolve_paged_kernel("pallas", dp) == "pallas"


class TestTokenIdentity:
    """tp=2 must reproduce tp=1 token for token, bitwise."""

    def _serve(self, model, params, gens, mesh, **kw):
        eng = _engine(model, params, mesh=mesh, **kw)
        prompts = _prompts(1, (8, 12, 5), model.config.vocab_size)
        reqs = eng.serve(prompts, gens)
        return [list(r.tokens) for r in reqs], eng

    def test_greedy(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        t1, e1 = self._serve(model, params, gen, None)
        t2, e2 = self._serve(model, params, gen, _mesh_tp2())
        assert t1 == t2
        assert e1.compiled_executable_counts() == e2.compiled_executable_counts()

    def test_sampled(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=True, temperature=0.8)
        t1, _ = self._serve(model, params, gen, None, rng_seed=7)
        t2, _ = self._serve(model, params, gen, _mesh_tp2(), rng_seed=7)
        assert t1 == t2

    def test_speculative(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        t1, e1 = self._serve(model, params, gen, None, speculate_k=2)
        t2, e2 = self._serve(model, params, gen, _mesh_tp2(),
                             speculate_k=2)
        assert t1 == t2
        assert e1.compiled_executable_counts() == e2.compiled_executable_counts()

    def test_int8_kv(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        t1, e1 = self._serve(model, params, gen, None, kv_dtype="int8")
        t2, e2 = self._serve(model, params, gen, _mesh_tp2(),
                             kv_dtype="int8")
        assert t1 == t2
        assert e2.kv_pool_bytes() * 2 == e1.kv_pool_bytes()

    def test_interleaved_prefill_matches_and_flash_kernel_refused(self):
        """prefill_kernel="pallas" under tp=2 is refused at construction (the
        flash kernel is single-chip) — never swapped for the XLA arm — and
        the interleaved ordering on the XLA arm stays token-identical to the
        unsharded, non-interleaved engine."""
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        with pytest.raises(ValueError, match="single-chip"):
            _engine(model, params, mesh=_mesh_tp2(),
                    prefill_kernel="pallas", interleave_prefill=True)
        t1, _ = self._serve(model, params, gen, None)
        t2, e2 = self._serve(model, params, gen, _mesh_tp2(),
                             interleave_prefill=True)
        assert t1 == t2
        assert e2.prefill_kernel == "xla"


class TestReplicaMeshes:
    def test_disjoint_slices(self):
        meshes = replica_meshes(2, {"tp": 2})
        assert len(meshes) == 2
        d0 = {d.id for d in meshes[0].devices.ravel()}
        d1 = {d.id for d in meshes[1].devices.ravel()}
        assert len(d0) == len(d1) == 2 and not d0 & d1

    def test_oversubscription_rejected(self):
        with pytest.raises(ValueError):
            replica_meshes(5, {"tp": 2})


class TestReplicaRouter:
    def _replicas(self, model, params, n=2, **kw):
        return [_engine(model, params, prefix_cache_mb=4.0, **kw)
                for _ in range(n)]

    def test_affinity_prefers_warm_replica(self):
        model, params = _tiny_model()
        engines = self._replicas(model, params)
        router = ReplicaRouter(engines, policy="affinity")
        common = _prompts(2, (16,), model.config.vocab_size)[0]
        gen = GenerationConfig(max_new_tokens=4, do_sample=False)
        first = router.submit(np.concatenate([common, [5, 6]]), config=gen)
        router.run()
        warm = first.replica
        for sfx in ([7, 8], [9, 10, 11]):
            req = router.submit(np.concatenate([common, sfx]), config=gen)
            router.run()
            assert req.replica == warm
        assert router.health()["affinity_hit_rate"] > 0

    def test_cold_cache_falls_back_least_loaded(self):
        model, params = _tiny_model()
        engines = self._replicas(model, params)
        router = ReplicaRouter(engines, policy="affinity")
        gen = GenerationConfig(max_new_tokens=4, do_sample=False)
        prompts = _prompts(3, (8, 8), model.config.vocab_size)
        r0 = router.submit(prompts[0], config=gen)
        r1 = router.submit(prompts[1], config=gen)  # r0's replica now loaded
        assert {r0.replica, r1.replica} == {0, 1}
        router.run()

    def test_round_robin_cycles(self):
        model, params = _tiny_model()
        router = ReplicaRouter(self._replicas(model, params),
                               policy="round_robin")
        gen = GenerationConfig(max_new_tokens=4, do_sample=False)
        prompts = _prompts(4, (8, 8, 8, 8), model.config.vocab_size)
        placed = [router.submit(p, config=gen).replica for p in prompts]
        router.run()
        assert placed == [0, 1, 0, 1]

    def test_failover_when_replica_refuses(self):
        model, params = _tiny_model()
        small = _engine(model, params, max_len=16, max_prompt_len=8,
                        prefill_buckets=(8,))
        big = _engine(model, params, max_len=64)
        router = ReplicaRouter([small, big], policy="affinity")
        gen = GenerationConfig(max_new_tokens=8, do_sample=False)
        # 12-token prompt exceeds the small replica's admission cap: the
        # least-loaded choice (replica 0) refuses, the router fails over
        long = _prompts(5, (12,), model.config.vocab_size)[0]
        req = router.submit(long, config=gen)
        assert req.replica == 1
        router.run()
        assert len(req.tokens) == 8
        # every replica refusing surfaces the last error
        with pytest.raises(ValueError):
            router.submit(_prompts(6, (63,), model.config.vocab_size)[0],
                          config=GenerationConfig(max_new_tokens=60))

    def test_bad_policy_and_empty_engines_rejected(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError):
            ReplicaRouter([], policy="affinity")
        with pytest.raises(ValueError):
            ReplicaRouter(self._replicas(model, params), policy="random")

    def test_cross_replica_stats_aggregation(self):
        model, params = _tiny_model()
        engines = self._replicas(model, params)
        reg = MetricsRegistry()
        router = ReplicaRouter(engines, policy="affinity", registry=reg)
        gen = GenerationConfig(max_new_tokens=4, do_sample=False)
        reqs = router.serve(_prompts(7, (8, 10, 6, 9), model.config.vocab_size),
                            gen)
        assert all(len(r.tokens) == 4 for r in reqs)
        agg = router.stats()
        assert agg["routed"] == 4
        for key in ("requests_completed", "decode_steps"):
            assert agg[key] == sum(e.stats[key] for e in engines)
        assert agg["requests_completed"] == 4
        pcs = router.prefix_cache_stats()
        assert len(pcs["per_replica"]) == 2
        assert 0.0 <= pcs["hit_rate"] <= 1.0
        assert reg.gauge("serve/replicas").value == 2.0
        health = router.health()
        assert health["replicas"] == 2
        assert all(not r["has_work"] for r in health["per_replica"])

    def test_route_flight_events(self):
        from accelerate_tpu.telemetry import get_flight_recorder

        model, params = _tiny_model()
        router = ReplicaRouter(self._replicas(model, params))
        gen = GenerationConfig(max_new_tokens=4, do_sample=False)
        req = router.submit(_prompts(8, (8,), model.config.vocab_size)[0],
                            config=gen)
        router.run()
        events = [e for e in get_flight_recorder().tail()
                  if e.get("kind") == "serve/route"]
        assert events and events[-1]["replica"] == req.replica

    def test_cancel_targets_owning_replica(self):
        model, params = _tiny_model()
        engines = self._replicas(model, params)
        router = ReplicaRouter(engines, policy="affinity")
        gen = GenerationConfig(max_new_tokens=8, do_sample=False)
        req = router.submit(_prompts(9, (8,), model.config.vocab_size)[0],
                            config=gen)
        assert router.cancel(req)
        router.run()
        assert len(req.tokens) < 8


class TestRouterOverTpReplicas:
    def test_tp_sharded_replicas_serve_through_router(self):
        """The headline composition: 2 replicas x tp=2 = 4 chips, one router."""
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=8, do_sample=False)
        prompts = _prompts(10, (8, 12, 5, 9), model.config.vocab_size)
        # single-chip reference
        ref = _engine(model, params)
        expected = [list(r.tokens) for r in ref.serve(prompts, gen)]
        engines = [
            _engine(model, params, mesh=m, prefix_cache_mb=4.0)
            for m in replica_meshes(2, {"tp": 2})
        ]
        router = ReplicaRouter(engines, policy="affinity")
        reqs = router.serve(prompts, gen)
        assert [list(r.tokens) for r in reqs] == expected
        assert all(r["tp_degree"] == 2 for r in router.health()["per_replica"])

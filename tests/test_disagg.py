"""Disaggregated prefill/decode serving and live KV page migration.

Fast tier covers the host-side contracts: the two new chaos points parse,
``pad_page_ids`` keeps per-lane page counts out of jit signatures, role and
policy validation refuse inconsistent fleets.  The engine-level contracts are
slow-marked: a lane migrated mid-generation continues **bit-identically** —
greedy AND sampled, the live RNG row travels — across bf16/int8/fp8 pools,
tp=1 and tp=2, both transfer arms (d2d and pinned-host bounce); quant scales
survive the bounce; prefix-cache pins drop on the source and re-establish on
the destination zero-copy; the compiled budget grows by exactly the
documented ``{migrate_extract, migrate_install}`` pair on participating
engines only; an injected mid-migration fault falls back to re-prefill
replay (token-identical under greedy) with the source replica left healthy;
and the ``role="prefill"``/``role="decode"`` split behind
``ReplicaRouter(policy="disaggregated")`` serves token-identically to a
monolithic engine, including failover upgraded from replay to migration.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from accelerate_tpu.models.generation import GenerationConfig  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    Transformer,
    TransformerConfig,
)
from accelerate_tpu.parallel.mesh import build_mesh  # noqa: E402
from accelerate_tpu.serving import (  # noqa: E402
    NULL_PAGE,
    MigrationError,
    PageMigrator,
    ReplicaRouter,
    ServingEngine,
)
from accelerate_tpu.serving import faults, transfer  # noqa: E402
from accelerate_tpu.serving.pool import pad_page_ids  # noqa: E402
from accelerate_tpu.serving.readback import fetch  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402


# ----------------------------------------------------------------- fast tier
class TestFaultPoints:
    def test_migration_points_registered(self):
        assert "migrate_d2d" in faults.FAULT_POINTS
        assert "migrate_bounce" in faults.FAULT_POINTS

    def test_plan_parses_migration_points(self):
        plan = faults.FaultPlan.parse("seed=3,migrate_d2d@1,migrate_bounce=0.5")
        assert plan.at == {"migrate_d2d": 1}
        assert plan.probs == {"migrate_bounce": 0.5}

    def test_unknown_point_still_rejected(self):
        with pytest.raises(ValueError, match="unknown fault point"):
            faults.FaultPlan.parse("migrate_sideways=0.1")


class TestPadPageIds:
    def test_pads_with_null_page(self):
        out = pad_page_ids([3, 9, 4], 6)
        assert out.dtype == np.int32 and out.shape == (6,)
        assert list(out) == [3, 9, 4, NULL_PAGE, NULL_PAGE, NULL_PAGE]

    def test_full_width_passthrough(self):
        assert list(pad_page_ids([1, 2], 2)) == [1, 2]

    def test_overflow_raises(self):
        with pytest.raises(ValueError):
            pad_page_ids([1, 2, 3], 2)


class TestMigrationError:
    def test_defaults_non_retriable(self):
        err = MigrationError("nope")
        assert err.retriable is False and err.reason == "nope"
        assert MigrationError("later", retriable=True).retriable is True


# ------------------------------------------------------------- shared helpers
def _tiny_model(seed=0, **kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2,
                    prefix_cache_mb=0.01, async_depth=1,
                    registry=MetricsRegistry())
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


def _gen(mode, n=10):
    if mode == "sampled":
        return GenerationConfig(max_new_tokens=n, do_sample=True,
                                temperature=0.8, top_k=50, eos_token_id=None)
    return GenerationConfig(max_new_tokens=n, do_sample=False,
                            eos_token_id=None)


def _prompt(seed=7, n=8, vocab=256):
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, (n,)).astype(np.int32)


def _slot_of(engine, req):
    return next(s for s in range(engine.num_slots)
                if engine._slot_req[s] is req)


def _run_until(engine, req, n_tokens, max_steps=200):
    steps = 0
    while len(req.tokens) < n_tokens:
        engine.step()
        steps += 1
        assert steps < max_steps, "engine did not generate enough tokens"


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


# ------------------------------------------------------------------ slow tier
@pytest.mark.slow
class TestRoleAndPolicyValidation:
    def test_bad_role_rejected(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError, match="role"):
            _engine(model, params, role="decoder")

    def test_role_gauge_and_health(self):
        model, params = _tiny_model()
        pre = _engine(model, params, role="prefill")
        dec = _engine(model, params, role="decode")
        r = ReplicaRouter([pre, dec], policy="disaggregated",
                          registry=MetricsRegistry())
        roles = [p["role"] for p in r.health()["per_replica"]]
        assert roles == ["prefill", "decode"]
        assert pre.metrics.gauge("serve/role").value == 1.0
        assert dec.metrics.gauge("serve/role").value == 2.0

    def test_disaggregated_needs_both_capabilities(self):
        model, params = _tiny_model()
        pre = _engine(model, params, role="prefill")
        with pytest.raises(ValueError, match="decode-capable"):
            ReplicaRouter([pre], policy="disaggregated",
                          registry=MetricsRegistry())
        dec = _engine(model, params, role="decode")
        with pytest.raises(ValueError, match="prefill-capable"):
            ReplicaRouter([dec], policy="disaggregated",
                          registry=MetricsRegistry())


def _migrate_pair(model, params, gen_modes, xfer, kv_dtype=None, mesh=None,
                  migrate_at=4, **kw):
    """Baseline tokens vs migrate-mid-generation tokens for one lane per
    mode in ``gen_modes`` — returns (baseline, migrated) token lists."""
    prompts = [_prompt(11 + i) for i in range(len(gen_modes))]
    gens = [_gen(m) for m in gen_modes]

    base = _engine(model, params, kv_dtype=kv_dtype, mesh=mesh, **kw)
    breqs = [base.submit(p.copy(), config=g) for p, g in zip(prompts, gens)]
    base.run()
    baseline = [list(r.tokens) for r in breqs]

    src = _engine(model, params, kv_dtype=kv_dtype, mesh=mesh, **kw)
    dst = _engine(model, params, kv_dtype=kv_dtype, mesh=mesh, **kw)
    mig = PageMigrator(registry=MetricsRegistry())
    reqs = [src.submit(p.copy(), config=g) for p, g in zip(prompts, gens)]
    for r in reqs:
        _run_until(src, r, migrate_at)
    for r in reqs:
        mig.migrate(src, dst, _slot_of(src, r), mode=xfer)
    assert src._poisoned is None
    dst.run()
    return baseline, [list(r.tokens) for r in reqs]


@pytest.mark.slow
class TestMigrationTokenIdentity:
    """A migrated lane must continue bit-identically — greedy AND sampled
    (the live RNG row travels with the lane, unlike adopt's re-seed)."""

    @pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8", "fp8"])
    @pytest.mark.parametrize("xfer", ["d2d", "bounce"])
    def test_identity_tp1(self, xfer, kv_dtype):
        model, params = _tiny_model()
        baseline, migrated = _migrate_pair(
            model, params, ["greedy", "sampled"], xfer, kv_dtype=kv_dtype)
        assert migrated == baseline

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_identity_tp2(self, kv_dtype):
        model, params = _tiny_model()
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        baseline, migrated = _migrate_pair(
            model, params, ["greedy", "sampled"], "d2d",
            kv_dtype=kv_dtype, mesh=mesh)
        assert migrated == baseline

    def test_identity_tp2_bounce(self):
        model, params = _tiny_model()
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        baseline, migrated = _migrate_pair(
            model, params, ["greedy", "sampled"], "bounce",
            kv_dtype="int8", mesh=mesh)
        assert migrated == baseline


@pytest.mark.slow
class TestMigrationMechanics:
    def test_scales_survive_bounce(self):
        model, params = _tiny_model()
        src = _engine(model, params, kv_dtype="int8")
        dst = _engine(model, params, kv_dtype="int8")
        mig = PageMigrator(registry=MetricsRegistry())
        req = src.submit(_prompt(), config=_gen("greedy"))
        _run_until(src, req, 4)
        slot = _slot_of(src, req)
        src._drain_inflight()
        old_ids = src.kv.lane_pages(slot)
        ks = np.asarray(fetch(src.kv.k_scales))[:, old_ids]
        vs = np.asarray(fetch(src.kv.v_scales))[:, old_ids]
        mig.migrate(src, dst, slot, mode="bounce")
        new_ids = dst.kv.lane_pages(req.slot)
        assert len(new_ids) == len(old_ids)
        np.testing.assert_array_equal(
            np.asarray(fetch(dst.kv.k_scales))[:, new_ids], ks)
        np.testing.assert_array_equal(
            np.asarray(fetch(dst.kv.v_scales))[:, new_ids], vs)

    def test_prefix_pins_drop_on_source_and_reestablish_on_destination(self):
        model, params = _tiny_model()
        src = _engine(model, params)
        dst = _engine(model, params)
        mig = PageMigrator(registry=MetricsRegistry())
        prompt = _prompt(n=8)
        req = src.submit(prompt.copy(), config=_gen("greedy"))
        _run_until(src, req, 6)
        slot = _slot_of(src, req)
        src._drain_inflight()
        lane_ids = set(src.kv.lane_pages(slot))
        mig.migrate(src, dst, slot)
        # source: the lane's own refs dropped — its pages are free unless the
        # source cache holds them (cache nodes keep their own refs and stay
        # servable); none remain pinned on the lane's behalf
        src_cache_pages = {
            p for n in src.prefix_cache._nodes
            if n.pages is not None for p in n.pages
        }
        for p in lane_ids:
            refs = int(src.kv.allocator.refs[p])
            cached = p in src_cache_pages
            assert refs == (1 if cached else 0), (p, refs)
        # destination: the prompt chunk re-established, aliasing the lane's
        # NEW pages zero-copy, and a lookalike request hits it
        hit = dst.prefix_cache.match(prompt, [(8, 8)])
        assert hit, "migrated prefix not re-established on destination"
        assert set(hit[0].pages) <= set(dst.kv.lane_pages(req.slot))
        dst.run()
        req2 = dst.submit(prompt.copy(), config=_gen("greedy"))
        dst.run()
        assert dst.stats["prefix_hit_tokens"] >= 8
        assert list(req2.tokens) == list(req.tokens)

    def test_migration_behind_inflight_destination_window(self):
        model, params = _tiny_model()
        base = _engine(model, params)
        b1 = base.submit(_prompt(1), config=_gen("greedy"))
        b2 = base.submit(_prompt(2), config=_gen("greedy"))
        base.run()
        src = _engine(model, params)
        dst = _engine(model, params)
        mig = PageMigrator(registry=MetricsRegistry())
        r1 = src.submit(_prompt(1), config=_gen("greedy"))
        r2 = dst.submit(_prompt(2), config=_gen("greedy"))
        _run_until(src, r1, 4)
        _run_until(dst, r2, 2)  # leaves a window in flight on dst
        assert dst._inflight is not None or dst._prev_handle is not None
        mig.migrate(src, dst, _slot_of(src, r1))
        dst.run()
        assert list(r1.tokens) == list(b1.tokens)
        assert list(r2.tokens) == list(b2.tokens)

    def test_compiled_budget_grows_by_exactly_the_migration_pair(self):
        model, params = _tiny_model()
        src = _engine(model, params)
        dst = _engine(model, params)
        mono = _engine(model, params)
        mig = PageMigrator(registry=MetricsRegistry())
        mreq = mono.submit(_prompt(), config=_gen("greedy"))
        mono.run()
        req = src.submit(_prompt(), config=_gen("greedy"))
        _run_until(src, req, 4)
        before_src = src.compiled_executable_counts()
        before_dst = dst.compiled_executable_counts()
        assert not any(k.startswith("migrate_") for k in before_src)
        mig.migrate(src, dst, _slot_of(src, req))
        dst.run()
        assert list(req.tokens) == list(mreq.tokens)
        for eng, before in ((src, before_src), (dst, before_dst)):
            after = eng.compiled_executable_counts()
            assert set(after) - set(before) == \
                {"migrate_extract", "migrate_install"}
            assert all(v <= 1 for v in after.values()), after
        # a replica that never migrated gains nothing
        assert not any(k.startswith("migrate_")
                       for k in mono.compiled_executable_counts())

    def test_retriable_when_destination_full(self):
        model, params = _tiny_model()
        src = _engine(model, params)
        dst = _engine(model, params)
        mig = PageMigrator(registry=MetricsRegistry())
        req = src.submit(_prompt(1), config=_gen("greedy"))
        d1 = dst.submit(_prompt(2), config=_gen("greedy", n=30))
        d2 = dst.submit(_prompt(3), config=_gen("greedy", n=30))
        _run_until(src, req, 4)
        _run_until(dst, d1, 1)
        _run_until(dst, d2, 1)
        with pytest.raises(MigrationError) as ei:
            mig.migrate(src, dst, _slot_of(src, req))
        assert ei.value.retriable is True
        # nothing mutated: the lane finishes on the source, token-identical
        base = _engine(model, params)
        breq = base.submit(_prompt(1), config=_gen("greedy"))
        base.run()
        src.run()
        dst.run()
        assert list(req.tokens) == list(breq.tokens)

    def test_geometry_mismatch_not_retriable(self):
        model, params = _tiny_model()
        src = _engine(model, params)
        dst = _engine(model, params, max_len=32)  # pages_per_lane differs
        mig = PageMigrator(registry=MetricsRegistry())
        req = src.submit(_prompt(), config=_gen("greedy"))
        _run_until(src, req, 2)
        with pytest.raises(MigrationError) as ei:
            mig.migrate(src, dst, _slot_of(src, req))
        assert ei.value.retriable is False


@pytest.mark.slow
class TestMigrationChaos:
    @pytest.mark.parametrize("point", ["migrate_d2d", "migrate_bounce"])
    def test_fault_mid_migration_falls_back_to_replay(self, point, monkeypatch):
        """An injected mid-migration fault leaves the source healthy; the
        router falls back to single-lane replay, token-identical greedy."""
        if point == "migrate_bounce":
            # same-platform replicas auto-resolve to d2d; pin the bounce arm
            # so router-level migrate_lane() walks through the armed point
            monkeypatch.setattr(transfer.PageMigrator, "resolve_mode",
                                staticmethod(lambda s, d: "bounce"))
        model, params = _tiny_model()
        base = _engine(model, params)
        breq = base.submit(_prompt(), config=_gen("greedy"))
        base.run()
        src = _engine(model, params)
        dst = _engine(model, params)
        router = ReplicaRouter([src, dst], registry=MetricsRegistry())
        req = router.submit(_prompt(), config=_gen("greedy"))
        owner = router.engines[req.replica]
        other = router.engines[1 - req.replica]
        _run_until(owner, req, 4)
        faults.install(faults.FaultPlan(
            at={point: 1}), registry=MetricsRegistry())
        xfer = "d2d" if point == "migrate_d2d" else "bounce"
        with pytest.raises(MigrationError) as ei:
            router.migrator.migrate(owner, other, _slot_of(owner, req),
                                    mode=xfer)
        assert ei.value.retriable is False
        assert owner._poisoned is None  # source replica stays healthy
        assert req.state.name == "RUNNING"
        # now the router-level fallback: second fire replays the lane
        faults.install(faults.FaultPlan(
            at={point: 1}), registry=MetricsRegistry())
        moved = router.migrate_lane(reason="test")
        assert moved is True
        assert owner._poisoned is None
        router.run()
        assert list(req.tokens) == list(breq.tokens)
        assert router.stats()["requests_replayed"] >= 1

    def test_failover_upgrades_to_migration(self):
        """Under the disaggregated policy a killed replica's RUNNING lanes
        migrate bit-identically instead of replaying — zero replays when
        the dying replica's pages are still readable."""
        model, params = _tiny_model()
        base = _engine(model, params)
        breq = base.submit(_prompt(), config=_gen("greedy"))
        base.run()
        a = _engine(model, params)
        b = _engine(model, params)
        router = ReplicaRouter([a, b], policy="disaggregated",
                               registry=MetricsRegistry())
        req = router.submit(_prompt(), config=_gen("greedy"))
        owner = router.engines[req.replica]
        _run_until(owner, req, 4)
        owner.kill("test kill")
        router.step()
        router.run()
        assert list(req.tokens) == list(breq.tokens)
        assert router.stats()["requests_replayed"] == 0
        assert router.migrator.metrics.counter(
            "serve/migrations_total").value >= 1

    def test_failover_falls_back_when_pages_unreadable(self):
        """When migration off the dying replica fails, ejection degrades to
        the export/replay path — still token-identical under greedy."""
        model, params = _tiny_model()
        base = _engine(model, params)
        breq = base.submit(_prompt(), config=_gen("greedy"))
        base.run()
        a = _engine(model, params)
        b = _engine(model, params)
        router = ReplicaRouter([a, b], policy="disaggregated",
                               registry=MetricsRegistry())
        req = router.submit(_prompt(), config=_gen("greedy"))
        owner = router.engines[req.replica]
        _run_until(owner, req, 4)
        owner.kill("test kill")
        faults.install(faults.FaultPlan(
            at={"migrate_d2d": 1, "migrate_bounce": 1}),
            registry=MetricsRegistry())
        router.step()
        faults.clear()
        router.run()
        assert list(req.tokens) == list(breq.tokens)
        assert router.stats()["requests_replayed"] >= 1


@pytest.mark.slow
class TestDisaggregatedServing:
    def test_role_split_token_identical_to_monolithic(self):
        model, params = _tiny_model()
        prompts = [_prompt(20 + i) for i in range(4)]
        gens = [_gen("greedy"), _gen("sampled"), _gen("greedy"),
                _gen("sampled")]
        mono = _engine(model, params, num_slots=4)
        mreqs = [mono.submit(p.copy(), config=g)
                 for p, g in zip(prompts, gens)]
        mono.run()
        pre = _engine(model, params, role="prefill")
        dec = _engine(model, params, role="decode", num_slots=4)
        router = ReplicaRouter([pre, dec], policy="disaggregated",
                               registry=MetricsRegistry())
        reqs = [router.submit(p.copy(), config=g)
                for p, g in zip(prompts, gens)]
        router.run()
        for r, m in zip(reqs, mreqs):
            assert list(r.tokens) == list(m.tokens)
        # every lane moved exactly once, by handoff; prefill never decoded
        assert router.migrator.metrics.counter(
            "serve/prefill_handoffs_total").value == len(prompts)
        assert pre.stats["decode_steps"] == 0
        assert dec.stats["decode_steps"] > 0

    def test_migrate_lane_rebalances(self):
        model, params = _tiny_model()
        base = _engine(model, params)
        b1 = base.submit(_prompt(1), config=_gen("greedy"))
        b2 = base.submit(_prompt(2), config=_gen("greedy"))
        base.run()
        a = _engine(model, params)
        b = _engine(model, params)
        router = ReplicaRouter([a, b], policy="disaggregated",
                               registry=MetricsRegistry())
        r1 = router.submit(_prompt(1), config=_gen("greedy"))
        r2 = router.submit(_prompt(2), config=_gen("greedy"))
        for _ in range(3):
            router.step()
        assert router.migrate_lane(reason="rebalance") is True
        router.run()
        assert list(r1.tokens) == list(b1.tokens)
        assert list(r2.tokens) == list(b2.tokens)

    def test_migrate_lane_returns_false_when_idle(self):
        model, params = _tiny_model()
        a = _engine(model, params)
        b = _engine(model, params)
        router = ReplicaRouter([a, b], registry=MetricsRegistry())
        assert router.migrate_lane() is False

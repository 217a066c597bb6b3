"""Hierarchical prefix cache: host-RAM spill tier + decode-overlapped H2D
promotion.

Two layers under test.  The :class:`PrefixCache` tier mechanics run against a
fake spill hook (no jit, tier-1 fast): per-tier LRU, refcount pins never
spilling, byte budgets per tier, the quantized-pool byte-accounting contract
(node nbytes == page data + BOTH f32 scale slabs, via the one accounting unit
``PagedKVPool.chunk_bytes``), and the disk ring roundtrip.  The engine-level
contracts are slow-marked: greedy/sampled/speculative outputs are
token-identical with the host tier on or off across bf16/int8/fp8 pools and
tp=1/tp=2, a failed ``promote_h2d`` degrades to a plain cache miss (never a
poisoned engine), promotions are enqueued BEHIND the in-flight decode window
(``behind_window=True`` flight events under ``async_depth=1``), and the
compiled-executable budget grows by exactly the documented per-bucket
spill/install set.
"""

import os

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
    PagedKVPool,
    PrefixCache,
    ServingEngine,
)
from accelerate_tpu.serving import faults  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

NBYTES = 100  # per-node cost for the fake-spill unit tests


class _SpillRecorder:
    """Fake engine side of the spill protocol: hands each demoted node a
    sentinel payload and records the traffic."""

    def __init__(self, payload=None, fail=False):
        self.spilled = []
        self.evicted = []
        self.payload = payload
        self.fail = fail

    def spill(self, node):
        if self.fail:
            return None
        self.spilled.append(node)
        if self.payload is not None:
            return self.payload
        return (f"k{node.key}", f"v{node.key}", "ks", "vs")

    def on_evict(self, node):
        self.evicted.append(node)


def _cache(capacity=2 * NBYTES + NBYTES // 2, host=0, rec=None, **kw):
    rec = rec if rec is not None else _SpillRecorder()
    cache = PrefixCache(
        capacity, registry=MetricsRegistry(), on_evict=rec.on_evict,
        host_capacity_bytes=host, spill=rec.spill if host else None, **kw,
    )
    return cache, rec


def _tokens(i, n=4):
    return np.full(n, 10 + i, np.int32)


def _insert(cache, i, parent=None, nbytes=NBYTES):
    node = cache.insert_pages(parent, _tokens(i), (2 * i, 2 * i + 1),
                              nbytes=nbytes)
    assert node is not None
    return node


class TestSpillTierMechanics:
    def test_eviction_demotes_and_node_stays_matchable(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)  # over budget: LRU node a demotes, not drops
        assert a.tier == "host" and a.pages is None
        assert rec.spilled == [a] and rec.evicted == []
        assert cache.spills == 1 and cache.host_bytes == NBYTES
        hit = cache.match(_tokens(0), [(4, 4)])
        assert hit == [a]  # spilled nodes still hit the radix walk

    def test_without_host_tier_eviction_drops(self):
        cache, rec = _cache(host=0)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)
        assert rec.evicted == [a] and cache.spills == 0
        assert cache.match(_tokens(0), [(4, 4)]) == []

    def test_failed_spill_falls_back_to_drop(self):
        rec = _SpillRecorder(fail=True)
        cache, _ = _cache(host=10 * NBYTES, rec=rec)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)
        assert a.tier == "device" and rec.evicted == [a]
        assert cache.spills == 0 and cache.host_bytes == 0

    def test_per_tier_lru(self):
        cache, rec = _cache(host=2 * NBYTES + NBYTES // 2)
        nodes = [_insert(cache, i) for i in range(5)]
        # device holds the 2 newest; 3 spilled, but the host ring only holds
        # 2 — the LRU spill (nodes[0]) was evicted host-side to make room
        assert [n.tier for n in nodes] == \
            ["device", "host", "host", "device", "device"]
        assert cache.host_evictions == 1 and rec.evicted == [nodes[0]]
        assert cache.host_bytes == 2 * NBYTES

    def test_pinned_nodes_never_spill(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        cache.acquire([a])
        b = _insert(cache, 1)
        cache.acquire([b])
        # both resident nodes pinned: nothing to evict, inserts refused
        assert not cache.evict_one()
        assert cache.insert_pages(None, _tokens(2), (9,), nbytes=NBYTES) is None
        assert a.tier == b.tier == "device" and rec.spilled == []
        cache.release([a])
        _insert(cache, 3)
        assert a.tier == "host" and b.tier == "device"  # only the unpinned moved

    def test_promote_readmits_to_device(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)
        assert a.tier == "host"
        payload = cache.node_payload(a)
        assert payload[0] == f"k{a.key}"
        assert cache.promote_node(a, (40, 41))
        assert a.tier == "device" and a.pages == (40, 41) and a.host is None
        assert cache.promotions == 1
        # the promotion made room by demoting another LRU device node: a left
        # the host ring but its victim entered it
        assert cache.host_bytes == NBYTES
        assert cache.bytes <= cache.capacity

    def test_promotion_blocked_by_pins_keeps_payload(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        b, c = _insert(cache, 1), _insert(cache, 2)
        assert a.tier == "host"
        cache.acquire([b, c])  # device tier fully pinned: no room
        assert not cache.promote_node(a, (40, 41))
        assert a.tier == "host" and cache.node_payload(a) is not None
        # the H2D install itself succeeded engine-side: it still counts
        assert cache.promotions == 1

    def test_settle_payload_lands_only_on_host_tier(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)
        cache.settle_payload(a, ("landed",) * 4)
        assert a.host == ("landed",) * 4
        assert cache.promote_node(a, (40, 41))
        cache.settle_payload(a, ("stale",) * 4)  # late settle after promote
        assert a.host is None  # ignored: node is device-tier again

    def test_host_budget_and_stats_surface(self):
        cache, _ = _cache(host=2 * NBYTES)
        for i in range(6):
            _insert(cache, i)
        st = cache.stats()
        assert st["host_bytes"] <= st["host_capacity_bytes"]
        for key in ("host_nodes", "host_evictions", "spills", "promotions",
                    "disk_bytes", "disk_nodes"):
            assert key in st
        assert st["host_nodes"] == len(cache._host_nodes)

    def test_flush_purges_all_tiers_without_spilling(self):
        cache, rec = _cache(host=10 * NBYTES)
        for i in range(4):
            _insert(cache, i)
        assert cache.host_bytes > 0
        spilled_before = len(rec.spilled)
        removed = cache.flush()
        assert removed == 4
        assert cache.bytes == 0 and cache.host_bytes == 0
        assert cache.num_nodes == 0 and not cache._host_nodes
        # flush drops stale-weight KV outright — it must never demote
        assert len(rec.spilled) == spilled_before

    def test_discard_spilled_drops_without_payload_landing(self):
        cache, rec = _cache(host=10 * NBYTES)
        a = _insert(cache, 0)
        _insert(cache, 1)
        _insert(cache, 2)
        cache.discard_spilled(a)
        assert cache.host_bytes == 0 and cache.match(_tokens(0), [(4, 4)]) == []
        cache.discard_spilled(a)  # idempotent on a detached node


class TestDiskTier:
    def _payload(self):
        rng = np.random.default_rng(0)
        return tuple(rng.standard_normal((2, 3)).astype(np.float32)
                     for _ in range(4))

    def test_host_eviction_parks_on_disk_and_roundtrips(self, tmp_path):
        payload = self._payload()
        rec = _SpillRecorder(payload=payload)
        cache, _ = _cache(host=NBYTES, rec=rec,
                          disk_capacity_bytes=10 * NBYTES,
                          disk_dir=str(tmp_path))
        a = _insert(cache, 0)
        for i in range(1, 4):
            _insert(cache, i)
        assert a.tier == "disk"
        files = list(tmp_path.glob("prefix_*.npz"))
        assert len(files) == 1 and cache.disk_bytes == NBYTES
        loaded = cache.node_payload(a)
        for got, want in zip(loaded, payload):
            np.testing.assert_array_equal(got, want)
        a_path = a.host
        assert cache.promote_node(a, (50, 51)) and a.tier == "device"
        assert not os.path.exists(a_path)  # ring file unlinked on re-admit

    def test_inflight_payload_is_not_disk_eligible(self, tmp_path):
        # device handles (non-ndarray payload) must never be np.savez'd
        cache, rec = _cache(host=NBYTES, disk_capacity_bytes=10 * NBYTES,
                            disk_dir=str(tmp_path))
        a = _insert(cache, 0)
        for i in range(1, 4):
            _insert(cache, i)
        assert a.tier == "device" and not list(tmp_path.glob("*.npz"))
        assert rec.evicted == [a]  # dropped, not torn onto disk

    def test_flush_unlinks_disk_files(self, tmp_path):
        cache, _ = _cache(host=NBYTES, rec=_SpillRecorder(payload=self._payload()),
                          disk_capacity_bytes=10 * NBYTES,
                          disk_dir=str(tmp_path))
        for i in range(4):
            _insert(cache, i)
        assert list(tmp_path.glob("prefix_*.npz"))
        cache.flush()
        assert not list(tmp_path.glob("prefix_*.npz"))

    def test_disk_requires_dir(self):
        with pytest.raises(ValueError):
            PrefixCache(1024, registry=MetricsRegistry(),
                        disk_capacity_bytes=1024)


class TestQuantizedByteAccounting:
    """Satellite regression: a quantized pool's cache-node nbytes must charge
    the page data AND both per-page f32 scale slabs — ``chunk_bytes`` is the
    single accounting unit, pinned here against the actual device arrays."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
    def test_chunk_bytes_matches_real_arrays(self, kv_dtype):
        cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                     max_seq_len=64)
        pool = PagedKVPool(cfg, num_slots=2, max_len=64, page_size=8,
                           num_pages=17, registry=MetricsRegistry(),
                           kv_dtype=kv_dtype)
        # bytes of ONE page across all layers, measured on the live arrays:
        # K + V data at the storage dtype plus the two f32 scale slabs
        per_page_data = 2 * (
            pool.pages_k.nbytes // pool.num_pages
        )
        per_page_scales = 2 * (pool.k_scales.nbytes // pool.num_pages)
        assert pool.page_kv_bytes == per_page_data + per_page_scales
        for npg in (1, 2, 5):
            assert pool.chunk_bytes(npg) == npg * (per_page_data + per_page_scales)

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_quantized_node_nbytes_includes_scales(self, kv_dtype):
        cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                     max_seq_len=64)
        pool = PagedKVPool(cfg, num_slots=2, max_len=64, page_size=8,
                           num_pages=17, registry=MetricsRegistry(),
                           kv_dtype=kv_dtype)
        cache = PrefixCache(10 * pool.page_kv_bytes, registry=MetricsRegistry())
        node = cache.insert_pages(None, _tokens(0, 8), (3,),
                                  nbytes=pool.chunk_bytes(1))
        scale_bytes = 2 * (pool.k_scales.nbytes // pool.num_pages)
        data_bytes = 2 * (pool.pages_k.nbytes // pool.num_pages)
        assert node.nbytes == data_bytes + scale_bytes
        assert node.nbytes > data_bytes  # the regression: scales were free


# --------------------------------------------------------------------------
# engine-level contracts (slow: real serves on the tiny model)
# --------------------------------------------------------------------------

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


def _shared_workload(vocab, seed=7, n=4, repeat=2):
    """Distinct full-bucket prompts, each submitted ``repeat`` times: the
    duplicates hit prefixes the tiny device budget has already spilled."""
    rng = np.random.default_rng(seed)
    base = [rng.integers(1, vocab, (8,)).astype(np.int32) for _ in range(n)]
    return [p.copy() for _ in range(repeat) for p in base]


def _spec_workload(n=4, repeat=2):
    """Periodic prompts (n-gram draftable), distinct across i."""
    base = [np.tile(np.array([5 + i, 6 + i, 7 + i], np.int32), 4)[:8]
            for i in range(n)]
    return [p.copy() for _ in range(repeat) for p in base]


def _cache_mb_for(cfg, kv_dtype, nodes=2.5):
    """Device-tier budget sized so ~2 cached chunks fit whatever the storage
    dtype — quantized nodes are ~4x smaller, so a fixed byte budget would
    never overflow (and never spill) on int8/fp8 pools."""
    pool = PagedKVPool(cfg, num_slots=2, max_len=64, page_size=4,
                       num_pages=17, registry=MetricsRegistry(),
                       kv_dtype=kv_dtype)
    return nodes * pool.chunk_bytes(2) / 2**20


def _gen(mode):
    if mode == "sampled":
        return GenerationConfig(max_new_tokens=5, do_sample=True,
                                temperature=0.8, top_k=50, eos_token_id=None)
    return GenerationConfig(max_new_tokens=5, do_sample=False,
                            eos_token_id=None)


def _serve(model, params, prompts, gen, host_mb, **kw):
    eng = _engine(model, params, prefix_host_mb=host_mb, **kw)
    reqs = eng.serve([p.copy() for p in prompts], configs=gen)
    return eng, [r.tokens for r in reqs]


@pytest.mark.slow
class TestPromotionTokenIdentity:
    """Host tier on vs off must be invisible in every token stream —
    including promotions landing mid-decode under async_depth=1."""

    @pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8", "fp8"])
    @pytest.mark.parametrize("mode", ["greedy", "sampled", "speculative"])
    def test_identity_tp1(self, mode, kv_dtype):
        model, params = _tiny_model()
        kw = {"speculate_k": 2} if mode == "speculative" else {}
        kw["prefix_cache_mb"] = _cache_mb_for(model.config, kv_dtype)
        prompts = (_spec_workload() if mode == "speculative"
                   else _shared_workload(model.config.vocab_size))
        eng_on, on = _serve(model, params, prompts, _gen(mode), 8.0,
                            kv_dtype=kv_dtype, **kw)
        _, off = _serve(model, params, prompts, _gen(mode), 0.0,
                        kv_dtype=kv_dtype, **kw)
        assert on == off
        st = eng_on.prefix_cache_stats()
        assert st["spills"] > 0, "workload failed to pressure the device tier"
        assert eng_on.stats["prefix_hit_tokens_host"] > 0, \
            "no hit was ever served from the host tier"

    @pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
    @pytest.mark.parametrize("mode", ["greedy", "sampled", "speculative"])
    def test_identity_tp2(self, mode, kv_dtype):
        model, params = _tiny_model()
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        kw = {"speculate_k": 2} if mode == "speculative" else {}
        kw["prefix_cache_mb"] = _cache_mb_for(model.config, kv_dtype)
        prompts = (_spec_workload() if mode == "speculative"
                   else _shared_workload(model.config.vocab_size))
        eng_on, on = _serve(model, params, prompts, _gen(mode), 8.0,
                            kv_dtype=kv_dtype, mesh=mesh, **kw)
        _, off = _serve(model, params, prompts, _gen(mode), 0.0,
                        kv_dtype=kv_dtype, mesh=mesh, **kw)
        assert on == off
        assert eng_on.stats["prefix_hit_tokens_host"] > 0


@pytest.mark.slow
class TestPromotionChaos:
    """Satellite: a failed promote_h2d degrades to a plain cache miss —
    re-prefill, token-identical — never a poisoned engine."""

    def test_injected_promotion_failure_is_a_cache_miss(self):
        model, params = _tiny_model()
        prompts = _shared_workload(model.config.vocab_size)
        gen = _gen("greedy")
        _, baseline = _serve(model, params, prompts, gen, 0.0)
        reg = MetricsRegistry()
        faults.install("promote_h2d=1.0", registry=reg)
        try:
            eng, toks = _serve(model, params, prompts, gen, 8.0, registry=reg)
            assert toks == baseline
            assert faults.ACTIVE.fired("promote_h2d") > 0, \
                "the chaos plan never reached a promotion attempt"
            # every promotion degraded: nothing was served from the host tier
            assert eng.stats["prefix_hit_tokens_host"] == 0
            assert eng.prefix_cache_stats()["promotions"] == 0
        finally:
            faults.clear()
        # the engine is not poisoned: it serves again, fault-free, and the
        # previously degraded prefixes now promote
        more = eng.serve([p.copy() for p in prompts[:4]], configs=gen)
        assert [r.tokens for r in more] == baseline[:4]

    def test_one_shot_fault_mid_run(self):
        model, params = _tiny_model()
        prompts = _shared_workload(model.config.vocab_size)
        gen = _gen("greedy")
        _, baseline = _serve(model, params, prompts, gen, 0.0)
        reg = MetricsRegistry()
        faults.install("promote_h2d@1", registry=reg)
        try:
            _, toks = _serve(model, params, prompts, gen, 8.0, registry=reg)
            assert toks == baseline
        finally:
            faults.clear()


@pytest.mark.slow
class TestPromotionOverlap:
    """Promotion must be enqueued BEHIND the in-flight decode window, not
    serialized in front of it."""

    def test_promote_events_ride_behind_the_window(self):
        model, params = _tiny_model()
        eng = _engine(model, params, prefix_host_mb=8.0, async_depth=1)
        eng.recorder.clear()
        prompts = _shared_workload(model.config.vocab_size)
        eng.serve([p.copy() for p in prompts], configs=_gen("greedy"))
        events = eng.recorder.tail()
        promotes = [e for e in events if e.get("kind") == "serve/promote_h2d"]
        lands = [e for e in events if e.get("kind") == "serve/promote_land"]
        assert promotes, "workload produced no promotions"
        assert any(e.get("behind_window") for e in promotes), \
            "every promotion dispatched against an idle device — nothing overlapped"
        # each dispatched promotion is acknowledged at a later drain
        assert len(lands) == len(promotes)

    def test_spill_events_record_dispatch(self):
        model, params = _tiny_model()
        eng = _engine(model, params, prefix_host_mb=8.0, async_depth=1)
        eng.recorder.clear()
        eng.serve([p.copy() for p in
                   _shared_workload(model.config.vocab_size, repeat=1)],
                  configs=_gen("greedy"))
        spills = [e for e in eng.recorder.tail()
                  if e.get("kind") == "serve/spill"]
        assert spills and all("bucket" in e for e in spills)


@pytest.mark.slow
class TestCompiledBudget:
    """The host tier adds exactly one spill gather + one promote install per
    prefill bucket — nothing else, and nothing retraces."""

    def test_budget_grows_by_exactly_the_spill_install_set(self):
        model, params = _tiny_model()
        prompts = _shared_workload(model.config.vocab_size)
        gen = _gen("greedy")
        eng_off, _ = _serve(model, params, prompts, gen, 0.0)
        eng_on, _ = _serve(model, params, prompts, gen, 8.0)
        off_counts = eng_off.compiled_executable_counts()
        on_counts = eng_on.compiled_executable_counts()
        expected_extra = {f"spill_{b}" for b in eng_on.buckets} \
            | {f"promote_{b}" for b in eng_on.buckets}
        assert set(on_counts) - set(off_counts) == expected_extra
        assert all(v <= 1 for v in on_counts.values()), on_counts
        # the exercised bucket compiled exactly once each way
        assert on_counts["spill_8"] == 1 and on_counts["promote_8"] == 1
        # shared executables were untouched by the tier
        for key in off_counts:
            assert on_counts[key] == off_counts[key], key

    def test_host_tier_off_builds_nothing(self):
        model, params = _tiny_model()
        eng = _engine(model, params, prefix_host_mb=0.0)
        assert not any(k.startswith(("spill_", "promote_"))
                       for k in eng.compiled_executable_counts())

    def test_knob_validation(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError):
            _engine(model, params, prefix_host_mb=8.0, prefix_cache_mb=0)
        with pytest.raises(ValueError):
            _engine(model, params, prefix_host_mb=0.0, prefix_disk_mb=8.0)


@pytest.mark.slow
class TestHostAccounting:
    def test_host_bytes_bounded_and_published(self):
        model, params = _tiny_model()
        eng = _engine(model, params, prefix_host_mb=0.01)  # ~2 spilled nodes
        eng.serve([p.copy() for p in
                   _shared_workload(model.config.vocab_size, n=6, repeat=1)],
                  configs=_gen("greedy"))
        st = eng.prefix_cache_stats()
        assert st["host_bytes"] <= st["host_capacity_bytes"]
        assert st["host_bytes"] == sum(
            n.nbytes for n in eng.prefix_cache._host_nodes)
        # every resident node charges the chunk_bytes unit (data + scales)
        for node in eng.prefix_cache._nodes:
            assert node.nbytes == eng.kv.chunk_bytes(len(node.pages))

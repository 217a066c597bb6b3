"""Continuous-batching serving engine: correctness pins.

The engine's contract is that iteration-level scheduling is *invisible* in the
outputs: greedy decode through the slot pool is token-exact against the static
``generate`` path per request, regardless of which slot a request lands in,
which requests it shares the pool with, or how its prompt was chunked during
prefill.  On top of that, the device program set is FIXED — one decode-window
executable, one insert, one prefill per bucket — asserted via the jit cache
counters (the no-per-request-retrace property that makes this TPU-viable).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig, generate
from accelerate_tpu.models.transformer import KVCache, Transformer, TransformerConfig
from accelerate_tpu.serving import PrefixCache, ServingEngine, RequestState
from accelerate_tpu.serving.pool import plan_chunks
from accelerate_tpu.serving.prefix_cache import rolling_hash
from accelerate_tpu.serving.spec import propose_ngram_draft
from accelerate_tpu.telemetry import MetricsRegistry
from accelerate_tpu.utils.jax_compat import jit_cache_supported


def _tiny_model(seed=0, **kw):
    # float32 everywhere: token-exactness comparisons need the argmax margins
    # of full precision, not bf16 ties
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompts(rng, lengths, vocab):
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]


def _expected(model, params, prompt, gen):
    """The static-``generate`` tokens for one request, pad tail trimmed."""
    seqs, _ = generate(model, params, jnp.asarray(prompt, jnp.int32)[None], gen)
    out = np.asarray(seqs[0])[len(prompt):]
    if gen.eos_token_id is not None:
        hits = np.nonzero(out == gen.eos_token_id)[0]
        if hits.size:
            out = out[: hits[0] + 1]
    return out.tolist()


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2)
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


class TestPlanChunks:
    def test_largest_fit_final_chunk_padded(self):
        assert plan_chunks(9, (4, 8)) == ((8, 8), (4, 1))
        assert plan_chunks(8, (4, 8)) == ((8, 8),)
        assert plan_chunks(3, (4, 8)) == ((4, 3),)
        assert plan_chunks(21, (4, 8)) == ((8, 8), (8, 8), (4, 4), (4, 1))

    def test_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            plan_chunks(5, ())
        with pytest.raises(ValueError):
            plan_chunks(5, (0, 4))


class TestPerLaneCache:
    def test_index_shapes(self):
        cfg = TransformerConfig.tiny()
        assert KVCache.create(cfg, 3, 16).index.shape == ()
        per_lane = KVCache.create(cfg, 3, 16, per_lane_index=True)
        assert per_lane.index.shape == (3,)
        assert per_lane.index.dtype == jnp.int32
        # per-head rows: flat (kv heads x width), positions minor
        flat = cfg.num_kv_heads * cfg.resolved_head_dim
        assert per_lane.k.shape == per_lane.v.shape == (cfg.num_layers, 3, flat, 16)
        assert per_lane.max_len == 16

    def test_per_lane_decode_matches_lockstep(self):
        """A per-lane-index cache with every lane at the same position must
        reproduce the scalar-index cache bit-for-bit — the degenerate case
        that ties the serving path back to ``generate``'s."""
        model, params = _tiny_model()
        cfg = model.config
        ids = jnp.asarray(
            np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 5)), jnp.int32
        )
        scalar = KVCache.create(cfg, 2, 16)
        vector = KVCache.create(cfg, 2, 16, per_lane_index=True)
        ls, scalar = model.apply({"params": params}, ids, cache=scalar)
        lv, vector = model.apply({"params": params}, ids, cache=vector)
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lv))
        np.testing.assert_array_equal(np.asarray(scalar.k), np.asarray(vector.k))
        assert int(scalar.index) == 5
        np.testing.assert_array_equal(np.asarray(vector.index), [5, 5])


class TestTokenExact:
    def test_greedy_matches_generate_mixed_lengths(self):
        """More requests than slots, mixed prompt/output lengths, prompts
        spanning multiple prefill chunks: every request's tokens equal its own
        static ``generate`` row."""
        model, params = _tiny_model()
        rng = np.random.default_rng(1)
        prompts = _prompts(rng, [3, 7, 5, 9, 4], model.config.vocab_size)
        gens = [GenerationConfig(max_new_tokens=n) for n in (6, 9, 5, 7, 8)]
        eng = _engine(model, params)
        reqs = eng.serve(prompts, gens)
        for req, prompt, gen in zip(reqs, prompts, gens):
            assert req.state is RequestState.DONE
            assert req.tokens == _expected(model, params, prompt, gen), req.rid
            np.testing.assert_array_equal(
                req.output_ids, np.concatenate([prompt, np.int32(req.tokens)])
            )
        assert eng.stats["requests_completed"] == len(prompts)
        assert eng.stats["slots_reused"] >= len(prompts) - eng.num_slots

    def test_eos_stops_early_and_slot_is_reused(self):
        """EOS frees a slot mid-flight; the queued request takes that exact
        slot and still decodes token-exact."""
        model, params = _tiny_model()
        rng = np.random.default_rng(2)
        p0, p1 = _prompts(rng, [5, 6], model.config.vocab_size)
        # derive an EOS the greedy path actually emits: the 3rd generated token
        probe = _expected(model, params, p0, GenerationConfig(max_new_tokens=8))
        eos = probe[2]
        gen0 = GenerationConfig(max_new_tokens=12, eos_token_id=eos)
        gen1 = GenerationConfig(max_new_tokens=6)
        eng = _engine(model, params, num_slots=1, decode_window=1)
        r0, r1 = eng.serve([p0, p1], [gen0, gen1])
        assert r0.tokens == _expected(model, params, p0, gen0)
        assert r0.tokens[-1] == eos and len(r0.tokens) <= 4
        assert r1.tokens == _expected(model, params, p1, gen1)
        assert r0.slot == r1.slot == 0
        assert eng.stats["slots_reused"] == 1
        # the freed slot was re-admitted on the very next engine step
        assert r1.finish_step > r0.finish_step

    def test_slot_permutation_does_not_change_outputs(self):
        """Per-slot length masking keeps lanes independent: admitting the same
        workload through a permuted slot order leaves every request's tokens
        unchanged (no cross-lane leakage through the shared pool arrays)."""
        model, params = _tiny_model()
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, [4, 8, 3, 6], model.config.vocab_size)
        gens = [GenerationConfig(max_new_tokens=n) for n in (7, 4, 8, 5)]
        outs = []
        for order in [(0, 1, 2), (2, 0, 1), (1, 2, 0)]:
            eng = _engine(model, params, num_slots=3, slot_order=order)
            reqs = eng.serve(prompts, gens)
            outs.append([r.tokens for r in reqs])
        assert outs[0] == outs[1] == outs[2]
        for toks, prompt, gen in zip(outs[0], prompts, gens):
            assert toks == _expected(model, params, prompt, gen)


class TestCompiledShapes:
    def test_fixed_executable_set(self):
        """After a varied workload (both buckets hit, slots reused, partial
        pool occupancy) the engine compiled exactly one executable per role —
        the documented ``1 + len(buckets) + 1`` budget."""
        model, params = _tiny_model()
        rng = np.random.default_rng(4)
        prompts = _prompts(rng, [2, 9, 5, 13, 7], model.config.vocab_size)
        gens = [GenerationConfig(max_new_tokens=n) for n in (3, 8, 6, 4, 7)]
        eng = _engine(model, params, num_slots=2)
        eng.serve(prompts, gens)
        counts = eng.compiled_executable_counts()
        # copy_page exists but stays uncompiled: no prompt ends on a whole
        # cached chunk, so no lane's tail page is shared with the cache
        assert counts == {"decode_window": 1, "copy_page": 0, "lane_install": 1,
                          "prefill_4": 1, "prefill_8": 1}

    def test_mixed_sampling_configs_share_decode_executable(self):
        """Per-request knobs (greedy vs sampled, different temps/top-k/eos)
        are traced vectors, not static args: they never fork the decode
        window."""
        model, params = _tiny_model()
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, [4, 5, 6], model.config.vocab_size)
        gens = [
            GenerationConfig(max_new_tokens=5),
            GenerationConfig(max_new_tokens=5, do_sample=True, temperature=0.7, top_k=8),
            GenerationConfig(max_new_tokens=5, do_sample=True, temperature=1.3, top_p=0.9,
                             eos_token_id=1),
        ]
        eng = _engine(model, params)
        eng.serve(prompts, gens)
        assert eng.compiled_executable_counts()["decode_window"] == 1


class TestStreamingAndSampling:
    def test_on_token_streams_exactly_the_final_tokens(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, [3, 7], model.config.vocab_size)
        streamed = {}
        eng = _engine(model, params)
        reqs = eng.serve(
            prompts,
            GenerationConfig(max_new_tokens=6),
            on_token=lambda req, tok: streamed.setdefault(req.rid, []).append(tok),
        )
        for req in reqs:
            assert streamed[req.rid] == req.tokens

    def test_sampling_is_deterministic_per_seed_and_rid(self):
        """Sampled requests draw from per-request fold_in(seed, rid) streams:
        same seed → identical tokens across engines, even when slot traffic
        differs (num_slots changes which lanes requests land in)."""
        model, params = _tiny_model()
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, [4, 6, 5], model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8)
        runs = []
        for slots in (1, 3):
            eng = _engine(model, params, num_slots=slots, rng_seed=123)
            reqs = eng.serve(prompts, gen)
            for r in reqs:
                assert len(r.tokens) == 6
                assert all(0 <= t < model.config.vocab_size for t in r.tokens)
            runs.append([r.tokens for r in reqs])
        assert runs[0] == runs[1]

    def test_submit_validation(self):
        model, params = _tiny_model()
        eng = _engine(model, params, max_prompt_len=8)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros(0, np.int32))
        with pytest.raises(ValueError, match="max_prompt_len"):
            eng.submit(np.ones(9, np.int32))
        with pytest.raises(ValueError, match="capacity"):
            eng.submit(np.ones(8, np.int32), max_new_tokens=60)

    def test_occupancy_accounting(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(8)
        prompts = _prompts(rng, [4, 4], model.config.vocab_size)
        eng = _engine(model, params, num_slots=2)
        eng.serve(prompts, GenerationConfig(max_new_tokens=4))
        occ = eng.mean_slot_occupancy()
        assert 0.0 < occ <= 1.0
        assert eng.stats["tokens_generated"] == 8
        assert eng.stats["prefill_tokens"] == 8


class TestServingTelemetry:
    def test_latency_histograms_and_compile_gauges(self):
        from accelerate_tpu.telemetry import MetricsRegistry

        model, params = _tiny_model()
        rng = np.random.default_rng(11)
        prompts = _prompts(rng, [3, 7, 5], model.config.vocab_size)
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        eng.serve(prompts, GenerationConfig(max_new_tokens=4))
        snap = reg.snapshot()
        # one TTFT sample per request; one latency sample per generated token
        assert snap["serve/ttft_s"]["count"] == 3
        assert snap["serve/ttft_s"]["p99"] > 0
        assert snap["serve/token_latency_s"]["count"] == eng.stats["tokens_generated"]
        # counters mirror the stats dict exactly
        for key, value in eng.stats.items():
            assert snap[f"serve/{key}_total"] == value
        # each executable behind the watchdog compiled exactly one signature
        assert snap["compile/serve/decode_window/count"] == 1
        assert snap["compile/serve/prefill_4/count"] == 1
        assert all(
            not wd.over_budget()
            for wd in [eng._decode, eng._copy_page, *eng._prefill.values()]
        )
        assert 0.0 < snap["serve/slot_occupancy"] <= 1.0

    def test_stats_dict_stays_resettable_in_place(self):
        from accelerate_tpu.telemetry import MetricsRegistry

        model, params = _tiny_model()
        rng = np.random.default_rng(12)
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        eng.serve(_prompts(rng, [4], model.config.vocab_size),
                  GenerationConfig(max_new_tokens=3))
        generated = eng.stats["tokens_generated"]
        assert generated == 3
        for k in eng.stats:  # the bench's warmup reset idiom must keep working
            eng.stats[k] = 0
        eng.serve(_prompts(rng, [5], model.config.vocab_size),
                  GenerationConfig(max_new_tokens=3))
        assert eng.stats["tokens_generated"] == 3
        # registry counters are cumulative across the reset
        assert reg.get("serve/tokens_generated_total").value == generated + 3

    def test_metrics_interval_logs_health_line(self, caplog):
        import logging

        model, params = _tiny_model()
        rng = np.random.default_rng(13)
        from accelerate_tpu.telemetry import MetricsRegistry

        eng = _engine(model, params, registry=MetricsRegistry())
        with caplog.at_level(logging.INFO, logger="accelerate_tpu.serving.engine"):
            eng.serve(_prompts(rng, [4, 6], model.config.vocab_size),
                      GenerationConfig(max_new_tokens=4), metrics_interval=0.0)
        health = [r for r in caplog.records if "serve health" in r.getMessage()]
        assert health, "metrics_interval=0.0 should log every step"
        assert "tokens/s=" in health[0].getMessage()
        assert "occupancy=" in health[0].getMessage()

    def test_no_health_logging_by_default(self, caplog):
        import logging

        model, params = _tiny_model()
        rng = np.random.default_rng(14)
        from accelerate_tpu.telemetry import MetricsRegistry

        eng = _engine(model, params, registry=MetricsRegistry())
        with caplog.at_level(logging.INFO, logger="accelerate_tpu.serving.engine"):
            eng.serve(_prompts(rng, [4], model.config.vocab_size),
                      GenerationConfig(max_new_tokens=3))
        assert not [r for r in caplog.records if "serve health" in r.getMessage()]


PAGE_BYTES = 128        # a fake page of 4 tokens: the cache only counts bytes
_page_ids = itertools.count(1)


def _insert(cache, parent, tokens):
    """Retain ``tokens`` (a multiple of 4) as that many fresh 4-token pages."""
    n = len(tokens) // 4
    return cache.insert_pages(parent, tokens, [next(_page_ids) for _ in range(n)],
                              nbytes=n * PAGE_BYTES)


class TestPrefixCacheUnit:
    """Radix-tree mechanics in isolation: page ids, no engine, no device."""

    def test_rolling_hash_composes(self):
        a, b = np.arange(4, dtype=np.int32), np.arange(4, 9, dtype=np.int32)
        assert rolling_hash(rolling_hash(1, a), b) == rolling_hash(1, np.concatenate([a, b]))
        assert rolling_hash(1, a) != rolling_hash(1, a[::-1].copy())

    def test_match_insert_roundtrip_and_partial_chunks(self):
        cache = PrefixCache(1 << 20, registry=MetricsRegistry())
        prompt = np.arange(1, 13, dtype=np.int32)           # 12 tokens
        chunks = plan_chunks(12, (4, 8))                    # ((8, 8), (4, 4))
        assert cache.match(prompt, chunks) == []
        n1 = _insert(cache, None, prompt[:8])
        n2 = _insert(cache, n1, prompt[8:12])
        assert [n1, n2] == cache.match(prompt, chunks)
        # an 11-token prompt shares only the full first chunk: (8,8),(4,3)
        assert cache.match(prompt[:11], plan_chunks(11, (4, 8))) == [n1]
        # same tokens, different alignment: a (4,4) head chunk is a miss
        assert cache.match(prompt[:4], plan_chunks(4, (4, 8))) == []
        # re-inserting an already-resident chunk returns the existing node
        assert _insert(cache, n1, prompt[8:12]) is n2
        assert cache.num_nodes == 2

    def test_lru_eviction_under_tiny_budget(self):
        cache = PrefixCache(2 * PAGE_BYTES, registry=MetricsRegistry())
        ta = np.arange(0, 4, dtype=np.int32)
        tb = np.arange(4, 8, dtype=np.int32)
        tc = np.arange(8, 12, dtype=np.int32)
        a = _insert(cache, None, ta)
        assert _insert(cache, None, tb) is not None
        cache.match(ta, ((4, 4),))                          # touch a: b is now LRU
        assert _insert(cache, None, tc) is not None
        assert cache.evictions == 1 and cache.num_nodes == 2
        assert cache.match(ta, ((4, 4),)) == [a]            # survived
        assert cache.match(tb, ((4, 4),)) == []             # evicted
        # a chunk larger than the whole budget is refused outright
        assert _insert(cache, None, np.arange(32, dtype=np.int32)) is None

    def test_refcount_pins_mid_prefill_hit(self):
        """A pinned node (a request mid-prefill depends on its pages) never
        evicts, even as fresh inserts churn everything unpinned around it."""
        cache = PrefixCache(2 * PAGE_BYTES, registry=MetricsRegistry())
        ta = np.arange(0, 4, dtype=np.int32)
        a = _insert(cache, None, ta)
        cache.acquire([a])                                  # hit is mid-prefill
        for i in range(1, 4):                               # churn: b, c, d
            t = np.arange(4 * i, 4 * i + 4, dtype=np.int32)
            assert _insert(cache, None, t) is not None
        assert cache.match(ta, ((4, 4),)) == [a]            # pinned throughout
        cache.release([a])
        # release also LRU-touched it, so one more insert evicts the OTHER node
        assert _insert(cache, None, np.arange(40, 44, dtype=np.int32)) is not None
        assert cache.match(ta, ((4, 4),)) == [a]
        with pytest.raises(RuntimeError, match="underflow"):
            cache.release([a])

    def test_interior_nodes_never_evict_before_leaves(self):
        cache = PrefixCache(3 * PAGE_BYTES, registry=MetricsRegistry())
        prompt = np.arange(0, 8, dtype=np.int32)
        parent = _insert(cache, None, prompt[:4])
        child = _insert(cache, parent, prompt[4:])
        cache.match(prompt[:4], ((4, 4),))                  # parent is MRU, child LRU
        assert _insert(cache, None, np.arange(20, 28, dtype=np.int32)) is not None
        # the leaf went, not the (older-but-interior would break the chain) parent
        assert cache.match(prompt, ((4, 4), (4, 4))) == [parent]
        assert child not in cache._nodes


class TestPrefixCacheEngine:
    """End-to-end: reuse must be invisible in outputs and visible in stats."""

    def _shared_workload(self, model, rng, shared_len=8):
        vocab = model.config.vocab_size
        shared = rng.integers(1, vocab, (shared_len,)).astype(np.int32)
        warm = [np.concatenate([shared, s]) for s in _prompts(rng, [3, 5, 2], vocab)]
        cold = _prompts(rng, [5, 9], vocab)
        return shared, warm, cold

    def test_token_exact_cache_on_vs_off_mixed_shared_cold(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(21)
        shared, warm, cold = self._shared_workload(model, rng)
        prompts = [warm[0], cold[0], warm[1], cold[1], warm[2]]
        gens = [GenerationConfig(max_new_tokens=n) for n in (6, 5, 7, 4, 6)]
        eng_on = _engine(model, params, prefix_cache_mb=16)
        eng_off = _engine(model, params, prefix_cache_mb=0)
        reqs_on = eng_on.serve(prompts, gens)
        reqs_off = eng_off.serve(prompts, gens)
        for r_on, r_off, prompt, gen in zip(reqs_on, reqs_off, prompts, gens):
            assert r_on.tokens == r_off.tokens == _expected(model, params, prompt, gen)
        # warm[1] and warm[2] each replayed the shared 8-token chunk
        assert eng_on.stats["prefix_hit_tokens"] == 16
        assert eng_on.stats["prefix_hit_tokens"] + eng_on.stats["prefix_miss_tokens"] \
            == eng_on.stats["prefill_tokens"]
        assert eng_off.stats["prefix_hit_tokens"] == 0
        assert eng_off.prefix_cache is None
        stats = eng_on.prefix_cache_stats()
        assert 0.0 < stats["hit_rate"] < 1.0 and stats["nodes"] > 0

    def test_cache_prefix_opt_out(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(22)
        shared, warm, _ = self._shared_workload(model, rng)
        eng = _engine(model, params)
        gen = GenerationConfig(max_new_tokens=4)
        reqs = [eng.submit(warm[0], config=gen),
                eng.submit(warm[1], config=gen, cache_prefix=False)]
        eng.run()
        # the opted-out request neither hit nor populated, and stayed exact
        assert eng.stats["prefix_hit_tokens"] == 0
        for req, prompt in zip(reqs, warm[:2]):
            assert req.tokens == _expected(model, params, prompt, gen)

    def test_compiled_shape_budget_with_hits(self):
        """The engine's whole program set: one decode window, one prefill per
        bucket, one lane_install, one copy_page — hits alias pages, so they
        add no executable, and nothing retraces across a workload that mixes
        cold prompts, duplicate-prefix hits, and copy-on-write."""
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        rng = np.random.default_rng(123)
        vocab = model.config.vocab_size
        p8 = rng.integers(1, vocab, (8,)).astype(np.int32)
        prompts = [p8, p8.copy(), np.concatenate([p8, p8[:5]]),
                   rng.integers(1, vocab, (11,)).astype(np.int32)]
        eng = _engine(model, params)
        gen = GenerationConfig(max_new_tokens=3)
        reqs = eng.serve(prompts, [gen] * len(prompts))
        for req, prompt in zip(reqs, prompts):
            assert req.tokens == _expected(model, params, prompt, gen)
        assert eng.compiled_executable_counts() == {
            "decode_window": 1, "copy_page": 1, "lane_install": 1,
            "prefill_4": 1, "prefill_8": 1,
        }
        assert not eng._decode.over_budget()
        assert not eng._copy_page.over_budget()

    def test_eviction_under_tiny_engine_budget_stays_exact(self):
        """A budget far below the workload's page footprint churns the cache
        hard (insert/evict on nearly every chunk) without touching outputs."""
        model, params = _tiny_model()
        rng = np.random.default_rng(24)
        prompts = _prompts(rng, [8, 12, 9, 16, 8], model.config.vocab_size)
        gens = [GenerationConfig(max_new_tokens=n) for n in (4, 6, 3, 5, 4)]
        # a float32 8-token chunk's pages for the tiny model are ~4 KiB; 6 KiB holds
        # barely one, so every new full chunk forces an eviction decision
        eng = _engine(model, params, prefix_cache_mb=6 / 1024)
        reqs = eng.serve(prompts, gens)
        for req, prompt, gen in zip(reqs, prompts, gens):
            assert req.tokens == _expected(model, params, prompt, gen)
        assert eng.prefix_cache.evictions > 0
        assert eng.prefix_cache.bytes <= eng.prefix_cache.capacity

    def test_hit_metrics_flow_through_registry(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(25)
        shared, warm, _ = self._shared_workload(model, rng)
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        eng.serve(warm, GenerationConfig(max_new_tokens=3))
        snap = reg.snapshot()
        assert snap["serve/prefix_hit_tokens_total"] == eng.stats["prefix_hit_tokens"] > 0
        assert snap["serve/prefix_miss_tokens_total"] == eng.stats["prefix_miss_tokens"]
        assert 0.0 < snap["serve/prefix_hit_rate"] < 1.0
        assert snap["serve/prefix_cache_bytes"] == eng.prefix_cache.bytes > 0
        assert snap["serve/prefix_cache_nodes"] == eng.prefix_cache.num_nodes


class TestCancel:
    def test_cancel_queued_request(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(26)
        prompts = _prompts(rng, [4, 5, 4], model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=3)
        eng = _engine(model, params, num_slots=1, decode_window=1)
        reqs = [eng.submit(p, config=gen) for p in prompts]
        assert eng.cancel(reqs[2])          # by handle, while still queued
        eng.run()
        assert reqs[2].state is RequestState.CANCELLED and reqs[2].tokens == []
        for req, prompt in zip(reqs[:2], prompts[:2]):
            assert req.done and req.tokens == _expected(model, params, prompt, gen)
        assert eng.stats["cancelled"] == 1
        assert eng.stats["requests_completed"] == 2

    def test_cancel_running_true_done_or_unknown_false(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(27)
        eng = _engine(model, params)
        prompts = _prompts(rng, [4, 4], model.config.vocab_size)
        req = eng.submit(prompts[0], max_new_tokens=3)
        eng.step()                          # admitted: lane is RUNNING
        assert eng.cancel(req.rid)          # running lanes cancel mid-stream
        assert req.state is RequestState.CANCELLED
        assert eng.stats["cancelled"] == 1
        other = eng.submit(prompts[1], max_new_tokens=3)
        eng.run()
        assert other.done and not eng.cancel(other)
        assert not eng.cancel(999)
        assert eng.stats["cancelled"] == 1

    def test_cancel_releases_pinned_prefix_nodes(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(28)
        shared = rng.integers(1, model.config.vocab_size, (8,)).astype(np.int32)
        eng = _engine(model, params)
        eng.serve([shared], GenerationConfig(max_new_tokens=2))   # populate
        (node,) = eng.prefix_cache._nodes
        assert node.refs == 0
        req = eng.submit(np.concatenate([shared, shared[:3]]), max_new_tokens=2)
        assert node.refs == 1               # pinned by the submit-time match
        assert eng.cancel(req)
        assert node.refs == 0


class TestNgramDraft:
    """Host-side prompt-lookup drafting in isolation (pure numpy)."""

    def test_most_recent_match_and_continuation(self):
        ctx = np.array([1, 2, 3, 9, 1, 2, 3], np.int32)
        assert propose_ngram_draft(ctx, 2).tolist() == [9, 1]
        # the trailing trigram recurs twice; the most recent copy wins
        ctx = np.array([1, 2, 3, 4, 1, 2, 3, 5, 1, 2, 3], np.int32)
        assert propose_ngram_draft(ctx, 1).tolist() == [5]

    def test_short_continuation_extends_cyclically(self):
        # match one period from the tail: the draft wraps around the cycle
        # instead of running out of context
        d = propose_ngram_draft(np.array([1, 2, 1, 2], np.int32), 3)
        assert d.tolist() == [1, 2, 1]
        d = propose_ngram_draft(np.array([7, 3, 4, 3, 4], np.int32), 6)
        assert d.tolist() == [3, 4, 3, 4, 3, 4]

    def test_minimal_and_degenerate_contexts(self):
        # the shortest drafting context: a repeated unigram
        assert propose_ngram_draft(np.array([5, 5], np.int32), 1).tolist() == [5]
        assert propose_ngram_draft(np.array([5], np.int32), 2) is None
        assert propose_ngram_draft(np.array([5, 5], np.int32), 0) is None

    def test_no_recurrence_returns_none(self):
        assert propose_ngram_draft(np.array([1, 2, 3, 4], np.int32), 2) is None


class TestSpeculative:
    """Speculative decoding: invisible in greedy outputs, visible in stats."""

    def _workload(self, model, rng):
        vocab = model.config.vocab_size
        # two heavily self-repetitive prompts (n-gram drafting's home turf)
        # interleaved with a random one (the fallback path)
        rep_a = np.tile(rng.integers(1, vocab, (5,)), 4)[:16].astype(np.int32)
        rep_b = np.tile(rng.integers(1, vocab, (3,)), 5).astype(np.int32)
        return [rep_a, rng.integers(1, vocab, (9,)).astype(np.int32), rep_b]

    def test_greedy_token_exact_across_k(self):
        """speculate_k in {0, 2, 4} — and the static ``generate`` reference —
        all produce byte-identical greedy tokens (prefix cache on)."""
        model, params = _tiny_model()
        rng = np.random.default_rng(31)
        prompts = self._workload(model, rng)
        gens = [GenerationConfig(max_new_tokens=n, eos_token_id=1)
                for n in (12, 8, 10)]
        outs = {}
        for k in (0, 2, 4):
            eng = _engine(model, params, speculate_k=k)
            reqs = eng.serve(prompts, gens)
            outs[k] = [r.tokens for r in reqs]
            if k:
                assert eng.stats["spec_drafted"] > 0
        assert outs[0] == outs[2] == outs[4]
        for toks, prompt, gen in zip(outs[0], prompts, gens):
            assert toks == _expected(model, params, prompt, gen)

    def test_token_exact_with_cancel_mid_stream(self):
        """Cancelling a queued request under speculation leaves every other
        request's tokens exactly what the non-speculative engine produces."""
        model, params = _tiny_model()
        rng = np.random.default_rng(32)
        prompts = self._workload(model, rng)
        gen = GenerationConfig(max_new_tokens=8)
        results = {}
        for k in (0, 3):
            eng = _engine(model, params, num_slots=1, decode_window=1,
                          speculate_k=k)
            reqs = [eng.submit(p, config=gen) for p in prompts]
            eng.step()                       # request 0 mid-stream, 1/2 queued
            assert eng.cancel(reqs[1])
            eng.run()
            assert reqs[1].state is RequestState.CANCELLED
            results[k] = [reqs[0].tokens, reqs[2].tokens]
        assert results[0] == results[3]
        assert results[0][0] == _expected(model, params, prompts[0], gen)
        assert results[0][1] == _expected(model, params, prompts[2], gen)

    def test_compiled_budget_adds_exactly_one_verify_executable(self):
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        rng = np.random.default_rng(33)
        prompts = self._workload(model, rng)
        gens = [GenerationConfig(max_new_tokens=n) for n in (10, 6, 8)]
        eng = _engine(model, params, speculate_k=3)
        eng.serve(prompts, gens)
        # mixed drafted + fallback cycles ran; exactly ONE verify signature
        assert eng.stats["spec_drafted"] > 0
        assert eng.compiled_executable_counts() == {
            "decode_window": 1, "copy_page": 1, "verify_window": 1,
            "lane_install": 1, "prefill_4": 1, "prefill_8": 1,
        }
        assert not eng._verify.over_budget()

    def test_per_request_opt_out(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(34)
        prompts = self._workload(model, rng)
        gen = GenerationConfig(max_new_tokens=8)
        eng = _engine(model, params, speculate_k=3)
        reqs = [eng.submit(p, config=gen, speculate=False) for p in prompts]
        eng.run()
        # nobody drafted, so every cycle fell back to the decode window
        assert eng.stats["spec_drafted"] == 0
        counts = eng.compiled_executable_counts()
        assert counts["verify_window"] == 0 and counts["decode_window"] == 1
        for req, prompt in zip(reqs, prompts):
            assert req.tokens == _expected(model, params, prompt, gen)

    def test_spec_metrics_flow_through_registry(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(35)
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg, speculate_k=3)
        eng.serve(self._workload(model, rng),
                  GenerationConfig(max_new_tokens=10))
        snap = reg.snapshot()
        assert snap["serve/spec_drafted_total"] == eng.stats["spec_drafted"] > 0
        assert snap["serve/spec_accepted_total"] == eng.stats["spec_accepted"]
        assert 0.0 < snap["serve/spec_accept_rate"] <= 1.0
        assert snap["serve/spec_accept_rate"] == pytest.approx(
            eng.stats["spec_accepted"] / eng.stats["spec_drafted"]
        )
        # token-latency samples still equal tokens generated (the amortized
        # accounting must count 1..K+1 landed tokens per lane per cycle)
        assert snap["serve/token_latency_s"]["count"] == eng.stats["tokens_generated"]

    def test_sampled_speculation_is_deterministic_and_in_vocab(self):
        """Sampled lanes under speculation: the accept/resample rule preserves
        the output *distribution*, not the sample stream — so we pin what is
        guaranteed: per-seed determinism and valid tokens."""
        model, params = _tiny_model()
        rng = np.random.default_rng(36)
        prompts = self._workload(model, rng)
        gen = GenerationConfig(max_new_tokens=8, do_sample=True, temperature=0.8)
        runs = []
        for _ in range(2):
            eng = _engine(model, params, speculate_k=3, rng_seed=123)
            reqs = eng.serve(prompts, gen)
            for r in reqs:
                assert len(r.tokens) == 8
                assert all(0 <= t < model.config.vocab_size for t in r.tokens)
            runs.append([r.tokens for r in reqs])
        assert runs[0] == runs[1]

    def test_capacity_check_covers_verify_span(self):
        model, params = _tiny_model()
        eng = _engine(model, params, decode_window=2, speculate_k=7)
        # max(window, k + 1) = 8: an 8-token prompt + 49 new > 64 capacity
        with pytest.raises(ValueError, match="speculation span"):
            eng.submit(np.ones(8, np.int32), max_new_tokens=49)
        eng.submit(np.ones(8, np.int32), max_new_tokens=48)


class TestInterleavedPrefill:
    """Decode-interleaved chunked prefill must be invisible in the token
    streams: dispatching a prompt's chunks behind the same cycle's decode
    window (instead of ahead of it) reorders device work, never outputs —
    lane RNG streams are keyed by request id, not arrival cycle."""

    def _workload(self, model, seed=40, lens=(3, 14, 5, 22, 9)):
        rng = np.random.default_rng(seed)
        return _prompts(rng, lens, model.config.vocab_size)

    def _serve(self, model, params, prompts, gen, **kw):
        defaults = dict(page_size=4, async_depth=1)
        defaults.update(kw)
        eng = _engine(model, params, **defaults)
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        return eng, [r.tokens for r in reqs]

    def test_greedy_identical_and_chunks_interleave(self):
        model, params = _tiny_model()
        prompts = self._workload(model)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen)
        eng, inter = self._serve(model, params, prompts, gen,
                                 interleave_prefill=True)
        assert inter == base
        for toks, prompt in zip(base, prompts):
            assert toks == _expected(model, params, prompt, gen)
        # the mix is wide enough that some chunks really did ride behind a
        # decode window — the property the knob exists for
        assert eng.stats["interleaved_chunks"] > 0
        assert eng.stats["interleaved_chunks"] <= eng.stats["prefill_chunks"]

    def test_sampled_identical(self):
        model, params = _tiny_model()
        prompts = self._workload(model, seed=41)
        gen = GenerationConfig(max_new_tokens=6, do_sample=True,
                               temperature=0.8, top_k=50, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen)
        _, inter = self._serve(model, params, prompts, gen,
                               interleave_prefill=True)
        assert inter == base

    def test_speculative_identical(self):
        model, params = _tiny_model()
        base_p = np.tile(np.array([5, 6, 7], np.int32), 8)
        prompts = [base_p[:9], base_p[:18], base_p[:9], base_p[:21]]
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen, speculate_k=2)
        eng, inter = self._serve(model, params, prompts, gen, speculate_k=2,
                                 interleave_prefill=True)
        assert inter == base
        assert eng.stats["spec_accepted"] > 0

    @pytest.mark.parametrize("prefill_kernel", ["xla", "pallas"])
    def test_flash_prefill_identical(self, prefill_kernel):
        """prefill_kernel="pallas" (the paged flash-prefill kernel, interpret
        mode on CPU) + interleaving vs the default gather/scatter ordering."""
        model, params = _tiny_model()
        prompts = self._workload(model, seed=42)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen)
        _, out = self._serve(model, params, prompts, gen,
                             interleave_prefill=True,
                             prefill_kernel=prefill_kernel)
        assert out == base

    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_quantized_flash_prefill_identical(self, fmt):
        """Quantized pages: interleaved flash prefill must match the
        non-interleaved quantized engine exactly — chunks quantize at scatter
        time with the same per-page scales either way."""
        model, params = _tiny_model()
        prompts = self._workload(model, seed=43)
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen, kv_dtype=fmt,
                              decode_kernel="pallas")
        _, out = self._serve(model, params, prompts, gen, kv_dtype=fmt,
                             decode_kernel="pallas", prefill_kernel="pallas",
                             interleave_prefill=True)
        assert out == base

    def test_prefix_cache_hits_stay_exact_under_interleave(self):
        """Cached chunks alias pages (zero budget, no forward pass); the
        interleaved scheduler must replay them identically and still count
        hits — SRTF ordering cannot skip or double-play a cached chunk."""
        model, params = _tiny_model()
        rng = np.random.default_rng(44)
        vocab = model.config.vocab_size
        shared = rng.integers(1, vocab, (8,)).astype(np.int32)
        warm = [np.concatenate([shared, s]) for s in _prompts(rng, [3, 5, 2], vocab)]
        cold = _prompts(rng, [5, 14], vocab)
        prompts = [warm[0], cold[0], warm[1], cold[1], warm[2]]
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        _, base = self._serve(model, params, prompts, gen, prefix_cache_mb=16)
        eng, inter = self._serve(model, params, prompts, gen, prefix_cache_mb=16,
                                 interleave_prefill=True)
        assert inter == base
        assert eng.stats["prefix_hit_tokens"] == 16
        assert (eng.stats["prefix_hit_tokens"] + eng.stats["prefix_miss_tokens"]
                == eng.stats["prefill_tokens"])

    def test_prefill_kernel_validation(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError):
            _engine(model, params, prefill_kernel="mosaic")

    def test_prefill_kernel_follows_decode_kernel_by_default(self):
        model, params = _tiny_model()
        eng = _engine(model, params, decode_kernel="pallas")
        assert eng.prefill_kernel == "pallas"
        eng = _engine(model, params)
        assert eng.prefill_kernel == "xla"
        eng = _engine(model, params, decode_kernel="pallas",
                      prefill_kernel="xla")
        assert eng.prefill_kernel == "xla"

    def test_interleave_metrics_flow_through_registry(self):
        model, params = _tiny_model()
        prompts = self._workload(model, seed=45)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        reg = MetricsRegistry()
        eng = _engine(model, params, page_size=4, async_depth=1,
                      interleave_prefill=True, registry=reg)
        reqs = [eng.submit(p, config=gen,
                           request_class="chat" if i % 2 else "bulk")
                for i, p in enumerate(prompts)]
        eng.run()
        snap = reg.snapshot()
        assert snap["serve/interleaved_chunks_total"] == eng.stats["interleaved_chunks"]
        assert 0.0 <= snap["serve/prefill_interleave_ratio"] <= 1.0
        assert snap["serve/prefill_tokens_per_s"] > 0.0
        # per-class TTFT histograms: every request observed exactly once
        chat = snap["serve/ttft_s_class_chat"]
        bulk = snap["serve/ttft_s_class_bulk"]
        assert chat["count"] + bulk["count"] == len(reqs)
        assert chat["count"] == sum(1 for i in range(len(prompts)) if i % 2)

    def test_compiled_budget_flat_across_orderings(self):
        """Interleaving reorders dispatch of executables that already exist;
        the flash-prefill kernel replaces each bucket's program.  No arm may
        add a compiled shape."""
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        prompts = self._workload(model, seed=46)
        gen = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=None)
        counts = []
        for kw in (dict(), dict(interleave_prefill=True),
                   dict(interleave_prefill=True, prefill_kernel="pallas")):
            eng, _ = self._serve(model, params, prompts, gen, **kw)
            counts.append(eng.compiled_executable_counts())
            assert not eng._prefill[4].over_budget()
        assert counts[0] == counts[1] == counts[2]

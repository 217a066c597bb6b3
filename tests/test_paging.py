"""Paged KV allocator: refcounting, sharing, copy-on-write, preemption.

The page pool's contract: block-table indirection is *invisible* in the
outputs.  Greedy decode through the page pool is token-identical to the
static ``generate`` path — the gathered per-lane view has exactly the width
of ``generate``'s contiguous cache, so the attention program is bitwise the
same — while prefix-cache hits alias
physical pages with zero KV copies, shared pages survive eviction pressure
for as long as anything references them, and page pressure preempts the
youngest lane instead of corrupting anyone's KV.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig, generate
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.serving import NULL_PAGE, PageAllocator, PagedKVPool, ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry
from accelerate_tpu.utils.jax_compat import jit_cache_supported


def _tiny_model(seed=0, **kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _expected(model, params, prompt, gen):
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


class TestPageAllocator:
    def test_alloc_is_all_or_nothing_and_deterministic(self):
        a = PageAllocator(6)  # 5 real pages
        assert a.free_count == 5 and a.used_count == 0
        assert a.alloc(3) == [1, 2, 3]  # ascending: allocation order is stable
        assert a.alloc(3) is None       # only 2 left: nothing taken
        assert a.free_count == 2
        assert a.alloc(2) == [4, 5]
        assert a.alloc(0) == []

    def test_refcount_lifecycle(self):
        a = PageAllocator(4)
        ids = a.alloc(2)
        a.ref(ids)                       # a second owner
        assert a.deref(ids) == 0         # first deref frees nothing
        assert a.deref(ids) == 2         # second returns both to the free list
        assert a.free_count == 3
        with pytest.raises(RuntimeError):
            a.deref(ids)                 # underflow is a hard bug, not a no-op
        with pytest.raises(RuntimeError):
            a.ref([ids[0]])              # ref on a free page likewise

    def test_null_page_is_reserved(self):
        a = PageAllocator(3)
        assert NULL_PAGE not in a.alloc(2)
        assert a.deref([NULL_PAGE]) == 0  # deref of the sink is a no-op
        assert a.refs[NULL_PAGE] == 1

    def test_shared_extra_refs_counts_aliases_only(self):
        a = PageAllocator(5)
        ids = a.alloc(2)
        assert a.shared_extra_refs() == 0
        a.ref(ids)
        a.ref([ids[0]])
        assert a.shared_extra_refs() == 3  # (3-1) + (2-1)


class TestPagedKVPool:
    def test_geometry_validation(self):
        cfg = TransformerConfig.tiny(max_seq_len=64)
        with pytest.raises(ValueError):  # view width must equal max_len
            PagedKVPool(cfg, 2, max_len=10, page_size=4, num_pages=8,
                        registry=MetricsRegistry())
        with pytest.raises(ValueError):  # one full lane must always fit
            PagedKVPool(cfg, 2, max_len=16, page_size=4, num_pages=4,
                        registry=MetricsRegistry())

    def test_lane_table_ops(self):
        cfg = TransformerConfig.tiny(max_seq_len=64)
        pool = PagedKVPool(cfg, 2, max_len=16, page_size=4, num_pages=9,
                           registry=MetricsRegistry())
        ids = pool.allocator.alloc(2)
        pool.lane_append_owned(0, ids)
        pool.lane_append_shared(1, ids)  # lane 1 aliases: refs go to 2
        assert pool.chunk_ids(0, 0, 2) == ids == pool.chunk_ids(1, 0, 2)
        assert all(pool.allocator.refs[p] == 2 for p in ids)
        new = pool.allocator.alloc(1)
        old = pool.lane_replace(1, 0, new[0])  # lane 1 COWs its first page
        assert old == ids[0] and pool.allocator.refs[old] == 1
        assert pool.lane_release(1) == 1       # frees only the COW'd page
        assert pool.lane_release(0) == 2
        assert np.all(pool.tables == NULL_PAGE)
        assert pool.allocator.used_count == 0


class TestOnePool:
    def test_default_engine_runs_the_page_pool_and_returns_every_page(self):
        model, params = _tiny_model()
        eng = ServingEngine(model, params, max_len=64, prefill_buckets=(4, 8),
                            registry=MetricsRegistry())
        assert eng.kv.allocator.used_count == 0
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (7, 12, 8, 5, 3)]
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        assert eng.kv.allocator.used_count > 0           # the cache holds prefixes
        for req, prompt in zip(reqs, prompts):
            assert req.tokens == _expected(model, params, prompt, gen)
        while eng.prefix_cache.evict_one():
            pass
        assert eng.kv.allocator.used_count == 0

    def test_paged_false_is_refused_by_name(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError, match="paged=False"):
            ServingEngine(model, params, paged=False)
        # what the benchmark's workload files pass still constructs
        assert ServingEngine(model, params, max_len=64, paged=True,
                             registry=MetricsRegistry()).kv is not None


class TestPagedTokenIdentity:
    """The acceptance gate: outputs through the page pool are ``generate``'s,
    and do not depend on which lanes or which loop carried them."""

    def _serve(self, model, params, prompts, gen, **kw):
        eng = _engine(model, params, registry=MetricsRegistry(), **kw)
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        return eng, [r.tokens for r in reqs]

    def test_mixed_lengths_match_generate(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 3, 12, 7, 16)]
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        eng, paged = self._serve(model, params, prompts, gen)
        for toks, prompt in zip(paged, prompts):
            assert toks == _expected(model, params, prompt, gen)
        # every page came back once the pool drained and the cache let go
        while eng.prefix_cache.evict_one():
            pass
        assert eng.kv.allocator.used_count == 0

    @pytest.mark.parametrize("other", [
        dict(slot_order=(1, 0)), dict(async_depth=0),
        dict(interleave_prefill=True), dict(mesh="tp2"),
    ], ids=["slot_order", "async_depth", "interleave_prefill", "tp2"])
    def test_sampled_stream_is_independent_of(self, other):
        # same base seed + same per-rid fold-in => the identical sample stream,
        # whichever lanes carry the requests, whether the loop is pipelined,
        # whether chunks queue ahead of or behind the window, and whether the
        # pool is whole or head-sharded over two devices; none of them may
        # add a device program either
        if other.get("mesh") == "tp2":
            other = dict(mesh=build_mesh({"tp": 2}, devices=jax.devices()[:2]))
        model, params = _tiny_model()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (6, 11, 9)]
        gen = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8,
                               top_k=50, eos_token_id=None)
        e0, base = self._serve(model, params, prompts, gen)
        e1, varied = self._serve(model, params, prompts, gen, **other)
        assert varied == base
        assert e1.compiled_executable_counts() == e0.compiled_executable_counts()

    def test_speculative_paged_matches_generate(self, cycling_prompts):
        model, params = _tiny_model()
        prompts = cycling_prompts(model, params, new_tokens=8, k=2)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        eng, paged = self._serve(model, params, prompts, gen, speculate_k=2)
        assert paged == [_expected(model, params, p, gen) for p in prompts]
        # the prompts' continuations are ones the drafter provably predicts,
        # so the verify path ran AND committed drafts
        assert eng.stats["spec_accepted"] > 0

    def test_compiled_shape_budget(self):
        """The whole device program set is decode + per-bucket prefill +
        lane_install + copy_page: a prefix hit adds no executable."""
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 12, 8)]
        gen = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=None)
        eng, _ = self._serve(model, params, prompts, gen)
        counts = eng.compiled_executable_counts()
        assert set(counts) == {"decode_window", "copy_page", "lane_install",
                               "prefill_4", "prefill_8"}
        assert counts["decode_window"] == 1
        assert counts["prefill_4"] == 1 and counts["prefill_8"] == 1
        assert counts["copy_page"] <= 1  # compiles only on the first COW
        assert not eng._decode.over_budget()


class TestPagedPrefixSharing:
    def test_partial_hit_is_zero_copy(self):
        """A hit whose prompt extends past the shared prefix aliases pages
        through the block table: no copy executable ever compiles."""
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        rng = np.random.default_rng(10)
        vocab = model.config.vocab_size
        shared = rng.integers(1, vocab, (8,)).astype(np.int32)
        prompts = [np.concatenate([shared, rng.integers(1, vocab, (5,)).astype(np.int32)])
                   for _ in range(3)]
        gen = GenerationConfig(max_new_tokens=5, do_sample=False, eos_token_id=None)
        expect = [_expected(model, params, p, gen) for p in prompts]
        eng = _engine(model, params, registry=MetricsRegistry())
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        assert [r.tokens for r in reqs] == expect
        assert eng.stats["prefix_hit_tokens"] > 0
        assert eng.stats["cow_copies"] == 0
        assert eng.compiled_executable_counts()["copy_page"] == 0

    def test_cow_never_mutates_sibling_lanes(self):
        """Two lanes fully aliasing the same cached prompt: each COWs the
        shared tail page before writing, and both streams stay exact."""
        model, params = _tiny_model()
        rng = np.random.default_rng(11)
        shared = rng.integers(1, model.config.vocab_size, (8,)).astype(np.int32)
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        expect = _expected(model, params, shared, gen)
        eng = _engine(model, params, registry=MetricsRegistry())
        reqs = eng.serve([shared.copy(), shared.copy(), shared.copy()], configs=gen)
        assert all(r.tokens == expect for r in reqs)
        assert eng.stats["cow_copies"] >= 1

    def test_shared_pages_survive_eviction_while_referenced(self):
        """A cache squeezed far below the workload's footprint churns nodes
        constantly; pages a running lane still aliases must outlive their
        node's eviction (refcount, not tree residency, frees HBM)."""
        model, params = _tiny_model()
        rng = np.random.default_rng(12)
        vocab = model.config.vocab_size
        shared = rng.integers(1, vocab, (8,)).astype(np.int32)
        prompts = [np.concatenate([shared, rng.integers(1, vocab, (n,)).astype(np.int32)])
                   for n in (4, 6, 5, 7)]
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        expect = [_expected(model, params, p, gen) for p in prompts]
        # ~2.5 bucket-8 chunk-nodes of budget: inserts evict constantly
        cfg = model.config
        page_bytes = 2 * 4 * cfg.num_kv_heads * cfg.resolved_head_dim * cfg.num_layers * 4
        eng = _engine(model, params,
                      prefix_cache_mb=2.5 * 2 * page_bytes / 2**20,
                      registry=MetricsRegistry())
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        assert [r.tokens for r in reqs] == expect
        assert eng.prefix_cache.evictions > 0
        # no page leaked: drain the cache and everything returns
        while eng.prefix_cache.evict_one():
            pass
        assert eng.kv.allocator.used_count == 0

    def test_cache_pages_freed_only_at_refcount_zero(self):
        """Direct check of the eviction hook: a lane's alias keeps the page
        allocated after the cache node is evicted; releasing the lane frees it."""
        model, params = _tiny_model()
        rng = np.random.default_rng(13)
        prompt = rng.integers(1, model.config.vocab_size, (8,)).astype(np.int32)
        gen = GenerationConfig(max_new_tokens=20, do_sample=False, eos_token_id=None)
        eng = _engine(model, params, registry=MetricsRegistry())
        req = eng.submit(prompt, config=gen)
        while not eng._active.any():
            eng.step()
        # the lane runs and the cache holds the prefix chunks it populated
        cached_pages = [p for node in eng.prefix_cache._nodes for p in node.pages]
        assert cached_pages
        refs = eng.kv.allocator.refs
        # the tail page was COW'd at install (decode writes position plen-1),
        # leaving the cache its sole owner; earlier pages stay lane+cache shared
        assert refs[cached_pages[0]] == 2
        assert refs[cached_pages[-1]] == 1
        while eng.prefix_cache.evict_one():
            pass
        assert refs[cached_pages[0]] == 1   # the lane's alias keeps it alive
        assert refs[cached_pages[-1]] == 0  # cache-only page freed at zero
        eng.run()
        assert req.done
        assert eng.kv.allocator.used_count == 0


class TestPagedPressure:
    def test_preemption_stays_token_exact(self):
        """A pool barely over one lane's worth of pages forces preemption:
        the youngest lane releases its pages, requeues, replays, and every
        output stays identical to ``generate``'s."""
        model, params = _tiny_model()
        rng = np.random.default_rng(14)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (12, 16, 9, 14)]
        gen = GenerationConfig(max_new_tokens=28, do_sample=False, eos_token_id=None)
        expect = [_expected(model, params, p, gen) for p in prompts]
        eng = _engine(model, params, prefix_cache_mb=None,
                      num_pages=17, registry=MetricsRegistry())  # Pmax=16 + null
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        assert [r.tokens for r in reqs] == expect
        assert eng.stats["preemptions"] >= 1
        assert eng.kv.allocator.used_count == 0

    def test_cancel_running_lane_returns_pages(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(15)
        p1, p2 = (rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                  for n in (12, 16))
        gen = GenerationConfig(max_new_tokens=16, do_sample=False, eos_token_id=None)
        expect2 = _expected(model, params, p2, gen)
        # async_depth=0: this test pins the *immediate* page-return contract
        # of the synchronous loop.  Under the depth-1 pipeline the pages are
        # deferred until the in-flight window retires — that path is covered
        # by test_serving_async.py::test_cancel_running_mid_flight.
        eng = _engine(model, params, prefix_cache_mb=None,
                      registry=MetricsRegistry(), async_depth=0)
        r1 = eng.submit(p1, config=gen)
        r2 = eng.submit(p2, config=gen)
        while r1.state.value != "running":
            eng.step()
        free_before = eng.kv.allocator.free_count
        assert eng.cancel(r1)
        assert r1.state.value == "cancelled"
        assert eng.kv.allocator.free_count > free_before  # pages back NOW
        assert eng.stats["cancelled"] == 1
        eng.run()
        assert r2.tokens == expect2  # the surviving lane never noticed
        assert eng.kv.allocator.used_count == 0

    def test_gauges_published(self):
        model, params = _tiny_model()
        rng = np.random.default_rng(16)
        prompt = rng.integers(1, model.config.vocab_size, (9,)).astype(np.int32)
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        eng.serve([prompt], configs=GenerationConfig(
            max_new_tokens=4, do_sample=False, eos_token_id=None))
        snap = reg.snapshot()
        assert "serve/kv_pages_in_use" in snap
        assert "serve/kv_pages_free" in snap
        assert "serve/kv_bytes_shared" in snap
        assert snap["serve/kv_pages_in_use"] + snap["serve/kv_pages_free"] \
            == eng.kv.num_pages - 1


# ------------------------------------------------ the windows' write-back
# ``pool._store_span_pages`` stores whole pages; the oracle stores the span's
# rows one by one.  Each case: page size, the lanes' tables (page 0 is the null
# page), their write indices, the span's width, who is active, K's and V's
# row shapes ``[H, D]``.  Every page but the null one must come out the same,
# bit for bit, so a page nobody wrote is a page nobody touched.
_STORE_CASES = {
    "inside_one_page": dict(page=8, tables=[[1, 2, 3], [4, 5, 6]], start=[9, 17], width=4),
    "crosses_a_boundary": dict(page=8, tables=[[1, 2, 3], [4, 5, 6]], start=[6, 13], width=4),
    # lane 0's span ends with the table: its second slot lies past the end
    "last_page_clipped_slot": dict(page=8, tables=[[1, 2, 3], [4, 5, 6]], start=[20, 2], width=4),
    # lane 1 is mid-prefill: pages 1 and 2 are lane 0's prefix (shared), its
    # index is stale and points into page 2
    "inactive_lane_shares_pages": dict(page=8, tables=[[1, 2, 3], [1, 2, 4]], start=[18, 11],
                                       width=4, active=[True, False]),
    "width_over_a_page": dict(page=16, tables=[[1, 2, 3, 4], [5, 6, 7, 8]], start=[13, 30], width=20),
    "latent_rows_differ": dict(page=8, tables=[[1, 2, 3], [4, 5, 6]], start=[7, 12], width=4,
                               rows=((1, 32), (1, 8))),
}


def _row_store(pages, view, tables, start, width, active):
    """``view`` position-major ``[L, N, M, H, D]``."""
    out, page = pages.copy(), pages.shape[3]
    for lane in np.flatnonzero(active):
        for pos in range(start[lane], start[lane] + width):
            out[:, tables[lane, pos // page], :, pos % page] = view[:, lane, pos]
    return out


# the per-head view is ``[L, N, H * D, M]`` (rows flat, positions minor), the
# latent one ``[L, N, M, 1, width]``: a latent model's rows go through the
# position-major arm only
@pytest.mark.parametrize("case,flat", [
    (case, flat) for case in _STORE_CASES for flat in (True, False)
    if not (flat and "rows" in _STORE_CASES[case])
])
def test_store_span_pages_matches_row_store(case, flat):
    from accelerate_tpu.serving import pool

    c = _STORE_CASES[case]
    page, width, layers = c["page"], c["width"], 2
    tables = np.asarray(c["tables"], np.int32)
    start = np.asarray(c["start"], np.int32)
    active = np.asarray(c.get("active", [True] * len(start)))
    rng = np.random.default_rng(29)
    for heads, dim in c.get("rows", ((3, 4), (3, 4))):
        pages = rng.standard_normal((layers, tables.max() + 1, heads, page, dim)).astype(np.float32)
        # the view a window sees, then the rows its forward wrote (an inactive
        # lane's are garbage that must never reach the pool)
        live = pool._live_tables(jnp.asarray(tables), jnp.asarray((start + width - 1) // page + 1))
        view = np.array(pool._gather_view(jnp.asarray(pages), live, flat))
        lanes, max_len = tables.shape[0], tables.shape[1] * page
        if flat:
            assert view.shape == (layers, lanes, heads * dim, max_len)
            # positions major for the oracle: [L, N, M, H, D]
            rows = view.reshape(layers, lanes, heads, dim, max_len).transpose(0, 1, 4, 2, 3).copy()
        else:
            assert view.shape == (layers, lanes, max_len, heads, dim)
            rows = view
        for lane, at in enumerate(start):
            rows[:, lane, at:at + width] = rng.standard_normal((layers, width, heads, dim))
        if flat:
            view = rows.transpose(0, 1, 3, 4, 2).reshape(view.shape)
        got = jax.jit(pool._store_span_pages, static_argnums=(4, 6))(
            jnp.asarray(pages), jnp.asarray(view), jnp.asarray(tables), jnp.asarray(start),
            width, jnp.asarray(active), flat)
        want = _row_store(pages, rows, tables, start, width, active)
        np.testing.assert_array_equal(np.asarray(got)[:, 1:], want[:, 1:])
        assert not np.array_equal(want[:, 1:], pages[:, 1:])     # the span was stored


# the in-place arm's XLA read gathers each layer's pages itself
# (``paged_attention_reference``); the gathered arm reads the view
# ``pool._gather_view`` built once a call.  Same attention program, same
# operand order in memory: equal bit for bit on the CPU.
_READ_CASES = {
    "decode": dict(s=1, heads=(4, 2, 8)),
    "verify_span": dict(s=4, heads=(4, 2, 8)),
    "mha_window": dict(s=2, heads=(3, 3, 8), window=5),
    "alibi": dict(s=1, heads=(4, 4, 8), alibi=True),
}


@pytest.mark.parametrize("case", list(_READ_CASES))
def test_in_place_read_equals_gathered_read(case):
    from accelerate_tpu.models.transformer import cached_attention
    from accelerate_tpu.ops.paged_attention import paged_attention_reference
    from accelerate_tpu.serving import pool

    c = _READ_CASES[case]
    (n_q, n_kv, d), s, page = c["heads"], c["s"], 8
    tables = jnp.asarray([[3, 1, 5], [2, 6, 4]], jnp.int32)
    lengths = jnp.asarray([9, 17], jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (2, s, n_q, d), jnp.float32)
    pages_k = jax.random.normal(kk, (1, 7, n_kv, page, d), jnp.float32)
    pages_v = jax.random.normal(kv, (1, 7, n_kv, page, d), jnp.float32)
    kw = dict(window=c.get("window"), alibi=c.get("alibi", False))
    in_place = paged_attention_reference(q, pages_k[0], pages_v[0], tables, lengths, **kw)
    live = pool._live_tables(tables, (lengths + s - 1) // page + 1)
    view_k = pool._gather_view(pages_k, live, True)
    view_v = pool._gather_view(pages_v, live, True)
    assert view_k.shape == (1, 2, n_kv * d, 3 * page)
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    gathered = cached_attention(q, view_k[0], view_v[0], positions, **kw)
    np.testing.assert_array_equal(np.asarray(in_place), np.asarray(gathered))

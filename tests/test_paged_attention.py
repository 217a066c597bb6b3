"""Pallas paged decode attention + quantized KV pages.

Two contracts layered on PR 6's paged allocator:

* the **kernel swap is invisible** — the in-place Pallas kernel (run in
  interpret mode on CPU, the tier-1 discipline) matches the pure-XLA gather
  reference numerically, and an engine decoding with ``decode_kernel="pallas"``
  emits token-identical greedy/sampled/speculative streams to the XLA engine;
* **quantized pages are honest** — per-(page, kv-head) scales are exactly
  ``amax / qmax`` written at scatter time, a fresh page round-trips within
  half a quantization step, untouched entries requantize exactly when the
  page's amax is unchanged, stale slots can never inflate a scale, and the
  whole serving stack (COW, preemption replay, compiled-shape budget) runs
  unchanged on int8/fp8 pools.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.ops.paged_attention import (
    KV_FORMATS,
    kv_qmax,
    kv_storage_dtype,
    paged_attention,
    paged_attention_reference,
    paged_flash_prefill,
    paged_flash_prefill_reference,
    paged_insert,
    paged_quantized_insert,
    resolve_paged_kernel,
)
from accelerate_tpu.serving import NULL_PAGE, ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry
from accelerate_tpu.utils.jax_compat import jit_cache_supported


def _scenario(rng, n, s, page, pages_per_lane, hkv, rep, d, dtype=jnp.float32):
    """A random ragged paged-KV state: per-lane block tables over a shared
    pool, histories of uneven length, and the ``s`` new positions' KV already
    inserted (the call contract of both attention entry points)."""
    num_pages = n * pages_per_lane + 1
    tables = np.arange(1, num_pages).reshape(n, pages_per_lane).astype(np.int32)
    # leave the last table slot dead on every lane so dead-slot handling is
    # always exercised
    cap = page * (pages_per_lane - 1) - s
    lengths = rng.integers(0, cap + 1, n).astype(np.int32)
    pages_k = np.zeros((num_pages, hkv, page, d), np.float32)
    pages_v = np.zeros((num_pages, hkv, page, d), np.float32)
    for lane in range(n):
        t_total = int(lengths[lane]) + s
        kv = rng.normal(size=(2, t_total, hkv, d)).astype(np.float32)
        for t in range(t_total):
            pages_k[tables[lane, t // page], :, t % page] = kv[0, t]
            pages_v[tables[lane, t // page], :, t % page] = kv[1, t]
    q = rng.normal(size=(n, s, hkv * rep, d)).astype(np.float32)
    return (
        jnp.asarray(q, dtype), jnp.asarray(pages_k, dtype),
        jnp.asarray(pages_v, dtype), jnp.asarray(tables),
        jnp.asarray(lengths),
    )


class TestKernelParity:
    """paged_attention (interpret mode) vs the pure-XLA reference oracle."""

    @pytest.mark.parametrize(
        "n,s,page,pages_per_lane,hkv,rep,d",
        [
            (1, 1, 8, 4, 2, 1, 16),    # plain decode, MHA
            (3, 1, 8, 4, 2, 2, 32),    # batched decode, GQA fold
            (2, 3, 8, 4, 2, 1, 16),    # verify-window span crossing a page
            (2, 1, 16, 3, 1, 4, 64),   # wide GQA group, bigger head
        ],
    )
    def test_matches_reference(self, n, s, page, pages_per_lane, hkv, rep, d):
        rng = np.random.default_rng(hash((n, s, page, rep, d)) % 2**32)
        q, pk, pv, tables, lengths = _scenario(
            rng, n, s, page, pages_per_lane, hkv, rep, d
        )
        ref = paged_attention_reference(q, pk, pv, tables, lengths)
        out = paged_attention(q, pk, pv, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_ragged_lengths_never_read_dead_pages(self):
        """Poisoning every page past each lane's live count must not change
        the kernel's output — the live-page skip is real, not cosmetic."""
        rng = np.random.default_rng(42)
        q, pk, pv, tables, lengths = _scenario(rng, 3, 1, 8, 4, 2, 2, 16)
        out = paged_attention(q, pk, pv, tables, lengths)
        live = (np.asarray(lengths) + 1 - 1) // 8 + 1
        pk_poison, pv_poison = np.asarray(pk).copy(), np.asarray(pv).copy()
        for lane in range(3):
            for slot in range(int(live[lane]), tables.shape[1]):
                pk_poison[int(tables[lane, slot])] = 1e9
                pv_poison[int(tables[lane, slot])] = 1e9
        out_p = paged_attention(
            q, jnp.asarray(pk_poison), jnp.asarray(pv_poison), tables, lengths
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))

    def test_bf16_matches_reference(self):
        rng = np.random.default_rng(7)
        q, pk, pv, tables, lengths = _scenario(
            rng, 2, 1, 8, 4, 2, 2, 32, dtype=jnp.bfloat16
        )
        ref = paged_attention_reference(q, pk, pv, tables, lengths)
        out = paged_attention(q, pk, pv, tables, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_quantized_pages_match_reference(self, fmt):
        """Kernel-side dequantization agrees with the reference's — same
        scales, same pages, same math."""
        dtype, qmax = KV_FORMATS[fmt]
        rng = np.random.default_rng(11)
        q, pk, pv, tables, lengths = _scenario(rng, 2, 1, 8, 4, 2, 2, 16)
        num_pages, hkv, _, _ = pk.shape
        qk = jnp.asarray(
            rng.integers(-100, 101, pk.shape).astype(np.float32)
        ).astype(dtype)
        qv = jnp.asarray(
            rng.integers(-100, 101, pv.shape).astype(np.float32)
        ).astype(dtype)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (num_pages, hkv)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (num_pages, hkv)).astype(np.float32))
        ref = paged_attention_reference(q, qk, qv, tables, lengths,
                                        k_scales=ks, v_scales=vs)
        out = paged_attention(q, qk, qv, tables, lengths, k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_quantized_without_scales_rejected(self):
        rng = np.random.default_rng(0)
        q, pk, pv, tables, lengths = _scenario(rng, 1, 1, 8, 3, 1, 1, 16)
        with pytest.raises(ValueError):
            paged_attention(q, pk.astype(jnp.int8), pv.astype(jnp.int8),
                            tables, lengths)


class TestFlashPrefillParity:
    """paged_flash_prefill (interpret mode) vs the pure-XLA oracle: the
    causal flash kernel over pool pages must agree with the reference on
    every chunk shape the engine can dispatch — mid-prompt chunks attending
    prior pages, first chunks with no history, ragged tails, GQA folds, and
    quantized pages."""

    @pytest.mark.parametrize(
        "n,s,page,pages_per_lane,hkv,rep,d",
        [
            (1, 8, 8, 4, 2, 1, 16),    # one chunk == one page, MHA
            (2, 16, 8, 6, 2, 2, 32),   # chunk spans pages, GQA fold
            (2, 8, 8, 5, 1, 4, 64),    # wide GQA group, bigger head
            (3, 4, 16, 3, 2, 1, 16),   # chunk smaller than a page
        ],
    )
    def test_matches_reference(self, n, s, page, pages_per_lane, hkv, rep, d):
        rng = np.random.default_rng(hash(("pf", n, s, page, rep, d)) % 2**32)
        q, pk, pv, tables, lengths = _scenario(
            rng, n, s, page, pages_per_lane, hkv, rep, d
        )
        ref = paged_flash_prefill_reference(q, pk, pv, tables, lengths)
        out = paged_flash_prefill(q, pk, pv, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_causal_mask_at_chunk_boundary(self):
        """A later chunk's rows must see ALL prior-chunk history plus only
        their own causal prefix: shifting a token the chunk should not see
        (a future in-chunk position) must leave earlier rows unchanged,
        while shifting history must change them."""
        rng = np.random.default_rng(31)
        page, s = 8, 8
        q, pk, pv, tables, _ = _scenario(rng, 1, s, page, 5, 2, 2, 16)
        # mid-prompt: pin 13 tokens of history (all within mapped pages, so
        # any zero tail just attends zeros — determinism is what's probed)
        lengths = jnp.asarray([13])
        out = np.asarray(paged_flash_prefill(q, pk, pv, tables, lengths))
        # poke the KV at the chunk's LAST position (13 + s - 1): only the
        # final query row may change
        pk2, pv2 = np.asarray(pk).copy(), np.asarray(pv).copy()
        t = 13 + s - 1
        pk2[int(tables[0, t // page]), :, t % page] += 3.0
        out2 = np.asarray(paged_flash_prefill(
            q, jnp.asarray(pk2), jnp.asarray(pv2), tables, lengths
        ))
        np.testing.assert_allclose(out2[:, :-1], out[:, :-1], atol=2e-5)
        assert not np.allclose(out2[:, -1], out[:, -1], atol=1e-4)
        # poke history (position 3): EVERY row must change (softmax weights)
        pk3 = np.asarray(pk).copy()
        pk3[int(tables[0, 3 // page]), :, 3 % page] += 3.0
        out3 = np.asarray(paged_flash_prefill(
            q, jnp.asarray(pk3), pv, tables, lengths
        ))
        assert not np.allclose(out3[:, 0], out[:, 0], atol=1e-4)

    def test_ragged_final_chunk_and_dead_pages(self):
        """Pages past each lane's causal frontier are never read: poisoning
        them must not perturb a single output element (the page-skip bound
        subsumes the dead-page check)."""
        rng = np.random.default_rng(33)
        n, s, page, ppl = 3, 8, 8, 6
        q, pk, pv, tables, lengths = _scenario(rng, n, s, page, ppl, 2, 2, 16)
        out = paged_flash_prefill(q, pk, pv, tables, lengths)
        live = (np.asarray(lengths) + s - 1) // page + 1
        pk_p, pv_p = np.asarray(pk).copy(), np.asarray(pv).copy()
        for lane in range(n):
            for slot in range(int(live[lane]), ppl):
                pk_p[int(tables[lane, slot])] = 1e9
                pv_p[int(tables[lane, slot])] = 1e9
        out_p = paged_flash_prefill(
            q, jnp.asarray(pk_p), jnp.asarray(pv_p), tables, lengths
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))

    def test_bf16_matches_reference(self):
        rng = np.random.default_rng(34)
        q, pk, pv, tables, lengths = _scenario(
            rng, 2, 8, 8, 5, 2, 2, 32, dtype=jnp.bfloat16
        )
        ref = paged_flash_prefill_reference(q, pk, pv, tables, lengths)
        out = paged_flash_prefill(q, pk, pv, tables, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
        )

    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_quantized_pages_match_reference(self, fmt):
        dtype, _ = KV_FORMATS[fmt]
        rng = np.random.default_rng(35)
        q, pk, pv, tables, lengths = _scenario(rng, 2, 8, 8, 5, 2, 2, 16)
        num_pages, hkv, _, _ = pk.shape
        qk = jnp.asarray(
            rng.integers(-100, 101, pk.shape).astype(np.float32)
        ).astype(dtype)
        qv = jnp.asarray(
            rng.integers(-100, 101, pv.shape).astype(np.float32)
        ).astype(dtype)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (num_pages, hkv)).astype(np.float32))
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (num_pages, hkv)).astype(np.float32))
        ref = paged_flash_prefill_reference(q, qk, qv, tables, lengths,
                                            k_scales=ks, v_scales=vs)
        out = paged_flash_prefill(q, qk, qv, tables, lengths,
                                  k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_quantized_without_scales_rejected(self):
        rng = np.random.default_rng(36)
        q, pk, pv, tables, lengths = _scenario(rng, 1, 8, 8, 3, 1, 1, 16)
        with pytest.raises(ValueError):
            paged_flash_prefill(q, pk.astype(jnp.int8), pv.astype(jnp.int8),
                                tables, lengths)


class TestResolvePrefillKernel:
    def test_prefill_role_refused_under_tp(self):
        class FakeMesh:
            shape = {"tp": 2}
            axis_names = ("tp",)
        with pytest.raises(ValueError, match="single-chip"):
            resolve_paged_kernel("pallas", FakeMesh(), "tp", role="prefill")
        assert resolve_paged_kernel("pallas", None, "tp", role="prefill") == "pallas"
        assert resolve_paged_kernel("xla", FakeMesh(), "tp", role="prefill") == "xla"

    def test_unknown_role_rejected(self):
        with pytest.raises(ValueError):
            resolve_paged_kernel("pallas", None, "tp", role="train")


class TestPagedInsert:
    def test_insert_routes_inactive_lanes_to_null(self):
        pages = jnp.zeros((4, 1, 4, 2), jnp.float32)
        new = jnp.ones((2, 1, 1, 2), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 2]], jnp.int32)
        out = paged_insert(pages, new, tables, jnp.asarray([0, 0]),
                           jnp.asarray([True, False]))
        out = np.asarray(out)
        assert out[1, :, 0].sum() == 2          # active lane landed on its page
        assert out[3].sum() == 0                # frozen lane never touched its page
        assert out[NULL_PAGE, :, 0].sum() == 2  # ...its write sank into the null page


class TestQuantizedInsert:
    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_single_shot_scale_is_amax_over_qmax(self, fmt):
        """Fresh page, one insert: scale == amax/qmax per (page, kv-head) and
        the round-trip error is bounded by the format's step size."""
        dtype, qmax = KV_FORMATS[fmt]
        rng = np.random.default_rng(3)
        page, h, d = 8, 2, 16
        pages = jnp.zeros((3, h, page, d), dtype)
        scales = jnp.ones((3, h), jnp.float32)
        new = jnp.asarray(rng.normal(size=(1, page, h, d)).astype(np.float32))
        tables = jnp.asarray([[1, 2]], jnp.int32)
        pages, scales, err = paged_quantized_insert(
            pages, scales, new, tables, jnp.asarray([0]), jnp.asarray([True])
        )
        amax = np.max(np.abs(np.asarray(new[0])), axis=(0, 2))       # [H]
        np.testing.assert_allclose(np.asarray(scales)[1], amax / qmax, rtol=1e-6)
        got = np.asarray(pages[1], np.float32) * np.asarray(scales)[1][:, None, None]
        got = got.swapaxes(0, 1)                                     # [page, H, D]
        diff = np.abs(got - np.asarray(new[0]))
        if fmt == "int8":
            bound = (amax / qmax / 2)[None, :, None] + 1e-7  # half a step
        else:
            bound = np.abs(np.asarray(new[0])) / 8 + 1e-7    # e4m3: 3-bit mantissa
        assert (diff <= bound).all()
        assert float(err) > 0.0 and float(err) <= diff.max() + 1e-7

    def test_requant_exact_when_amax_unchanged(self):
        """A second insert into the same page whose values stay under the
        existing amax requantizes the old entries EXACTLY — they are integer
        multiples of the unchanged scale, so repeated touches do not drift."""
        rng = np.random.default_rng(4)
        page, h, d = 8, 1, 4
        pages = jnp.zeros((2, h, page, d), jnp.int8)
        scales = jnp.ones((2, h), jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)
        first = rng.normal(size=(1, 4, h, d)).astype(np.float32)
        first[0, 0, 0, 0] = 5.0  # pins the page amax
        pages, scales, _ = paged_quantized_insert(
            pages, scales, jnp.asarray(first), tables,
            jnp.asarray([0]), jnp.asarray([True]),
        )
        old = np.asarray(pages[1], np.float32).copy()
        old_scale = float(scales[1, 0])
        second = np.clip(rng.normal(size=(1, 4, h, d)), -1, 1).astype(np.float32)
        pages, scales, _ = paged_quantized_insert(
            pages, scales, jnp.asarray(second), tables,
            jnp.asarray([4]), jnp.asarray([True]),
        )
        assert float(scales[1, 0]) == old_scale
        np.testing.assert_array_equal(np.asarray(pages[1], np.float32)[:, :4], old[:, :4])

    def test_stale_slots_cannot_inflate_the_scale(self):
        """A realloc'd / rolled-back page carries garbage past the lane's
        frontier; the insert must zero it out of the amax, not encode it."""
        page, h, d = 8, 1, 2
        pages = np.zeros((2, h, page, d), np.int8)
        pages[1, :, 4:] = 127  # stale garbage at slots >= the write frontier
        scales = jnp.full((2, h), 100.0, jnp.float32)  # huge stale scale
        new = jnp.full((1, 2, h, d), 0.5, jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)
        out_pages, out_scales, err = paged_quantized_insert(
            jnp.asarray(pages), scales, new, tables,
            jnp.asarray([2]), jnp.asarray([True]),
        )
        # scale reflects history (slots 0-1, zeros) + new rows only: 0.5/127
        np.testing.assert_allclose(np.asarray(out_scales)[1], 0.5 / 127, rtol=1e-6)
        assert np.asarray(out_pages)[1, :, 4:].sum() == 0  # garbage zeroed

    def test_inactive_lane_is_a_noop_on_real_pages(self):
        page, h, d = 4, 1, 2
        pages = jnp.zeros((2, h, page, d), jnp.int8)
        scales = jnp.ones((2, h), jnp.float32)
        new = jnp.full((1, 1, h, d), 3.0, jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)
        out_pages, out_scales, _ = paged_quantized_insert(
            pages, scales, new, tables, jnp.asarray([0]), jnp.asarray([False])
        )
        assert np.asarray(out_pages)[1].sum() == 0
        np.testing.assert_array_equal(np.asarray(out_scales)[1],
                                      np.asarray(scales)[1])


def _tiny_model(seed=0, **kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2)
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


def _serve(model, params, prompts, gen, **kw):
    eng = _engine(model, params, registry=MetricsRegistry(), **kw)
    reqs = eng.serve([p.copy() for p in prompts], configs=gen)
    return eng, [r.tokens for r in reqs]


class TestEngineKernelIdentity:
    """decode_kernel="pallas" must be invisible in the token streams."""

    def _prompts(self, model, seed, lens):
        rng = np.random.default_rng(seed)
        return [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                for n in lens]

    def test_greedy_identical(self):
        model, params = _tiny_model()
        prompts = self._prompts(model, 20, (5, 9, 3, 12, 7))
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        _, xla = _serve(model, params, prompts, gen, decode_kernel="xla")
        _, pallas = _serve(model, params, prompts, gen, decode_kernel="pallas")
        assert pallas == xla

    def test_sampled_stream_identical(self):
        model, params = _tiny_model()
        prompts = self._prompts(model, 21, (6, 11, 9))
        gen = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8,
                               top_k=50, eos_token_id=None)
        _, xla = _serve(model, params, prompts, gen, decode_kernel="xla")
        _, pallas = _serve(model, params, prompts, gen, decode_kernel="pallas")
        assert pallas == xla

    def test_speculative_identical(self, cycling_prompts):
        model, params = _tiny_model()
        prompts = cycling_prompts(model, params, new_tokens=8, k=2)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        _, xla = _serve(model, params, prompts, gen, speculate_k=2)
        eng, pallas = _serve(model, params, prompts, gen, speculate_k=2,
                             decode_kernel="pallas")
        assert pallas == xla
        # the prompts' continuations are ones the drafter provably predicts,
        # so the direct verify path ran AND committed drafts
        assert eng.stats["spec_accepted"] > 0

    def test_compiled_budget_stays_flat(self):
        """The kernel REPLACES the decode executable: same program-key set,
        one shape each, and the nested paged_attn watchdog stays in budget."""
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        prompts = self._prompts(model, 22, (5, 9, 12, 8))
        gen = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=None)
        eng, _ = _serve(model, params, prompts, gen, decode_kernel="pallas")
        counts = eng.compiled_executable_counts()
        assert set(counts) == {"decode_window", "copy_page", "lane_install",
                               "prefill_4", "prefill_8"}
        assert counts["decode_window"] == 1
        assert not eng._decode.over_budget()


class TestEngineQuantizedKV:
    @pytest.mark.parametrize("fmt", ["int8", "fp8"])
    def test_quantized_pool_serves_and_gauges_error(self, fmt):
        model, params = _tiny_model()
        rng = np.random.default_rng(23)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 12)]
        gen = GenerationConfig(max_new_tokens=6, do_sample=False, eos_token_id=None)
        reg = MetricsRegistry()
        eng = _engine(model, params, kv_dtype=fmt, registry=reg)
        assert eng.kv.pages_k.dtype == kv_storage_dtype(fmt, model.config.dtype)
        reqs = eng.serve([p.copy() for p in prompts], configs=gen)
        assert all(len(r.tokens) == 6 for r in reqs)
        snap = reg.snapshot()
        assert snap.get("serve/kv_quant_error", 0.0) > 0.0
        assert snap["serve/kv_bytes_per_token"] == pytest.approx(
            eng.kv.page_kv_bytes / eng.kv.page_size
        )
        # the quantized pool really is smaller than the native one per token
        native = _engine(model, params, registry=MetricsRegistry())
        assert eng.kv.page_kv_bytes < native.kv.page_kv_bytes / 2
        assert kv_qmax(eng.kv.pages_k.dtype) is not None

    def test_byte_equal_int8_pool_holds_more_lanes(self):
        """The same pool bytes hold proportionally more concurrent lanes: a
        native pool two lanes wide against an int8 pool of no more bytes,
        both offered near-full-lane requests so concurrency is page-bound."""
        model, params = _tiny_model()
        rng = np.random.default_rng(26)
        prompts = [rng.integers(1, model.config.vocab_size, (16,)).astype(np.int32)
                   for _ in range(8)]
        gen = GenerationConfig(max_new_tokens=40, do_sample=False, eos_token_id=None)
        kw = dict(num_slots=8, prefix_cache_mb=None)
        native = _engine(model, params, num_pages=2 * 16 + 1,
                         registry=MetricsRegistry(), **kw)
        # fp32 pages against int8 pages with their f32 scales: 3 to a page's bytes
        quant = _engine(model, params, kv_dtype="int8", num_pages=3 * (2 * 16 + 1),
                        registry=MetricsRegistry(), **kw)
        assert quant.kv.kv_bytes() <= native.kv.kv_bytes()
        for eng in (native, quant):
            reqs = eng.serve([p.copy() for p in prompts], configs=gen)
            assert all(len(r.tokens) == 40 for r in reqs)
        assert quant.peak_active_lanes >= 1.8 * native.peak_active_lanes

    def test_quantized_budget_matches_native_paged(self):
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        rng = np.random.default_rng(24)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 12, 8)]
        gen = GenerationConfig(max_new_tokens=4, do_sample=False, eos_token_id=None)
        eng, _ = _serve(model, params, prompts, gen, kv_dtype="int8")
        counts = eng.compiled_executable_counts()
        assert set(counts) == {"decode_window", "copy_page", "lane_install",
                               "prefill_4", "prefill_8"}
        assert all(c <= 1 for c in counts.values())

    def test_preemption_replay_is_deterministic_under_int8(self):
        """A page-starved int8 pool preempts and replays; the replayed
        requests still land their full output, the run is repeatable
        token-for-token, and every page returns to the free list."""
        model, params = _tiny_model()
        rng = np.random.default_rng(25)
        prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
                   for n in (12, 16, 9, 14)]
        gen = GenerationConfig(max_new_tokens=28, do_sample=False, eos_token_id=None)

        def run():
            eng, toks = _serve(model, params, prompts, gen, prefix_cache_mb=None,
                               num_pages=17, kv_dtype="int8")  # Pmax=16 + null
            return eng, toks

        eng1, toks1 = run()
        eng2, toks2 = run()
        assert eng1.stats["preemptions"] >= 1
        assert toks1 == toks2
        assert all(len(t) == 28 for t in toks1)
        assert eng1.kv.allocator.used_count == 0
        assert eng2.kv.allocator.used_count == 0

"""Autoregressive generation: KV-cache decode, sampling, streaming decode.

Parity target: the reference's published benchmark is token generation under
offload (``/root/reference/benchmarks/big_model_inference.py:141-155``); its
correctness substrate is transformers' cache. Here the contract under test is:
incremental (prefill + per-token decode) logits == full-context forward logits,
for every layer layout and weight placement the framework supports.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.big_modeling import StreamingTransformer, cpu_offload
from accelerate_tpu.models.generation import (
    GenerationConfig,
    generate,
    make_decode_step,
    make_prefill_step,
    sample_tokens,
)
from accelerate_tpu.models.transformer import (
    KVCache,
    Transformer,
    TransformerConfig,
    alibi_slopes,
    cached_attention,
)


def _tiny(scan_layers=False, **kw):
    return TransformerConfig.tiny(scan_layers=scan_layers, **kw)


def _model_and_params(cfg, batch=2, seq=10, seed=0):
    model = Transformer(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed), ids)["params"]
    return model, params, ids


def _plain_attention(q, k, v, q_positions, window=None, alibi=False, tree_mask=None):
    """Float32 attention of ``q [B,S,Hq,D]`` over position-major ``k``/``v``
    ``[B,M,Hkv,D]``, every mask written out: the oracle for
    :func:`cached_attention`, which reads the cache rows flat and positions
    minor."""
    b, s, n_q, d = q.shape
    m, n_kv = k.shape[1], k.shape[2]
    k = jnp.repeat(k, n_q // n_kv, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, n_q // n_kv, axis=2).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) / np.sqrt(d)
    j = np.arange(m)
    pos = np.asarray(q_positions)
    if tree_mask is not None:
        allowed = np.zeros((b, s, m), bool)
        for lane in range(b):
            base = pos[lane, 0]
            allowed[lane, :, :base] = True
            allowed[lane, :, base:base + s] = tree_mask
    else:
        allowed = j[None, None, :] <= pos[:, :, None]
        if window is not None:
            allowed &= j[None, None, :] > pos[:, :, None] - window
    if alibi:
        rel = (j[None, None, :] - pos[:, :, None]).astype(np.float32)
        logits = logits + alibi_slopes(n_q)[None, :, None, None] * rel[:, None]
    logits = jnp.where(allowed[:, None], logits, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)


def _chain_mask(s):
    """Ancestor-or-self mask of a root with two chains under it."""
    parent = [0, 0, 1, 0, 3][:s]
    mask = np.eye(s, dtype=bool)
    for node in range(1, s):
        mask[node] |= mask[parent[node]]
    return mask


#: heads (query, kv, width), queries a lane, the lanes' positions, the masks
_ATTENTION_CASES = {
    "gpt2_xl_heads_decode": dict(heads=(25, 25, 64), s=1, index=[3, 40, 63]),
    "gqa_8q_2kv_chunk": dict(heads=(8, 2, 16), s=6, index=[0, 17, 30]),
    "window": dict(heads=(8, 2, 16), s=3, index=[2, 20, 45], window=7),
    "alibi": dict(heads=(6, 3, 16), s=2, index=[5, 33, 50], alibi=True),
    "tree_mask": dict(heads=(4, 2, 16), s=5, index=[4, 21, 40], tree=True),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_ATTENTION_CASES))
def test_cached_attention_matches_plain_attention(case, dtype):
    """The per-head cache's layout (``[B, Hkv*D, M]``) changes where the
    operands lie, not what is computed: in float32 the result is the plain
    attention's to rounding; a bfloat16 cache under a float32 softmax stays
    within bfloat16's own step of it."""
    c = _ATTENTION_CASES[case]
    (n_q, n_kv, d), s, m = c["heads"], c["s"], 64
    index = np.asarray(c["index"])
    b = len(index)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(kq, (b, s, n_q, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, m, n_kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, m, n_kv, d), jnp.float32).astype(dtype)
    tree = _chain_mask(s) if c.get("tree") else None
    depth = tree.sum(axis=1) - 1 if tree is not None else np.arange(s)
    positions = index[:, None] + depth[None, :]
    flat = lambda x: x.reshape(b, m, n_kv * d).swapaxes(1, 2)      # [B, Hkv*D, M]
    got = cached_attention(q, flat(k), flat(v), jnp.asarray(positions),
                           window=c.get("window"), alibi=c.get("alibi", False),
                           tree_mask=tree)
    want = _plain_attention(q, k, v, positions, window=c.get("window"),
                            alibi=c.get("alibi", False), tree_mask=tree)
    assert got.shape == (b, s, n_q, d) and got.dtype == q.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)


class TestKVCacheDecode:
    @pytest.mark.parametrize("scan_layers", [False, True])
    def test_incremental_matches_full_forward(self, scan_layers):
        cfg = _tiny(scan_layers)  # num_kv_heads < num_heads: GQA covered
        model, params, ids = _model_and_params(cfg)
        full = np.asarray(model.apply({"params": params}, ids))

        cache = KVCache.create(cfg, 2, ids.shape[1])
        prefill = make_prefill_step(model)
        decode = make_decode_step(model)
        logits_p, cache = prefill(params, ids[:, :4], cache)
        np.testing.assert_allclose(np.asarray(logits_p), full[:, :4], rtol=2e-2, atol=2e-2)
        assert int(cache.index) == 4
        for t in range(4, ids.shape[1]):
            lt, cache = decode(params, ids[:, t], cache)
            np.testing.assert_allclose(np.asarray(lt), full[:, t], rtol=2e-2, atol=2e-2)
        assert int(cache.index) == ids.shape[1]

    def test_cache_longer_than_sequence(self):
        # slots beyond the written region must not leak into attention
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg)
        full = np.asarray(model.apply({"params": params}, ids))
        cache = KVCache.create(cfg, 2, ids.shape[1] + 17)
        logits, _ = model.apply({"params": params}, ids, cache=cache)
        np.testing.assert_allclose(np.asarray(logits), full, rtol=2e-2, atol=2e-2)

    def test_moe_model_decodes(self):
        cfg = TransformerConfig.tiny_moe()
        model, params, ids = _model_and_params(cfg)
        full = np.asarray(model.apply({"params": params}, ids))
        cache = KVCache.create(cfg, 2, ids.shape[1])
        logits_p, cache = model.apply({"params": params}, ids[:, :-1], cache=cache)
        lt, cache = model.apply({"params": params}, ids[:, -1:], cache=cache)
        np.testing.assert_allclose(np.asarray(lt[:, 0]), full[:, -1], rtol=5e-2, atol=5e-2)


class TestGenerate:
    def test_greedy_matches_manual_loop(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=5)
        seqs, cache = generate(model, params, ids, GenerationConfig(max_new_tokens=6))
        assert seqs.shape == (2, 11)
        # cache holds prompt + max_new_tokens - 1 entries: the final sampled
        # token is returned but never fed back
        assert int(cache.index) == 10
        # manual loop: argmax over the full uncached forward each step
        cur = np.asarray(ids)
        for _ in range(6):
            logits = np.asarray(model.apply({"params": params}, jnp.asarray(cur)))
            nxt = logits[:, -1].argmax(-1).astype(cur.dtype)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(seqs), cur)

    def test_eos_masks_to_pad(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=4)
        # pick the first greedily generated token as "EOS" for lane 0
        probe, _ = generate(model, params, ids, GenerationConfig(max_new_tokens=3))
        eos = int(np.asarray(probe)[0, 4])
        seqs, _ = generate(
            model, params, ids,
            GenerationConfig(max_new_tokens=5, eos_token_id=eos, pad_token_id=0),
        )
        row = np.asarray(seqs)[0, 4:]
        assert row[0] == eos
        np.testing.assert_array_equal(row[1:], 0)

    def test_cache_too_small_raises(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=5)
        small = KVCache.create(cfg, 2, 6)
        with pytest.raises(ValueError, match="max_len"):
            generate(model, params, ids, GenerationConfig(max_new_tokens=6), cache=small)

    def test_warm_cache_overflow_raises(self):
        # capacity must account for entries already written: dynamic_update_slice
        # clamps out-of-range writes, which would silently corrupt the cache
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=5)
        cache = KVCache.create(cfg, 2, 12)
        _, cache = generate(model, params, ids, GenerationConfig(max_new_tokens=3), cache=cache)
        assert int(cache.index) == 7
        with pytest.raises(ValueError, match="already written"):
            generate(model, params, ids[:, :2], GenerationConfig(max_new_tokens=6), cache=cache)

    def test_streaming_warm_cache_overflow_raises(self):
        from accelerate_tpu.big_modeling import StreamingTransformer

        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=5)
        st = StreamingTransformer(cfg, params)
        with pytest.raises(ValueError, match="max_len"):
            st.generate(ids, max_new_tokens=16, cache=st.init_cache(2, 10))

    def test_sampled_generation_shape_and_determinism(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=4)
        gen = GenerationConfig(max_new_tokens=5, do_sample=True, temperature=0.7, top_k=16)
        a, _ = generate(model, params, ids, gen, rng=jax.random.PRNGKey(7))
        b, _ = generate(model, params, ids, gen, rng=jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # same key, same draw
        c, _ = generate(model, params, ids, gen, rng=jax.random.PRNGKey(8))
        assert a.shape == c.shape == (2, 9)


class TestSampling:
    def _logits(self, vocab=64, batch=512, seed=0):
        return jax.random.normal(jax.random.PRNGKey(seed), (batch, vocab)) * 3.0

    def test_greedy_is_argmax(self):
        logits = self._logits()
        toks = sample_tokens(logits)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(logits).argmax(-1))

    def test_temperature_zero_is_greedy_even_with_do_sample(self):
        logits = self._logits()
        toks = sample_tokens(logits, jax.random.PRNGKey(0), do_sample=True, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(logits).argmax(-1))

    def test_top_k_membership(self):
        logits = self._logits()
        toks = np.asarray(
            sample_tokens(logits, jax.random.PRNGKey(1), do_sample=True, top_k=5)
        )
        top5 = np.argsort(np.asarray(logits), axis=-1)[:, -5:]
        assert all(t in row for t, row in zip(toks, top5))

    def test_top_p_nucleus_membership(self):
        logits = self._logits()
        toks = np.asarray(
            sample_tokens(logits, jax.random.PRNGKey(2), do_sample=True, top_p=0.5)
        )
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        for b, t in enumerate(toks):
            order = np.argsort(-probs[b])
            cum = np.cumsum(probs[b][order])
            nucleus = order[: int(np.searchsorted(cum, 0.5)) + 1]
            assert t in nucleus

    def test_top_p_one_keeps_everything(self):
        logits = jnp.zeros((4, 8))
        toks = np.asarray(
            sample_tokens(logits, jax.random.PRNGKey(3), do_sample=True, top_p=1.0)
        )
        assert ((0 <= toks) & (toks < 8)).all()

    def test_do_sample_without_rng_raises(self):
        with pytest.raises(ValueError, match="rng"):
            sample_tokens(self._logits(), do_sample=True)


class TestStreamingDecode:
    @pytest.mark.parametrize("scan_layers", [False, True])
    def test_streaming_generate_matches_monolithic(self, scan_layers):
        cfg = _tiny(scan_layers)
        model, params, ids = _model_and_params(cfg, seq=6)
        ref, _ = generate(model, params, ids, GenerationConfig(max_new_tokens=7))
        host_params, loader = cpu_offload(params)
        st = StreamingTransformer(cfg, host_params, weights_loader=loader)
        seqs = st.generate(ids, max_new_tokens=7)
        np.testing.assert_array_equal(seqs, np.asarray(ref))

    def test_streaming_prefill_logits_match_full(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=8)
        full = np.asarray(model.apply({"params": params}, ids))
        st = StreamingTransformer(cfg, params)
        cache = st.init_cache(2, 8)
        logits, cache = st.forward_with_cache(ids, cache)
        np.testing.assert_allclose(np.asarray(logits), full, rtol=2e-2, atol=2e-2)
        assert int(cache["index"]) == 8

    def test_streaming_eos_early_stop(self):
        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=4)
        probe, _ = generate(model, params, ids, GenerationConfig(max_new_tokens=2))
        eos = int(np.asarray(probe)[0, 4])
        st = StreamingTransformer(cfg, params)
        seqs = st.generate(ids, max_new_tokens=5, eos_token_id=eos, pad_token_id=0)
        row = seqs[0, 4:]
        assert row[0] == eos and (row[1:] == 0).all()

    def test_quantized_streaming_decode_finite(self):
        import dataclasses

        from accelerate_tpu.ops.quantization import Int8Config, quantize_model_params

        cfg = _tiny()
        model, params, ids = _model_and_params(cfg, seq=6)
        qparams = quantize_model_params(params, Int8Config())
        qcfg = dataclasses.replace(cfg, quantization=8)
        st = StreamingTransformer(qcfg, qparams)
        seqs = st.generate(ids, max_new_tokens=4)
        assert seqs.shape == (2, 10)
        assert ((0 <= seqs) & (seqs < cfg.vocab_size)).all()

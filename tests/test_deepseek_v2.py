"""DeepSeek-V2's block (latent attention, dropless routed experts with a shared
expert, a dense leading layer) through ``Transformer`` and the serving engine,
against the plain reference ``bench/reference/deepseek_v2.py`` at tiny widths on
the CPU, float32, seeded weights.

Tolerances: program and reference compute the same float32 mathematics at
highest matmul precision in another order of summation (the program's blocked
online softmax, its absorbed form, its sorted expert products), so logits of
size ~1 agree to a few 1e-6; ``ATOL`` leaves an order of magnitude of room.
Routing choices are compared exactly: the test inputs have no near-ties at
that noise.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))

from reference import deepseek_v2 as ref  # noqa: E402

from accelerate_tpu.models import latent_attention as mla  # noqa: E402
from accelerate_tpu.models.transformer import KVCache, Transformer, TransformerConfig  # noqa: E402
from accelerate_tpu.parallel.moe import MoEMLP, RoutedExperts, route_top_k  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.serving import engine as engine_module  # noqa: E402
from accelerate_tpu.serving.paging import PagedKVPool  # noqa: E402
from accelerate_tpu.serving.transfer import PageMigrator  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

ATOL = 5e-5
TINY = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 16,
    "experts_held": [4, 12], "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 4.0, "norm_topk_prob": False, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "vocab_size": 97, "rope_theta": 10000,
    "max_position_embeddings": 256,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
}


def _config(published=TINY, **kw):
    return TransformerConfig(**ref.program_fields(published), dtype=jnp.float32,
                             param_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    """``(model, program params, reference params)`` from one seeded draw."""
    model = Transformer(_config())
    ref_params = jax.jit(lambda: ref.init_params(7, TINY, jnp.float32))()
    return model, ref.to_program_tree(ref_params, TINY), ref_params


_REF_WIDTH = 256
_reference_forward = jax.jit(lambda ref_params, ids: ref.forward(ref_params, ids, TINY))


def _reference_logits(ref_params, ids):
    """The reference's full forward over one row, padded to one width so that
    one compiled program serves every test (causal: padding changes nothing
    before it)."""
    row = np.zeros((_REF_WIDTH,), np.int32)
    row[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference_forward(ref_params, jnp.asarray(row)))[:len(ids)]


def _ids(seed, shape, vocab=TINY["vocab_size"]):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(np.int32)


# ------------------------------------------------------------------ the block
def test_parameter_tree_is_the_references_under_program_names(tiny):
    model, params, _ = tiny
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want), jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape, path
    # a dense leading layer and expert layers in one stack
    assert "mlp" in params["layers_0"] and "moe_mlp" in params["layers_1"]
    assert params["layers_1"]["moe_mlp"]["experts"]["gate_proj"]["kernel"].shape == (8, 64, 32)
    assert params["layers_1"]["moe_mlp"]["router"]["kernel"].shape == (64, 16)


def test_forward_without_cache_matches_reference(tiny):
    model, params, ref_params = tiny
    ids = _ids(0, (2, 48))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(ids)))
    for row in range(2):
        np.testing.assert_allclose(got[row], _reference_logits(ref_params, ids[row]), atol=ATOL)


def test_prefill_chunks_then_decode_through_kvcache_match_reference(tiny):
    """A chunk of 40 rows (decompressed), then single rows and a span of four
    (absorbed), all through one ``KVCache``: the reference's full forward."""
    model, params, ref_params = tiny
    ids = _ids(1, (2, 48))
    cache = KVCache.create(model.config, 2, 64, dtype=jnp.float32)
    assert cache.k.shape == (3, 2, 64, 1, 32) and cache.v.shape == (3, 2, 64, 1, 8)
    outs, pos = [], 0
    step = jax.jit(lambda tokens, cache: model.apply({"params": params}, tokens, cache=cache))
    with jax.default_matmul_precision("highest"):
        for n in (40, 1, 1, 4, 1, 1):
            logits, cache = step(jnp.asarray(ids[:, pos:pos + n]), cache)
            outs.append(np.asarray(logits))
            pos += n
    got = np.concatenate(outs, axis=1)
    for row in range(2):
        np.testing.assert_allclose(got[row], _reference_logits(ref_params, ids[row]), atol=ATOL)


def test_long_cached_chunk_runs_in_key_blocks(tiny, monkeypatch):
    """A view longer than a key block: the dynamic loop over live blocks and
    the online softmax give what one pass gives."""
    model, params, ref_params = tiny
    monkeypatch.setattr(mla, "KEY_BLOCK", 128)
    ids = _ids(2, (1, 200))
    cache = KVCache.create(model.config, 1, 256, dtype=jnp.float32)
    step = jax.jit(lambda tokens, cache: model.apply({"params": params}, tokens, cache=cache))
    with jax.default_matmul_precision("highest"):
        first, cache = step(jnp.asarray(ids[:, :160]), cache)
        second, cache = step(jnp.asarray(ids[:, 160:]), cache)
    got = np.concatenate([np.asarray(first), np.asarray(second)], axis=1)[0]
    np.testing.assert_allclose(got, _reference_logits(ref_params, ids[0]), atol=ATOL)


def test_absorbed_attention_equals_decompressed():
    rng = np.random.default_rng(3)
    b, s, m, h, c, nope, rope, v = 2, 5, 24, 4, 32, 16, 8, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    args = (f(b, s, h, nope), f(b, s, h, rope), f(b, m, c), f(b, m, rope), f(c, h, nope) * 0.2,
            f(c, h, v) * 0.2, jnp.asarray([[7, 8, 9, 10, 11], [19, 20, 21, 22, 23]]), 0.3)
    with jax.default_matmul_precision("highest"):
        absorbed = np.asarray(mla.attend_absorbed(*args))
        decompressed = np.asarray(mla.attend_decompressed(*args))
    np.testing.assert_allclose(absorbed, decompressed, atol=1e-5)
    # which form runs is read off the call's shape
    assert mla.use_absorbed(True, 1) and mla.use_absorbed(True, 4)
    assert not mla.use_absorbed(False, 1) and not mla.use_absorbed(True, 128)


def test_yarn_frequencies_and_scale_at_published_values():
    yarn = dict(factor=40, original_max_position=4096, beta_fast=32, beta_slow=1, mscale=0.707,
                mscale_all_dim=0.707)
    cfg = TransformerConfig.tiny(latent_attention=dict(q_rank=8, kv_rank=8, nope_dim=128, rope_dim=64,
                                                       v_dim=128, yarn=yarn))
    la = cfg.latent_attention
    assert mla.softmax_scale(cfg) == pytest.approx(0.11472, rel=1e-4)       # 192^-1/2 x 1.2608^2
    assert mla.rope_amplitude(la.yarn) == pytest.approx(1.0)
    inv = np.asarray(mla.rope_frequencies(64, 10000.0, la.yarn))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)             # fast dims stay
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 40, rtol=1e-6)        # slow dims / factor
    published = dict(TINY, qk_rope_head_dim=64, rope_scaling=dict(TINY["rope_scaling"],
                                                                 original_max_position_embeddings=4096))
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(published), rtol=1e-6)


# ---------------------------------------------------------------- the experts
def _expert_layer(held=(0, 16), seed=5, **spec_kw):
    published = dict(TINY, experts_held=list(held), **spec_kw)
    cfg = _config(published)
    layer = RoutedExperts(cfg)
    ref_layer = jax.jit(lambda: ref.init_layer(seed, published, 1, jnp.float32))()
    moe = {}
    for name, path in ref.EXPERT_PATHS.items():            # ("moe_mlp", module.., leaf)
        node = moe
        for key in path[1:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = ref_layer[name]
    return layer, moe, ref_layer, published


def test_routing_choices_equal_the_references_token_for_token():
    layer, moe, ref_layer, published = _expert_layer()
    h = jnp.asarray(np.random.default_rng(6).normal(size=(64, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(h @ ref_layer["router"], axis=-1)
        want_e, want_g = ref.route(scores, published)
        got_e, got_g = route_top_k(scores, layer.config.experts)
        _, ref_experts, _ = ref.expert_layer(h, ref_layer, published)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(ref_experts))
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), rtol=1e-6)
    # group-limited: every token's choices lie in at most topk_group groups of 4 experts
    assert all(len({int(e) // 4 for e in row}) <= 2 for row in np.asarray(got_e))
    # not renormalised, scaled by 4
    np.testing.assert_allclose(np.asarray(got_g), 4.0 * np.take_along_axis(np.asarray(scores), np.asarray(got_e), 1),
                               rtol=1e-6)


def test_lane_result_does_not_depend_on_who_shares_its_batch():
    """Dropless: a lane's output is the same alone and beside 63 rows that all
    prefer its experts.  The capacity dispatch fails this by construction."""
    layer, moe, _, _ = _expert_layer()
    rng = np.random.default_rng(8)
    lane = rng.normal(size=(1, 1, 64)).astype(np.float32)
    crowd = np.repeat(lane, 63, axis=1) + 1e-3 * rng.normal(size=(1, 63, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(layer.apply)
        alone = np.asarray(apply({"params": moe}, jnp.asarray(lane)))
        shared = np.asarray(apply({"params": moe}, jnp.asarray(np.concatenate([crowd, lane], axis=1))))
    np.testing.assert_allclose(shared[0, -1], alone[0, 0], atol=1e-6)

    old_cfg = TransformerConfig.tiny_moe(dtype=jnp.float32, param_dtype=jnp.float32, expert_capacity_factor=1.0)
    old = MoEMLP(old_cfg)
    old_params = old.init(jax.random.PRNGKey(0), jnp.asarray(lane))["params"]
    old_apply = jax.jit(old.apply)
    old_alone = np.asarray(old_apply({"params": old_params}, jnp.asarray(lane)))
    old_shared = np.asarray(old_apply({"params": old_params}, jnp.asarray(np.concatenate([crowd, lane], axis=1))))
    assert np.abs(old_shared[0, -1] - old_alone[0, 0]).max() > 1e-3        # the last row was dropped


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four devices of an
    expert-parallel group compute, plus what each computes alike (the shared
    expert) counted once, are the whole layer as the uncut reference gives it."""
    whole, moe, ref_layer, published = _expert_layer()
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 24, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        ref_apply = jax.jit(lambda layer, lo: ref.expert_layer(
            x.reshape(48, 64), layer, dict(published, experts_held=[lo, lo + layer["e_gate"].shape[0]]))[0],
            static_argnums=1)
        uncut = np.asarray(ref_apply(ref_layer, 0)).reshape(2, 24, 64)
        np.testing.assert_allclose(np.asarray(jax.jit(whole.apply)({"params": moe}, x)), uncut, atol=1e-5)
        shared_only = np.asarray(ref._swiglu(x.reshape(48, 64), ref_layer["s_gate"], ref_layer["s_up"],
                                             ref_layer["s_down"], "float32")).reshape(2, 24, 64)
        routed_parts = []
        for lo in (0, 4, 8, 12):
            share = _expert_layer(held=(lo, lo + 4))[0]
            part = jax.tree_util.tree_map(lambda a: a, moe)
            part["experts"] = jax.tree_util.tree_map(lambda a: a[lo:lo + 4], moe["experts"])
            y = np.asarray(jax.jit(share.apply)({"params": part}, x))
            # the reference given the same share computes the same partial result
            ref_part = dict(ref_layer, **{k: ref_layer[k][lo:lo + 4] for k in ("e_gate", "e_up", "e_down")})
            np.testing.assert_allclose(y, np.asarray(ref_apply(ref_part, lo)).reshape(2, 24, 64), atol=1e-5)
            routed_parts.append(y - shared_only)
    np.testing.assert_allclose(sum(routed_parts) + shared_only, uncut, atol=2e-5)


def test_rows_past_the_groups_are_unspecified_and_never_read(monkeypatch):
    """A ragged matmul says nothing of the rows that belong to no group (the
    pairs on experts held elsewhere, sorted last): the TPU's leaves them
    unwritten, and on the chip a NaN there times a gate of 0 made every hidden
    state NaN from the first expert layer on.  Poison them as the chip does."""
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        return jnp.where((jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None], out, jnp.nan)

    layer, moe, ref_layer, published = _expert_layer(held=(4, 12))      # 8 of 16 held: half the pairs sort last
    x = jnp.asarray(np.random.default_rng(12).normal(size=(1, 16, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_layer(x[0], ref_layer, published)[0])
        monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
        got = np.asarray(jax.jit(layer.apply)({"params": moe}, x))[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_renormalised_gates_and_one_group_cover_the_mixtral_style_router():
    layer, moe, ref_layer, published = _expert_layer(n_group=1, topk_group=1, norm_topk_prob=True,
                                                     routed_scaling_factor=1.0)
    x = jnp.asarray(np.random.default_rng(10).normal(size=(1, 16, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_layer(x[0], ref_layer, published)[0])
        got = np.asarray(layer.apply({"params": moe}, x))[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


# ------------------------------------------------------------------ the cache
def test_latent_pool_bytes_a_token():
    cfg = _config()
    pool = PagedKVPool(cfg, num_slots=2, max_len=64, page_size=16, num_pages=9, registry=MetricsRegistry())
    assert pool.pages_k.shape == (3, 9, 1, 16, 32) and pool.pages_v.shape == (3, 9, 1, 16, 8)
    # 32 latent + 8 rope values a token and layer, float32, plus two scales a page and layer
    assert pool.page_kv_bytes == 3 * ((32 + 8) * 16 * 4 + 2 * 4)
    assert pool.kv_bytes() == pool.pages_k.nbytes + pool.pages_v.nbytes + 2 * 3 * 9 * 4
    published = json.loads((REPO / "bench" / "configs" / "deepseek-v2.json").read_text())
    (k_heads, k_width), (v_heads, v_width) = TransformerConfig(**published["transformer"]).cache_row_shapes
    assert 2 * (k_heads * k_width + v_heads * v_width) == 1152              # bytes a token and layer, bf16
    assert 2 * 2 * 128 * 128 == 65536 == 1152 * 56 + 1024                   # against K and V of 128 heads of 128
    # a model with K/V heads asks for what it always had
    plain = TransformerConfig.tiny()
    assert plain.cache_row_shapes == ((2, 16), (2, 16))


# ----------------------------------------------------------------- the engine
def _served(engine, prompts, max_new=9):
    reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    engine.run()
    return [list(r.tokens) for r in reqs]


def _check_against_reference(ref_params, prompts, served):
    """Every served token is the reference's first choice, teacher-forced."""
    for prompt, tokens in zip(prompts, served):
        logits = _reference_logits(ref_params, np.concatenate([prompt, np.asarray(tokens, np.int32)]))
        at = logits[len(prompt) - 1:-1]
        assert (at.max(-1) - at[np.arange(len(tokens)), tokens]).max() <= ATOL


@pytest.fixture(scope="module")
def paged_run(tiny):
    """One run of the paged engine over four prompts, its fetches counted."""
    model, params, _ = tiny
    fetches = []
    real = engine_module.fetch
    engine_module.fetch = lambda *a: fetches.append(len(a)) or real(*a)
    try:
        engine = ServingEngine(model, params, num_slots=3, max_len=128, prefill_buckets=(16, 32),
                               decode_window=4, prefix_cache_mb=None, registry=MetricsRegistry())
        prompts = [_ids(20 + i, (n,)) for i, n in enumerate((5, 40, 70, 33))]
        served = _served(engine, prompts)
    finally:
        engine_module.fetch = real
    return engine, prompts, served, fetches


def test_paged_engine_prefills_by_chunks_and_decodes_the_references_tokens(tiny, paged_run):
    _, prompts, served, _ = paged_run
    assert all(len(t) == 9 for t in served)
    _check_against_reference(tiny[2], prompts, served)


def test_engine_prefix_cache_preemption_and_cancel_work_unchanged(tiny):
    model, params, ref_params = tiny
    shared = _ids(30, (32,))
    prompts = [np.concatenate([shared, _ids(31 + i, (n,))]) for i, n in enumerate((9, 20, 3, 27))]
    # a pool too small for three lanes at once: the youngest is preempted and replayed
    tight = ServingEngine(model, params, num_slots=3, max_len=128, prefill_buckets=(16, 32), decode_window=4,
                          num_pages=9, interleave_prefill=True, registry=MetricsRegistry())
    first = _served(tight, prompts[:1], max_new=12)
    rest = _served(tight, prompts[1:], max_new=40)      # lanes outgrow the pool while they decode
    assert tight.stats["prefix_hit_tokens"] >= 32 and tight.stats["preemptions"] > 0
    _check_against_reference(ref_params, prompts, first + rest)
    idle = tight.kv.allocator.free_count
    req = tight.submit(prompts[0], max_new_tokens=40)
    for _ in range(3):
        tight.step()
    assert tight.cancel(req) and tight.stats["cancelled"] == 1
    tight.run()
    assert tight.kv.allocator.free_count == idle


def test_counters_reach_stats_in_the_windows_own_fetch(paged_run):
    engine, prompts, _, fetches = paged_run
    stats = engine.stats
    # one fetch a window, as without the counters; they ride in it
    assert len(fetches) == stats["decode_steps"] // 4 and max(fetches) > 1
    # 3 choices a token in each of the 2 expert layers, for every prompt row
    # prefilled and every lane-step of an occupied lane; padding rows and
    # frozen lanes run through the static shapes and are not counted
    assert stats["moe_pairs_total"] == 6 * (sum(len(p) for p in prompts) + stats["occupied_lane_steps"])
    assert 0 < stats["moe_pairs_here"] < stats["moe_pairs_total"]           # 8 of 16 experts held
    assert 0 < stats["moe_experts_hit"] <= 8 * 2 * stats["decode_steps"]
    assert engine.metrics.counter("serve/moe_pairs_here_total").value == stats["moe_pairs_here"]


def test_model_without_routed_experts_has_no_such_counters():
    plain = Transformer(TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64))
    shapes = jax.eval_shape(lambda: plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)
    engine = ServingEngine(plain, params, max_len=64, prefill_buckets=(8,), registry=MetricsRegistry())
    assert not any(k.startswith("moe_") for k in engine.stats)
    # and its parameter tree is what it always was
    assert sorted(shapes["layers_0"]) == ["attn", "input_norm", "mlp", "post_attn_norm"]
    assert sorted(shapes["layers_0"]["attn"]) == ["k_proj", "o_proj", "q_proj", "v_proj"]


REFUSALS = {
    "kv_dtype": dict(kv_dtype="int8"),
    "speculate_k": dict(speculate_k=2),
    "draft_model": dict(draft_model=1),
    "decode_kernel": dict(decode_kernel="pallas"),
    "prefill_kernel": dict(prefill_kernel="pallas"),
    "prefix_host_mb": dict(prefix_host_mb=1.0),
    "role": dict(role="prefill"),
    "mesh": dict(mesh="tp2"),
}


@pytest.mark.parametrize("option", sorted(REFUSALS))
def test_engine_refuses_by_name_what_a_latent_cache_does_not_have(tiny, option):
    model, params, _ = tiny
    kw = dict(REFUSALS[option])
    if kw.get("mesh") == "tp2":
        from accelerate_tpu.parallel.mesh import build_mesh

        kw["mesh"] = build_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=rf"^{option} is not ported to a latent-attention cache"):
        ServingEngine(model, params, max_len=64, prefill_buckets=(16,), registry=MetricsRegistry(), **kw)


def test_page_migration_and_in_place_paged_cache_refuse_a_latent_model(tiny):
    model, params, _ = tiny
    engines = [ServingEngine(model, params, max_len=64, prefill_buckets=(16,),
                             registry=MetricsRegistry()) for _ in range(2)]
    assert "latent-attention" in PageMigrator.compatible(*engines)
    with pytest.raises(ValueError, match="latent_attention"):
        dataclasses.replace(model.config, paged_kernel="pallas")
    with pytest.raises(ValueError, match="experts"):
        dataclasses.replace(model.config, num_experts=4)


# ---------------------------------------------------------- the configuration
def test_configuration_file_builds_the_table_of_the_issue():
    """``bench/configs/deepseek-v2.json`` under ``jax.eval_shape`` (no memory):
    the parameter counts of the table it was cut by, so that file and table
    cannot drift apart."""
    config = json.loads((REPO / "bench" / "configs" / "deepseek-v2.json").read_text())
    published, fields = config["published"], dict(config["transformer"])
    want = dict(ref.program_fields(published), dtype="bfloat16", param_dtype="bfloat16")
    assert fields == want
    fields["dtype"], fields["param_dtype"] = jnp.bfloat16, jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: int(sum(np.prod(a.shape) for a in jax.tree_util.tree_leaves(tree)))
    matrices = lambda tree: int(sum(np.prod(a.shape) for a in jax.tree_util.tree_leaves(tree) if a.ndim > 1))
    assert matrices(shapes["layers_0"]["attn"]) == 149_225_472                    # 149.2 M
    assert round(count(shapes["layers_0"]) / 1e6, 1) == 338.0
    experts = shapes["layers_1"]["moe_mlp"]["experts"]
    assert count(experts) // 40 == 23_592_960                                     # 23.59 M an expert
    assert round(count(shapes["layers_1"]) / 1e6, 1) == 1141.0
    assert count(shapes) == config["parameters"] == ref.parameter_count(published)
    assert round(count(shapes) / 1e6, 1) == 5164.0
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(shapes))
    counts = ref.parameter_counts(published)
    assert (counts["attention"], counts["expert"]) == (149_225_472, 23_592_960)
    # every number of the source's config.json is in the file under its key, the cuts listed
    catalog = {"hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512, "q_lora_rank": 1536,
               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "moe_intermediate_size": 1536,
               "num_attention_heads": 128, "num_key_value_heads": 128, "num_experts_per_tok": 6, "n_group": 8,
               "topk_group": 3, "n_shared_experts": 2, "first_k_dense_replace": 1, "routed_scaling_factor": 16,
               "rope_theta": 10000, "rms_norm_eps": 1e-06, "moe_layer_freq": 1}
    assert {k: config[k] for k in catalog} == catalog
    cut = {"num_hidden_layers": (5, 60), "n_routed_experts": (40, 160), "vocab_size": (25600, 102400),
           "max_position_embeddings": (8192, 163840)}
    assert sorted(config["reduced"]) == sorted(cut)
    assert all(config[k] == here for k, (here, _) in cut.items())

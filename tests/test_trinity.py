"""Trinity-Large's block (window and full attention layers in one stack, rope on
the one kind and no positions on the other, a sigmoid gate on the attention
output, four norms a block, sigmoid-routed experts with a bias on the choice)
through ``Transformer``, ``generate`` and the serving engine's pool of two
retention rules, against the plain reference ``bench/reference/trinity.py`` at
tiny widths on the CPU, seeded weights.

Sizes: window 16, pages of 4, a ring of 9 pages (``ceil((16 + 16) / 4) + 1``),
contexts to 110 positions (past six windows).  Tolerances: program and
reference compute the same float32 mathematics at highest matmul precision in
another order of summation, so logits of size ~3 agree to a few 1e-6; ``ATOL``
is 2e-5.  Weights are drawn at normal(0.1): at 0.02 every score is near 0 and
a wrong mask or gate would not show.
"""

import hashlib
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

from reference import trinity as ref  # noqa: E402

from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    DecoderLayer,
    ExpertSpec,
    KVCache,
    Transformer,
    TransformerConfig,
    lm_loss_fn,
)
from accelerate_tpu.parallel.moe import route_top_k  # noqa: E402
from accelerate_tpu.serving import ServingEngine, pool  # noqa: E402
from accelerate_tpu.serving.paging import NULL_PAGE, MixedKVPool  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

ATOL = 2e-5
WINDOW, PAGE, BUCKETS = 16, 4, (4, 16)
TINY = {
    "hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": WINDOW, "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 16,
    "experts_held": [4, 12], "num_experts_per_tok": 4, "num_shared_experts": 1, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"], "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid", "mup_enabled": True, "vocab_size": 97,
    "max_position_embeddings": 256, "init_std": 0.1, "router_init_std": 0.1, "expert_bias_std": 0.05,
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _config(published=TINY, **kw):
    return TransformerConfig(**ref.program_fields(published), dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def _ids(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, TINY["vocab_size"]), np.int32)


@pytest.fixture(scope="module")
def tiny():
    """``(model, program params, reference params)`` from one seeded draw."""
    ref_params = ref.init_params(3, TINY, jnp.float32)
    return Transformer(_config()), ref.to_program_tree(ref_params, TINY), ref_params


def _engine(tiny, **kw):
    model, params, _ = tiny
    kw = dict(dict(num_slots=2, max_len=128, page_size=PAGE, prefill_buckets=BUCKETS, decode_window=4,
                   prefix_cache_mb=0, registry=MetricsRegistry()), **kw)
    return ServingEngine(model, params, **kw)


# ------------------------------------------------------------------ the model
def test_parameter_tree_is_the_references_under_program_names(tiny):
    model, params, _ = tiny
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, shapes)
            == jax.tree_util.tree_map(lambda a: a.shape, params))


@pytest.mark.parametrize("layer,what", [(0, "window_dense"), (1, "window_experts"), (4, "full_experts")])
def test_block_matches_reference(tiny, layer, what):
    """One block of each kind the cut has: a window layer with the dense MLP,
    a window layer with experts, the full (position-free) layer with experts."""
    model, params, ref_params = tiny
    cfg = model.config
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(layer), (70, TINY["hidden_size"]), jnp.float32)
    want = ref.layer_forward(x, ref_params["layers"][layer], TINY, layer)
    block = DecoderLayer(cfg, layer < cfg.experts.dense_layers, cfg.layer_kind(layer))
    got = block.apply({"params": params[f"layers_{layer}"]}, x[None], jnp.arange(70)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_whole_model_logits_match_reference(tiny):
    model, params, ref_params = tiny
    ids = _ids(1, 90)
    want = ref.forward(ref_params, jnp.asarray(ids), TINY)
    got = model.apply({"params": params}, ids[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_layer_at_a_time_in_blocks_of_queries_is_the_whole_forward(tiny, monkeypatch):
    """``forward_by_layer`` (what the chip's check runs: one layer drawn and run
    at a time, queries in blocks) gives ``forward``'s hidden states."""
    _, _, ref_params = tiny
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    ids = _ids(2, 64)
    xs, top = ref.forward_by_layer(3, [ids], TINY, "float32")
    want = ref.forward(ref_params, jnp.asarray(ids), TINY)
    got = ref.head_logits(xs["float32"][0], top, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_contiguous_cache_prefill_then_decode_matches_reference(tiny):
    """``generate``'s cache keeps ``max_len`` columns for every layer and masks
    the window layers by the band."""
    model, params, ref_params = tiny
    ids = _ids(4, 80)
    want = ref.forward(ref_params, jnp.asarray(ids), TINY)
    cache = KVCache.create(model.config, 1, 128)
    logits, cache = model.apply({"params": params}, ids[None, :45], cache=cache)
    rows = [logits[0]]
    for t in range(45, 80):
        logits, cache = model.apply({"params": params}, ids[None, t:t + 1], cache=cache)
        rows.append(logits[0])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(rows)), np.asarray(want), atol=ATOL)


# ------------------------------------------------------------------ the router
def _route_by_hand(scores, bias, k, norm, scale):
    experts, gates = [], []
    for row in np.asarray(scores, np.float64):
        chosen = sorted(range(len(row)), key=lambda e: -(row[e] + bias[e]))[:k]
        w = np.asarray([row[e] for e in chosen])
        if norm:
            w = w / (w.sum() + 1e-20)
        experts.append(chosen)
        gates.append(w * scale)
    return np.asarray(experts), np.asarray(gates)


@pytest.mark.parametrize("norm", [True, False], ids=["renormalise_then_scale", "scale_only"])
def test_route_top_k_sigmoid_bias_against_a_loop_written_out(norm):
    spec = ExpertSpec(num_routed=16, top_k=4, width=8, scaling=2.448, norm_topk=norm, scale_normed=True,
                      score_func="sigmoid", select_bias=True)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (50, 16)))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    experts, gates = route_top_k(scores, spec, bias)
    want_experts, want_gates = _route_by_hand(scores, np.asarray(bias, np.float64), 4, norm, 2.448)
    np.testing.assert_array_equal(np.asarray(experts), want_experts)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-6)
    # the bias moved choices, and never entered a gate
    plain, _ = route_top_k(scores, spec, jnp.zeros((16,)))
    assert (np.sort(np.asarray(plain), -1) != np.sort(want_experts, -1)).any()


def test_softmax_group_limited_route_is_what_it_was():
    """DeepSeek-V2's path through ``route_top_k``: the best 2 of 4 groups by
    their largest score, top 3 among their experts, gates ``16 x score``, no
    renormalisation; and ``norm_topk`` alone still renormalises without
    scaling."""
    spec = ExpertSpec(num_routed=16, top_k=3, width=8, n_group=4, topk_group=2, scaling=16.0)
    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (40, 16)), -1)
    experts, gates = route_top_k(scores, spec)
    for row, chosen, g in zip(np.asarray(scores), np.asarray(experts), np.asarray(gates)):
        groups = sorted(range(4), key=lambda j: -row[4 * j:4 * j + 4].max())[:2]
        allowed = [e for e in range(16) if e // 4 in groups]
        want = sorted(allowed, key=lambda e: -row[e])[:3]
        assert list(chosen) == want
        np.testing.assert_allclose(g, 16.0 * row[want], rtol=1e-6)
    normed = ExpertSpec(num_routed=16, top_k=3, width=8, scaling=16.0, norm_topk=True)
    _, gates = route_top_k(scores, normed)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)


def test_eight_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts that every share ``[lo, hi)`` of the experts gives, with
    the shared expert counted once, add up to the uncut reference's layer; and
    the program's layer told ``held`` gives its share's part."""
    from accelerate_tpu.parallel.moe import RoutedExperts

    uncut = dict(TINY, experts_held=[0, 16])
    p = ref.init_layer(7, uncut, 1, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, TINY["hidden_size"]), jnp.float32)
    whole = ref.expert_layer(h, p, uncut)[0]
    total = ref.shared_part(h, p, uncut)
    for lo in range(0, 16, 2):
        cut = dict(TINY, experts_held=[lo, lo + 2])
        share = {k: (v[lo:lo + 2] if k in ("e_gate", "e_up", "e_down") else v) for k, v in p.items()}
        part = ref.routed_part(h, share, cut)[0]
        total = total + part
        tree = {}
        for name, path in ref.EXPERT_PATHS.items():              # the share under the program's names
            node = tree
            for key in path[1:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = share[name]
        got = RoutedExperts(_config(cut)).apply({"params": tree}, h[None])[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(part + ref.shared_part(h, p, uncut)), atol=ATOL)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=ATOL)


# ------------------------------------------------------------------ the engine
def _served_gap(ref_params, prompt, tokens):
    """How far each served token's reference logit lies below the reference's
    best at its position (0 where the engine chose what the reference would)."""
    ids = np.concatenate([prompt, tokens])
    logits = np.asarray(ref.forward(ref_params, jnp.asarray(ids), TINY))[len(prompt) - 1:-1]
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


@pytest.fixture(scope="module")
def served(tiny):
    """Four requests through one engine of two lanes: prompts of 70, 23, 90 and
    5 tokens (lanes of unequal length; chunks of 16 and 4), 20 tokens each."""
    with jax.default_matmul_precision("highest"):
        engine = _engine(tiny)
        prompts = [_ids(10 + i, n) for i, n in enumerate((70, 23, 90, 5))]
        requests = [engine.submit(p, max_new_tokens=20) for p in prompts]
        engine.run()
    return engine, prompts, requests


@pytest.mark.parametrize("which", range(4), ids=["ctx90", "ctx43", "ctx110_past_six_windows", "ctx25"])
def test_chunked_prefill_then_decode_through_the_engine_matches_reference(tiny, served, which):
    _, prompts, requests = served
    tokens = np.asarray(requests[which].tokens, np.int32)
    assert len(tokens) == 20
    np.testing.assert_allclose(_served_gap(tiny[2], prompts[which], tokens), 0.0, atol=ATOL)


@pytest.mark.parametrize("which", range(4))
def test_generate_and_the_engine_give_the_same_greedy_tokens(tiny, served, which):
    model, params, _ = tiny
    _, prompts, requests = served
    seq, _ = generate(model, params, prompts[which][None], max_new_tokens=20)
    assert [int(t) for t in seq[0, len(prompts[which]):]] == list(requests[which].tokens)


def test_counters_of_the_two_rule_pool(served):
    engine, prompts, _ = served
    stats = engine.stats
    # a decode step of a lane at position i sees min(i + 1, 16) keys in each of
    # the four window layers and i + 1 in the full one: 20 steps a request
    rows = window_rows = 0
    for p in prompts:
        for i in range(len(p) - 1, len(p) + 19):
            window_rows += 4 * min(i + 1, WINDOW)
            rows += 4 * min(i + 1, WINDOW) + i + 1
    assert (stats["kv_rows_live"], stats["kv_rows_live_window"]) == (rows, window_rows)
    assert 0 < stats["kv_pages_released_window"] < stats["kv_pages_taken"]
    assert stats["moe_pairs_total"] > stats["moe_pairs_here"] > 0
    # everything went back: a lane's end returns both kinds' pages
    kv = engine.kv
    assert kv.allocator.free_count == kv.allocator.num_pages - 1
    assert kv.ring_allocator.free_count == kv.ring_allocator.num_pages - 1
    assert (kv.ring_tables == NULL_PAGE).all() and (kv.tables == NULL_PAGE).all()
    assert engine.prefix_cache is None


def test_pool_bytes_are_a_ring_for_the_window_layers_and_whole_tables_for_the_full(tiny):
    engine = _engine(tiny)
    kv = engine.kv
    assert isinstance(kv, MixedKVPool) and kv.ring_pages == -(-(WINDOW + BUCKETS[-1]) // PAGE) + 1 == 9
    page_bytes = 2 * 2 * PAGE * 16 * 4                               # k and v, 2 heads of 16, float32
    scales = 2 * (2 * 32 + 1) * 2 * 4
    assert engine.kv_pool_bytes() == (1 * (2 * 32 + 1) + 4 * (2 * 9 + 1)) * page_bytes + scales


# ------------------------------------------------------------ pool invariants
def _walk(pool_, slot, context, chunk=16, width=4):
    """A lane's life on the host: chunks of ``chunk`` up to ``context``, then
    decode windows of ``width``; after every advance, what the invariants say."""
    base = 0
    while base < context:
        yield base, base + chunk - 1, pool_.ring_advance(slot, base, base + chunk - 1)
        base += chunk
    n = context - 1
    for _ in range(12):
        yield n, n + width - 1, pool_.ring_advance(slot, n, n + width - 1)
        n += width


@pytest.mark.parametrize("context", [5, 16, 17, 64, 100, 200])
def test_window_layer_never_holds_more_than_its_ring_and_never_less_than_a_query_sees(tiny, context):
    kv = MixedKVPool(tiny[0].config, 2, 256, PAGE, 2 * 64 + 1, 9, registry=MetricsRegistry())
    for query, last, _ in _walk(kv, 1, context):
        held = [int(p) for p in kv.ring_tables[1] if p != NULL_PAGE]
        assert len(held) == kv.ring_held(1) <= kv.ring_pages and len(set(held)) == len(held)
        # every position a query from here on can see, and every one about to
        # be written, is on a mapped page; nothing behind the window's page is
        first_seen = max(0, query - WINDOW + 1)
        for position in range(first_seen, last + 1):
            assert kv.ring_tables[1, (position // PAGE) % kv.ring_pages] != NULL_PAGE
        assert kv.ring_lo[1] == first_seen // PAGE
        assert kv.ring_hi[1] >= last // PAGE + 1                     # (a padded chunk's pages stay mapped)
    assert (kv.ring_tables[0] == NULL_PAGE).all()                    # the other lane's ring is its own
    kv.lane_release(1)
    assert kv.ring_allocator.free_count == kv.ring_allocator.num_pages - 1


def test_released_page_is_handed_to_the_next_taker_and_counted(tiny):
    kv = MixedKVPool(tiny[0].config, 2, 256, PAGE, 2 * 64 + 1, 9, registry=MetricsRegistry())
    taken = released = 0
    for _, _, (t, r) in _walk(kv, 0, 100):
        taken, released = taken + t, released + r
    assert taken - released == kv.ring_held(0) == kv.ring_allocator.used_count
    assert released == kv.ring_lo[0] > 0
    with pytest.raises(RuntimeError, match="more than its ring"):
        kv.ring_advance(0, 148, 148 + 40)                            # a span the ring was not sized for


def test_lane_of_the_engine_never_holds_more_than_its_ring(tiny):
    engine = _engine(tiny)
    engine.submit(_ids(30, 100), max_new_tokens=24)
    engine.submit(_ids(31, 40), max_new_tokens=60)
    most = 0
    while engine.has_work:
        engine.step()
        most = max(most, max(engine.kv.ring_held(s) for s in range(2)))
        assert engine.kv.ring_allocator.used_count <= 2 * engine.kv.ring_pages
    assert 5 <= most <= engine.kv.ring_pages
    assert engine.stats["requests_completed"] == 2


def test_admission_counts_both_rules_pages(tiny):
    engine = _engine(tiny)
    engine.submit(_ids(32, 40), max_new_tokens=4)
    request = engine.scheduler.queue[0]
    assert engine._admission_pages_ok(request)
    taken = engine.kv.ring_allocator.alloc(engine.kv.ring_allocator.free_count - 8)   # 9 needed, 8 left
    assert not engine._admission_pages_ok(request)
    engine.kv.ring_allocator.deref(taken)
    full = engine.kv.allocator.alloc(engine.kv.allocator.free_count - 9)              # 10 needed, 9 left
    assert not engine._admission_pages_ok(request)
    engine.kv.allocator.deref(full)
    engine.run()
    assert engine.stats["requests_completed"] == 1


# ------------------------------------------------------------------- refusals
REFUSALS = {
    "prefix_cache_mb": dict(prefix_cache_mb=64.0),
    "kv_dtype": dict(kv_dtype="int8"),
    "speculate_k": dict(speculate_k=2),
    "draft_model": dict(draft_model=1),
    "decode_kernel": dict(decode_kernel="pallas"),
    "prefill_kernel": dict(prefill_kernel="pallas"),
    "prefix_host_mb": dict(prefix_host_mb=1.0),
    "prefix_disk_mb": dict(prefix_disk_mb=1.0),
    "role": dict(role="prefill"),
    "mesh": dict(mesh="tp2"),
}


@pytest.mark.parametrize("option", sorted(REFUSALS))
def test_engine_refuses_by_name_what_a_two_rule_pool_does_not_have(tiny, option):
    kw = dict(REFUSALS[option])
    if kw.get("mesh") == "tp2":
        from accelerate_tpu.parallel.mesh import build_mesh

        kw["mesh"] = build_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=rf"^{option} is not ported to a pool of window and full layers"):
        _engine(tiny, **kw)


EXCLUDED = {
    "latent_attention": dict(latent_attention=dict(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=8, v_dim=8)),
    "scan_layers": dict(scan_layers=True),
    "positional": dict(positional="learned"),
}


@pytest.mark.parametrize("field", sorted(EXCLUDED))
def test_configuration_refuses_by_name_what_layer_types_excludes(field):
    with pytest.raises(ValueError, match=rf"layer_types excludes {field}"):
        TransformerConfig.tiny(layer_types=("window", "full"), sliding_window=8, **EXCLUDED[field])


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("window", "full", "full")), "one of 'window' / 'full' for each"),
    (dict(layer_types=("window", "global")), "one of 'window' / 'full' for each"),
    (dict(layer_types=("window", "full"), sliding_window=None), "set sliding_window"),
    (dict(sandwich_norm=True, parallel_residual=True), "parallel_residual must stay off"),
    (dict(experts=dict(num_routed=8, top_k=2, width=8, score_func="tanh")), "Unknown score_func"),
    (dict(experts=dict(num_routed=8, top_k=2, width=8, n_group=2, select_bias=True)), "n_group must be 1"),
], ids=["length", "kind", "no_window", "sandwich_parallel", "score_func", "bias_groups"])
def test_configuration_refuses_what_it_cannot_mean(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig.tiny(**dict(dict(sliding_window=8), **kw))


# ------------------------------------------------- the configuration's file
def test_configuration_file_counts_what_the_issue_states():
    """``bench/configs/trinity-large.json`` under ``jax.eval_shape`` (no
    memory): 4,322 M parameters held, 998.0 M an expert layer, 176.2 M the
    dense one, as the file states and the reference counts."""
    config = json.loads((REPO / "bench" / "configs" / "trinity-large.json").read_text())
    fields = dict(config["transformer"])
    fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == config["parameters"] == ref.parameter_count(config["published"]) == 4_321_903_872
    assert count(shapes["layers_1"]) == config["parameters_by_part"]["expert_layer"] == 997_995_008
    assert count(shapes["layers_0"]) == config["parameters_by_part"]["dense_layer"] == 176_173_312
    assert count(shapes["layers_1"]["attn"]) - 2 * 128 == config["parameters_by_part"]["attention"] == 62_914_560
    assert fields == dict(ref.program_fields(config["published"]), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert model.config.layer_types == ("window",) * 4 + ("full",)
    # every number of the source's config is in the file under its key, the reduced ones apart
    assert set(config["reduced"]) == set(config["reduced_from"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size", "max_position_embeddings"}
    assert len(config["layer_types"]) == 60 and config["layer_types"][3::4] == ["full_attention"] * 15


def test_counts_of_the_yardstick():
    config = json.loads((REPO / "bench" / "configs" / "trinity-large.json").read_text())["published"]
    assert ref.cache_row_bytes(config) == 4096 and ref.expert_bytes(config) == 56_623_104
    assert ref.keys_seen(config, 100) == 5 * 100 and ref.keys_seen(config, 10_000) == 4 * 4096 + 10_000
    by_contexts = ref.decode_least_bytes(config, [100, 10_000], 8, 3)
    by_counter = ref.decode_least_bytes(config, [100, 10_000], 8, 3, rows_live=500 + 4 * 4096 + 10_000)
    assert by_contexts == by_counter == (26_884 * 4096 + 2 * ref.dense_weight_bytes(config) / 8 + 3 * 56_623_104)
    assert ref.forward_flops_span(config, 0, 5000, 1) == sum(
        ref.forward_flops_token(config, c + 1, c == 4999) for c in range(5000))


# --------------------------------------- configurations without layer_types
def _abstract(model):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


def _lanes(n):
    spec = jax.ShapeDtypeStruct
    return (spec((n,), jnp.int32), spec((n,), jnp.bool_), spec((n,), jnp.int32), spec((n,), jnp.bool_),
            spec((n,), jnp.float32), spec((n,), jnp.int32), spec((n,), jnp.float32), spec((n,), jnp.int32),
            spec((n, 2), jnp.uint32))


def _rehearsal_model(config_name, cell_name):
    fields = dict(json.loads((REPO / "bench" / "configs" / f"{config_name}.json").read_text())["transformer"])
    fields.update(json.loads((REPO / "bench" / "workloads" / f"{cell_name}.json").read_text())
                  ["rehearse"]["transformer"])
    fields["dtype"], fields["param_dtype"] = getattr(jnp, fields["dtype"]), getattr(jnp, fields["param_dtype"])
    return Transformer(TransformerConfig(**fields))


def _lowered(program):
    spec, i32 = jax.ShapeDtypeStruct, lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if program.startswith("deepseek"):
        model = _rehearsal_model("deepseek-v2", "deepseek-v2.serve-doc-surge")
        pages = [spec((3, 4 * 8 + 1, h, 32, d), jnp.float32) for h, d in model.config.cache_row_shapes]
        if program.endswith("decode"):
            return pool.make_paged_decode_window(model, 4).lower(
                _abstract(model), *pages, i32(4, 8), i32(4), *_lanes(4))
        return pool.make_paged_prefill_chunk(model, 32, 32).lower(
            _abstract(model), i32(1, 32), *pages, i32(8), i32(), i32())
    if program == "brumby.decode":
        from accelerate_tpu.models.retention import state_shapes

        model = _rehearsal_model("brumby-14b", "brumby-14b.serve-reason-surge")
        s, z = (spec(shape, model.config.retention.dtype) for shape in state_shapes(model.config, 4))
        return pool.make_state_decode_window(model, 4).lower(_abstract(model), s, z, i32(4), *_lanes(4))
    if program == "llama_window_headnorm.decode":
        model = Transformer(TransformerConfig.tiny(dtype=jnp.float32, qk_norm=True, sliding_window=16))
        pages = [spec((2, 9, 2, 16, 16), jnp.float32)] * 2
        return pool.make_paged_decode_window(model, 4).lower(
            _abstract(model), *pages, i32(2, 4), i32(2), *_lanes(2))
    model = Transformer(TransformerConfig.gpt2(num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=4,
                                               intermediate_size=128, vocab_size=256, max_seq_len=64,
                                               dtype=jnp.float32))
    return jax.jit(jax.value_and_grad(lm_loss_fn(model))).lower(_abstract(model), {"input_ids": i32(2, 32)})


#: SHA-256 (16 digits) of the lowered programs as PR 35's parent commit lowered
#: them here (jax 0.9.0, no debug locations in the text).  A PR that changes one
#: of these programs on purpose records it anew and says so.
LOWERED_BEFORE = {
    "deepseek.decode": "e3b000cca9284aa9",
    "deepseek.chunk": "da4016b0391442aa",
    "brumby.decode": "b4dac956ea563eb2",
    "llama_window_headnorm.decode": "72b00bd2a4df7ebe",
    "gpt2.train_value_and_grad": "e2f5be36820b617d",
}


@pytest.mark.parametrize("program", sorted(LOWERED_BEFORE))
def test_configuration_without_layer_types_lowers_to_the_program_it_lowered_to_before(program):
    with jax.default_matmul_precision("default"):
        text = _lowered(program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED_BEFORE[program]
    assert "attn/" not in text

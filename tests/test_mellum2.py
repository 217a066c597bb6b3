"""Mellum2-12B-A2.5B's block (window layers with plain rope and full layers
with YaRN in one stack, a per-head norm on q and k, 64 softmax-routed experts
top 8 renormalised, every one held, no shared expert) through
``Transformer``, ``generate`` and the serving engine's pool of two retention
rules, against the plain reference ``bench/reference/mellum2.py`` at tiny
widths on the CPU, seeded weights.

Sizes: window 16, pages of 4, a ring of 9 pages (``ceil((16 + 16) / 4) + 1``),
contexts to 110 positions (past six windows); YaRN over 64 original positions
at theta 10,000, so that its blend and its amplitude move every score.
Tolerances: program and reference compute the same float32 mathematics at
highest matmul precision in another order of summation, so logits of size ~3
agree to a few 1e-6; ``ATOL`` is 2e-5.  Weights are drawn at normal(0.1): at
0.02 every score is near 0 and a wrong mask or rope would not show.
"""

import hashlib
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))

from reference import mellum2 as ref  # noqa: E402

from accelerate_tpu.models import transformer  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.transformer import (  # noqa: E402
    DecoderLayer,
    ExpertSpec,
    KVCache,
    Transformer,
    TransformerConfig,
    YarnScaling,
    rope_amplitude,
    rope_frequencies,
)
from accelerate_tpu.parallel.moe import RoutedExperts, route_top_k  # noqa: E402
from accelerate_tpu.serving import ServingEngine, pool  # noqa: E402
from accelerate_tpu.serving.paging import MixedKVPool  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry, get_tracer  # noqa: E402

ATOL = 2e-5
WINDOW, PAGE, BUCKETS = 16, 4, (4, 16)
ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 16, "original_max_position_embeddings": 64,
                       "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
}
TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": WINDOW, "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 32,
    "experts_held": [0, 32], "num_experts_per_tok": 8, "norm_topk_prob": True,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2, "rope_parameters": ROPE,
    "rms_norm_eps": 1e-6, "vocab_size": 97, "max_position_embeddings": 256, "init_std": 0.1,
}
PUBLISHED = json.loads((REPO / "bench" / "configs" / "mellum2-12b.json").read_text())


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _config(published=TINY, dtype=jnp.float32, **kw):
    return TransformerConfig(**dict(ref.program_fields(published), **kw), dtype=dtype, param_dtype=jnp.float32)


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


# ------------------------------------------------------------------- the rope
def _hf_yarn(dim, rope):
    """Hugging Face's ``_compute_yarn_parameters`` written out (``truncate``
    on, torch float32 arithmetic replaced by numpy float64): ``(inv_freq,
    attention_factor)``."""
    base, factor = rope["rope_theta"], rope["factor"]

    def find_correction_dim(num_rotations):
        return (dim * math.log(rope["original_max_position_embeddings"] / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float64) - low) / (high - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq = (1.0 / (factor * pos_freqs)) * (1 - extrapolation_factor) + (1.0 / pos_freqs) * extrapolation_factor
    attention_factor = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv_freq, attention_factor


def _published_rope():
    return PUBLISHED["published"]["rope_parameters"]


def _program_yarn():
    cfg = TransformerConfig(**{k: v for k, v in PUBLISHED["transformer"].items() if "dtype" not in k})
    return cfg.full_rope


@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_of_the_full_layers_is_hugging_faces_at_the_published_numbers(side):
    rope = _published_rope()["full_attention"]
    want, factor = _hf_yarn(128, rope)
    assert factor == pytest.approx(1.2772588722239782, abs=1e-12)
    if side == "program":
        full = _program_yarn()
        assert full.theta == 500000 and full.yarn == YarnScaling(16, 8192, 32, 1)
        got, amplitude = np.asarray(rope_frequencies(128, full.theta, full.yarn), np.float64), rope_amplitude(full.yarn)
    else:
        got, amplitude = ref.rope_inv_freq(rope, 128), ref.rope_amplitude(rope)
    np.testing.assert_allclose(got, want, rtol=1e-6)             # float32 against float64
    assert amplitude == pytest.approx(1.2772588722239782, abs=1e-12)
    # the blend is there: the fastest frequencies are kept, the slowest divided by 16
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    assert got[0] == pytest.approx(plain[0]) and got[-1] == pytest.approx(plain[-1] / 16, rel=1e-6)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_window_layers_keep_plain_rope(side):
    rope = _published_rope()["sliding_attention"]
    want = 500000.0 ** (-np.arange(0, 128, 2, dtype=np.float64) / 128)
    if side == "program":
        theta = PUBLISHED["transformer"]["rope_theta"]
        got, amplitude = np.asarray(rope_frequencies(128, theta), np.float64), rope_amplitude(None)
    else:
        got, amplitude = ref.rope_inv_freq(rope, 128), ref.rope_amplitude(rope)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert amplitude == 1.0


def test_latent_attention_reads_the_one_yarn():
    from accelerate_tpu.models import latent_attention as mla

    assert mla.rope_frequencies is rope_frequencies and mla.rope_amplitude is rope_amplitude


# ------------------------------------------------------------------ the model
def test_parameter_tree_is_the_references_under_program_names(tiny):
    model, params, _ = tiny
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, shapes)
            == jax.tree_util.tree_map(lambda a: a.shape, params))


@pytest.mark.parametrize("layer,what", [(0, "window"), (3, "full_yarn")])
def test_block_matches_reference(tiny, layer, what):
    model, params, ref_params = tiny
    cfg = model.config
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(layer), (70, TINY["hidden_size"]), jnp.float32)
    want = ref.layer_forward(x, ref_params["layers"][layer], TINY, layer)
    block = DecoderLayer(cfg, False, cfg.layer_kind(layer))
    got = block.apply({"params": params[f"layers_{layer}"]}, x[None], jnp.arange(70)[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_whole_model_logits_match_reference(tiny):
    model, params, ref_params = tiny
    ids = _ids(1, 90)
    want = ref.forward(ref_params, jnp.asarray(ids), TINY)
    got = model.apply({"params": params}, ids[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def _fault_kinds_swapped(original):
    return lambda x, positions, cfg, rope=None: original(x, positions, cfg, cfg.full_rope if rope is None else None)


@pytest.mark.parametrize("fault", ["kinds_rope_swapped", "yarn_left_out", "amplitude_left_out", "bfloat16"])
def test_program_with_a_piece_wrong_or_a_lower_precision_fails_the_comparison(tiny, monkeypatch, fault):
    """What the comparisons above hold the program to: the two kinds' ropes
    swapped, the full layers' YaRN or its amplitude left out, or the same
    program computed in bfloat16 (the precision below the tests' float32)
    misses the reference's logits by far more than ``ATOL``."""
    model, params, ref_params = tiny
    if fault == "kinds_rope_swapped":
        monkeypatch.setattr(transformer, "_apply_rope", _fault_kinds_swapped(transformer._apply_rope))
    elif fault == "amplitude_left_out":
        monkeypatch.setattr(transformer, "rope_amplitude", lambda yarn: 1.0)
    elif fault == "yarn_left_out":
        model = Transformer(_config(full_rope={"theta": 10000}))
    else:
        model = Transformer(_config(dtype=jnp.bfloat16))
    ids = _ids(1, 90)
    want = ref.forward(ref_params, jnp.asarray(ids), TINY)
    got = model.apply({"params": params}, ids[None])[0]
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) > 100 * ATOL


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
    """``generate``'s cache keeps ``max_len`` columns for every layer, masks the
    window layers by the band and keeps the full layers' keys YaRN-rotated."""
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
def _route_by_hand(probs, k, norm):
    experts, gates = [], []
    for row in np.asarray(probs, np.float64):
        chosen = sorted(range(len(row)), key=lambda e: -row[e])[:k]
        w = np.asarray([row[e] for e in chosen])
        experts.append(chosen)
        gates.append(w / w.sum() if norm else w)
    return np.asarray(experts), np.asarray(gates)


@pytest.mark.parametrize("norm", [True, False], ids=["renormalised", "as_chosen"])
def test_softmax_top8_router_against_a_loop_written_out(norm):
    spec = ExpertSpec(num_routed=64, top_k=8, width=8, norm_topk=norm, score_func="softmax")
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (50, 64)), -1)
    experts, gates = route_top_k(probs, spec)
    want_experts, want_gates = _route_by_hand(probs, 8, norm)
    np.testing.assert_array_equal(np.asarray(experts), want_experts)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-6)
    ref_experts, ref_gates = ref.route(probs, dict(num_experts_per_tok=8, norm_topk_prob=norm))
    np.testing.assert_array_equal(np.asarray(ref_experts), want_experts)
    np.testing.assert_allclose(np.asarray(ref_gates), want_gates, rtol=1e-6)


def test_layer_of_every_expert_is_the_sum_of_its_shares(tiny):
    """Four shares ``[lo, hi)`` of the experts add up to the whole layer, in
    the reference and in the program told ``held``: what holding all 64 on
    one chip computes is what an expert-parallel group would."""
    p = ref.init_layer(7, TINY, 1, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(3), (40, TINY["hidden_size"]), jnp.float32)
    whole = ref.expert_layer(u, p, TINY)[0]
    total = jnp.zeros_like(whole)
    for lo in range(0, 32, 8):
        cut = dict(TINY, experts_held=[lo, lo + 8])
        share = {k: (v[lo:lo + 8] if k in ("e_gate", "e_up", "e_down") else v) for k, v in p.items()}
        part = ref.expert_layer(u, share, cut)[0]
        total = total + part
        tree = {"router": {"kernel": share["router"]},
                "experts": {n: {"kernel": share[k]} for n, k in
                            (("gate_proj", "e_gate"), ("up_proj", "e_up"), ("down_proj", "e_down"))}}
        got = RoutedExperts(_config(cut)).apply({"params": tree}, u[None])[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=ATOL)
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
    """Four requests through one engine of two lanes on a fresh tracer:
    prompts of 70, 23, 90 and 5 tokens (lanes of unequal length; chunks of 16
    and 4), 20 tokens each.  Returns the engine, the prompts, the requests and
    the tracer's events."""
    tracer = get_tracer()
    tracer.reset()
    with jax.default_matmul_precision("highest"):
        engine = _engine(tiny)
        prompts = [_ids(10 + i, n) for i, n in enumerate((70, 23, 90, 5))]
        requests = [engine.submit(p, max_new_tokens=20) for p in prompts]
        engine.run()
    return engine, prompts, requests, list(tracer.events)


@pytest.mark.parametrize("which", range(4), ids=["ctx90", "ctx43", "ctx110_past_six_windows", "ctx25"])
def test_chunked_prefill_then_decode_through_the_engine_matches_reference(tiny, served, which):
    _, prompts, requests, _ = served
    tokens = np.asarray(requests[which].tokens, np.int32)
    assert len(tokens) == 20
    np.testing.assert_allclose(_served_gap(tiny[2], prompts[which], tokens), 0.0, atol=ATOL)


@pytest.mark.parametrize("which", [0, 2])
def test_generate_and_the_engine_give_the_same_greedy_tokens(tiny, served, which):
    model, params, _ = tiny
    _, prompts, requests, _ = served
    seq, _ = generate(model, params, prompts[which][None], max_new_tokens=20)
    assert [int(t) for t in seq[0, len(prompts[which]):]] == list(requests[which].tokens)


def test_steps_say_which_experts_their_windows_read(served):
    """``serve/step``'s ``experts_hit`` and ``expert_slots`` sum to the
    engine's counters: held experts that got a live lane's row, of 32 held x 8
    expert layers x the steps of every decode window drained."""
    engine, _, _, events = served
    steps = [e for e in events if e["name"] == "serve/step"]
    stats = engine.stats
    assert sum(e["args"]["experts_hit"] for e in steps) == stats["moe_experts_hit"] > 0
    assert sum(e["args"]["expert_slots"] for e in steps) == stats["moe_expert_slots"]
    assert stats["moe_expert_slots"] == 32 * 8 * stats["decode_steps"]
    # two lanes of 8 choices hit at most 16 of 32 experts a layer-step
    assert stats["moe_experts_hit"] <= stats["moe_expert_slots"] // 2
    assert all(e["args"]["expert_slots"] % (32 * 8 * 4) == 0 for e in steps)


def test_pool_of_two_rules_at_the_rehearsal_sizes(tiny):
    engine = _engine(tiny)
    kv = engine.kv
    assert isinstance(kv, MixedKVPool) and kv.ring_pages == -(-(WINDOW + BUCKETS[-1]) // PAGE) + 1 == 9
    assert engine._expert_slots_a_step == 32 * 8


# ------------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw", [dict(rope_full_layers=False), dict(layer_types=None)],
                         ids=["no_positions_on_full_layers", "one_kind_of_layer"])
def test_full_rope_refuses_what_contradicts_it(kw):
    fields = dict(ref.program_fields(TINY), **kw)
    with pytest.raises(ValueError, match="full_rope is the rope of the 'full' layers"):
        TransformerConfig(**fields)


# ------------------------------------------------- the configuration's file
def test_configuration_file_counts_what_it_states():
    """``bench/configs/mellum2-12b.json`` under ``jax.eval_shape`` (no memory):
    3,795 M parameters held, 417.7 M a layer of which 396.4 M are its 64
    experts, as the file states and the reference counts."""
    fields = dict(PUBLISHED["transformer"])
    fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    parts = PUBLISHED["parameters_by_part"]
    assert count(shapes) == PUBLISHED["parameters"] == ref.parameter_count(PUBLISHED["published"]) == 3_794_968_832
    assert count(shapes["layers_3"]) == parts["layer"] == 417_747_712
    assert count(shapes["layers_0"]["moe_mlp"]["experts"]) == 64 * parts["expert"] == 396_361_728
    assert count(shapes["layers_0"]["attn"]) - 2 * 128 == parts["attention"] == 21_233_664
    assert count(shapes["layers_0"]["moe_mlp"]["router"]) == parts["router"] == 147_456
    assert fields == dict(ref.program_fields(PUBLISHED["published"]), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert model.config.layer_types == ("window",) * 3 + ("full",) + ("window",) * 3 + ("full",)
    # every number of the source's config is in the file under its key, the reduced ones apart
    assert set(PUBLISHED["reduced"]) == set(PUBLISHED["reduced_from"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert len(PUBLISHED["layer_types"]) == 28 and PUBLISHED["layer_types"][3::4] == ["full_attention"] * 7
    assert PUBLISHED["mlp_layer_types"] == ["sparse"] * 28


def test_counts_of_the_yardstick():
    config = PUBLISHED["published"]
    assert ref.cache_row_bytes(config) == 2048 and ref.expert_bytes(config) == 12_386_304
    assert ref.keys_seen(config, 100) == 8 * 100 and ref.keys_seen(config, 5000) == 6 * 1024 + 2 * 5000
    dense = ref.dense_weight_bytes(config)
    assert dense == 2 * (3_794_968_832 - 8 * 64 * 6_193_152 - 98_304 * 2_304)
    by_contexts = ref.decode_least_bytes(config, [100, 5000], 32, 3)
    by_counter = ref.decode_least_bytes(config, [100, 5000], 32, 3, rows_live=800 + 6 * 1024 + 10_000)
    assert by_contexts == by_counter == (16_944 * 2048 + 2 * dense / 32 + 3 * 12_386_304)
    assert ref.forward_flops_span(config, 0, 3000, 1) == sum(
        ref.forward_flops_token(config, c + 1, c == 2999) for c in range(3000))


# ------------------------------------ the other stacks of two kinds, unchanged
def _trinity_lowered(program):
    fields = dict(json.loads((REPO / "bench" / "configs" / "trinity-large.json").read_text())["transformer"])
    fields.update(json.loads((REPO / "bench" / "workloads" / "trinity-large.serve-longdoc-surge.json").read_text())
                  ["rehearse"]["transformer"])
    fields["dtype"], fields["param_dtype"] = getattr(jnp, fields["dtype"]), getattr(jnp, fields["param_dtype"])
    model = Transformer(TransformerConfig(**fields))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    spec, i32 = jax.ShapeDtypeStruct, lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lanes, table, ring = 4, 64, 9
    full = [spec((1, lanes * table + 1, 2, PAGE, 16), jnp.float32)] * 2
    rings = [spec((4, lanes * ring + 1, 2, PAGE, 16), jnp.float32)] * 2
    if program == "decode":
        vectors = (i32(lanes), spec((lanes,), jnp.bool_), i32(lanes), spec((lanes,), jnp.bool_),
                   spec((lanes,), jnp.float32), i32(lanes), spec((lanes,), jnp.float32), i32(lanes),
                   spec((lanes, 2), jnp.uint32))
        return pool.make_mixed_decode_window(model, 4).lower(
            params, *full, *rings, i32(lanes, table), i32(lanes, ring), i32(lanes), *vectors)
    return pool.make_mixed_prefill_chunk(model, 16, PAGE).lower(
        params, i32(1, 16), *full, *rings, i32(table), i32(ring), i32(), i32())


#: SHA-256 (16 digits) of Trinity's mixed programs at rehearsal size as the
#: parent of the change that gave the full layers a rope of their own lowered
#: them here (jax 0.9.0, no debug locations in the text).
TRINITY_LOWERED_BEFORE = {"decode": "059fd72c6d46e943", "chunk": "c9ffef543d85c055"}


@pytest.mark.parametrize("program", sorted(TRINITY_LOWERED_BEFORE))
def test_trinity_lowers_to_the_program_it_lowered_to_before(program):
    with jax.default_matmul_precision("default"):
        text = _trinity_lowered(program).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TRINITY_LOWERED_BEFORE[program]

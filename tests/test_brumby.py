"""Brumby-14B's block (power retention in every layer: a recurrent state a lane
for a cache, per-head rmsnorm of q and k) through ``Transformer``, ``generate``
and the serving engine, against the plain reference ``bench/reference/brumby.py``
(the attention form, float32) at tiny widths on the CPU, seeded weights.

Tolerances: program and reference compute the same float32 mathematics at
highest matmul precision in three forms (the reference's written-out weights,
the program's chunked form, its recurrent step), so logits of size ~1 agree to
a few 1e-6; ``ATOL`` is 1e-5.  The recurrent step sums the ``D`` products of
the feature map where the other two square a sum of ``d``: its terms cancel
where theirs do not, and it reads up to 4e-5 away (``ATOL_STEP`` 1e-4; a wrong
gate, power or normaliser reads 0.1 and more).  Weights are drawn at
normal(0.1): at 0.02 every head's products are near 0 and a wrong form would
not show.
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

from reference import brumby as ref  # noqa: E402

from accelerate_tpu.models import retention  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.retention import StateCache, phi, state_width  # noqa: E402
from accelerate_tpu.models.transformer import Attention, KVCache, Transformer, TransformerConfig  # noqa: E402
from accelerate_tpu.serving import ReplicaRouter, ServingEngine  # noqa: E402
from accelerate_tpu.serving import engine as engine_module  # noqa: E402
from accelerate_tpu.serving.paging import StatePool  # noqa: E402
from accelerate_tpu.serving.transfer import PageMigrator  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

ATOL, ATOL_STEP = 1e-5, 1e-4
TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 97, "max_position_embeddings": 256, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "power_degree": 2, "normaliser_eps": 1e-6, "init_std": 0.1,
}
MAX_NEW = 9


def _config(published=TINY, chunk=8, **kw):
    fields = ref.program_fields(published)
    fields["retention"] = dict(fields["retention"], chunk=chunk)
    return TransformerConfig(**fields, dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def _ids(seed, shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0, TINY["vocab_size"]), np.int32)


@pytest.fixture(scope="module")
def tiny():
    """``(model, program params, reference params)`` from one seeded draw."""
    ref_params = ref.init_params(5, TINY, jnp.float32)
    return Transformer(_config()), ref.to_program_tree(ref_params, TINY), ref_params


def _engine(tiny, **kw):
    model, params, _ = tiny
    kw = dict(dict(num_slots=2, max_len=128, prefill_buckets=(16, 32), decode_window=4,
                   registry=MetricsRegistry()), **kw)
    return ServingEngine(model, params, **kw)


def _generated(tiny, prompt, max_new=MAX_NEW):
    model, params, _ = tiny
    seq, _ = generate(model, params, np.asarray(prompt)[None], max_new_tokens=max_new)
    return [int(t) for t in seq[0, len(prompt):]]


# ------------------------------------------------------------- the feature map
@pytest.mark.parametrize("degree,d", [(2, 16), (2, 128), (1, 16)])
def test_phi_is_the_feature_map_of_the_powered_dot_product(degree, d):
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 5, d)), jax.random.normal(jax.random.PRNGKey(1), (2, 5, d))
    assert phi(q, degree).shape == (2, 5, state_width(d, degree))
    want = np.sum(np.asarray(q) * np.asarray(k), -1) ** degree
    np.testing.assert_allclose(np.sum(phi(q, degree) * phi(k, degree), -1), want, rtol=2e-5, atol=1e-4)


def test_state_width_at_the_published_head():
    # 65 rows of 128 lanes: the 8,256 distinct products, the 64 pairs at distance 64 twice
    assert state_width(128, 2) == 8320 and state_width(128, 1) == 128


# --------------------------------------------------------------- the three forms
def test_parameter_tree_is_the_references_under_program_names(tiny):
    model, params, _ = tiny
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    flat = lambda tree: {jax.tree_util.keystr(p): a.shape for p, a in jax.tree_util.tree_leaves_with_path(tree)}
    assert flat(shapes) == flat(params)
    assert sorted(shapes["layers_0"]["attn"]) == ["g_proj", "k_norm", "k_proj", "o_proj", "q_norm", "q_proj",
                                                  "v_proj"]


@pytest.mark.parametrize("chunk", [5, 7, 8, 64], ids=lambda c: f"chunk{c}")
def test_chunked_form_equals_the_references_attention_form(tiny, chunk):
    """20 rows: chunks of 5 divide them, 7 and 8 do not (the last sub-chunk is
    padded with rows that do not count), 64 holds them all."""
    _, params, ref_params = tiny
    ids = _ids(7, (2, 20))
    got = Transformer(_config(chunk=chunk)).apply({"params": params}, ids)
    for row in range(2):
        np.testing.assert_allclose(got[row], ref.forward(ref_params, ids[row], TINY), atol=ATOL)


def test_recurrent_form_token_by_token_equals_the_attention_form(tiny):
    model, params, ref_params = tiny
    ids = _ids(8, (2, 20))
    cache, rows = StateCache.create(model.config, 2), []
    for t in range(20):
        logits, cache = model.apply({"params": params}, ids[:, t:t + 1], cache=cache)
        rows.append(logits)
    got = jnp.concatenate(rows, axis=1)
    for row in range(2):
        np.testing.assert_allclose(got[row], ref.forward(ref_params, ids[row], TINY), atol=ATOL_STEP)
    assert int(cache.index) == 20


@pytest.mark.parametrize("per_lane", [False, True], ids=["scalar_index", "lane_index"])
def test_prefill_chunks_then_decode_through_the_state_cache_match_reference(tiny, per_lane):
    model, params, ref_params = tiny
    ids = _ids(9, (2, 30))
    cache, rows = StateCache.create(model.config, 2, per_lane_index=per_lane), []
    for lo, hi in [(0, 13), (13, 21)] + [(t, t + 1) for t in range(21, 30)]:
        logits, cache = model.apply({"params": params}, ids[:, lo:hi], cache=cache)
        rows.append(logits)
    got = jnp.concatenate(rows, axis=1)
    for row in range(2):
        np.testing.assert_allclose(got[row], ref.forward(ref_params, ids[row], TINY), atol=ATOL_STEP)


@pytest.mark.parametrize("rows", [1, 13], ids=["step", "chunk"])
def test_only_live_rows_enter_the_state(tiny, rows):
    """A frozen lane (``live`` 0) and a chunk's padding leave the state as it
    was: lane 0 takes all ``rows`` new rows, lane 1 none (step) or five."""
    model, params, _ = tiny
    ids = _ids(10, (2, 8 + rows))
    _, warm = model.apply({"params": params}, ids[:, :8], cache=StateCache.create(model.config, 2, per_lane_index=True))
    live = jnp.asarray([rows, 0 if rows == 1 else 5], jnp.int32)
    _, got = model.apply({"params": params}, ids[:, 8:], cache=warm.replace(live=live))
    _, lane0 = model.apply({"params": params}, ids[:, 8:], cache=warm)
    np.testing.assert_allclose(got.s[:, 0], lane0.s[:, 0], rtol=1e-5, atol=1e-5)
    if rows == 1:
        np.testing.assert_array_equal(got.s[:, 1], warm.s[:, 1])
        np.testing.assert_array_equal(got.z[:, 1], warm.z[:, 1])
    else:
        _, five = model.apply({"params": params}, ids[:, 8:13], cache=warm)
        np.testing.assert_allclose(got.s[:, 1], five.s[:, 1], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got.z[:, 1], five.z[:, 1], rtol=1e-5, atol=1e-5)


def test_two_token_case_by_hand():
    """One layer's retention on two rows, written out: ``y_0 = v_0`` (one
    weight, normalised by itself) and ``y_1 = (g_1 w_10 v_0 + w_11 v_1) / (g_1
    w_10 + w_11)`` with ``w_ti = (q̂_t . k̂_i)^2 / d``."""
    cfg = _config()
    d, eps = 16, 1e-6
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 2, 1, d)))
    k = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, 2, d)))
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (2, 2, d)))
    log_g = np.log(np.asarray([[0.9, 0.4], [0.7, 0.2]], np.float32))      # [T, Hk]
    s_shape, z_shape = retention.state_shapes(cfg, 1)
    y, _, _ = retention.retention_chunked(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None], jnp.asarray(log_g)[None],
        jnp.ones((1, 2), bool), jnp.zeros(s_shape[1:]), jnp.zeros(z_shape[1:]), 2, eps * d, 8)
    for head in range(2):
        w = lambda t, i: float(np.dot(q[t, head, 0], k[i, head])) ** 2
        g1 = float(np.exp(log_g[1, head]))
        y0 = w(0, 0) * v[0, head] / (w(0, 0) + eps * d)
        y1 = (g1 * w(1, 0) * v[0, head] + w(1, 1) * v[1, head]) / (g1 * w(1, 0) + w(1, 1) + eps * d)
        np.testing.assert_allclose(y[0, 0, head, 0], y0, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(y[0, 1, head, 0], y1, rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def served(tiny):
    """One run of the engine over prompts that take one chunk, several, a
    padded last chunk and no chunk's worth at all, with lanes reused."""
    engine = _engine(tiny)
    prompts = [_ids(20 + i, (n,)) for i, n in enumerate((5, 16, 33, 47, 17, 1, 32))]
    requests = [engine.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    engine.run()
    return engine, prompts, [list(r.tokens) for r in requests]


def test_generate_and_the_engine_give_the_same_greedy_tokens(tiny, served):
    _, prompts, tokens = served
    for prompt, got in zip(prompts, tokens):
        assert got == _generated(tiny, prompt)


def test_generate_runs_the_state_cache(tiny):
    model, params, ref_params = tiny
    prompt = _ids(40, (11,))
    seq, cache = generate(model, params, prompt[None], max_new_tokens=5)
    assert isinstance(cache, StateCache) and int(cache.index) == 11 + 5 - 1     # the last token is not fed
    logits = ref.forward(ref_params, jnp.asarray(seq[0, :-1]), TINY)
    assert [int(t) for t in seq[0, 11:]] == [int(t) for t in jnp.argmax(logits[10:], -1)]


def test_state_counters_gauge_and_no_prefix_cache(served):
    engine, prompts, _ = served
    stats = engine.stats
    assert isinstance(engine.kv, StatePool) and engine.prefix_cache is None
    assert engine.prefix_cache_stats()["built"] is False
    assert stats["state_installs"] == len(prompts) and stats["slots_reused"] == len(prompts) - 2
    # every lane's state is read and rewritten every step; a live one emitted a token
    assert stats["state_lane_steps"] == stats["decode_steps"] * engine.num_slots
    assert stats["state_live_lane_steps"] == stats["occupied_lane_steps"]
    layers, lanes, heads, width, d = engine.kv.s.shape
    assert (layers, lanes, heads, width, d) == (2, 2, 2, state_width(16, 2), 16)
    assert engine.kv_pool_bytes() == 4 * layers * lanes * heads * width * (d + 1)
    assert engine.metrics.gauge("serve/state_bytes").value == engine.kv_pool_bytes()
    counts = engine.compiled_executable_counts()
    assert counts["state_install"] == counts["decode_window"] == counts["prefill_16"] == 1 and counts["copy_page"] == 0


def test_reused_lane_gives_the_tokens_of_a_fresh_engine(tiny, monkeypatch):
    """One lane, two requests: the second reads a state the install zeroed.  Its
    prompt is two tokens, so a state left behind would have decayed by one gate
    only; with the install's program left out the lane's state differs."""
    first, second = _ids(50, (40,)), _ids(51, (2,))

    def run_both():
        engine = _engine(tiny, num_slots=1)
        for prompt in (first, second):
            request = engine.submit(prompt, max_new_tokens=MAX_NEW)
            engine.run()
        return list(request.tokens), np.asarray(engine.kv.s), np.asarray(engine.kv.z)

    fresh = _engine(tiny, num_slots=1)
    request = fresh.submit(second, max_new_tokens=MAX_NEW)
    fresh.run()
    tokens, s, z = run_both()
    assert tokens == list(request.tokens) == _generated(tiny, second)
    np.testing.assert_array_equal(s, np.asarray(fresh.kv.s))
    np.testing.assert_array_equal(z, np.asarray(fresh.kv.z))
    monkeypatch.setattr(engine_module, "make_state_install",
                        lambda shardings=None: jax.jit(lambda s, z, slot: (s, z)))
    _, kept, _ = run_both()
    assert np.abs(kept - s).max() > 1e-3


def test_cancellation_frees_the_lane_and_the_next_request_is_exact(tiny):
    engine = _engine(tiny, num_slots=1)
    victim = engine.submit(_ids(60, (30,)), max_new_tokens=60)
    for _ in range(4):
        engine.step()
    assert engine.cancel(victim) and engine.stats["cancelled"] == 1
    prompt = _ids(61, (9,))
    request = engine.submit(prompt, max_new_tokens=MAX_NEW)
    engine.run()
    assert list(request.tokens) == _generated(tiny, prompt)


def test_failover_replay_mid_generation_is_token_exact(tiny):
    """A replica dies mid-generation; the survivor replays prompt + generated
    by tokens (there are no pages to ship) and the stream goes on exactly."""
    dying, survivor = _engine(tiny), _engine(tiny)
    prompts = [_ids(70, (21,)), _ids(71, (6,))]
    requests = [dying.submit(p, max_new_tokens=24) for p in prompts]
    for _ in range(5):
        dying.step()
    assert all(0 < len(r.tokens) < 24 for r in requests)
    for request in dying.export_inflight():
        survivor.adopt(request)
    survivor.run()
    for prompt, request in zip(prompts, requests):
        assert list(request.tokens) == _generated(tiny, prompt, 24)
    assert "recurrent state" in PageMigrator.compatible(dying, survivor)


def test_preemption_replays_by_tokens(tiny):
    engine = _engine(tiny)
    prompt = _ids(72, (12,))
    request = engine.submit(prompt, max_new_tokens=20)
    for _ in range(3):
        engine.step()
    engine._drain_inflight()
    assert engine._preempt() and engine.stats["preemptions"] == 1
    engine.run()
    assert list(request.tokens) == _generated(tiny, prompt, 20)


def test_router_serves_a_retention_model(tiny):
    router = ReplicaRouter([_engine(tiny), _engine(tiny)])
    prompts = [_ids(80 + i, (n,)) for i, n in enumerate((7, 19, 3))]
    requests = [router.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    router.run()
    for prompt, request in zip(prompts, requests):
        assert list(request.tokens) == _generated(tiny, prompt)
    assert all(r["built"] is False for r in router.prefix_cache_stats()["per_replica"])


REFUSALS = {
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
def test_engine_refuses_by_name_what_a_recurrent_state_does_not_have(tiny, option):
    kw = dict(REFUSALS[option])
    if kw.get("mesh") == "tp2":
        from accelerate_tpu.parallel.mesh import build_mesh

        kw["mesh"] = build_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=rf"^{option} is not ported to a recurrent state"):
        _engine(tiny, **kw)


EXCLUDED = {
    "sliding_window": dict(sliding_window=8),
    "latent_attention": dict(latent_attention=dict(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=8, v_dim=8)),
    "quantization": dict(quantization=8),
    "use_fp8": dict(use_fp8=True),
    "paged_kernel": dict(paged_kernel="pallas"),
    "attention_impl": dict(attention_impl="pallas"),
    "positional": dict(positional="learned"),
    "scan_layers": dict(scan_layers=True),
}


@pytest.mark.parametrize("field", sorted(EXCLUDED))
def test_configuration_refuses_by_name_what_retention_excludes(field):
    with pytest.raises(ValueError, match=rf"retention excludes {field}"):
        _config(**EXCLUDED[field])


def test_retention_spec_refuses_what_it_has_no_feature_map_for():
    with pytest.raises(ValueError, match="degree 3"):
        _config(TINY | {"power_degree": 3})
    with pytest.raises(ValueError, match="gate_heads"):
        TransformerConfig.tiny(retention={"gate_heads": 3})
    with pytest.raises(NotImplementedError, match="StateCache"):
        model = Transformer(_config())
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
        jax.eval_shape(lambda p: model.apply({"params": p}, jnp.zeros((1, 4), jnp.int32),
                                             cache=KVCache.create(model.config, 1, 16)), params)


# ------------------------------------------------------ the head norm elsewhere
def _attn_shapes(config):
    model = Transformer(config)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    return shapes["layers_0"]["attn"]


def test_plain_attention_honours_the_head_norm_against_hand_arithmetic():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, qk_norm=True, rms_norm_eps=1e-6)
    attn = Attention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, cfg.hidden_size))
    positions = jnp.arange(6)[None]
    params = attn.init(jax.random.PRNGKey(1), x, positions)["params"]
    params["q_norm"]["scale"] = jax.random.uniform(jax.random.PRNGKey(2), (16,), minval=0.5, maxval=1.5)
    params["k_norm"]["scale"] = jax.random.uniform(jax.random.PRNGKey(3), (16,), minval=0.5, maxval=1.5)
    got = np.asarray(attn.apply({"params": params}, x, positions))[0]
    assert sorted(params) == ["k_norm", "k_proj", "o_proj", "q_norm", "q_proj", "v_proj"]

    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    xs = np.asarray(x, np.float64)[0]
    heads, kv_heads, d = cfg.num_heads, cfg.num_kv_heads, 16

    def normed_roped(w, scale, n):
        y = (xs @ w).reshape(6, n, d)
        y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + 1e-6) * scale       # over each head's width
        freqs = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2) / d)
        cos, sin = np.cos(np.arange(6)[:, None] * freqs)[:, None], np.sin(np.arange(6)[:, None] * freqs)[:, None]
        a, b = y[..., :d // 2], y[..., d // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    q = normed_roped(p["q_proj"]["kernel"], p["q_norm"]["scale"], heads)
    k = normed_roped(p["k_proj"]["kernel"], p["k_norm"]["scale"], kv_heads)
    v = (xs @ p["v_proj"]["kernel"]).reshape(6, kv_heads, d)
    out = np.zeros((6, heads, d))
    for h in range(heads):
        scores = q[:, h] @ k[:, h // (heads // kv_heads)].T / np.sqrt(d)
        scores = np.where(np.tril(np.ones((6, 6), bool)), scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, h] = probs / probs.sum(-1, keepdims=True) @ v[:, h // (heads // kv_heads)]
    np.testing.assert_allclose(got, out.reshape(6, -1) @ p["o_proj"]["kernel"], atol=2e-5)


def test_head_norm_runs_through_the_kv_cache_too():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, qk_norm=True)
    model = Transformer(cfg)
    ids = _ids(90, (1, 12)) % cfg.vocab_size
    params = model.init(jax.random.PRNGKey(4), ids)["params"]
    full = model.apply({"params": params}, ids)
    cache, rows = KVCache.create(cfg, 1, 16), []
    for lo, hi in [(0, 7)] + [(t, t + 1) for t in range(7, 12)]:
        logits, cache = model.apply({"params": params}, ids[:, lo:hi], cache=cache)
        rows.append(logits)
    np.testing.assert_allclose(jnp.concatenate(rows, 1), full, atol=2e-5)


@pytest.mark.parametrize("family", ["llama", "gpt2", "deepseek-v2"])
def test_existing_parameter_trees_are_unchanged_by_the_new_fields(family):
    """``retention`` and ``qk_norm`` off: not a leaf more."""
    if family == "deepseek-v2":
        fields = json.loads((REPO / "bench" / "workloads" / "deepseek-v2.serve-doc-surge.json").read_text())[
            "rehearse"]["transformer"]
        fields = dict(fields, dtype=jnp.float32, param_dtype=jnp.float32)
        attn = _attn_shapes(TransformerConfig(**fields))
        assert sorted(attn) == ["kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj", "q_a_norm", "q_a_proj", "q_b_proj"]
        return
    make = TransformerConfig.tiny if family == "llama" else _gpt2_tiny
    attn = _attn_shapes(make())
    assert sorted(attn) == ["k_proj", "o_proj", "q_proj", "v_proj"]
    assert make().retention is None and make().qk_norm is False


def _gpt2_tiny():
    return TransformerConfig.gpt2(vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=2,
                                  num_kv_heads=2, max_seq_len=32)


# ---------------------------------------------------------- the configuration
def test_configuration_file_counts_what_the_issue_states():
    """``bench/configs/brumby-14b.json`` under ``jax.eval_shape`` (no memory):
    4,859.4 M parameters held, 330.35 M a layer, 340.8 MB of state a lane at
    the 8,256 distinct entries the benchmark counts (the layout holds 8,320)."""
    config = json.loads((REPO / "bench" / "configs" / "brumby-14b.json").read_text())
    published, fields = config["published"], dict(config["transformer"])
    assert fields == dict(ref.program_fields(published), dtype="bfloat16", param_dtype="bfloat16")
    assert config["reduced"] == ["num_hidden_layers"] and published["num_hidden_layers"] == 10
    assert all(config[k] == v for k, v in published.items() if k not in ("power_degree", "normaliser_eps"))
    fields["dtype"], fields["param_dtype"] = jnp.bfloat16, jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: int(sum(np.prod(a.shape) for a in jax.tree_util.tree_leaves(tree)))
    assert count(shapes["layers_0"]) == 330_352_896 == config["parameters_by_part"]["layer"]
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) == 777_912_320
    assert count(shapes) == config["parameters"] == ref.parameter_count(published) == 4_859_358_720
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(shapes))
    assert ref.state_bytes_lane(published) == config["state_bytes_lane"] == 340_807_680
    s_shape, z_shape = retention.state_shapes(model.config, 8)
    assert s_shape == (10, 8, 8, 8320, 128) and z_shape == (10, 8, 8, 8320)
    # a full decode step's least bytes: ten layers and the head once, every lane's state in and out
    assert round(ref.decode_least_bytes(published, [0] * 8, 8) / 1e9, 2) == 13.62
    assert dataclasses.replace(model.config, num_layers=40).num_layers == 40

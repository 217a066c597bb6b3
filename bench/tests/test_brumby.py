"""The Brumby-14B reference: its attention form against a two-token case
written out by hand, its counts against hand counts and the configuration's
file, its layer-by-layer pass against the whole stack at a tiny size, the
control against the reference, and the cell's files against each other."""

import jax.numpy as jnp
import numpy as np

from lib import common
from reference import brumby as ref

CONFIG = common.read_json(common.BENCH / "configs" / "brumby-14b.json")
CELL = common.read_json(common.BENCH / "workloads" / "brumby-14b.serve-reason-surge.json")
PUBLISHED = CONFIG["published"]
TINY = dict(PUBLISHED, **CELL["rehearse"]["published"])


def test_two_token_case_by_hand():
    """One layer, two rows, every step written in numpy float64: ``y_0 = v_0``
    (up to eps) and ``y_1`` the gated, squared-dot weighted mean of ``v_0, v_1``."""
    cfg = dict(TINY, num_hidden_layers=1)
    p = {k: np.asarray(v, np.float64) for k, v in ref.init_layer(3, cfg, 0, jnp.float32).items()}
    x = np.asarray(np.random.default_rng(0).normal(size=(2, cfg["hidden_size"])))
    got = np.asarray(ref.retention(jnp.asarray(x, jnp.float32), {k: jnp.asarray(v, jnp.float32) for k, v in p.items()},
                                   cfg))
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rms = lambda a, g: a / np.sqrt(np.mean(a * a, -1, keepdims=True) + cfg["rms_norm_eps"]) * g
    h = rms(x, p["ln1"])

    def roped(a, t):
        freqs = 1.0 / cfg["rope_theta"] ** (np.arange(0, d, 2) / d)
        cos, sin = np.cos(t * freqs), np.sin(t * freqs)
        a1, a2 = a[..., :d // 2], a[..., d // 2:]
        return np.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)

    q = [roped(rms((h[t] @ p["wq"]).reshape(heads, d), p["qn"]), t) for t in range(2)]
    k = [roped(rms((h[t] @ p["wk"]).reshape(kv, d), p["kn"]), t) for t in range(2)]
    v = [(h[t] @ p["wv"]).reshape(kv, d) for t in range(2)]
    g1 = 1.0 / (1.0 + np.exp(-(h[1] @ p["wg"])))                      # the gate of row 1, a kv head
    y = np.zeros((2, heads, d))
    for head in range(heads):
        j = head // (heads // kv)
        w = lambda t, i: (q[t][head] @ k[i][j] / np.sqrt(d)) ** 2
        y[0, head] = w(0, 0) * v[0][j] / (w(0, 0) + 1e-6)
        y[1, head] = (g1[j] * w(1, 0) * v[0][j] + w(1, 1) * v[1][j]) / (g1[j] * w(1, 0) + w(1, 1) + 1e-6)
    np.testing.assert_allclose(got, y.reshape(2, -1) @ p["wo"], rtol=2e-4, atol=2e-6)


def test_parameter_counts_of_the_issues_table():
    counts = ref.parameter_counts(PUBLISHED)
    # q and o 26.21 M each, k and v 5.24 M each, the gate 40,960
    assert counts["retention"] == 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 == 62_955_520
    assert counts["layer"] == 62_955_520 + 2 * 128 + 3 * 5120 * 17408 + 2 * 5120 == 330_352_896
    assert counts["embedding"] == 151_936 * 5120 == 777_912_320
    assert counts["total"] == 10 * 330_352_896 + 2 * 777_912_320 + 5120 == CONFIG["parameters"] == 4_859_358_720
    assert CONFIG["parameters_by_part"] == counts


def test_state_flops_and_bytes():
    assert ref.state_entries(PUBLISHED) == 128 * 129 // 2 == 8256
    # 8 x (8256 x 128 + 8256) x 4 B = 34.08 MB a layer
    assert ref.state_bytes_lane(PUBLISHED) == 10 * 8 * (8256 * 128 + 8256) * 4 == CONFIG["state_bytes_lane"]
    assert round(ref.state_bytes_lane(PUBLISHED) / 10 / 1e6, 2) == 34.08
    token = ref.retention_flops_token(PUBLISHED)
    assert token == 10 * 2 * 8256 * 129 * 48                         # update 8 states, read by 40 heads
    m = ref.matmul_params_token(PUBLISHED)
    assert m["blocks"] == 10 * (62_955_520 + 3 * 5120 * 17408) and m["head"] == 5120 * 151_936
    assert ref.forward_flops_token(PUBLISHED, 17, True) == ref.forward_flops_token(PUBLISHED, 30_000, True)
    span = ref.forward_flops_span(PUBLISHED, 0, 512, 1)
    assert span == 512 * (2 * m["blocks"] + token) + 2 * m["head"]
    assert span == sum(ref.forward_flops_token(PUBLISHED, p + 1, p == 511) for p in range(512))
    assert 3.3e12 < 512 * 2 * m["blocks"] < 3.5e12 and 0.5e12 < 512 * token < 0.56e12     # the issue's 3.4 and 0.55
    # a decode step of 8 live lanes: ten layers and the head once, each state in and out
    dense = ref.dense_weight_bytes(PUBLISHED)
    assert dense == 2 * (4_859_358_720 - 777_912_320)
    assert ref.decode_least_bytes(PUBLISHED, [5, 900] * 4, 8) == dense + 8 * 2 * 340_807_680
    assert ref.decode_least_bytes(PUBLISHED, [5], 8) == dense / 8 + 2 * 340_807_680


def test_layer_by_layer_equals_the_whole_stack():
    ids = np.random.default_rng(1).integers(0, TINY["vocab_size"], (16,))
    xs, top = ref.forward_by_layer(7, [ids], TINY)
    whole = ref.forward(ref.init_params(7, TINY, jnp.float32), jnp.asarray(ids), TINY)
    np.testing.assert_allclose(ref.head_logits(xs["float32"][0], top, TINY), whole, atol=5e-5)


def test_padding_past_a_request_moves_none_of_its_gaps():
    rng = np.random.default_rng(2)
    sample = [(rng.integers(0, TINY["vocab_size"], (9,)), rng.integers(0, TINY["vocab_size"], (6,)))]
    tight = ref.served_token_gaps(3, sample, TINY, multiple=1)[0][0]
    padded = ref.served_token_gaps(3, sample, TINY, multiple=64)[0][0]
    assert tight.shape == (6,) and np.all(tight >= 0)
    np.testing.assert_allclose(tight, padded, atol=1e-5)


def test_the_control_reads_wider_than_rounding():
    rng = np.random.default_rng(4)
    sample = [(rng.integers(0, TINY["vocab_size"], (20,)), rng.integers(0, TINY["vocab_size"], (12,)))]
    gaps, low = ref.served_token_gaps(5, sample, TINY, lower="fp8")[0]
    same, _ = ref.served_token_gaps(5, sample, TINY)[0]
    assert low.max() > 1e-3 and np.all(low >= 0)
    np.testing.assert_allclose(gaps, same, atol=1e-5)


def test_cell_files_agree():
    manifest, entry, cell, config = common.load_cell("brumby-14b.serve-reason-surge")
    assert cell["kind"] == "serve_state" and config["reference"]["module"] == "brumby"
    assert entry["chips"] == 1 and entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert config["transformer"] == dict(ref.program_fields(PUBLISHED), dtype="bfloat16", param_dtype="bfloat16")
    assert config["reduced"] == ["num_hidden_layers"] == next(
        c for c in manifest["configs"] if c["name"] == "brumby-14b")["reduced"]
    assert common.metric_names(manifest, entry["name"], "end_to_end") == ["gap_ms_p95", "setup_s"]
    assert set(common.metric_names(manifest, entry["name"], "per_layer")) == {
        "decode_device_ms_per_token", "prefill_device_ms_per_ktoken", "decode_hbm_roofline.state",
        "prefill_mxu_roofline.state", "state_live_pass_pct"}
    engine, mix = cell["engine"], cell["traffic"]
    assert engine["num_slots"] == mix["initial_burst"] == 8 and engine["prefill_buckets"] == [128, 512]
    # every request fits its lane: the longest prompt with the longest answer and a window
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] + engine["decode_window"] <= engine["max_len"]
    # one chunk a request
    assert mix["prompt_tokens"]["max"] <= max(engine["prefill_buckets"])

"""The DeepSeek-V2 reference: its counts against hand counts, its layer-by-layer
pass against the whole stack at a tiny size, the control against the
reference, and the cell's files against each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import common
from reference import deepseek_v2 as ref

CONFIG = common.read_json(common.BENCH / "configs" / "deepseek-v2.json")
CELL = common.read_json(common.BENCH / "workloads" / "deepseek-v2.serve-doc-surge.json")
PUBLISHED = CONFIG["published"]
TINY = dict(PUBLISHED, **CELL["rehearse"]["published"])


def test_parameter_counts_of_the_configurations_table():
    counts = ref.parameter_counts(PUBLISHED)
    # 5120x1536 + 1536x24576 + 5120x576 + 512x32768 + 16384x5120
    assert counts["attention"] == 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080 == 149_225_472
    assert counts["expert"] == 3 * 5120 * 1536 == 23_592_960
    norms = 2 * 5120 + 1536 + 512
    assert counts["dense_layer"] == 149_225_472 + norms + 3 * 5120 * 12288 == 337_981_440
    assert counts["expert_layer"] == 149_225_472 + norms + 5120 * 160 + 3 * 5120 * 3072 + 40 * 23_592_960
    assert counts["total"] == counts["dense_layer"] + 4 * counts["expert_layer"] + 2 * 25600 * 5120 + 5120
    assert counts["total"] == CONFIG["parameters"] == 5_163_975_680


def test_flops_a_token_and_a_span():
    m = ref.matmul_params_token(PUBLISHED)
    # 1.5 routed experts a token and expert layer here: 6 x 40 / 160
    expert_layer = 5120 * 160 + 3 * 5120 * 3072 + 1.5 * 23_592_960
    assert m["blocks"] == 5 * 149_225_472 + 3 * 5120 * 12288 + 4 * expert_layer
    assert m["head"] == 5120 * 25600
    assert ref.attention_flops_key(PUBLISHED) == 2 * 128 * (192 + 128) * 5 == 409_600
    one = ref.forward_flops_token(PUBLISHED, 1000, True)
    assert one == 2 * m["blocks"] + 409_600 * 1000 + 2 * 5120 * 25600
    assert 2.4e9 < 2 * m["blocks"] < 2.6e9                       # about 2.5 GFLOP a prompt token
    span = ref.forward_flops_span(PUBLISHED, 0, 100, 1)
    assert span == 2 * m["blocks"] * 100 + 409_600 * (100 * 101 // 2) + 2 * 5120 * 25600
    assert span == sum(ref.forward_flops_token(PUBLISHED, p + 1, p == 99) for p in range(100))


def test_bytes_of_a_decode_step():
    assert ref.cache_bytes_token(PUBLISHED) == 5 * (512 + 64) * 2 == 5760
    assert ref.expert_bytes(PUBLISHED) == 47_185_920
    dense = ref.dense_weight_bytes(PUBLISHED)
    assert dense == 2 * (5_163_975_680 - 4 * 40 * 23_592_960 - 25600 * 5120)
    least = ref.decode_least_bytes(PUBLISHED, [1000, 3000], 16, 70)
    assert least == 4000 * 5760 + 2 * dense / 16 + 70 * 47_185_920


def test_tiny_hand_count():
    tiny = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4,
            "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2, "intermediate_size": 16,
            "moe_intermediate_size": 4, "n_routed_experts": 8, "experts_held": [2, 4], "num_experts_per_tok": 2,
            "n_shared_experts": 1, "first_k_dense_replace": 1, "vocab_size": 10}
    attention = 8 * 4 + 4 * 2 * 4 + 8 * 6 + 4 * 2 * 4 + 4 * 8                       # 176
    assert ref.parameter_counts(tiny)["attention"] == attention
    expert_layer = 8 * 8 + 3 * 8 * 4 + (2 * 2 / 8) * 3 * 8 * 4                     # router, shared, half an expert a token
    assert ref.matmul_params_token(tiny) == {"blocks": 2 * attention + 3 * 8 * 16 + expert_layer, "head": 80}
    assert ref.attention_flops_key(tiny) == 2 * 2 * (2 + 2 + 2) * 2
    assert ref.cache_bytes_token(tiny) == 2 * (4 + 2) * 2


@pytest.fixture(scope="module")
def rows():
    return [np.random.default_rng(i).integers(0, TINY["vocab_size"], (64,)).astype(np.int32) for i in range(2)]


def test_layer_by_layer_is_the_whole_stack(rows):
    params = jax.jit(lambda: ref.init_params(9, TINY, jnp.float32))()
    with jax.default_matmul_precision("highest"):
        xs, top = ref.forward_by_layer(9, rows, TINY, "float32", ("float32", "fp8"))
        for i, row in enumerate(rows):
            whole = np.asarray(jax.jit(lambda ids: ref.forward(params, ids, TINY))(jnp.asarray(row)))
            by_layer = np.asarray(ref.head_logits(xs["float32"][i], top, TINY))
            np.testing.assert_allclose(by_layer, whole, atol=1e-5)
            low = np.asarray(jax.jit(lambda ids: ref.forward(params, ids, TINY, "fp8"))(jnp.asarray(row)))
            np.testing.assert_allclose(np.asarray(ref.head_logits(xs["fp8"][i], top, TINY, "fp8")), low, atol=1e-4)
            assert np.abs(low - whole).max() > 100 * np.abs(by_layer - whole).max()


def test_served_token_gaps_reads_zero_for_the_references_own_choice(rows):
    params = jax.jit(lambda: ref.init_params(9, TINY, jnp.float32))()
    prompt = rows[0][:40]
    ids = list(prompt)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda padded: ref.forward(params, padded, TINY))
        for _ in range(6):                                       # greedy continuation by the reference itself
            padded = np.zeros((64,), np.int32)
            padded[:len(ids)] = ids
            ids.append(int(np.argmax(np.asarray(step(jnp.asarray(padded)))[len(ids) - 1])))
        served = np.asarray(ids[40:], np.int32)
        (gaps, low), (wrong, _) = ref.served_token_gaps(
            9, [(prompt, served), (prompt, (served + 1) % TINY["vocab_size"])], TINY, "float32", "fp8", multiple=64)
    assert gaps.shape == (6,) and gaps.max() <= 1e-5
    assert wrong[0] > 0.01 and low.shape == (6,) and (low >= 0).all()


def test_files_agree():
    fields = dict(ref.program_fields(PUBLISHED), dtype="bfloat16", param_dtype="bfloat16")
    assert CONFIG["transformer"] == fields
    tiny_fields = dict(ref.program_fields(TINY), dtype="float32", param_dtype="float32")
    assert CELL["rehearse"]["transformer"] == tiny_fields
    manifest = common.read_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "deepseek-v2")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == "deepseek-v2.serve-doc-surge")
    assert cell["why"] == CELL["why"] and len(cell["why"]) <= 200 and cell["chips"] == 1
    reported = common.metric_names(manifest, cell["name"], "per_layer")
    assert set(reported) == {"decode_hbm_roofline.moe", "moe_local_pairs_pct", "prefill_device_ms_per_ktoken",
                             "decode_device_ms_per_token"}
    # the cell's tokens/s spread 8.9 % over seeds (prefill-bound: which requests a window serves is the seed's
    # draw), so it reports the gap and set-up end to end, and only the per-layer metrics that move the gap
    assert common.metric_names(manifest, cell["name"], "end_to_end") == ["gap_ms_p95", "setup_s"]
    moves = {m["name"]: m["moves"] for m in manifest["per_layer"]}
    assert all(moves[name] == "gap_ms_p95" for name in reported)
    for name in reported:
        common.load_reducer(name)

"""The grouped matmul of the held experts (``accelerate_tpu/ops/grouped_matmul.py``)
in interpret mode on the CPU, against ``jax.lax.ragged_dot`` and against a loop
over experts written out in float64.

Tolerances: the kernel multiplies bfloat16 operands, accumulates in float32 and
rounds once to bfloat16, so against the float64 loop it is half a bfloat16 step
away (relative 2^-9 = 0.002) plus the float32 sum's noise: ``RTOL`` 0.004 with
an ``ATOL`` of 0.004 for results near 0 (the products here are of size ~1).  An
accumulator kept in bfloat16 over blocks of 128 reads ten times that
(``test_a_bfloat16_accumulator_fails_the_tolerance`` guards the precision).
What interpret mode cannot show (tiling, fast memory, no copy of a weight
array in the compiled programs) is ``tests/test_tpu_compile.py``'s.
"""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))

from reference import deepseek_v2 as ref_deepseek  # noqa: E402
from reference import trinity as ref_trinity  # noqa: E402

from accelerate_tpu.models.transformer import Transformer, TransformerConfig  # noqa: E402
from accelerate_tpu.ops import grouped_matmul as gm  # noqa: E402
from accelerate_tpu.ops.grouped_matmul import grouped_applies, grouped_matmul  # noqa: E402
from accelerate_tpu.parallel import moe  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

RTOL, ATOL = 4e-3, 4e-3
BF16 = jnp.bfloat16

#: name -> (rows, in, out, group sizes): what the programs hand the kernel, small
CASES = {
    "an_expert_with_no_row": (256, 256, 128, [40, 0, 31, 0, 0, 9]),
    "no_row_at_all": (256, 128, 128, [0, 0, 0, 0]),
    "all_rows_in_one_expert": (256, 128, 256, [0, 256, 0]),
    "a_group_straddles_a_row_tile": (384, 128, 128, [100, 60, 0, 130, 7]),         # 100..160 and 160..290 cross 128, 256
    "one_group_over_three_tiles": (512, 128, 128, [3, 300, 5]),
    "decode_window_of_32_rows": (32, 256, 384, [3, 0, 5, 0, 1, 0, 0, 2]),          # Trinity: 8 lanes x 4
    "decode_window_of_96_rows": (96, 384, 256, [10, 0, 20, 7, 0, 1]),              # DeepSeek-V2: 16 lanes x 6
    "rows_that_fill_no_sublane_pair": (6, 128, 128, [2, 1]),                       # one lane x 6: padded to 16
    "a_chunk_of_768_rows": (768, 256, 128, [19, 0, 23, 17, 0, 0, 31, 12, 20, 25, 14, 0, 22, 9, 0, 18]),
}


def _draw(m, k, n, groups, seed=0, weights=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + weights)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32).astype(BF16)
    kernels = [(jax.random.normal(key, (groups, k, n), jnp.float32) / np.sqrt(k)).astype(BF16) for key in keys[1:]]
    return rows, kernels


def _loop(rows, kernel, sizes):
    """Each expert's rows times its slice, written out in float64; rows past
    the groups are not computed (``NaN``: nothing may compare against them)."""
    rows, kernel = np.asarray(rows, np.float64), np.asarray(kernel, np.float64)
    out = np.full((rows.shape[0], kernel.shape[2]), np.nan)
    start = 0
    for g, size in enumerate(sizes):
        out[start:start + size] = rows[start:start + size] @ kernel[g]
        start += size
    return out


def _close(got, want, live):
    got, want = np.asarray(got, np.float64)[:live], np.asarray(want, np.float64)[:live]
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_product_equals_ragged_dot_and_the_loop_over_experts(case):
    m, k, n, sizes = CASES[case]
    rows, (kernel,) = _draw(m, k, n, len(sizes))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(functools.partial(grouped_matmul, interpret=True))(rows, kernel, group_sizes)
    assert got.shape == (m, n) and got.dtype == BF16
    _close(got, _loop(rows, kernel, sizes), sum(sizes))
    _close(got, jax.lax.ragged_dot(rows, kernel, group_sizes), sum(sizes))


@pytest.mark.parametrize("case", ["an_expert_with_no_row", "decode_window_of_96_rows", "a_chunk_of_768_rows"])
def test_an_expert_larger_than_a_block_is_summed_over_blocks_of_in(monkeypatch, case):
    """A served expert is one block; a larger one streams in blocks along
    ``in`` into the float32 accumulator.  Blocks of 128 rows here: two or three
    steps a visit, and the visits past the count repeat the last block."""
    m, k, n, sizes = CASES[case]
    monkeypatch.setattr(gm, "_BLOCK_BYTES", 128 * n * 2)
    assert gm._block_in(k, n) == 128 and k > 128
    rows, (kernel,) = _draw(m, k, n, len(sizes), seed=1)
    got = grouped_matmul(rows, kernel, jnp.asarray(sizes, jnp.int32), interpret=True)
    _close(got, _loop(rows, kernel, sizes), sum(sizes))


def test_a_served_expert_is_one_block():
    """Whole experts of both served configurations fit the block (so a visit is
    one grid step and one read of 15.7 or 18.9 MB); an expert of twice the
    bytes is split in two along ``in``."""
    assert gm._block_in(5120, 1536) == 5120 and gm._block_in(1536, 5120) == 1536 and gm._block_in(3072, 3072) == 3072
    assert gm._block_in(7168, 2048) == 3584
    assert gm._row_tile(3072) == 128 and gm._row_tile(96) == 96 and gm._row_tile(32) == 32 and gm._row_tile(6) == 16


@pytest.mark.parametrize("case", ["an_expert_with_no_row", "a_group_straddles_a_row_tile", "decode_window_of_96_rows"])
def test_rows_past_the_groups_never_reach_a_row_inside_one(case):
    """The pairs held elsewhere sort last and what they hold is nobody's: NaN
    there on input changes no row of a group, bit for bit."""
    m, k, n, sizes = CASES[case]
    live = sum(sizes)
    assert live < m
    rows, (kernel,) = _draw(m, k, n, len(sizes), seed=2)
    poisoned = rows.at[live:].set(jnp.nan)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    clean = grouped_matmul(rows, kernel, group_sizes, interpret=True)
    dirty = grouped_matmul(poisoned, kernel, group_sizes, interpret=True)
    assert np.array_equal(np.asarray(clean[:live], np.float32), np.asarray(dirty[:live], np.float32))
    assert np.all(np.isfinite(np.asarray(dirty[:live], np.float32)))


def test_visits_are_the_tile_expert_pairs_that_hold_rows():
    """100 | 60 | 0 | 130 | 7 rows over tiles of 128: expert 0 in tile 0, expert 1
    in tiles 0-1, expert 3 in tiles 1-2, expert 4 in tile 2; expert 2 is never
    visited and the entries past the count repeat the last visit."""
    expert, tile, bounds, count = gm._visits(jnp.asarray([100, 60, 0, 130, 7], jnp.int32), 128, 3)
    assert int(count[0]) == 6 and expert.shape == (3 + 5 - 1,)
    assert list(zip(expert.tolist(), tile.tolist())) == [(0, 0), (1, 0), (1, 1), (3, 1), (3, 2), (4, 2), (4, 2)]
    assert bounds.tolist() == [0, 100, 160, 160, 290, 297]
    none = gm._visits(jnp.zeros((4,), jnp.int32), 128, 2)
    assert int(none[3][0]) == 0 and int(none[1].max()) == 0


def test_a_bfloat16_accumulator_fails_the_tolerance():
    """2,048 terms summed in blocks of 128 with the running sum rounded to
    bfloat16 between blocks: what a kernel without its float32 scratch would
    give.  The tolerance can tell; the kernel passes it on the same operands."""
    m, k, n, sizes = 32, 2048, 128, [12, 0, 20]
    rows, (kernel,) = _draw(m, k, n, len(sizes), seed=3)
    want = _loop(rows, kernel, sizes)
    _close(grouped_matmul(rows, kernel, jnp.asarray(sizes, jnp.int32), interpret=True), want, 32)
    acc = jnp.zeros((m, n), BF16)
    expert_of = np.repeat(np.arange(len(sizes)), sizes)
    for at in range(0, k, 128):
        part = jnp.einsum("mk,mkn->mn", rows[:, at:at + 128], kernel[expert_of, at:at + 128],
                          preferred_element_type=jnp.float32)
        acc = (acc.astype(jnp.float32) + part).astype(BF16)
    gap = np.abs(np.asarray(acc, np.float64) - want)
    assert not np.all(gap <= ATOL + RTOL * np.abs(want)) and gap.max() > 3 * ATOL, gap.max()


@pytest.mark.parametrize("cotangent_rows", [63, 256], ids=["groups_only", "every_row"])
def test_gradients_are_ragged_dots(cotangent_rows):
    """``jax.grad`` through the kernel: the backward is that of ``ragged_dot`` on
    the same operands, for the rows and for the weights, through one product and
    through the experts' three."""
    m, k, n, sizes = 256, 128, 128, [40, 0, 23]
    rows, (gate, up, down) = _draw(m, k, n, len(sizes), seed=4, weights=3)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    weigh = jax.random.normal(jax.random.PRNGKey(5), (m, n), jnp.float32) * (jnp.arange(m) < cotangent_rows)[:, None]
    kernel_dot = lambda r, w: grouped_matmul(r, w, group_sizes, interpret=True)
    ragged_dot = lambda r, w: jax.lax.ragged_dot(r, w, group_sizes)
    three = lambda dot: lambda r, g, u, d: dot(jax.nn.silu(dot(r, g)) * dot(r, u), d)
    loss = lambda form: lambda *ops: jnp.sum(jnp.where(weigh != 0, form(*ops).astype(jnp.float32) * weigh, 0.0))
    for kernel_form, ragged_form, ops in ((kernel_dot, ragged_dot, (rows, gate)),
                                          (three(kernel_dot), three(ragged_dot), (rows, gate, up, down))):
        argnums = tuple(range(len(ops)))
        got = jax.grad(loss(kernel_form), argnums)(*ops)
        want = jax.grad(loss(ragged_form), argnums)(*ops)
        for g, w, op in zip(got, want, ops):
            assert g.shape == op.shape and g.dtype == op.dtype
            # the forward values the backward is taken at differ by the forms' summation order
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=2e-2, atol=2e-2)
            assert float(jnp.abs(g.astype(jnp.float32)).max()) > 0
    one = jax.grad(loss(kernel_dot), (0, 1))(rows, gate)
    for g, w in zip(one, jax.grad(loss(ragged_dot), (0, 1))(rows, gate)):
        assert np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))    # one product: bit for bit


@pytest.mark.parametrize("dtype,width_in,width_out,interpret,kernel", [
    (BF16, 5120, 1536, True, True),
    (BF16, 5120, 1536, None, False),             # a CPU takes ragged_dot unasked
    (jnp.float32, 5120, 1536, True, False),      # the float32 rehearsal sizes and every CPU test model
    (BF16, 64, 128, True, False),                # the tiny models: hidden 64
    (BF16, 128, 32, True, False),                # ... experts of 32
], ids=["published", "cpu_unasked", "float32", "narrow_hidden", "narrow_expert"])
def test_the_product_picks_its_form_by_platform_dtype_and_widths(dtype, width_in, width_out, interpret, kernel):
    rows = jax.ShapeDtypeStruct((96, width_in), dtype)
    weights = jax.ShapeDtypeStruct((40, width_in, width_out), dtype)
    assert grouped_applies(rows, weights, interpret) is kernel


# ------------------------------------------------------------- the whole layer
DEEPSEEK = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 128, "n_routed_experts": 16,
    "experts_held": [4, 12], "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 4.0, "norm_topk_prob": False, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "vocab_size": 97, "rope_theta": 10000,
    "max_position_embeddings": 256,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
}
TRINITY = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 16, "intermediate_size": 128, "moe_intermediate_size": 128, "num_experts": 16,
    "experts_held": [4, 12], "num_experts_per_tok": 4, "num_shared_experts": 1, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention"], "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid", "mup_enabled": True, "vocab_size": 97,
    "max_position_embeddings": 256, "init_std": 0.1, "router_init_std": 0.1, "expert_bias_std": 0.05,
}
SHAPED = {"deepseek_v2": (ref_deepseek, DEEPSEEK), "trinity": (ref_trinity, TRINITY)}


def _model(name, dtype=BF16):
    ref, published = SHAPED[name]
    config = TransformerConfig(**ref.program_fields(published), dtype=dtype, param_dtype=dtype)
    params = ref.to_program_tree(ref.init_params(11, published, jnp.float32), published)
    return Transformer(config), jax.tree_util.tree_map(lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)


def _interpreted(monkeypatch):
    """Run the kernel where a TPU would: interpreted, counted."""
    calls = []

    def counted(form):
        def run(*operands, interpret=None):
            calls.append((form.__name__, operands[0].shape))
            return form(*operands, interpret=True)
        return run

    monkeypatch.setattr(gm, "_platform_compiles", lambda: True)
    monkeypatch.setattr(moe, "grouped_matmul", counted(grouped_matmul))
    return calls


@pytest.mark.parametrize("name", sorted(SHAPED))
def test_a_model_stepped_by_the_kernel_gives_the_logits_of_the_ragged_dot_form(monkeypatch, name):
    """The whole layer round the kernel (router, sort, the ``in_group`` select,
    gates, the shared expert) in a bfloat16 model of two layers, hidden and
    experts 128 wide, 8 of 16 experts held: the same tokens through both forms,
    which differ by their order of summation."""
    model, params = _model(name)
    tokens = jnp.asarray(np.random.default_rng(13).integers(0, 97, (2, 24)), jnp.int32)
    assert moe.held_experts_grouped(model.config) is False                      # a CPU, unasked
    want = np.asarray(jax.jit(model.apply)({"params": params}, tokens), np.float32)
    calls = _interpreted(monkeypatch)
    assert moe.held_experts_grouped(model.config) is True
    got = np.asarray(jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens), np.float32)
    top_k = model.config.experts.top_k
    assert calls == [("grouped_matmul", (48 * top_k, 128))] * 3                   # gate, up, down of the one expert layer
    assert np.all(np.isfinite(got))
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max(), (np.abs(got - want).max(), np.abs(want).max())


@pytest.mark.parametrize("name", sorted(SHAPED))
def test_ragged_dot_serves_what_the_kernel_does_not_take(monkeypatch, name):
    """A float32 model on a platform that compiles the kernel: the widths fit
    and the dtype does not, ``ragged_dot`` runs and the kernel is never reached."""
    def never(*a, **k):
        raise AssertionError("the grouped kernel was called")

    monkeypatch.setattr(gm, "_platform_compiles", lambda: True)
    monkeypatch.setattr(moe, "grouped_matmul", never)
    model, params = _model(name, jnp.float32)
    assert moe.held_experts_grouped(model.config) is False
    logits = model.apply({"params": params}, jnp.asarray(np.arange(10)[None] % 97, jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))


def test_the_engine_says_which_form_its_programs_run(monkeypatch):
    """``serve/moe_grouped_kernel``: 0 on the CPU rig, 1 where the platform
    compiles the kernel and the model is bfloat16 with widths of whole lanes;
    a model without routed experts has no such gauge."""
    model, params = _model("deepseek_v2")
    build = lambda m, p, registry: ServingEngine(m, p, num_slots=2, max_len=64, prefill_buckets=(16,), decode_window=4,
                                                 prefix_cache_mb=None, registry=registry)
    registry = MetricsRegistry()
    engine = build(model, params, registry)
    assert registry.gauge("serve/moe_grouped_kernel").value == 0 and engine.moe_grouped_kernel is False
    monkeypatch.setattr(gm, "_platform_compiles", lambda: True)
    registry = MetricsRegistry()
    engine = build(model, params, registry)
    assert registry.gauge("serve/moe_grouped_kernel").value == 1 and engine.moe_grouped_kernel is True
    narrow = Transformer(dataclasses.replace(model.config, dtype=jnp.float32))
    registry = MetricsRegistry()
    assert build(narrow, params, registry).moe_grouped_kernel is False
    plain = Transformer(TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64))
    shapes = jax.eval_shape(lambda: plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    registry = MetricsRegistry()
    ServingEngine(plain, jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), shapes), max_len=64,
                  prefill_buckets=(8,), registry=registry)
    assert "serve/moe_grouped_kernel" not in registry.snapshot()

"""The plain reference against ``Transformer`` with ``TransformerConfig.gpt2``
at a tiny size in float32, and the weight renaming between them."""

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights
from reference import gpt2

TINY = {"n_embd": 128, "n_layer": 2, "n_head": 2, "n_inner": 512, "n_positions": 32,
        "vocab_size": 211, "layer_norm_epsilon": 1e-5}


def _program(dtype=jnp.float32):
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig.gpt2(
        vocab_size=211, hidden_size=128, intermediate_size=512, num_layers=2, num_heads=2,
        num_kv_heads=2, max_seq_len=32, dtype=dtype, param_dtype=jnp.float32)
    return Transformer(cfg)


def test_reference_matches_transformer_logits_and_loss():
    model = _program()
    params = weights.make_program_params(gpt2, 7, TINY, jnp.float32)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(params)
    assert all(a.shape == b.shape for a, b in zip(jax.tree_util.tree_leaves(want),
                                                  jax.tree_util.tree_leaves(params)))
    ids = np.random.default_rng(0).integers(0, 211, (3, 32)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    ref_params = jax.jit(lambda: gpt2.init_params(7, TINY, jnp.float32))()
    for row in range(3):
        ref = np.asarray(gpt2.forward(ref_params, jnp.asarray(ids[row]), TINY))
        np.testing.assert_allclose(got[row], ref, atol=2e-5, rtol=1e-4)


def test_low_precision_modes_differ_from_reference():
    ref_params = jax.jit(lambda: gpt2.init_params(3, TINY, jnp.float32))()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 211, (32,)).astype(np.int32))
    full = np.asarray(gpt2.forward(ref_params, ids, TINY))
    bf16 = np.asarray(gpt2.forward(ref_params, ids, TINY, "bfloat16"))
    fp8 = np.asarray(gpt2.forward(ref_params, ids, TINY, "fp8"))
    e_bf16, e_fp8 = np.abs(bf16 - full).max(), np.abs(fp8 - full).max()
    assert 0 < e_bf16 < e_fp8
    assert e_fp8 > 4 * e_bf16


def test_leaf_norms_roundtrip():
    params = weights.make_program_params(gpt2, 5, TINY, jnp.float32)
    ref_params = jax.jit(lambda: gpt2.init_params(5, TINY, jnp.float32))()
    a = weights.program_leaf_norms(params, 2)
    b = jax.tree_util.tree_map(np.asarray, gpt2.leaf_norms(ref_params))
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6)

"""The benchmark's seeded weights in the program's parameter tree, and the
program's leaves back under the reference's names.

The weights are the reference's own draw (``reference/gpt2.py``
``init_params``), made on the device in one jitted call, in the type the cell
runs them in; this module only renames: a stacked reference leaf
``wq[layer]`` is the program's ``layers_<layer>/attn/q_proj/kernel``.
"""

from __future__ import annotations

LAYER_PATHS = {
    "ln1_g": ("input_norm", "scale"), "ln1_b": ("input_norm", "bias"),
    "wq": ("attn", "q_proj", "kernel"), "bq": ("attn", "q_proj", "bias"),
    "wk": ("attn", "k_proj", "kernel"), "bk": ("attn", "k_proj", "bias"),
    "wv": ("attn", "v_proj", "kernel"), "bv": ("attn", "v_proj", "bias"),
    "wo": ("attn", "o_proj", "kernel"), "bo": ("attn", "o_proj", "bias"),
    "ln2_g": ("post_attn_norm", "scale"), "ln2_b": ("post_attn_norm", "bias"),
    "w_up": ("mlp", "up_proj", "kernel"), "b_up": ("mlp", "up_proj", "bias"),
    "w_down": ("mlp", "down_proj", "kernel"), "b_down": ("mlp", "down_proj", "bias"),
}
TOP_PATHS = {
    "wte": ("embed_tokens", "embedding"), "wpe": ("pos_embed", "embedding"),
    "lnf_g": ("final_norm", "scale"), "lnf_b": ("final_norm", "bias"),
}


def _put(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def to_program_tree(ref_params, n_layer):
    tree = {}
    for name, path in TOP_PATHS.items():
        _put(tree, path, ref_params[name])
    for name, path in LAYER_PATHS.items():
        for i in range(n_layer):
            _put(tree, (f"layers_{i}",) + path, ref_params[name][i])
    return tree


def make_program_params(reference, seed, published, dtype):
    """The program's parameter tree, drawn on the device in one jitted call."""
    import jax

    import numpy as np

    n_layer = published["n_layer"]
    # the seed is an argument, not a constant of the program: one compiled
    # program (and one entry of the persistent cache) serves every seed
    draw = jax.jit(lambda s: to_program_tree(reference.init_params(s, published, dtype), n_layer))
    return draw(np.uint32(seed % (2 ** 32)))


def program_leaf_norms(tree, n_layer):
    """Reference leaf name -> norm(s) of the program's leaves: a float for a
    top leaf, a list over layers for a stacked one.  One jitted reduction."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def norms(t):
        n = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        out = {name: n(_get(t, path)) for name, path in TOP_PATHS.items()}
        for name, path in LAYER_PATHS.items():
            out[name] = jnp.stack([n(_get(t, (f"layers_{i}",) + path)) for i in range(n_layer)])
        return out

    return jax.tree_util.tree_map(np.asarray, jax.jit(norms)(tree))

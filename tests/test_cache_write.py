"""The in-place cache write against the plumbing it replaced, bit for bit.

The unrolled forward used to slice each layer's slab out of the stacked cache,
update the slab and ``jnp.stack`` the slabs back; it now carries the stacked
arrays through the layers and writes each layer's new rows at ``[layer, ...]``.
Values, order of accumulation and masks must not move, so every case here runs
one forward both ways on the CPU and compares the logits and every array of the
cache byte for byte.

The oracle is the old forward, kept here: a flax method interceptor hands each
layer's attention the per-layer tuple it used to get (``cache.k[i]``, a slice)
and stacks what comes back, and for the slab cache the write itself is the
deleted ``dynamic_update_slice`` / ``vmap(_write)`` pair, on the cache's
layout of today (rows flat, positions minor).  The paged oracle
writes through the per-layer ``paged_insert`` / ``paged_quantized_insert``
(``layer=None``), whose own contents ``tests/test_paged_attention.py`` pins.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import transformer as tfm
from accelerate_tpu.models.transformer import (
    Attention,
    KVCache,
    PagedKVCache,
    Transformer,
    TransformerConfig,
)
from accelerate_tpu.ops.paged_attention import KV_FORMATS

LANES, MAX_LEN, PAGE = 3, 32, 8
PAGES_PER_LANE = MAX_LEN // PAGE


def _model(**kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=MAX_LEN,
        num_layers=3, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _old_write(slab, new, index, layer=None):
    """The deleted write into ONE layer's slab, in the cache's layout of today
    (``[B, H*D, M]``: rows flat, positions minor)."""
    assert layer is None
    b, s = new.shape[:2]
    cols = new.astype(slab.dtype).reshape(b, s, -1).swapaxes(1, 2)
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(slab, cols, (0, 0, index))

    def _write(c, u, i):
        return jax.lax.dynamic_update_slice(c, u, (0, i))

    return jax.vmap(_write)(slab, cols, index)


def _restack(stack, i, layer_i):
    return jnp.stack(
        [layer_i if j == i else stack[j] for j in range(stack.shape[0])]
    )


def _slice_update_stack(sliced, next_fun, args, kwargs, context):
    """Give each attention call the layer's own arrays, sliced out of the
    stack, and put what it returns back with ``jnp.stack``; ``sliced``
    collects the layers it did that for."""
    cache = kwargs.get("cache")
    if not (isinstance(context.module, Attention) and context.method_name == "__call__"
            and isinstance(cache, (KVCache, PagedKVCache))):
        return next_fun(*args, **kwargs)
    i = kwargs["layer"]
    sliced.append(i)
    kwargs = {**kwargs, "layer": None}
    if isinstance(cache, KVCache):
        out, (k_i, v_i) = next_fun(
            *args, **{**kwargs, "cache": (cache.k[i], cache.v[i], cache.index)}
        )
        return out, cache.replace(
            k=_restack(cache.k, i, k_i), v=_restack(cache.v, i, v_i)
        )
    out, (pk_i, pv_i, sk_i, sv_i, err_i) = next_fun(
        *args, **{**kwargs, "cache": (
            cache.pages_k[i], cache.pages_v[i], cache.k_scales[i],
            cache.v_scales[i], cache.tables, cache.index, cache.active,
        )}
    )
    return out, cache.replace(
        pages_k=_restack(cache.pages_k, i, pk_i),
        pages_v=_restack(cache.pages_v, i, pv_i),
        k_scales=_restack(cache.k_scales, i, sk_i),
        v_scales=_restack(cache.v_scales, i, sv_i),
        quant_err=jnp.maximum(cache.quant_err, err_i),
    )


def _oracle(monkeypatch, model, params, tokens, cache, **kw):
    sliced = []
    with monkeypatch.context() as m:
        m.setattr(tfm, "_write_columns", _old_write)
        with nn.intercept_methods(functools.partial(_slice_update_stack, sliced)):
            out = model.apply({"params": params}, tokens, cache=cache, **kw)
    assert sliced == list(range(model.config.num_layers))
    return out


def _assert_same_bytes(new, old):
    new_leaves, treedef = jax.tree_util.tree_flatten(new)
    old_leaves, old_treedef = jax.tree_util.tree_flatten(old)
    assert treedef == old_treedef
    for a, b in zip(new_leaves, old_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.tobytes() == b.tobytes()


def _both_ways(monkeypatch, model, params, spec, cache):
    """One forward of ``spec["s"]`` tokens a lane through the in-place write
    and through the oracle: ``(logits, cache)`` of each."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (LANES, spec["s"]), 0, 256)
    kw = {}
    if "tree" in spec:
        anc, node_depth = _chain_tree(*spec["tree"])
        kw = dict(tree_mask=anc,
                  positions=cache.index[:, None] + jnp.asarray(node_depth)[None, :])
    new = model.apply({"params": params}, tokens, cache=cache, **kw)
    old = _oracle(monkeypatch, model, params, tokens, cache, **kw)
    return new, old


def _slab_cache(cfg, batch, index, dtype=jnp.float32, seed=1):
    """A cache that already holds something everywhere, so a write that lands
    in the wrong row or layer shows."""
    shape = (cfg.num_layers, batch, cfg.num_kv_heads * cfg.resolved_head_dim, MAX_LEN)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return KVCache(
        k=jax.random.normal(kk, shape, jnp.float32).astype(dtype),
        v=jax.random.normal(kv, shape, jnp.float32).astype(dtype),
        index=jnp.asarray(index, jnp.int32),
    )


def _chain_tree(width, depth):
    """``width`` branches of ``depth`` drafted nodes under one root: the
    ancestor-or-self mask and every node's depth."""
    nodes = 1 + width * depth
    parent = np.zeros(nodes, np.int32)
    node_depth = np.zeros(nodes, np.int32)
    for b in range(width):
        for t in range(depth):
            node = 1 + b * depth + t
            parent[node] = 0 if t == 0 else node - 1
            node_depth[node] = t + 1
    anc = np.eye(nodes, dtype=bool)
    for node in range(1, nodes):
        anc[node] |= anc[parent[node]]
    return anc, node_depth


SLAB_CASES = {
    # scalar index (generate, a prefill chunk), several positions at once
    "scalar_index_chunk": dict(index=5, s=6),
    "scalar_index_bf16_cache": dict(index=0, s=4, dtype=jnp.bfloat16),
    # per-lane index: a decode step, a verify window of K + 1; the last lane
    # is a frozen one whose stale index would run past the end of its slab
    "per_lane_decode": dict(index=[3, 17, MAX_LEN], s=1),
    "per_lane_verify": dict(index=[0, 9, MAX_LEN - 2], s=4),
    "per_lane_tree": dict(index=[2, 11, 20], s=5, tree=(2, 2)),
}


@pytest.mark.parametrize("case", list(SLAB_CASES))
def test_slab_write_matches_slice_update_stack(monkeypatch, case):
    spec = SLAB_CASES[case]
    model, params = _model()
    cfg = model.config
    cache = _slab_cache(cfg, LANES, spec["index"], spec.get("dtype", jnp.float32))
    new, old = _both_ways(monkeypatch, model, params, spec, cache)
    _assert_same_bytes(new, old)
    # and the forward did write
    assert not np.array_equal(np.asarray(new[1].k, np.float32),
                              np.asarray(cache.k, np.float32))


def _paged_cache(cfg, index, active, page_dtype, seed=3):
    num_pages = LANES * PAGES_PER_LANE + 1
    hkv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (cfg.num_layers, num_pages, hkv, PAGE, d)
    kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    if page_dtype is None:
        pages_k = jax.random.normal(kk, shape, jnp.float32)
        pages_v = jax.random.normal(kv, shape, jnp.float32)
        k_scales = v_scales = jnp.ones(shape[:3], jnp.float32)
    else:
        storage, qmax = KV_FORMATS[page_dtype]
        pages_k = jax.random.randint(kk, shape, -qmax, qmax + 1).astype(storage)
        pages_v = jax.random.randint(kv, shape, -qmax, qmax + 1).astype(storage)
        k_scales = jax.random.uniform(ks, shape[:3], jnp.float32, 0.005, 0.02)
        v_scales = k_scales * 1.5
    # lane n owns pages 1 + n * P .. (n + 1) * P; page 0 is the null page
    tables = 1 + jnp.arange(LANES * PAGES_PER_LANE, dtype=jnp.int32).reshape(
        LANES, PAGES_PER_LANE
    )
    return PagedKVCache(
        pages_k=pages_k, pages_v=pages_v, k_scales=k_scales, v_scales=v_scales,
        tables=tables, index=jnp.asarray(index, jnp.int32),
        active=jnp.asarray(active, bool), quant_err=jnp.float32(0.0),
    )


PAGED_CASES = {
    "native_decode": dict(page_dtype=None, index=[3, 17, 9], s=1),
    # a span that crosses a page boundary, and a frozen lane (null page)
    "native_verify_frozen_lane": dict(page_dtype=None, index=[6, 15, 9], s=4,
                                      active=[True, True, False]),
    "native_tree": dict(page_dtype=None, index=[2, 11, 20], s=5, tree=(2, 2)),
    "native_decode_pallas_read": dict(page_dtype=None, index=[3, 17, 9], s=1,
                                      paged_kernel="pallas"),
    "int8_decode": dict(page_dtype="int8", index=[3, 17, 9], s=1),
    "int8_verify_frozen_lane": dict(page_dtype="int8", index=[6, 15, 9], s=4,
                                    active=[True, False, True]),
}


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_paged_write_matches_slice_update_stack(monkeypatch, case):
    spec = PAGED_CASES[case]
    model, params = _model(paged_kernel=spec.get("paged_kernel", "xla"))
    cfg = model.config
    cache = _paged_cache(cfg, spec["index"], spec.get("active", [True] * LANES),
                         spec["page_dtype"])
    new, old = _both_ways(monkeypatch, model, params, spec, cache)
    _assert_same_bytes(new, old)
    assert not np.array_equal(np.asarray(new[1].pages_k, np.float32),
                              np.asarray(cache.pages_k, np.float32))
    if spec["page_dtype"] is not None:
        assert float(new[1].quant_err) > 0.0

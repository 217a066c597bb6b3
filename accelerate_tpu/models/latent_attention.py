"""Multi-head latent attention (MLA, DeepSeek-V2) for :class:`Transformer`.

Queries go through a low-rank bottleneck; keys and values of all heads are
decompressed from ONE latent ``c_kv`` a token, and the rotary part of the key
is ONE vector a token that every head shares.  The cache therefore holds, a
token and layer, the normed latent (``kv_rank`` values) and the roped key
(``rope_dim`` values): 576 values for DeepSeek-V2 against 32,768 for the keys
and values of its 128 heads.  They live in the two arrays every cache has
(``KVCache.k`` = latent rows ``[.., 1, kv_rank]``, ``KVCache.v`` = rope-key
rows ``[.., 1, rope_dim]``: ``TransformerConfig.cache_row_shapes``), so the
slab pool, the page pool and their gather / write-back need no second code
path.

Two forms of the same attention, equal in exact arithmetic:

* **decompressed** — ``[k_nope ; v] = W_UKV c_kv`` for every key, then plain
  attention over ``nope + rope`` wide keys.  Costs ``kv_rank * H * (nope + v)``
  a key once a call, and ``H * (nope + rope + v)`` a query-key pair.
* **absorbed** — ``W_UK`` folded into the query (``q_lat = W_UK^T q_nope``) and
  ``W_UV`` applied after the weighted sum of latents: nothing is decompressed,
  ``H * (2 kv_rank + rope)`` a query-key pair (3.4 x the decompressed pair for
  DeepSeek-V2, and no per-key cost).

Which form runs is decided by the shape of the call (:func:`use_absorbed`):
with a cache and at most :data:`ABSORB_MAX_ROWS` new rows a lane (decode and
verify windows: a few rows against a long context) the absorbed form; without
a cache, and for prefill chunks, the decompressed form, in blocks of keys up
to the last live one so that neither the decompressed keys nor the scores of
the whole ``max_len`` are ever materialised.  A cached chunk whose shapes the
Pallas kernel takes (:func:`~accelerate_tpu.ops.latent_view_attention
.latent_flash_applies`: bfloat16, 128 rows or more, on a TPU) runs the
decompressed form there, with the decompression and the scores in fast memory
(:func:`~accelerate_tpu.ops.latent_view_attention.latent_view_attention`);
:func:`attend_decompressed` serves the rest.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.latent_view_attention import latent_flash_applies, latent_view_attention
from .transformer import (
    KVCache,
    PagedKVCache,
    RMSNorm,
    TransformerConfig,
    _tag_proj,
    _write_rows,
    _yarn_m,
    rope_amplitude,
    rope_frequencies,
)

#: a cached call with at most this many new rows a lane runs absorbed.  The
#: FLOP break-even is ``kv_rank * (nope + v) / (2 kv_rank - nope - v)`` rows
#: (170 for DeepSeek-V2); the line is drawn lower because the decompressed
#: form stops at the last live key and the absorbed one reads the whole view.
ABSORB_MAX_ROWS = 32
#: keys a block of the decompressed form
KEY_BLOCK = 1024


def use_absorbed(cached: bool, new_rows: int) -> bool:
    return cached and new_rows <= ABSORB_MAX_ROWS


def softmax_scale(cfg: TransformerConfig) -> float:
    """``(nope + rope)^-1/2``, times YaRN's ``m(factor, mscale_all_dim)^2``."""
    la = cfg.latent_attention
    scale = (la.nope_dim + la.rope_dim) ** -0.5
    if la.yarn is not None and la.yarn.mscale_all_dim:
        scale *= _yarn_m(la.yarn.factor, la.yarn.mscale_all_dim) ** 2
    return scale


def rope_pairs(x: jax.Array, positions: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Rotary embedding of ``x [B, S, H, rope_dim]`` over pairs ``(2j, 2j+1)``
    (DeepSeek's published layout) with the configuration's frequencies."""
    la = cfg.latent_attention
    angles = positions[..., None].astype(jnp.float32) * rope_frequencies(la.rope_dim, cfg.rope_theta, la.yarn)
    amp = rope_amplitude(la.yarn)
    cos = (jnp.cos(angles) * amp)[:, :, None, :]
    sin = (jnp.sin(angles) * amp)[:, :, None, :]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(xf.shape).astype(x.dtype)


def _masked_softmax_update(carry, scores, mask, values_of):
    """One block of an online softmax: ``scores [B,H,S,K]`` float32, ``mask``
    broadcastable to it, ``values_of(probs) -> [B,S,H,V]``."""
    m_prev, l_prev, acc = carry
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
    # a row with no visible key yet keeps weight 0 for this block
    p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc = acc * jnp.transpose(alpha, (0, 2, 1))[..., None] + values_of(p)
    return m_new, l_new, acc


def attend_decompressed(q_nope, q_pe, latent, k_pe, w_uk, w_uv, q_slots, scale, live_only=True):
    """Decompressed form.  ``q_nope [B,S,H,nope]``, ``q_pe [B,S,H,rope]``;
    ``latent [B,M,kv_rank]`` and ``k_pe [B,M,rope]`` the keys by slot; query
    ``i`` of lane ``b`` sees slots ``j <= q_slots[b, i]``.  ``w_uk
    [kv_rank,H,nope]``, ``w_uv [kv_rank,H,v]``.  Returns ``[B,S,H,v]``.

    Keys are taken in blocks of :data:`KEY_BLOCK`, with ``live_only`` up to the
    last visible slot (a dynamic trip count; without it every block, which
    keeps the loop differentiable): each block is decompressed, scored and
    folded into an online softmax, so the work follows the live context and
    not ``max_len``."""
    b, s, h, _ = q_nope.shape
    m = latent.shape[1]
    dtype = q_nope.dtype
    block = math.gcd(m, KEY_BLOCK)
    if block < 128 or m <= KEY_BLOCK:
        block = m
    v_dim = w_uv.shape[-1]

    def fold(j, carry):
        lat = jax.lax.dynamic_slice_in_dim(latent, j * block, block, axis=1)
        kpe = jax.lax.dynamic_slice_in_dim(k_pe, j * block, block, axis=1)
        k_nope = jnp.einsum("bkc,chd->bkhd", lat, w_uk)
        v = jnp.einsum("bkc,chd->bkhd", lat, w_uv)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_pe, kpe, preferred_element_type=jnp.float32)) * scale
        slots = j * block + jnp.arange(block)
        mask = slots[None, None, None, :] <= q_slots[:, None, :, None]
        return _masked_softmax_update(
            carry, scores, mask,
            lambda p: jnp.einsum("bhqk,bkhd->bqhd", p.astype(dtype), v, preferred_element_type=jnp.float32),
        )

    init = (jnp.full((b, h, s), jnp.finfo(jnp.float32).min, jnp.float32),
            jnp.zeros((b, h, s), jnp.float32), jnp.zeros((b, s, h, v_dim), jnp.float32))
    if block == m:
        _, l, acc = fold(0, init)
    else:
        n_blocks = jnp.max(q_slots) // block + 1 if live_only else m // block
        _, l, acc = jax.lax.fori_loop(0, n_blocks, fold, init)
    return (acc / jnp.transpose(l, (0, 2, 1))[..., None]).astype(dtype)


def attend_absorbed(q_nope, q_pe, latent, k_pe, w_uk, w_uv, q_slots, scale):
    """Absorbed form, same arguments and result as :func:`attend_decompressed`:
    scores against the latent itself, the weighted sum of latents decompressed
    once a query."""
    dtype = q_nope.dtype
    q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk)
    scores = (jnp.einsum("bqhc,bkc->bhqk", q_lat, latent, preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(latent.shape[1])[None, None, None, :] <= q_slots[:, None, :, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1).astype(dtype)
    o_lat = jnp.einsum("bhqk,bkc->bqhc", probs, latent)
    return jnp.einsum("bqhc,chd->bqhd", o_lat, w_uv)


class _Kernel(nn.Module):
    """A projection's ``kernel`` alone, for the one matrix both forms read in
    pieces (``kv_b_proj``: ``W_UK`` and ``W_UV`` side by side, a head at a time)."""

    shape: tuple
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(0.02), self.shape, self.param_dtype)


class LatentAttention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None, tree_mask=None, layer=None):
        """Same contract as :class:`~accelerate_tpu.models.transformer.Attention`
        for the caches a latent model has: none, a stacked :class:`KVCache`
        addressed at ``layer``, or this layer's own ``(latent, k_pe, index)``.
        The new latent (after its norm) and rope key (after its rope) are
        written in place at the cache's ``index`` (:func:`_write_rows`) and
        attention runs over the cache: absorbed for a few new rows a lane,
        decompressed for a chunk (:func:`use_absorbed`)."""
        cfg = self.config
        la = cfg.latent_attention
        if tree_mask is not None or segment_ids is not None:
            raise NotImplementedError("latent attention has no tree-mask or packed-segment form yet")
        if isinstance(cache, PagedKVCache) or (isinstance(cache, tuple) and len(cache) != 3):
            raise NotImplementedError(
                "latent attention reads its cache through the gathered view; the "
                "in-place paged cache (quantised pages, Pallas kernels) is not ported"
            )
        b, s = x.shape[:2]
        h = cfg.num_heads
        dense = lambda name, features: nn.Dense(
            features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02), name=name,
        )
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name=name)
        with jax.named_scope("mla/project"):
            c_q = norm("q_a_norm")(dense("q_a_proj", la.q_rank)(x))
            q = _tag_proj(dense("q_b_proj", h * (la.nope_dim + la.rope_dim))(c_q))
            q = q.reshape(b, s, h, la.nope_dim + la.rope_dim)
            kv_a = _tag_proj(dense("kv_a_proj", la.kv_rank + la.rope_dim)(x))
            latent = norm("kv_a_norm")(kv_a[..., :la.kv_rank])                # [B,S,kv_rank]
            q_nope = q[..., :la.nope_dim]
            q_pe = rope_pairs(q[..., la.nope_dim:], positions, cfg)
            k_pe = rope_pairs(kv_a[..., None, la.kv_rank:], positions, cfg)    # [B,S,1,rope]
            kv_b = _Kernel((la.kv_rank, h * (la.nope_dim + la.v_dim)), cfg.param_dtype,
                           name="kv_b_proj")().astype(cfg.dtype)
            w_ukv = kv_b.reshape(la.kv_rank, h, la.nope_dim + la.v_dim)
            w_uk, w_uv = w_ukv[..., :la.nope_dim], w_ukv[..., la.nope_dim:]
        scale = softmax_scale(cfg)
        new_cache = None
        if cache is None:
            keys, key_pe = latent, k_pe[:, :, 0]
            q_slots = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        else:
            stacked = isinstance(cache, KVCache)
            lat_buf, pe_buf, index = (cache.k, cache.v, cache.index) if stacked else cache
            lat_buf = _write_rows(lat_buf, latent[:, :, None, :], index, layer if stacked else None)
            pe_buf = _write_rows(pe_buf, k_pe, index, layer if stacked else None)
            new_cache = cache.replace(k=lat_buf, v=pe_buf) if stacked else (lat_buf, pe_buf)
            keys = (lat_buf[layer] if stacked else lat_buf)[:, :, 0]
            key_pe = (pe_buf[layer] if stacked else pe_buf)[:, :, 0]
            q_slots = positions
        if use_absorbed(cache is not None, s):
            with jax.named_scope("mla/attend_decode"):
                out = attend_absorbed(q_nope, q_pe, keys, key_pe, w_uk, w_uv, q_slots, scale)
        else:
            with jax.named_scope("mla/attend_prefill"):
                if cache is not None and latent_flash_applies(q_nope, q_pe, keys, kv_b):
                    # the views as the cache holds them: a stacked one's layer is
                    # picked inside the kernel, so none is sliced out for it
                    out = latent_view_attention(q_nope, q_pe, lat_buf[..., 0, :], pe_buf[..., 0, :], kv_b,
                                                q_slots, scale, layer=layer if stacked else None)
                else:
                    out = attend_decompressed(q_nope, q_pe, keys, key_pe, w_uk, w_uv, q_slots, scale,
                                              live_only=cache is not None)
        with jax.named_scope("mla/project"):
            out = _tag_proj(dense("o_proj", cfg.hidden_size)(out.reshape(b, s, h * la.v_dim)))
        return out if cache is None else (out, new_cache)

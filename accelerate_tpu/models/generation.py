"""Autoregressive generation: KV-cache decode loop + sampling.

The reference's only *published* benchmark is token generation — s/token for
big offloaded models (``/root/reference/benchmarks/big_model_inference.py:108-139``,
``benchmarks/README.md:27-37``) — delegated there to ``transformers``'
``model.generate`` over torch modules.  TPU-native generation is instead one
compiled program:

  * the KV cache is a static-shape pytree (:class:`~.transformer.KVCache`)
    updated in place at a *traced* position index, so a single decode
    executable serves every token;
  * the decode loop is ``lax.scan`` inside one ``jit`` — no per-token python,
    no retracing, cache donated so XLA aliases the update buffers;
  * sampling (greedy / temperature / top-k / top-p) is pure ``jnp`` and lives
    inside the same program; EOS early-stop is done by masking (done lanes emit
    ``pad_token_id``) because data-dependent loop exit would break the static
    schedule.

For weights that do not fit in HBM, the same ``decode_step`` shape is driven
per-token by :class:`~accelerate_tpu.big_modeling.StreamingTransformer`, which
streams layer weights host→HBM under the token loop (the AlignDevicesHook
workload, reference ``hooks.py:322-389``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .transformer import KVCache, Transformer, create_cache


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Decode-loop knobs (the transformers ``GenerationConfig`` analog, reduced
    to what a jittable loop can honor)."""

    max_new_tokens: int = 128
    do_sample: bool = False
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0


def sample_tokens(
    logits: jax.Array,
    rng: Optional[jax.Array] = None,
    *,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """``[B, V] logits -> [B] int32 tokens``; jit-safe (static shapes only).

    Greedy unless ``do_sample``; with sampling, temperature then top-k then
    top-p filters apply in the usual order (matching transformers'
    ``LogitsProcessor`` pipeline semantics).
    """
    if not do_sample or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        raise ValueError("do_sample=True needs an rng key")
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    neg_inf = jnp.finfo(jnp.float32).min
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1:]
        logits = jnp.where(logits < kth, neg_inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # a slot is OUTSIDE the nucleus when the mass before it already reaches
        # top_p; the first slot is always kept
        outside = (cum - probs) >= top_p
        min_kept = jnp.min(
            jnp.where(outside, jnp.inf, sorted_desc), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < min_kept, neg_inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def filter_logits_batched(
    logits: jax.Array,
    *,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """Per-lane sampling filters: ``[N, V]`` raw logits + knob *vectors* ->
    filtered fp32 logits (suppressed entries at ``-inf``), temperature then
    top-k then top-p — the same pipeline order as :func:`sample_tokens`.

    Factored out of :func:`sample_tokens_batched` so the serving engine's
    speculative verify window (:func:`~accelerate_tpu.serving.pool.make_paged_verify_window`)
    can apply the Leviathan accept/resample rule against exactly the
    distribution ordinary decode would have sampled from.  ``top_k <= 0`` and
    ``top_p >= 1`` disable their filters per lane.
    """
    v = logits.shape[-1]
    neg_inf = jnp.finfo(jnp.float32).min
    lf = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)[:, None]
    # top-k: kth-largest per lane via one sort; lanes with top_k <= 0 keep all
    sorted_desc = jnp.sort(lf, axis=-1)[:, ::-1]
    kidx = jnp.clip(top_k, 1, v) - 1
    kth = jnp.take_along_axis(sorted_desc, kidx[:, None], axis=-1)
    lf = jnp.where((top_k > 0)[:, None] & (lf < kth), neg_inf, lf)
    # top-p on the (possibly top-k-filtered) logits — same filter order as
    # sample_tokens; second sort because the k-filter changed the tail
    sorted_p = jnp.sort(lf, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_p, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    outside = (cum - probs) >= top_p[:, None]
    min_kept = jnp.min(jnp.where(outside, jnp.inf, sorted_p), axis=-1, keepdims=True)
    return jnp.where((top_p < 1.0)[:, None] & (lf < min_kept), neg_inf, lf)


def sample_tokens_batched(
    logits: jax.Array,
    rngs: jax.Array,
    *,
    do_sample: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """Per-lane sampling: ``[N, V] logits`` + per-lane knob *vectors* -> ``[N]``
    int32 tokens.  The serving engine's analog of :func:`sample_tokens`: one
    executable serves every mix of per-request configs currently occupying the
    slot pool (static knobs would force a retrace per config combination).

    ``rngs`` is ``[N, 2]`` uint32 (one key per lane); ``do_sample`` bool [N];
    ``temperature`` f32 [N]; ``top_k`` int32 [N] (``<= 0`` disables); ``top_p``
    f32 [N] (``>= 1`` disables).  Greedy lanes take ``argmax`` — bitwise the
    same decision :func:`sample_tokens` makes, which is what keeps the
    continuous-batching path token-exact vs ``generate`` for greedy requests.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    use_sample = do_sample & (temperature > 0.0)

    def _sampled(_):
        lf = filter_logits_batched(
            logits, temperature=temperature, top_k=top_k, top_p=top_p
        )
        sampled = jax.vmap(lambda r, row: jax.random.categorical(r, row))(rngs, lf)
        return jnp.where(use_sample, sampled.astype(jnp.int32), greedy)

    # two full-vocab sorts per token are pure waste while every occupied lane
    # is greedy (the common serving mix) — branch at runtime, not trace time
    return jax.lax.cond(jnp.any(use_sample), _sampled, lambda _: greedy, None)


@functools.lru_cache(maxsize=32)
def make_sampler(do_sample: bool = False, temperature: float = 1.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None):
    """Jitted ``(logits [B,V], rng) -> tokens [B]`` for fixed sampling knobs.

    Cached so repeated ``generate`` calls (serving loops, the streaming
    decoder) reuse one executable instead of retracing per call.
    """

    @jax.jit
    def sample(logits, rng):
        return sample_tokens(
            logits, rng, do_sample=do_sample, temperature=temperature,
            top_k=top_k, top_p=top_p,
        )

    return sample


def make_prefill_step(model: Transformer):
    """Jitted ``(params, input_ids, cache) -> (logits, cache)`` over the prompt."""

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(params, input_ids, cache):
        return model.apply({"params": params}, input_ids, cache=cache)

    return prefill


def make_decode_step(model: Transformer):
    """Jitted single-token step ``(params, tokens [B], cache) -> (logits [B,V], cache)``.

    The cache is donated: XLA updates it in place, so per-token cost is the
    weight reads + one cache-line write, not a cache copy.
    """

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode(params, tokens, cache):
        logits, cache = model.apply({"params": params}, tokens[:, None], cache=cache)
        return logits[:, -1], cache

    return decode


@functools.lru_cache(maxsize=32)
def _compiled_generate(model: Transformer, gen: GenerationConfig, prompt_len: int,
                       total_len: int):
    """One fused program: prefill + scan over max_new_tokens decode steps."""

    def run(params, input_ids, cache, rng):
        logits, cache = model.apply({"params": params}, input_ids, cache=cache)
        rng, sub = jax.random.split(rng)
        tok = sample_tokens(
            logits[:, -1], sub, do_sample=gen.do_sample, temperature=gen.temperature,
            top_k=gen.top_k, top_p=gen.top_p,
        )
        done = (
            tok == gen.eos_token_id
            if gen.eos_token_id is not None
            else jnp.zeros(tok.shape, bool)
        )

        def step(carry, _):
            cache, tok, rng, done = carry
            logits, cache = model.apply({"params": params}, tok[:, None], cache=cache)
            rng, sub = jax.random.split(rng)
            nxt = sample_tokens(
                logits[:, -1], sub, do_sample=gen.do_sample,
                temperature=gen.temperature, top_k=gen.top_k, top_p=gen.top_p,
            )
            nxt = jnp.where(done, gen.pad_token_id, nxt)
            if gen.eos_token_id is not None:
                done = done | (nxt == gen.eos_token_id)
            return (cache, nxt, rng, done), nxt

        (cache, _, _, _), rest = jax.lax.scan(
            step, (cache, tok, rng, done), None, length=gen.max_new_tokens - 1
        )
        seq = jnp.concatenate([input_ids, tok[:, None], rest.T.astype(input_ids.dtype)], axis=1)
        return seq, cache

    return jax.jit(run, donate_argnums=(2,))


def generate(
    model: Transformer,
    params,
    input_ids,
    generation_config: Optional[GenerationConfig] = None,
    rng: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    **overrides: Any,
):
    """Generate ``max_new_tokens`` continuations of ``input_ids`` [B, S].

    Returns ``(sequences [B, S + max_new_tokens], cache)``.  Lanes that hit
    ``eos_token_id`` emit ``pad_token_id`` for the remainder (static shapes).
    The whole loop is one cached executable per (model, config, shape) triple.
    """
    gen = generation_config or GenerationConfig()
    if overrides:
        gen = dataclasses.replace(gen, **overrides)
    b, s = input_ids.shape
    total = s + gen.max_new_tokens
    if cache is None:
        cache = create_cache(model.config, b, total)
    else:
        # account for already-written entries: dynamic_update_slice CLAMPS
        # out-of-range writes, which would silently corrupt the cache.  A
        # per-lane index (serving pool) bounds by its furthest lane.
        idx = jax.device_get(cache.index)
        used = int(idx.max()) if getattr(idx, "ndim", 0) else int(idx)
        if used + total > cache.max_len:
            raise ValueError(
                f"cache max_len {cache.max_len} < {used} already written + prompt {s} "
                f"+ max_new_tokens {gen.max_new_tokens}; create the cache with "
                f"max_len >= {used + total}"
            )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _compiled_generate(model, gen, s, cache.max_len)(
        params, jnp.asarray(input_ids), cache, rng
    )

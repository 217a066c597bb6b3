"""Power retention (Buckman, Gelada, Zhang et al., arXiv:2507.04239) for
:class:`Transformer`: a gated linear attention whose kernel is the ``p``-th
power of the scaled dot product, in place of softmax attention in every layer.

For a token ``t`` with query head ``h`` reading key/value head ``j`` (queries
grouped as in grouped-query attention), ``d`` the head width, ``g_t[j] =
sigmoid(a_t[j])`` one gate a key/value head and token (``a = W_g x``), and
``q̂``, ``k̂`` the per-head rms-normed, roped query and key::

    w[t, i] = prod_{s=i+1..t} g_s[j] * (q̂_t[h] . k̂_i[j] / sqrt(d))^p      (i <= t)
    y_t[h]  = sum_i w[t, i] v_i[j] / (sum_i w[t, i] + eps)

With ``p`` even every weight is non-negative.  ``(q . k)^p`` is an inner
product of feature maps, ``phi(q) . phi(k)``, so the sum over the past is a
*state of fixed size* a layer, lane and key/value head, read and rewritten
whole at every token, where softmax attention keeps rows a token::

    S_t = g_t S_{t-1} + phi(k̂_t) v_t^T          [D, d_v]
    z_t = g_t z_{t-1} + phi(k̂_t)                [D]
    y_t = phi(q̂_t)^T S_t / (phi(q̂_t) . z_t + d^(p/2) eps)

That state is the model's whole cache (:class:`StateCache`): no token
dimension, no ``max_len``.  For ``p = 2`` ``phi`` is the symmetric square; it is
laid out here in ``D = (d / 2 + 1) d`` entries (8,320 for ``d = 128``, 65 rows of
128 lanes; the 8,256 distinct products ``x_a x_b, a <= b`` with the 64 pairs at
circular distance ``d / 2`` written twice at weight 1 instead of once at
``sqrt 2``), so that the state tiles the chip's ``(8, 128)`` exactly and ``phi``
is 65 lane rotations and no gather (:func:`phi`).

**Two forms of one layer**, equal in exact arithmetic, chosen by the shape of
the call as latent attention chooses its forms:

* **recurrent** (``retention/step``) — a cached call with one new row a lane
  (a decode step): the three lines above, the state updated in place.  On a
  TPU, at a head width of whole lanes, one Pallas kernel a layer that reads
  the state once (:mod:`accelerate_tpu.ops.retention`); elsewhere XLA, which
  needs three passes (:func:`retention_step_stored`).
* **chunked** (``retention/chunk_intra``, ``retention/chunk_state``) — every
  other call (a full forward, ``generate``'s prompt pass, a prefill chunk), in
  sub-chunks of ``RetentionSpec.chunk`` rows carried by a scan: inside a
  sub-chunk the attention form over its own rows (``b_t = sum_{s<=t} log g_s``,
  weights ``e^{b_t - b_i} (q̂_t . k̂_i)^p``, every exponent ``<= 0``), plus what
  the entering state adds (``e^{b_t} phi(q̂_t)^T S_0``), and the state it leaves
  (``S_C = e^{b_C} S_0 + sum_i e^{b_C - b_i} phi(k̂_i) v_i^T``).

The gate, the state and everything computed from them are float32
(``RetentionSpec.state_dtype`` is what the state is *stored* in); products
against the state run at :data:`STATE_PRECISION`.

Not here: the published checkpoint keeps keys and values up to a switch-over
length and folds them into the state after it.  The state form is exact at
every length, so this module holds the state only.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import flax.struct as struct
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.retention import onepass_applies, retention_step_onepass
from .transformer import RMSNorm, TransformerConfig, _apply_rope, _tag_proj, functools_partial_dense

#: precision of the products against the float32 state (and of the float32
#: products inside a sub-chunk): the state is an accumulator, and a product
#: that rounds it to bfloat16 on the way in reads another state than the one
#: stored.  bfloat16 operands (a bfloat16 model's q̂ . k̂) are not affected.
STATE_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RetentionSpec:
    """Power retention in place of softmax attention (``TransformerConfig.
    retention``; a dict from a config file is accepted).  ``degree`` is the
    power ``p`` of the kernel: 2 (the published layer) or 1 (plain gated linear
    attention, ``phi`` the identity).  ``gate_heads`` is the width of the gate
    projection, one gate a key/value head (``None``: ``num_kv_heads``).
    ``state_dtype`` is the storage type of ``S`` and ``z``; ``chunk`` the rows
    of a sub-chunk of the chunked form; ``eps`` the normaliser's."""

    degree: int = 2
    gate_heads: Optional[int] = None
    state_dtype: Any = "float32"
    chunk: int = 128
    eps: float = 1e-6

    def __post_init__(self):
        if self.degree not in (1, 2):
            raise ValueError(f"retention degree {self.degree}: the feature map is written for 1 and 2")
        if self.chunk < 1:
            raise ValueError(f"retention chunk must be positive, got {self.chunk}")

    @property
    def dtype(self):
        return jnp.dtype(self.state_dtype)


def state_width(head_dim: int, degree: int) -> int:
    """``D``: entries of ``phi`` of one head."""
    if degree == 1:
        return head_dim
    if head_dim % 2:
        raise ValueError(f"the symmetric square is laid out for an even head width, got {head_dim}")
    return (head_dim // 2 + 1) * head_dim


def state_shapes(cfg: TransformerConfig, lanes: int):
    """``(S, z)`` shapes of the whole model's state for ``lanes`` lanes:
    ``[L, lanes, kv_heads, D, head_dim]`` and ``[L, lanes, kv_heads, D]``."""
    d = cfg.resolved_head_dim
    lead = (cfg.num_layers, lanes, cfg.num_kv_heads, state_width(d, cfg.retention.degree))
    return lead + (d,), lead


def phi(x: jax.Array, degree: int = 2) -> jax.Array:
    """Feature map with ``phi(q) . phi(k) == (q . k)^degree`` over the last
    axis, in float32.  Degree 2: row ``r`` of ``d / 2 + 1`` rows is ``c_r * x *
    roll(x, -r)`` with ``c_0 = 1`` (the squares), ``c_r = sqrt 2`` (each pair at
    circular distance ``r`` once) and ``c_{d/2} = 1`` (each such pair twice)."""
    x = x.astype(jnp.float32)
    if degree == 1:
        return x
    d = x.shape[-1]
    half = d // 2
    twice = jnp.concatenate([x, x], axis=-1)
    coef = np.full((half + 1,), math.sqrt(2.0), np.float32)
    coef[0] = coef[half] = 1.0
    rows = [coef[r] * x * twice[..., r:r + d] for r in range(half + 1)]
    return jnp.stack(rows, axis=-2).reshape(x.shape[:-1] + ((half + 1) * d,))


def log_gate(a: jax.Array) -> jax.Array:
    """``log g`` of the gate's pre-activation, float32: ``g = sigmoid(a)``."""
    return jax.nn.log_sigmoid(a.astype(jnp.float32))


def normalise(num: jax.Array, den: jax.Array, eps: float) -> jax.Array:
    """``num / (den + eps)``: the weighted sum over the sum of the weights."""
    return num / (den[..., None] + eps)


class StateCache(struct.PyTreeNode):
    """The cache of a retention model: the recurrent state of every layer and
    lane, and nothing a token.  Threaded through the unrolled layers whole as
    :class:`~accelerate_tpu.models.transformer.KVCache` is: layer ``i`` reads
    and rewrites ``s[i]``, ``z[i]`` in place.

    ``index`` is each lane's position (a scalar, or ``[B]`` in the serving
    pool): rope needs it, the state does not.  ``live [B]`` says how many of
    the call's new rows, from the first, enter each lane's state (``None``:
    all): a frozen lane of a decode window (0) and the padding of a prompt's
    last prefill chunk must leave the state as it was, where a KV cache would
    let them write rows that nobody reads."""

    s: jax.Array                       # [L, B, kv_heads, D, head_dim]
    z: jax.Array                       # [L, B, kv_heads, D]
    index: jax.Array                   # int32: scalar, or [B] per lane
    live: Optional[jax.Array] = None   # [B] int32 leading rows that count

    @classmethod
    def create(cls, config: TransformerConfig, batch_size: int, max_len: Optional[int] = None,
               dtype: Any = None, per_lane_index: bool = False) -> "StateCache":
        del max_len                                      # nothing grows with the context
        s_shape, z_shape = state_shapes(config, batch_size)
        dtype = dtype if dtype is not None else config.retention.dtype
        return cls(s=jnp.zeros(s_shape, dtype), z=jnp.zeros(z_shape, dtype),
                   index=jnp.zeros((batch_size,) if per_lane_index else (), jnp.int32))

    @property
    def max_len(self) -> int:
        """No row limit (``generate`` checks a KV cache's)."""
        return int(np.iinfo(np.int32).max)


def _einsum(spec, *operands):
    return jnp.einsum(spec, *operands, precision=STATE_PRECISION, preferred_element_type=jnp.float32)


def gated(k, log_g, live):
    """``(g_t, phi(k̂_t))`` as the state takes them: ``k [B,Hk,D]`` is
    ``phi(k̂)``, ``log_g [B,Hk]``, ``live [B]`` bool.  A frozen lane decays by 1
    and adds 0, so its state stays as it is."""
    return jnp.where(live[:, None], jnp.exp(log_g), 1.0), jnp.where(live[:, None, None], k, 0.0)


def state_update(k, v, log_g, s, z, live):
    """``S_t = g_t S_{t-1} + phi(k̂_t) v_t^T`` and ``z_t`` alike for one new row
    a lane: ``k [B,Hk,D]`` is ``phi(k̂)``, ``v [B,Hk,d]``, ``log_g [B,Hk]``, ``s
    [B,Hk,D,d]``, ``z [B,Hk,D]`` float32, ``live [B]`` bool (a frozen lane's
    state stays as it is)."""
    gate, pk = gated(k, log_g, live)
    s = gate[..., None, None] * s + pk[..., None] * v.astype(jnp.float32)[..., None, :]
    return s, gate[..., None] * z + pk


def state_read(q, s, z, eps: float):
    """``y_t = phi(q̂_t)^T S_t / (phi(q̂_t) . z_t + eps)``: ``q [B,Hk,G,D]`` is
    ``phi(q̂)``; returns ``[B,Hk,G,d]``."""
    return normalise(_einsum("bhgD,bhDv->bhgv", q, s), _einsum("bhgD,bhD->bhg", q, z), eps)


def retention_step(q, k, v, log_g, s, z, live, degree: int, eps: float):
    """Recurrent form, one new row a lane.  ``q [B,Hk,G,d]``, ``k, v
    [B,Hk,d]``, ``log_g [B,Hk]``, ``s [B,Hk,D,d]``, ``z [B,Hk,D]`` float32,
    ``live [B]`` bool.  Returns ``(y [B,Hk,G,d], s, z)``."""
    s, z = state_update(phi(k, degree), v, log_g, s, z, live)
    return state_read(phi(q, degree), s, z, eps), s, z


def retention_step_stored(q, k, v, log_g, cache, layer: int, degree: int, eps: float,
                          interpret: Optional[bool] = None):
    """:func:`retention_step` on the stacked ``cache``, layer ``layer``'s state
    rewritten in place.  Returns ``(y [B,Hk,G,d], cache)``.  One algorithm, two
    executions, picked by what the call can observe
    (:func:`~accelerate_tpu.ops.retention.onepass_applies`):

    * a float32 state of the symmetric square with a head width of whole lanes,
      on a TPU (or with ``interpret`` given, as the CPU tests do): one Pallas
      kernel a layer that reads the state once, updates it where it lies and
      takes the read-out from the tile it holds
      (:func:`~accelerate_tpu.ops.retention.retention_step_onepass`);
    * anything else (a narrow head, ``degree == 1``, a CPU): XLA, the state
      stored FIRST and the read-out reading what was stored, behind an
      ``optimization_barrier``: three passes over the state.  Left to itself
      the TPU compiler fuses the update into the read-out a second time (two
      readers of the old state), and at ten layers its rematerialisation then
      ran a layer's in-place update twice in one step: every served token
      wrong on the chip, nothing to see at two layers or on the CPU (PERF.md
      section 6, PR 33; ``tests/test_tpu_compile.py`` compiles the ten-layer
      window and looks for it)."""
    alive = jnp.ones(q.shape[:1], bool) if cache.live is None else cache.live > 0
    if onepass_applies(cache.s, degree, interpret):
        gate, pk = gated(phi(k, degree), log_g, alive)
        num, den, s, z = retention_step_onepass(phi(q, degree), pk, v, gate, cache.s, cache.z, layer,
                                                interpret=interpret)
        return normalise(num, den, eps), cache.replace(s=s, z=z)
    s, z = state_update(phi(k, degree), v, log_g, cache.s[layer].astype(jnp.float32),
                        cache.z[layer].astype(jnp.float32), alive)
    stored = jax.lax.optimization_barrier((cache.s.at[layer].set(s.astype(cache.s.dtype)),
                                           cache.z.at[layer].set(z.astype(cache.z.dtype))))
    cache = cache.replace(s=stored[0], z=stored[1])
    y = state_read(phi(q, degree), cache.s[layer].astype(jnp.float32), cache.z[layer].astype(jnp.float32), eps)
    return y, cache


def retention_chunk(q, k, v, log_g, rows, s, z, degree: int, eps: float):
    """Chunked form over one sub-chunk of ``C`` rows entering with ``(s, z)``.
    ``q [B,C,Hk,G,d]``, ``k, v [B,C,Hk,d]``, ``log_g [B,C,Hk]``, ``rows [B,C]``
    bool (a row that is padding, or a frozen lane's, neither decays the state
    nor adds to it, and no row sees it as a key).  Returns ``(y, s, z)``."""
    c = q.shape[1]
    log_g = jnp.where(rows[..., None], log_g, 0.0)
    k = jnp.where(rows[..., None, None], k, 0).astype(k.dtype)
    with jax.named_scope("retention/chunk_intra"):
        b = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)                    # [B,Hk,C]
        causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        decay = jnp.exp(jnp.where(causal, b[..., :, None] - b[..., None, :], -jnp.inf))   # [B,Hk,C,C]
        scores = _einsum("bthgd,bihd->bhgti", q, k)
        weights = decay[:, :, None] * (scores ** degree if degree > 1 else scores)
        num = _einsum("bhgti,bihv->bthgv", weights, v.astype(jnp.float32))
        den = jnp.sum(weights, axis=-1).transpose(0, 3, 1, 2)               # [B,C,Hk,G]
    with jax.named_scope("retention/chunk_state"):
        enter = jnp.exp(b).transpose(0, 2, 1)                               # [B,C,Hk]: e^{b_t}
        pq = phi(q, degree) * enter[..., None, None]
        num = num + _einsum("bthgD,bhDv->bthgv", pq, s)
        den = den + _einsum("bthgD,bhD->bthg", pq, z)
        total = b[..., -1]                                                  # [B,Hk]: b_C
        leave = jnp.exp(total[..., None] - b).transpose(0, 2, 1)            # [B,C,Hk]: e^{b_C - b_i}
        pk = phi(k, degree) * leave[..., None]
        carry = jnp.exp(total)
        s = carry[..., None, None] * s + _einsum("bihD,bihv->bhDv", pk, v.astype(jnp.float32))
        z = carry[..., None] * z + jnp.sum(pk, axis=1)
    return normalise(num, den, eps), s, z


def retention_chunked(q, k, v, log_g, rows, s, z, degree: int, eps: float, chunk: int):
    """:func:`retention_chunk` over ``T`` rows in sub-chunks of ``chunk``
    (the last one padded with rows that do not count), the state carried by a
    scan.  Shapes as there with ``T`` for ``C``."""
    t = q.shape[1]
    if t <= chunk:
        return retention_chunk(q, k, v, log_g, rows, s, z, degree, eps)
    n = -(-t // chunk)
    pad = n * chunk - t

    def split(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape((a.shape[0], n, chunk) + a.shape[2:]).swapaxes(0, 1)

    def body(carry, xs):
        y, s, z = retention_chunk(*xs, *carry, degree, eps)
        return (s, z), y

    (s, z), y = jax.lax.scan(body, (s, z), tuple(split(a) for a in (q, k, v, log_g, rows)))
    y = y.swapaxes(0, 1).reshape((q.shape[0], n * chunk) + y.shape[3:])
    return y[:, :t], s, z


class PowerRetention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None, tree_mask=None, layer=None):
        """Same contract as :class:`~accelerate_tpu.models.transformer.Attention`
        for the caches a retention model has: none (the state starts at zero),
        or a :class:`StateCache` addressed at the static ``layer``, which comes
        back with ``s[layer]`` and ``z[layer]`` rewritten."""
        cfg = self.config
        spec = cfg.retention
        if tree_mask is not None or segment_ids is not None:
            raise NotImplementedError("power retention has no tree-mask or packed-segment form")
        if cache is not None and not isinstance(cache, StateCache):
            raise NotImplementedError("a retention layer's cache is a StateCache: it keeps no rows a token")
        b, t = x.shape[:2]
        d, hk = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // hk
        dense = functools_partial_dense(cfg, use_bias=False)
        with jax.named_scope("retention/project"):
            q = _tag_proj(dense("q_proj", cfg.num_heads * d)(x)).reshape(b, t, cfg.num_heads, d)
            k = _tag_proj(dense("k_proj", hk * d)(x)).reshape(b, t, hk, d)
            v = _tag_proj(dense("v_proj", hk * d)(x)).reshape(b, t, hk, d)
            if cfg.qk_norm:
                q = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="q_norm")(q)
                k = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="k_norm")(k)
            q = _apply_rope(q, positions, cfg).reshape(b, t, hk, groups, d)
            k = _apply_rope(k, positions, cfg)
        with jax.named_scope("retention/gate"):
            log_g = log_gate(dense("g_proj", spec.gate_heads or hk)(x))            # [B,T,Hk]
        eps = spec.eps * d ** (spec.degree / 2)
        if cache is not None and t == 1:
            with jax.named_scope("retention/step"):
                y, cache = retention_step_stored(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], cache, layer,
                                                 spec.degree, eps)
                y = y[:, None]
        else:
            if cache is None:
                s_shape, z_shape = state_shapes(cfg, b)
                s, z = jnp.zeros(s_shape[1:], jnp.float32), jnp.zeros(z_shape[1:], jnp.float32)
                rows = jnp.ones((b, t), bool)
            else:
                s, z = cache.s[layer].astype(jnp.float32), cache.z[layer].astype(jnp.float32)
                rows = jnp.ones((b, t), bool) if cache.live is None else jnp.arange(t)[None, :] < cache.live[:, None]
            y, s, z = retention_chunked(q, k, v, log_g, rows, s, z, spec.degree, eps, spec.chunk)
            if cache is not None:
                cache = cache.replace(s=cache.s.at[layer].set(s.astype(cache.s.dtype)),
                                      z=cache.z.at[layer].set(z.astype(cache.z.dtype)))
        with jax.named_scope("retention/project"):
            out = _tag_proj(dense("o_proj", cfg.hidden_size)(y.astype(cfg.dtype).reshape(b, t, cfg.num_heads * d)))
        return out if cache is None else (out, cache)

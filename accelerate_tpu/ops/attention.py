"""Attention dispatch: one entry point, multiple TPU implementations.

The reference delegates fused attention to its CUDA backends (Megatron fused
kernels, ``utils/megatron_lm.py``); here the implementations are:

  - ``"xla"``: ``jax.nn.dot_product_attention`` — XLA's fused attention path
    (flash-attention-style tiling on TPU via Mosaic when available).
  - ``"blocked"``: causal-blocked attention at the XLA level — the query axis
    is split into static chunks and chunk ``i`` contracts only against keys
    ``[0, (i+1)*chunk)``, so the masked upper triangle is never computed.
    Halves attention matmul FLOPs *and* the S^2 logits bandwidth vs ``"xla"``
    (which materializes the full square), keeps GQA KV heads unexpanded, and
    needs no custom kernel: on a v5e at seq 2048 / GQA 32:4 / head-dim 64 it
    out-ran XLA's path, the in-tree pallas flash, and splash attention (an
    earlier round's sweep; not measured on today's code).
  - ``"pallas"``: hand-written flash attention kernel (``ops/flash_attention.py``).
  - ``"ring"``: sequence-parallel ring attention over an ``sp`` mesh axis
    (``parallel/ring_attention.py``) — net-new capability vs the reference
    (SURVEY §5.7: long context is absent upstream).

All take ``[batch, seq, heads, head_dim]`` (BSHD) tensors.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def causal_mask(q_len: int, kv_len: int, dtype=jnp.float32) -> jax.Array:
    """Additive causal mask of shape [q_len, kv_len] (0 keep / -inf drop)."""
    i = jnp.arange(q_len)[:, None]
    j = jnp.arange(kv_len)[None, :]
    offset = kv_len - q_len
    return jnp.where(j <= i + offset, 0.0, jnp.finfo(dtype).min).astype(dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    implementation: str = "xla",
    segment_ids: Optional[jax.Array] = None,
    ring_layout: str = "contiguous",
    window: Optional[int] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """BSHD attention. GQA supported (k/v may have fewer heads than q).

    ``window`` enables sliding-window attention (Mistral-family,
    ``config.sliding_window``): query ``i`` sees keys ``j`` with
    ``i - window < j <= i`` — the causal band of width ``window`` including
    self.  ``bias`` is an additive pre-softmax logits bias broadcastable to
    ``[B, H, Q, K]`` (alibi position penalties).  Both are currently the
    ``"xla"`` implementation only and compose with ``segment_ids``.
    """
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires causal=True")
        if implementation != "xla":
            raise NotImplementedError(
                f"window (sliding-window attention) is implemented for "
                f"implementation='xla' only, got {implementation!r}."
            )
    if bias is not None and implementation != "xla":
        raise NotImplementedError(
            f"bias (alibi) is implemented for implementation='xla' only, "
            f"got {implementation!r}."
        )
    if implementation == "pallas":
        from .flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale, segment_ids=segment_ids)
    if implementation == "blocked":
        if not causal:
            raise ValueError(
                "implementation='blocked' is a causal-only schedule (its win is "
                "skipping the masked upper triangle); use 'xla' for bidirectional "
                "attention."
            )
        return blocked_causal_attention(q, k, v, scale=scale, segment_ids=segment_ids)
    if implementation == "ring":
        # Sequence-parallel path: shard_map ring over the active mesh's `sp`
        # axis.  The mesh comes from the process state (set by Accelerator /
        # PartialState); with no sp axis present, plain attention computes the
        # same thing without the ring machinery, so fall through to XLA.
        from ..state import PartialState, is_initialized

        if not is_initialized():
            raise ValueError(
                "attention_impl='ring' needs the active mesh: construct "
                "Accelerator/PartialState (with an sp-axis mesh) before the "
                "forward, or call parallel.ring_attention_sharded(q, k, v, mesh) "
                "directly with an explicit mesh."
            )
        mesh = PartialState().mesh
        from ..parallel.mesh import mesh_axis_size, sp_shardable

        if sp_shardable(mesh, q.shape[0], q.shape[1]):
            from ..parallel.ring_attention import ring_attention_sharded

            return ring_attention_sharded(
                q, k, v, mesh,
                causal=causal, scale=scale, segment_ids=segment_ids,
                layout=ring_layout,
            )
        sp = mesh_axis_size(mesh, "sp")
        if sp > 1 and (q.shape[1] % sp != 0 or q.shape[0] > 1):
            # A forward on an sp mesh that cannot shard would leave every sp
            # device replicating the whole computation for the entire run —
            # the silent-waste trap the trainer's sp guard exists to prevent.
            # Sequence divisibility always raises (init probes share the real
            # seq, so a bad seq fails loudly at init too); only batch-1 shapes
            # with a GOOD seq fall through (model.init probes on a dp+sp mesh).
            raise ValueError(
                f"attention_impl='ring' on an sp={sp} mesh requires seq "
                f"divisible by sp and batch divisible by the data axes; got "
                f"batch={q.shape[0]}, seq={q.shape[1]}. Pad the sequence (or "
                "drop sp_degree) — falling back would silently replicate "
                "compute across the sp devices."
            )
        if sp > 1:
            # batch-1 with data axes >1: init shape probes land here (model.init
            # uses batch 1 on a dp+sp mesh), but so does a REAL batch-1
            # eval/generation forward — which would replicate the whole
            # computation across the sp devices for the entire run.  The two
            # are indistinguishable at trace time, so warn once instead of
            # raising (raising would break init on every dp+sp mesh).
            from ..logging import get_logger

            get_logger(__name__).warning_once(
                f"attention_impl='ring' on an sp={sp} mesh got a batch-1 forward "
                "that cannot shard over the data axes; computing UNSHARDED "
                "attention (replicated across the sp devices). Harmless for "
                "model.init shape probes — but if this is a real batch-1 "
                "eval/generation run, the sp devices are doing redundant work: "
                "use a batch divisible by the data axes or drop sp_degree."
            )
        # no sp axis / shape probes: the unsharded path computes the same result
        implementation = "xla"

    # XLA path: grouped-query handled by repeating kv heads.
    n_q_heads, n_kv_heads = q.shape[2], k.shape[2]
    if n_kv_heads != n_q_heads:
        rep = n_q_heads // n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    mask = None
    if segment_ids is not None:
        # packed sequences: tokens attend only within their own segment
        mask = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None, :, :]
    if window is not None:
        # banded causal: i - j < window (the causal half rides is_causal below)
        i = jnp.arange(q.shape[1])[:, None]
        j = jnp.arange(k.shape[1])[None, :]
        band = ((i - j) < window)[None, None, :, :]
        mask = band if mask is None else (mask & band)
    try:
        return jax.nn.dot_product_attention(
            q, k, v, bias=bias, mask=mask, is_causal=causal, scale=scale,
            implementation=None,
        )
    except TypeError:  # older signature
        return _reference_attention(
            q, k, v, causal=causal, scale=scale, mask=mask, bias=bias
        )


def blocked_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    chunk: int = 256,
) -> jax.Array:
    """Causal attention that never computes the masked upper triangle.

    BSHD in/out.  The query axis is split into ``S/chunk`` static chunks
    (python-unrolled, so every slice is static-shape); chunk ``i`` contracts
    against keys ``[0, (i+1)*chunk)`` only.  Relative to the full-square XLA
    einsum this halves both the score-matmul FLOPs and the fp32 logits HBM
    traffic — on bandwidth-bound TPU attention that is ~2x.  GQA folds the
    query-head groups into the einsum (``bqgrd,bkgd->bgrqk``) so K/V are
    contracted unexpanded.  Softmax statistics are fp32.

    Only the diagonal block needs a triangular mask; earlier key blocks are
    fully visible — the mask work (iota/compare/where over [chunk, chunk])
    is O(S*chunk) instead of O(S^2).
    """
    b, s, n_q, d = q.shape
    n_kv = k.shape[2]
    rep = n_q // n_kv
    scale = scale if scale is not None else d**-0.5
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"blocked attention needs seq {s} divisible by chunk {chunk}")
    # [B, S, Hkv, rep, D] query groups; K/V stay [B, S, Hkv, D]
    qg = q.reshape(b, s, n_kv, rep, d)
    neg = jnp.finfo(jnp.float32).min
    diag_mask = jnp.where(
        jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :], 0.0, neg
    )  # [chunk, chunk] additive
    outs = []
    for i in range(s // chunk):
        lo, hi = i * chunk, (i + 1) * chunk
        qi = qg[:, lo:hi]                      # [B, c, Hkv, rep, D]
        ki = k[:, :hi]                         # [B, hi, Hkv, D]
        vi = v[:, :hi]
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qi, ki, preferred_element_type=jnp.float32
        ) * scale                              # [B, Hkv, rep, c, hi]
        # causal: keys < lo are fully visible; only the trailing diagonal
        # block is triangular (mask work is O(S*chunk), not O(S^2))
        logits = jnp.concatenate(
            [logits[..., :lo], logits[..., lo:] + diag_mask], axis=-1
        )
        if segment_ids is not None:
            seg_mask = (
                segment_ids[:, lo:hi, None] == segment_ids[:, None, :hi]
            )[:, None, None, :, :]
            logits = jnp.where(seg_mask, logits, neg)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", probs, vi))
    return jnp.concatenate(outs, axis=1).reshape(b, s, n_q, d)


def _reference_attention(q, k, v, *, causal: bool, scale: Optional[float], mask=None,
                         bias=None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if causal:
        logits = logits + causal_mask(q.shape[1], k.shape[1], logits.dtype)[None, None]
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

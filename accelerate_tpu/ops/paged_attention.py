"""Paged decode attention: a Pallas kernel that reads KV straight from the page pool.

The serving engine's paged windows (:mod:`accelerate_tpu.serving.pool`) keep
every lane's KV in a shared refcounted page pool ``[num_pages, Hkv, page, D]``
addressed through per-lane block tables.  The kv-head axis sits OUTSIDE the
page: Mosaic tiles the last two axes of a block, so a ``(page, D)`` tile per
(page, kv-head) is the block the TPU compiler accepts.  PR 6 ran attention by
*gathering* each lane's pages into a contiguous slab-width view — bitwise-identical logits,
but every decode step moves ``pages_per_lane * page`` KV rows per lane through
HBM even when the lane holds three tokens.  This module removes the gather:

* :func:`paged_attention` — the Mosaic/pallas kernel.  Block tables and lane
  lengths ride in as *scalar prefetch* operands, so the BlockSpec index maps
  dereference ``tables[lane, p]`` and the pipeline fetches each KV page
  **in place** — one grid program per (lane, kv-head) marching over that
  lane's pages, online softmax (flash-style m/l/acc carry) over *valid* pages
  only.  Dead table slots hold the null page, whose repeated block index the
  pipeline does not re-fetch, and ``pl.when`` skips their compute: no
  full-width gather, no padding reads.  GQA folds the ``rep`` query heads
  sharing a KV head into the row dimension (same trick as
  :mod:`.flash_attention`).  ``interpret=`` runs the identical kernel on CPU —
  the tier-1 testing discipline.
* :func:`paged_flash_prefill` — the prefill-side twin: chunk-wide queries
  walk the same scalar-prefetched block tables with a flash online softmax,
  q-blocked with each block's page walk cut at its causal frontier, so a
  prefill chunk reads prior pages in place instead of the gather/scatter
  round-trip.  :func:`paged_flash_prefill_reference` is its pure-XLA oracle.
* :func:`paged_attention_reference` — pure-XLA oracle and fallback: a
  live-masked page gather (the satellite fix — dead table slots gather the
  null page instead of whole stale pages) feeding the exact
  ``cached_attention`` program, so the native-dtype reference stays bitwise
  identical to the slab pool.
* :func:`paged_insert` / :func:`paged_quantized_insert` — the scatter-time
  write path.  Quantized pages (int8, or fp8-e4m3 via the :mod:`.fp8` format
  constants) store one f32 scale per (page, kv-head), written at scatter time:
  each touched page is dequantized, the new rows inserted, positions past the
  lane's write frontier zeroed (realloc'd pages carry a previous owner's
  garbage, which must not inflate the scale), and the page requantized against
  its own fresh amax.  When the page's amax is unchanged the old entries
  round-trip exactly (they are integer multiples of the unchanged scale), so
  repeated touches do not accumulate drift.  Both take one layer's pool, or
  the whole stack ``[L, num_pages, Hkv, page, D]`` with a static ``layer``:
  the model's unrolled forward carries the stack through its layers and each
  insert writes at ``[layer, ...]`` in place.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    DEFAULT_MASK_VALUE,
    NUM_LANES,
    _default_interpret,
    pick_block_divisor,
)
from .fp8 import E4M3_MAX

#: reserved garbage-sink page id — must match ``serving.paging.NULL_PAGE``
NULL_PAGE = 0

#: quantized KV storage formats: jnp dtype + the largest representable
#: magnitude the per-page scale maps each head's amax onto
KV_FORMATS = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, E4M3_MAX),
}


def kv_storage_dtype(kv_dtype: Optional[str], native):
    """Resolve a ``ServingEngine(kv_dtype=...)`` string to the page dtype.
    ``None`` keeps the model's native KV dtype (the token-identical path)."""
    if kv_dtype is None:
        return jnp.dtype(native)
    if kv_dtype == "bf16":
        return jnp.dtype(jnp.bfloat16)
    if kv_dtype in KV_FORMATS:
        return jnp.dtype(KV_FORMATS[kv_dtype][0])
    raise ValueError(
        f"unknown kv_dtype {kv_dtype!r}; choose None, 'bf16', 'int8' or 'fp8'"
    )


def kv_qmax(dtype) -> Optional[float]:
    """The quantization ceiling for a page dtype; None for direct-store dtypes."""
    for d, qmax in KV_FORMATS.values():
        if jnp.dtype(dtype) == jnp.dtype(d):
            return qmax
    return None


def resolve_paged_kernel(kernel: str, mesh=None, tp_axis: str = "tp",
                         role: str = "decode") -> str:
    """Shard-aware kernel check: the Pallas grid reads whole ``(kv-head,
    page)`` tiles of an unsharded pool, so under a tensor-parallel mesh
    (tp > 1) a requested ``"pallas"`` cannot be honoured and RAISES — it is
    never swapped for the XLA reference behind the caller's back.  tp=1
    meshes (and no mesh at all) keep the requested kernel; ``"xla"`` (whose
    einsum partitions head-parallel under GSPMD) passes everywhere.

    ``role`` names which pool program is being resolved — ``"decode"``
    (:func:`paged_attention`), ``"prefill"`` (:func:`paged_flash_prefill`) or
    ``"tree_verify"`` (the decode kernel carrying a token-tree ancestor mask
    for speculative tree verification).  All walk the same head-sharded page
    pool through the same scalar-prefetched block tables, so the condition is
    identical; the arms exist so no caller can route any of them around the
    sharding check."""
    if role not in ("decode", "prefill", "tree_verify"):
        raise ValueError(f"unknown paged-kernel role {role!r}")
    if kernel == "pallas" and mesh is not None:
        tp = mesh.shape[tp_axis] if tp_axis in mesh.axis_names else 1
        if tp > 1:
            raise ValueError(
                f"{role} kernel 'pallas' is single-chip: it addresses an "
                f"unsharded page pool and cannot run under {tp_axis}={tp}; "
                f"ask for the 'xla' kernel on a tensor-parallel mesh"
            )
    return kernel


def _lane_scales(tables, k_scales, v_scales, quantized: bool):
    """The kernels' scale operands: each ``[NP, Hkv]`` table gathered through
    the block tables into per-lane rows ``[N, Hkv, 1, P]``, so one VMEM block
    per (lane, kv-head) carries every page's scale.  Native-dtype pools pass
    no scale operands at all."""
    if not quantized:
        return ()
    return tuple(sc[tables].transpose(0, 2, 1)[:, :, None, :]
                 for sc in (k_scales, v_scales))


def _live_pages(lengths: jax.Array, s: int, page: int) -> jax.Array:
    """Pages holding any key visible to this call's queries: keys
    ``0 .. lengths + s - 1`` (the ``s`` new positions included)."""
    return (lengths + s - 1) // page + 1


# ------------------------------------------------------------------- writes
def paged_insert(pages, new, tables, index, active, layer=None):
    """Scatter ``new [N, S, H, D]`` into ``pages [NP, H, page, D]`` at
    positions ``index[n] .. index[n] + S - 1`` through lane ``n``'s block
    table.  Inactive lanes are rerouted to the null page — a lane mid-prefill
    has real (possibly shared) pages mapped and a stale index that must never
    trample them.  Values are cast to the page dtype exactly as the slab pool
    casts into its cache, so native-dtype storage stays bitwise identical.

    With a static ``layer`` the pool is the whole stack ``[L, NP, H, page,
    D]`` and the same ``N * S`` rows are written at ``[layer, ...]``: the
    model's unrolled forward carries the stack through its layers and no
    layer's pool is sliced out or stacked back (a slice -> insert -> stack
    round trip compiles to copies of the whole pool on every forward)."""
    n, s, h, d = new.shape
    lead = () if layer is None else (layer,)
    page = pages.shape[-2]
    p_max = tables.shape[1] - 1
    pos = index[:, None] + jnp.arange(s)[None, :]                    # [N, S]
    pid = jnp.take_along_axis(tables, jnp.clip(pos // page, 0, p_max), axis=1)
    pid = jnp.where(active[:, None], pid, NULL_PAGE)
    off = pos % page
    # advanced indices split by a slice: the indexed rows lead, [N*S, H, D]
    return pages.at[(*lead, pid.reshape(-1), slice(None), off.reshape(-1))].set(
        new.astype(pages.dtype).reshape(n * s, h, d)
    )


def paged_quantized_insert(pages, scales, new, tables, index, active,
                           layer=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantized scatter: requantize every page the ``S`` new positions touch.

    ``pages [NP, H, page, D]`` (int8 / fp8-e4m3), ``scales [NP, H]`` f32 with
    ``dequant = pages * scales``.  Returns ``(pages, scales, max_abs_err)``
    where the error is the largest round-trip quantization error over the
    newly written values — the measurable upper bound the engine exposes as
    ``serve/kv_quant_error``.

    Per touched page: dequantize, insert the new rows, zero every slot at or
    past the lane's pre-call frontier that is not written now (stale KV from a
    rolled-back speculation or a page's previous owner must not pollute the
    amax), recompute the per-head scale from the page's own amax, requantize.
    Writes for inactive lanes (and slots past each lane's touched span) are
    rerouted to the null page.

    With a static ``layer``, ``pages`` and ``scales`` are the whole stacks
    ``[L, NP, H, page, D]`` / ``[L, NP, H]``: the touched pages are read from
    and written back to ``[layer, ...]`` in place (see :func:`paged_insert`).
    """
    qmax = kv_qmax(pages.dtype)
    if qmax is None:
        raise ValueError(f"pages dtype {pages.dtype} is not a quantized KV format")
    n, s, h, d = new.shape
    lead = () if layer is None else (layer,)
    page = pages.shape[-2]
    p_max = tables.shape[1] - 1
    t = (s + page - 2) // page + 1              # max pages a span of S can touch
    p0 = index // page
    pt = p0[:, None] + jnp.arange(t)[None, :]                        # [N, T]
    last = (index + s - 1) // page
    touched = (pt <= last[:, None]) & active[:, None]
    pid = jnp.take_along_axis(tables, jnp.clip(pt, 0, p_max), axis=1)
    pid = jnp.where(touched, pid, NULL_PAGE)                         # [N, T]

    old = (pages[(*lead, pid)].astype(jnp.float32)
           * scales[(*lead, pid)][..., None, None])
    g = pt[:, :, None] * page + jnp.arange(page)[None, None, :]      # [N, T, page]
    i_new = g - index[:, None, None]
    use_new = ((i_new >= 0) & (i_new < s))[:, :, None, :, None]
    gathered = jnp.take_along_axis(
        new.astype(jnp.float32), jnp.clip(i_new, 0, s - 1).reshape(n, t * page)[:, :, None, None],
        axis=1,
    ).reshape(n, t, page, h, d).transpose(0, 1, 3, 2, 4)        # [N, T, H, page, D]
    # valid history, strictly pre-frontier
    keep_old = (g < index[:, None, None])[:, :, None, :, None]
    content = jnp.where(use_new, gathered, jnp.where(keep_old, old, 0.0))
    amax = jnp.max(jnp.abs(content), axis=(3, 4))                    # [N, T, H]
    new_scales = jnp.maximum(amax, 1e-8) / qmax
    q = content / new_scales[..., None, None]
    if jnp.dtype(pages.dtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    q = q.astype(pages.dtype)
    err = jnp.max(
        jnp.where(
            use_new,
            jnp.abs(q.astype(jnp.float32) * new_scales[..., None, None] - content),
            0.0,
        )
    )
    flat = pid.reshape(-1)
    pages = pages.at[(*lead, flat)].set(q.reshape(n * t, h, page, d))
    scales = scales.at[(*lead, flat)].set(new_scales.reshape(n * t, h))
    return pages, scales, err


# ------------------------------------------------------------------ reference
def paged_attention_reference(q, pages_k, pages_v, tables, lengths,
                              k_scales=None, v_scales=None, window=None,
                              alibi: bool = False, tree_mask=None):
    """Pure-XLA oracle/fallback: live-masked gather + the slab attention math.

    ``q [N, S, Hq, D]`` against pages ``[NP, Hkv, page, D]`` through
    ``tables [N, P]``; query ``i`` of lane ``n`` sits at position
    ``lengths[n] + i`` and sees keys ``j <= lengths[n] + i`` (the new
    positions' KV must already be inserted).  Table slots past each lane's
    live page count gather the null page instead of whole stale pages — the
    gather moves only pages that can contain visible keys, and since masked
    positions never reach the softmax the native-dtype output is bitwise
    identical to the full gather (and so to the slab pool).

    ``tree_mask`` (``[S, S]`` ancestor-or-self constant) switches the row
    mask to token-tree visibility for speculative tree verification: the
    ``S`` queries are tree *nodes* written at slots ``lengths[n] ..
    lengths[n] + S - 1``, each seeing committed history plus its own
    root-to-self chain.  The live-page arithmetic is unchanged — all tree
    slots fall inside the same ``lengths + S - 1`` frontier a linear verify
    window spans."""
    from ..models.transformer import cached_attention

    n, s, _, d = q.shape
    num_p = tables.shape[1]
    hkv, page = pages_k.shape[1:3]
    live = _live_pages(lengths, s, page)
    t = jnp.where(jnp.arange(num_p)[None, :] < live[:, None], tables, NULL_PAGE)
    k = pages_k[t]                                    # [N, P, Hkv, page, D]
    v = pages_v[t]
    if k_scales is not None:
        k = (k.astype(jnp.float32) * k_scales[t][..., None, None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * v_scales[t][..., None, None]).astype(q.dtype)
    else:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    # [N, Hkv*D, M]: a per-head KVCache layer, positions minor (the pool's own
    # order on the chip: a move of whole (D, page) tiles)
    k = k.transpose(0, 2, 4, 1, 3).reshape(n, hkv * d, num_p * page)
    v = v.transpose(0, 2, 4, 1, 3).reshape(n, hkv * d, num_p * page)
    q_positions = lengths[:, None] + jnp.arange(s)[None, :]
    return cached_attention(q, k, v, q_positions, window=window, alibi=alibi,
                            tree_mask=tree_mask)


# --------------------------------------------------------------------- kernel
def _page_scale(scale_ref, p):
    """Lane ``p`` of a ``(1, 1, 1, P)`` per-lane scale row as a ``(1, 1)``
    array.  The row sits in VMEM (Mosaic refuses ``(1, 1)`` SMEM blocks over a
    2-D table), where a dynamic lane index is a select-and-reduce."""
    row = scale_ref[0, 0]                                      # [1, P]
    at_p = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == p
    return jnp.sum(jnp.where(at_p, row, 0.0), axis=1, keepdims=True)


def _paged_attn_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *rest,
                       page: int, s_len: int, scale: float, quantized: bool,
                       tree_words=None):
    """One (lane, kv-head, page) step of the online softmax.

    Row ``r`` of the folded query block holds query head ``h * rep + r //
    s_len`` at sequence position ``lengths[lane] + r % s_len``.  The page loop
    is the innermost grid dimension, so m/l/acc VMEM scratch carries across
    it; pages at or past the lane's live count are skipped (their block index
    degenerates to the null page, which the pipeline fetched at most once).

    ``tree_words`` (a tuple of ``s_len`` Python ints — node ``i``'s uint32
    ancestor word) switches the causal row mask to token-tree visibility:
    bit ``j`` of node ``i``'s word says whether ``i`` may see tree node ``j``
    (ancestor-or-self), where node ``j`` occupies slot ``lengths[lane] + j``.
    The words are baked in as SCALAR immediates (Pallas rejects captured
    array constants) and selected per query row by an iota-compare chain —
    at most 32 selects, folded at compile time.  History slots
    (``j < length``) stay visible to every node — the page walk and online
    softmax are untouched, only the mask predicate changes."""
    ks_ref, vs_ref = rest[:2] if quantized else (None, None)
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    lane, p = pl.program_id(0), pl.program_id(2)
    n_p = pl.num_programs(2)
    gs = acc_ref.shape[0]
    head_dim = acc_ref.shape[-1]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[lane]
    live = (length + s_len - 1) // page + 1

    @pl.when(p < live)
    def _compute():
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if quantized:
            k = k.astype(jnp.float32) * _page_scale(ks_ref, p)
            v = v.astype(jnp.float32) * _page_scale(vs_ref, p)
        q = q_ref[0, 0].astype(jnp.float32) * scale
        s = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [GS, page]
        j = p * page + jax.lax.broadcasted_iota(jnp.int32, (gs, page), 1)
        if tree_words is None:
            qi = jax.lax.broadcasted_iota(jnp.int32, (gs, page), 0) % s_len
            s = jnp.where(j <= length + qi, s, DEFAULT_MASK_VALUE)
        else:
            # token-tree mask: key slot j holds tree node rel = j - length;
            # visible iff committed history (j < length) or bit rel of this
            # row's ancestor word is set (row r = group-major fold, node
            # r % s_len; the word materializes from scalar immediates)
            node = jax.lax.broadcasted_iota(jnp.int32, (gs, page), 0) % s_len
            word = jnp.zeros((gs, page), jnp.uint32)
            for idx, w in enumerate(tree_words):
                word = jnp.where(node == idx, jnp.uint32(w), word)
            rel = j - length
            in_tree = (rel >= 0) & (rel < s_len)
            anc = ((word >> jnp.clip(rel, 0, 31).astype(jnp.uint32)) & 1) == 1
            s = jnp.where((j < length) | (in_tree & anc), s, DEFAULT_MASK_VALUE)

        if page >= NUM_LANES:
            lane_bcast = lambda a: jnp.tile(a[:, :1], (1, page))
        else:
            lane_bcast = lambda a: a[:, :page]
        if head_dim >= NUM_LANES:
            acc_bcast = lambda a: jnp.tile(a[:, :1], (1, head_dim))
        else:
            acc_bcast = lambda a: a[:, :head_dim]

        m_prev = m_ref[...]                                    # [GS, 128]
        l_prev = l_ref[...]
        m_curr = jnp.max(s, axis=1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)
        prob = jnp.exp(s - lane_bcast(m_next))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(prob, axis=1)[:, None]
        pv = jax.lax.dot(
            prob, v.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * acc_bcast(alpha) + pv

    @pl.when(p == n_p - 1)
    def _store():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[...] = (acc_ref[...] / acc_bcast_store(l_safe, head_dim))[None, None].astype(
            o_ref.dtype
        )


def acc_bcast_store(a, head_dim: int):
    if head_dim >= NUM_LANES:
        return jnp.tile(a[:, :1], (1, head_dim))
    return a[:, :head_dim]


def paged_attention(q, pages_k, pages_v, tables, lengths, k_scales=None,
                    v_scales=None, interpret: Optional[bool] = None,
                    tree_mask=None):
    """Decode attention over paged KV, reading pages in place.

    Parameters
    ----------
    q: ``[N, S, Hq, D]`` queries for the ``S`` positions being written this
        call (decode: 1; speculative verify: K+1).  Query ``i`` of lane ``n``
        sits at position ``lengths[n] + i``.
    pages_k, pages_v: the page pool ``[NP, Hkv, page, D]`` for ONE layer, with
        this call's new KV already inserted (:func:`paged_insert` /
        :func:`paged_quantized_insert`).
    tables: ``[N, P]`` int32 per-lane block tables; dead slots hold the null
        page.
    lengths: ``[N]`` int32 — each lane's valid length before this call.
    k_scales, v_scales: ``[NP, Hkv]`` f32 per-page-per-head dequantization
        scales; required iff the pages are a quantized format.
    interpret: run the kernel in pallas interpret mode (defaults to True off
        TPU — the CPU testing discipline shared with
        :mod:`.flash_attention`).
    tree_mask: ``[S, S]`` ancestor-or-self boolean (host numpy constant) for
        speculative tree verification — query ``i`` is tree node ``i`` at slot
        ``lengths[n] + i`` and sees history plus its root-to-self chain.  The
        mask is packed to one uint32 ancestor word per folded query row and
        baked into the kernel (``S <= 32``), so the executable is specialized
        per tree topology exactly as it already is per ``S``.

    Returns ``[N, S, Hq, D]`` in ``q.dtype``.  Grid: one program per
    (lane, kv-head) marching over the lane's pages innermost; GQA query heads
    fold into rows so each KV page streams from HBM once per group.
    """
    if interpret is None:
        interpret = _default_interpret()
    n, s, hq, d = q.shape
    _, hkv, page, _ = pages_k.shape
    num_p = tables.shape[1]
    rep = hq // hkv
    gs = rep * s
    tree_words = None
    if tree_mask is not None:
        tm = np.asarray(tree_mask, dtype=bool)
        if tm.shape != (s, s):
            raise ValueError(f"tree_mask {tm.shape} must be [S, S] = [{s}, {s}]")
        if s > 32:
            raise ValueError(
                f"pallas tree verification packs ancestor sets into uint32 "
                f"words: {s} tree nodes > 32 (use the xla reference)"
            )
        bits = (tm.astype(np.uint32)
                << np.arange(s, dtype=np.uint32)[None, :]).sum(axis=1)
        # plain Python ints: baked into the kernel as scalar immediates (an
        # array here would be a captured constant, which Pallas rejects)
        tree_words = tuple(int(w) for w in bits)
    quantized = kv_qmax(pages_k.dtype) is not None
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("quantized pages need k_scales/v_scales")

    # fold GQA groups into rows: row r = g * S + i  ->  head h*rep + g, query i
    qf = (
        q.transpose(0, 2, 1, 3)
        .reshape(n, hkv, rep, s, d)
        .reshape(n, hkv, gs, d)
    )
    lengths = lengths.astype(jnp.int32)
    tables = tables.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, hkv, num_p),
        in_specs=[
            pl.BlockSpec((1, 1, gs, d), lambda i, h, p, t, ln: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), lambda i, h, p, t, ln: (t[i, p], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), lambda i, h, p, t, ln: (t[i, p], h, 0, 0)),
            *[pl.BlockSpec((1, 1, 1, num_p), lambda i, h, p, t, ln: (i, h, 0, 0))
              ] * (2 if quantized else 0),
        ],
        out_specs=pl.BlockSpec((1, 1, gs, d), lambda i, h, p, t, ln: (i, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gs, NUM_LANES), jnp.float32),
            pltpu.VMEM((gs, NUM_LANES), jnp.float32),
            pltpu.VMEM((gs, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel,
        page=page, s_len=s, scale=d ** -0.5, quantized=quantized,
        tree_words=tree_words,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, gs, d), q.dtype),
        interpret=interpret,
    )(tables, lengths, qf, pages_k, pages_v,
      *_lane_scales(tables, k_scales, v_scales, quantized))
    return (
        out.reshape(n, hkv, rep, s, d)
        .reshape(n, hq, s, d)
        .transpose(0, 2, 1, 3)
    )


# ------------------------------------------------------------------- prefill
def paged_flash_prefill_reference(q, pages_k, pages_v, tables, lengths,
                                  k_scales=None, v_scales=None, window=None,
                                  alibi: bool = False):
    """Pure-XLA prefill oracle: the exact program :func:`paged_flash_prefill`
    must reproduce.  Chunk-wide queries against paged KV share the decode
    reference's math — query ``i`` sits at ``lengths[n] + i`` and sees keys
    ``j <= lengths[n] + i``, which covers both the attention over prior pages
    and the in-chunk causal triangle (the chunk's own KV is inserted before
    the call, exactly like decode) — so this is a documented delegation, not
    a reimplementation.  It is also the program tp>1 engines run
    (``prefill_kernel="xla"``)."""
    return paged_attention_reference(
        q, pages_k, pages_v, tables, lengths,
        k_scales=k_scales, v_scales=v_scales, window=window, alibi=alibi,
    )


def _paged_prefill_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, *rest,
                          page: int, block_q: int, rep: int, scale: float,
                          quantized: bool):
    """One (lane, kv-head, q-block, page) step of the prefill online softmax.

    Query-major GQA fold: row ``r`` of a q-block holds query head
    ``h * rep + r % rep`` at in-chunk offset ``iq * block_q + r // rep`` —
    query-major (unlike the decode kernel's group-major fold) so each q-block
    covers one contiguous query span and the causal page walk can stop at that
    span's frontier.  Pages are the innermost grid dimension, so the m/l/acc
    VMEM scratch carries across a q-block's page walk; pages whose first key
    lies past the block's last query position are skipped outright — that
    bound subsumes the dead-page check (a dead slot's index degenerates to the
    null page, fetched at most once and never past any lane's frontier)."""
    ks_ref, vs_ref = rest[:2] if quantized else (None, None)
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    lane, iq, p = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_p = pl.num_programs(3)
    rows = acc_ref.shape[0]
    head_dim = acc_ref.shape[-1]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[lane]

    @pl.when(p * page <= length + (iq + 1) * block_q - 1)
    def _compute():
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if quantized:
            k = k.astype(jnp.float32) * _page_scale(ks_ref, p)
            v = v.astype(jnp.float32) * _page_scale(vs_ref, p)
        q = q_ref[0, 0].astype(jnp.float32) * scale
        s = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [rows, page]
        j = p * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        qi = (iq * block_q
              + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // rep)
        s = jnp.where(j <= length + qi, s, DEFAULT_MASK_VALUE)

        if page >= NUM_LANES:
            lane_bcast = lambda a: jnp.tile(a[:, :1], (1, page))
        else:
            lane_bcast = lambda a: a[:, :page]

        m_prev = m_ref[...]                                    # [rows, 128]
        l_prev = l_ref[...]
        m_curr = jnp.max(s, axis=1)[:, None]
        m_next = jnp.maximum(m_prev, m_curr)
        prob = jnp.exp(s - lane_bcast(m_next))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(prob, axis=1)[:, None]
        pv = jax.lax.dot(
            prob, v.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * acc_bcast_store(alpha, head_dim) + pv

    @pl.when(p == n_p - 1)
    def _store():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[...] = (acc_ref[...] / acc_bcast_store(l_safe, head_dim))[
            None, None
        ].astype(o_ref.dtype)


def paged_flash_prefill(q, pages_k, pages_v, tables, lengths, k_scales=None,
                        v_scales=None, interpret: Optional[bool] = None):
    """Flash-attention prefill over paged KV, reading pages in place.

    The prefill-side twin of :func:`paged_attention`: chunk-wide queries
    instead of a decode step's one-or-few.  The chunk's K/V must already be
    scattered into the pool (:func:`paged_insert` /
    :func:`paged_quantized_insert` — scatter-time quantization with the
    per-page scales), so the causal online softmax over prior pages and the
    in-chunk triangle are one uniform page walk.

    Parameters
    ----------
    q: ``[N, S, Hq, D]`` — the chunk's queries; query ``i`` of lane ``n``
        sits at position ``lengths[n] + i``.
    pages_k, pages_v: the page pool ``[NP, Hkv, page, D]`` for ONE layer.
    tables: ``[N, P]`` int32 per-lane block tables; dead slots hold the null
        page.
    lengths: ``[N]`` int32 — each lane's valid length before this chunk (the
        chunk base offset).
    k_scales, v_scales: ``[NP, Hkv]`` f32 per-page-per-head scales; required
        iff the pages are a quantized format.
    interpret: pallas interpret mode (defaults to True off TPU).

    Returns ``[N, S, Hq, D]`` in ``q.dtype``.  Grid: one program per
    (lane, kv-head, q-block) marching over the lane's pages innermost, with
    the page walk cut at each q-block's causal frontier — early q-blocks of a
    late chunk never touch the chunk's own later pages."""
    if interpret is None:
        interpret = _default_interpret()
    n, s, hq, d = q.shape
    _, hkv, page, _ = pages_k.shape
    num_p = tables.shape[1]
    rep = hq // hkv
    quantized = kv_qmax(pages_k.dtype) is not None
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("quantized pages need k_scales/v_scales")

    block_q = pick_block_divisor(s)
    n_qb = s // block_q
    rows = block_q * rep

    # fold GQA groups into rows QUERY-major: row r = i * rep + g  ->  head
    # h*rep + g, query i — a q-block of ``block_q * rep`` rows covers one
    # contiguous query span across all groups of the kv head
    qf = (
        q.reshape(n, s, hkv, rep, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(n, hkv, s * rep, d)
    )
    lengths = lengths.astype(jnp.int32)
    tables = tables.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, hkv, n_qb, num_p),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda i, h, b, p, t, ln: (i, h, b, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda i, h, b, p, t, ln: (t[i, p], h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda i, h, b, p, t, ln: (t[i, p], h, 0, 0)),
            *[pl.BlockSpec((1, 1, 1, num_p),
                           lambda i, h, b, p, t, ln: (i, h, 0, 0))
              ] * (2 if quantized else 0),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda i, h, b, p, t, ln: (i, h, b, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, NUM_LANES), jnp.float32),
            pltpu.VMEM((rows, NUM_LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_prefill_kernel,
        page=page, block_q=block_q, rep=rep, scale=d ** -0.5,
        quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, s * rep, d), q.dtype),
        interpret=interpret,
    )(tables, lengths, qf, pages_k, pages_v,
      *_lane_scales(tables, k_scales, v_scales, quantized))
    return (
        out.reshape(n, hkv, s, rep, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(n, s, hq, d)
    )

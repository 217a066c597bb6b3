"""A prefill chunk's latent attention over its gathered view: a Pallas flash kernel.

:class:`~accelerate_tpu.models.latent_attention.LatentAttention` gets, for a
cached chunk, the queries ``q_nope [B, S, H, nope]`` and ``q_pe [B, S, H,
rope]`` and one layer's view of the cache, position-major: the normed latent
``[B, M, kv_rank]`` and the roped key ``[B, M, rope]`` a position, ``M`` the
most a lane may hold (8,192 in the DeepSeek-V2 cell).  Written in XLA
(:func:`~accelerate_tpu.models.latent_attention.attend_decompressed`) it loops
over the live key blocks, but each block's float32 scores ``[B, H, S, block]``
(268 MB at 128 heads, 512 rows and 1,024 keys) go through HBM several times,
so the loop runs at about a tenth of the MXU's peak.

:func:`latent_view_attention` is the same attention as one kernel that

* visits only the key blocks that can hold a visible key: their count is worked
  out from the query positions on the device and handed over as a
  scalar-prefetch argument (``view_attention._plan``), which the index maps of
  the latent and the rope key read; a dead grid step points at the last live
  block, so nothing new is fetched, and does nothing;
* decompresses each latent block in fast memory, for a group of heads at a
  time: ``[k_nope ; v] = latent W_UKV`` against the group's column block of
  ``kv_b_proj``'s kernel ``[kv_rank, H * (nope + v)]``, as the parameter lies,
  rounded to bfloat16 as XLA's einsum rounds them;
* keeps the scores ``(q_nope k_nope^T + q_pe k_pe^T) x scale``, the running
  maximum and sum and the accumulator in fast memory (online softmax, as
  :mod:`.view_attention`); probabilities are cast to bfloat16 for ``P V`` and
  the rows normalised once at the end;
* writes ``[B, S, H * v]``, the layout ``o_proj`` reads.

Decompressed, not absorbed: decompressing costs ``4 x block x kv_rank x nope``
a head and key block (K and V), absorbing ``4 x rows x block x kv_rank`` (the
scores and the weighted sum against the 512-wide latent); at a 512-chunk the
decompressed form is half the work, at a 128-chunk the two are about even.

``docs/kernels/latent_view_attention.md`` has the grid, the block sizes, the
FLOPs and the measurements.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_MASK_VALUE, NUM_LANES
from .retention import _platform_compiles
from .view_attention import _COUNT, _FIRST, _LOW, _MIN_VIEW, _ROW_BLOCK, KEY_BLOCK, _plan, _xla_form

#: heads a grid step decompresses and attends for: two fill the 128 lanes of
#: ``q_pe``'s 64-wide heads, and make the decompression one 512-wide product
HEAD_GROUP = 2
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def latent_flash_applies(q_nope, q_pe, latent, w_ukv, interpret: Optional[bool] = None) -> bool:
    """Whether the kernel takes ``q_nope [B, S, H, nope]``, ``q_pe [B, S, H,
    rope]`` against ``latent [B, M, kv_rank]`` through ``w_ukv [kv_rank, H *
    (nope + v)]`` (arrays or their shapes and dtypes): bfloat16, a chunk's worth
    of rows (``S >= 128``: never a decode or verify window), ``nope``, ``v`` and
    ``kv_rank`` of whole lanes and heads in whole groups, a view at least 2,048
    wide, on a TPU (or wherever a caller says how to run it: ``interpret=True``
    is the CPU tests' way) and not under
    :func:`~accelerate_tpu.ops.view_attention.xla_form`.
    :func:`~accelerate_tpu.models.latent_attention.attend_decompressed` serves
    everything else."""
    s, h, nope = q_nope.shape[1:]
    rope = q_pe.shape[3]
    m, kv_rank = latent.shape[-2:]
    v = w_ukv.shape[1] // h - nope
    return (all(a.dtype == jnp.bfloat16 for a in (q_nope, q_pe, latent, w_ukv))
            and s >= NUM_LANES and h % HEAD_GROUP == 0
            and nope % NUM_LANES == 0 and v % NUM_LANES == 0 and kv_rank % NUM_LANES == 0
            and (HEAD_GROUP * rope) % NUM_LANES == 0
            and m % NUM_LANES == 0 and m >= _MIN_VIEW
            and not _xla_form.get()
            and (interpret is not None or _platform_compiles()))


def _kernel(meta_ref, layer_ref, qn_ref, qp_ref, lat_ref, kpe_ref, w_ref, pos_ref, out_ref, acc_ref, m_ref, l_ref, *,
            scale: float, nope: int, rope: int):
    group, _, v_dim = acc_ref.shape
    block = lat_ref.shape[1]
    kb = pl.program_id(3)
    at = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    first, count, low = meta_ref[_FIRST, at], meta_ref[_COUNT, at], meta_ref[_LOW, at]
    j0 = (first + kb) * block
    width = nope + v_dim

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        # the group's keys and values, decompressed from the block's latents
        kv = jax.lax.dot_general(lat_ref[0], w_ref[...], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32).astype(lat_ref.dtype)   # [block, group * width]
        k_pe = kpe_ref[0]                                                                    # [block, rope]
        mask = None
        if masked:
            j = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            mask = j <= jnp.tile(pos_ref[0], (1, block // NUM_LANES))                        # [rows, block]
        for g in range(group):
            k_nope = kv[:, g * width:g * width + nope]
            v = kv[:, g * width + nope:(g + 1) * width]
            s = (jax.lax.dot_general(qn_ref[0, :, g * nope:(g + 1) * nope], k_nope, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp_ref[0, :, g * rope:(g + 1) * rope], k_pe, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)) * scale
            if mask is not None:
                s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[g]                                                                # [rows, 128], lanes alike
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - jnp.tile(m_next, (1, block // NUM_LANES)))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[g] = m_next
            pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)                     # [rows, v]
            acc_ref[g] = acc_ref[g] * jnp.tile(alpha, (1, v_dim // NUM_LANES)) + pv

    @pl.when(kb < count)
    def _():
        # a block every row of the row block sees whole takes the unmasked body
        needs = j0 + block - 1 > low
        pl.when(needs)(lambda: visit(True))
        pl.when(jnp.logical_not(needs))(lambda: visit(False))

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        for g in range(group):
            # every query sees a key (its own), so the sum is at least exp(0)
            out_ref[0, :, g * v_dim:(g + 1) * v_dim] = (
                acc_ref[g] * jnp.tile(1.0 / l_ref[g], (1, v_dim // NUM_LANES))).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block", "stacked"))
def _call(q_nope, q_pe, latent, k_pe, w_ukv, q_positions, layer, *, scale, interpret, block, stacked):
    # jitted: a program's layers call it with the same shapes, and are traced
    # and lowered to the Mosaic kernel once and not once a layer (a stacked
    # view's layer is a prefetched scalar, not a static argument)
    b, s, h, nope = q_nope.shape
    rope = q_pe.shape[3]
    m_cols, kv_rank = latent.shape[-2:]
    v_dim = w_ukv.shape[1] // h - nope
    q_positions = q_positions.astype(jnp.int32)
    # whole row blocks: the rows added repeat the last one and are cut off again
    padded = -(-s // NUM_LANES) * NUM_LANES
    rows = next(r for r in (_ROW_BLOCK, 256, NUM_LANES) if padded % r == 0)
    qn, qp = q_nope.reshape(b, s, h * nope), q_pe.reshape(b, s, h * rope)
    if padded != s:
        qn, qp = (jnp.pad(a, ((0, 0), (0, padded - s), (0, 0))) for a in (qn, qp))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, padded - s)), mode="edge")
    row_blocks = padded // rows
    meta = _plan(q_positions, rows, block, m_cols, None, False)
    pos = jax.lax.broadcast_in_dim(q_positions, (b, padded, NUM_LANES), (0, 1))

    def key_block(lane, head, rb, kb, meta, layer):
        at = lane * row_blocks + rb
        j = meta[_FIRST, at] + jnp.minimum(kb, meta[_COUNT, at] - 1)     # a dead step fetches nothing new
        return (layer[0], lane, j, 0) if stacked else (lane, j, 0)

    # a stacked view's layer is picked by the index map, not sliced out in front of the call
    view_block = lambda width: pl.BlockSpec((None, 1, block, width) if stacked else (1, block, width), key_block)

    row_block = lambda width: pl.BlockSpec((1, rows, HEAD_GROUP * width),
                                           lambda lane, head, rb, kb, meta, layer: (lane, rb, head))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nope=nope, rope=rope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // HEAD_GROUP, row_blocks, -(-m_cols // block)),
            in_specs=[row_block(nope),
                      row_block(rope),
                      view_block(kv_rank),
                      view_block(rope),
                      pl.BlockSpec((kv_rank, HEAD_GROUP * (nope + v_dim)),
                                   lambda lane, head, rb, kb, meta, layer: (0, head)),
                      pl.BlockSpec((1, rows, NUM_LANES), lambda lane, head, rb, kb, meta, layer: (lane, rb, 0))],
            out_specs=row_block(v_dim),
            scratch_shapes=[pltpu.VMEM((HEAD_GROUP, rows, v_dim), jnp.float32),
                            pltpu.VMEM((HEAD_GROUP, rows, NUM_LANES), jnp.float32),
                            pltpu.VMEM((HEAD_GROUP, rows, NUM_LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, padded, h * v_dim), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="latent_view_attention",
    )(meta, layer, qn, qp, latent, k_pe, w_ukv, pos)
    return out[:, :s]


def latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, q_positions, scale: float, *,
                          layer: Optional[int] = None, interpret: Optional[bool] = None):
    """Latent attention of ``q_nope [B, S, H, nope]`` / ``q_pe [B, S, H, rope]``
    against one layer's view ``latent [B, M, kv_rank]`` / ``k_pe [B, M, rope]``
    (position-major, ``M`` a multiple of 128; with ``layer``, that layer of the
    stacked ``[L, B, M, kv_rank]`` / ``[L, B, M, rope]``), the keys and values
    decompressed through ``w_ukv [kv_rank, H * (nope + v)]`` (``kv_b_proj``'s kernel: a
    head's ``nope`` key columns, then its ``v`` value columns); query ``i`` of
    lane ``b`` sees the positions ``j <= q_positions[b, i]``, and every query
    must see one (its own).  :func:`~accelerate_tpu.models.latent_attention
    .attend_decompressed`'s result as ``[B, S, H * v]`` in ``q_nope``'s dtype.

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    h, nope = q_nope.shape[2:]
    m_cols = latent.shape[-2]
    if (nope % NUM_LANES or m_cols % NUM_LANES or latent.shape[-1] % NUM_LANES or h % HEAD_GROUP
            or (HEAD_GROUP * q_pe.shape[3]) % NUM_LANES
            or w_ukv.shape[1] % h or (w_ukv.shape[1] // h - nope) % NUM_LANES):
        raise ValueError(f"latent_view_attention wants heads in groups of {HEAD_GROUP}, and head widths "
                         f"and view of whole lanes: q_nope {q_nope.shape}, latent {latent.shape}, "
                         f"w_ukv {w_ukv.shape}")
    if interpret is None:
        interpret = not _platform_compiles()
    # a block that divides the view: no tail to mask
    return _call(q_nope, q_pe, latent, k_pe, w_ukv, q_positions, jnp.full((1,), layer or 0, jnp.int32),
                 scale=float(scale), interpret=interpret, block=math.gcd(KEY_BLOCK, m_cols),
                 stacked=layer is not None)

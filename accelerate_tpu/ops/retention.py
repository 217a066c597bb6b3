"""The retention decode step in one pass over the state: a Pallas kernel.

A power-retention layer (:mod:`accelerate_tpu.models.retention`) keeps, for
every lane and key/value head, a float32 state ``S [D, d]`` (``D = 8,320`` for
a head of ``d = 128``: 4.26 MB) and a normaliser ``z [D]``.  A decode step is

    S <- g S + phi(k) v^T        z <- g z + phi(k)
    y  = phi(q)^T S / (phi(q) . z + eps)        for the head's G query heads

and its cost is the bytes of ``S``: one read and one write are the least a
step can do.  Written in XLA it takes three passes (the in-place update, then
the read-out reads the stored state again behind a barrier:
``retention_step_stored``).  :func:`retention_step_onepass` is the step as one
kernel that owns the tile: for each (lane, key/value head) the state is
streamed through fast memory once, updated on the vector unit, written back
to the same place, and the read-out ``phi(q)^T S_new`` is taken on the MXU at
float32 precision from the tile while it is there.

The state is passed STACKED, ``[L, B, Hk, D, d]``, and returned through
``input_output_aliases``; the static ``layer`` enters the block index maps, so
only that layer's blocks are fetched and written (the pattern
:mod:`.paged_attention` uses for pages) and no other layer's byte is touched.
``docs/kernels/retention_step.md`` has the tiling and the measurements.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.imports import is_tpu_platform
from .flash_attention import NUM_LANES

#: the read-out's product against the float32 state: an operand rounded to
#: bfloat16 reads another state than the one stored (8,320 signed terms cancel)
_PRECISION = jax.lax.Precision.HIGHEST

#: rows of the state updated and read out at a time inside a (lane, head) tile
_MAX_BLOCK_ROWS = 2048

#: a (lane, head) tile of the state lives in fast memory four times (in and
#: out, double-buffered); what does not fit this takes the XLA form
_TILE_BUDGET_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _platform_compiles() -> bool:
    """A TPU compiles the kernel; no other platform takes it unasked."""
    return is_tpu_platform(jax.devices()[0].platform)


def _tiles(s) -> bool:
    """A float32 state whose head width fills whole lanes and whose (lane,
    head) tile fits fast memory."""
    rows, width = s.shape[-2:]
    return (s.dtype == jnp.float32 and width % NUM_LANES == 0 and rows % NUM_LANES == 0
            and 16 * rows * width <= _TILE_BUDGET_BYTES)


def onepass_applies(s, degree: int, interpret: Optional[bool] = None) -> bool:
    """Whether :func:`retention_step_onepass` takes a step on the stacked state
    ``s [L, B, Hk, D, d]`` (an array or its shape and dtype): the symmetric
    square (``degree == 2``) in a state the kernel tiles, on a TPU (or wherever
    a caller says how to run it: ``interpret=True`` is the CPU tests' way)."""
    return degree == 2 and _tiles(s) and (interpret is not None or _platform_compiles())


def _block_rows(rows: int) -> int:
    """Largest divisor of ``rows`` that is a multiple of 128 and at most
    ``_MAX_BLOCK_ROWS`` (8,320 = 65 x 128 -> 1,664)."""
    n = rows // NUM_LANES
    return NUM_LANES * max(k for k in range(1, n + 1) if n % k == 0 and k * NUM_LANES <= _MAX_BLOCK_ROWS)


def _step_kernel(gate_ref, pq_ref, pk_ref, v_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out, *, block):
    lane, head = pl.program_id(0), pl.program_id(1)
    gate = gate_ref[lane, head]
    mine = pl.ds(head, 1)                                              # this head's row of a lane's block
    v = v_ref[0, mine, :]                                              # [1, d]
    groups, rows = pq_ref.shape[2:]

    z = gate * z_ref[0, 0, mine, :] + pk_ref[0, mine, :]               # [1, D]
    z_out[0, 0, mine, :] = z
    den_ref[0, 0] = jnp.sum(pq_ref[0, 0] * z, axis=-1, keepdims=True)  # [G, 1]

    def body(i, num):
        at = pl.ds(pl.multiple_of(i * block, block), block)
        # phi(k) of these rows as a column: eight rows transposed, one kept
        pk = jnp.concatenate([pk_ref[0, mine, at], jnp.zeros((7, block), jnp.float32)], axis=0)
        s = gate * s_ref[0, 0, 0, at, :] + pk.T[:, 0:1] * v            # [block, d]
        s_out[0, 0, 0, at, :] = s
        return num + jnp.dot(pq_ref[0, 0, :, at], s, precision=_PRECISION,
                             preferred_element_type=jnp.float32)

    num_ref[0, 0] = jax.lax.fori_loop(0, rows // block, body,
                                      jnp.zeros((groups, v.shape[-1]), jnp.float32))


def retention_step_onepass(pq, pk, v, gate, s, z, layer: int, *, interpret: Optional[bool] = None):
    """One decode step of layer ``layer`` on the stacked state, in place.

    ``pq [B, Hk, G, D]`` is ``phi(q)`` of the head's ``G`` query heads, ``pk
    [B, Hk, D]`` ``phi(k)`` (0 for a frozen lane), ``v [B, Hk, d]``, ``gate [B,
    Hk]`` the decay (1 for a frozen lane), ``s [L, B, Hk, D, d]`` and ``z [L,
    B, Hk, D]`` the float32 state of every layer.  Returns ``(num [B, Hk, G,
    d], den [B, Hk, G], s, z)``: the read-out's weighted sum and sum of weights
    against the NEW state, and the state with layer ``layer`` rewritten (donate
    ``s`` and ``z``: they come back through ``input_output_aliases``).

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if not _tiles(s):
        raise ValueError(f"the one-pass step takes a float32 state of whole lanes that fits fast memory, got "
                         f"{s.dtype}{list(s.shape)}")
    if interpret is None:
        interpret = not _platform_compiles()
    lanes, heads, groups, rows = pq.shape
    width = s.shape[-1]
    f32 = jnp.float32
    # whole sublanes of query heads: the array then has one layout, the one the
    # kernel reads, and the compiler neither pads nor copies it on the way in
    padded = -(-groups // 8) * 8
    pq = jnp.pad(pq.astype(f32), ((0, 0), (0, 0), (0, padded - groups), (0, 0)))
    a_head = lambda *block: pl.BlockSpec((1, 1) + block, lambda b, h: (b, h) + (0,) * len(block))
    a_lane = lambda *block: pl.BlockSpec((1,) + block, lambda b, h: (b,) + (0,) * len(block))
    state = pl.BlockSpec((1, 1, 1, rows, width), lambda b, h: (layer, b, h, 0, 0))
    norm = pl.BlockSpec((1, 1, heads, rows), lambda b, h: (layer, b, 0, 0))           # a lane's z: row h is used
    num, den, s, z = pl.pallas_call(
        functools.partial(_step_kernel, block=_block_rows(rows)),
        grid=(lanes, heads),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),                                 # gate, whole
                  a_head(padded, rows), a_lane(heads, rows), a_lane(heads, width), state, norm],
        out_specs=[a_head(padded, width), a_head(padded, 1), state, norm],
        out_shape=[jax.ShapeDtypeStruct((lanes, heads, padded, width), f32),
                   jax.ShapeDtypeStruct((lanes, heads, padded, 1), f32),
                   jax.ShapeDtypeStruct(s.shape, f32), jax.ShapeDtypeStruct(z.shape, f32)],
        input_output_aliases={4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="retention_step_onepass",
    )(gate.astype(f32), pq, pk.astype(f32), v.astype(f32), s, z)
    return num[:, :, :groups], den[:, :, :groups, 0], s, z

"""The held experts' products as a grouped matmul: a Pallas kernel.

:class:`~accelerate_tpu.parallel.moe.RoutedExperts` sorts its token-expert
pairs by held expert and multiplies each expert's rows by that expert's slice
of a stacked leaf ``kernel [held, in, out]``; pairs on experts held elsewhere
sort last and belong to no group.  At serving sizes an expert gets a handful of
rows (19 of a 512-chunk's 3,072 for DeepSeek-V2, 8 of 2,048 for Trinity), so
the cost of a product is the bytes of the weights it reads, and the least it
can read is each expert that got a row, once.  The TPU's ``jax.lax.ragged_dot``
is a kernel of the same design with row tiles of 512, at which a visit is bound
by the MXU and not by the read: 2.2 ms for a product that has to read 629 MB.

The kernel visits only ``(row tile, expert)`` pairs that hold rows.  The visits
are worked out from ``group_sizes`` on the device (:func:`_visits`) and handed
over as scalar-prefetch arguments, which the row, weight and output index maps
read (the pattern :mod:`.paged_attention` uses for page tables): an expert
without a row is never fetched, a row tile past ``sum(group_sizes)`` never
visited, and what those rows hold afterwards is unspecified, as with
``ragged_dot`` on the TPU.  A visited expert's weights stream through fast
memory in place, a whole expert a block where it fits (15.7 MB for
DeepSeek-V2, 18.9 MB for Trinity; double-buffered), with the ``in`` dimension
innermost in the grid and a float32 accumulator in scratch; a tile that
straddles experts keeps, of each visit, the rows inside that expert's bounds.
One rounding to the rows' dtype at the end: bfloat16 operands, float32
accumulation, the arithmetic of ``ragged_dot``.

:func:`grouped_matmul` differentiates as ``ragged_dot`` does
(``jax.custom_vjp``).  ``docs/kernels/grouped_matmul.md`` has the grid, the tile
sizes, what was tried and the measurements.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NUM_LANES
from .retention import _platform_compiles

#: rows a visit multiplies: a tile of the sorted rows (a decode window's rows
#: are fewer and make one tile).  Up to 256 rows a visit costs the read of the
#: expert's weights; at 512 the MXU's work takes twice as long as the read
_ROW_TILE = 128

#: bytes of one weight block (double-buffered): a whole expert of either
#: served configuration; a larger one is split along ``in``
_BLOCK_BYTES = 20 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def grouped_applies(rows, kernel, interpret: Optional[bool] = None) -> bool:
    """Whether the kernel takes ``rows [m, in] @ kernel [held, in, out]`` (arrays
    or their shapes and dtypes): bfloat16 operands whose ``in`` and ``out`` are
    whole lanes, on a TPU (or wherever a caller says how to run it:
    ``interpret=True`` is the CPU tests' way).  ``jax.lax.ragged_dot`` serves
    everything else."""
    return (rows.dtype == jnp.bfloat16 and kernel.dtype == jnp.bfloat16
            and kernel.shape[1] % NUM_LANES == 0 and kernel.shape[2] % NUM_LANES == 0
            and (interpret is not None or _platform_compiles()))


def _row_tile(m: int) -> int:
    """The row tile for ``m`` sorted rows: all of them where they are fewer than
    a tile (padded to whole bfloat16 sublane pairs), else :data:`_ROW_TILE`."""
    return _ROW_TILE if m > _ROW_TILE else -(-m // 16) * 16


def _block_in(k: int, n: int) -> int:
    """Rows of a weight block ``[block, n]``: the largest divisor of ``k`` that is
    a multiple of 128 and keeps the block within :data:`_BLOCK_BYTES`."""
    lanes = k // NUM_LANES
    fits = [d for d in range(1, lanes + 1) if lanes % d == 0 and d * NUM_LANES * n * 2 <= _BLOCK_BYTES]
    return NUM_LANES * max(fits, default=1)


def _visits(group_sizes, tile: int, tiles: int):
    """The (row tile, expert) pairs that hold rows, in the order the grid walks
    them: ``(expert [V], row_tile [V], bounds [G + 1], count [1])`` with ``V =
    tiles + G - 1``, the most there can be.  Experts ascend and an expert's
    tiles ascend, so a tile's visits are consecutive (its output block stays in
    fast memory between them).  Entries past ``count`` repeat the last visit:
    the pipeline fetches nothing for a block index that did not change."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = bounds[:-1] // tile
    spans = jnp.where(group_sizes > 0, (ends - 1) // tile - first + 1, 0)          # tiles an expert's rows touch
    upto = jnp.cumsum(spans)
    count = upto[-1]
    visit = jnp.minimum(jnp.arange(tiles + groups - 1, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    expert = jnp.minimum(jnp.sum(upto[None, :] <= visit[:, None], axis=1, dtype=jnp.int32), groups - 1)
    row_tile = jnp.clip(first[expert] + visit - (upto - spans)[expert], 0, tiles - 1)
    return expert, row_tile.astype(jnp.int32), bounds, count[None]


def _kernel(expert_ref, tile_ref, bounds_ref, count_ref, rows_ref, w_ref, out_ref, acc_ref, *, tile: int):
    visit, k = pl.program_id(0), pl.program_id(1)

    @pl.when(visit < count_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(rows_ref[...], w_ref[0], preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(1) - 1)
        def _():
            expert = expert_ref[visit]
            row = tile_ref[visit] * tile + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
            mine = (row >= bounds_ref[expert]) & (row < bounds_ref[expert + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _call(rows, kernel, group_sizes, interpret: bool, block: int):
    # jitted: a program's expert layers call it with the same shapes, and are
    # traced and lowered to the Mosaic kernel once, not once a layer and product
    m, k = rows.shape
    groups, _, n = kernel.shape
    tile = _row_tile(m)
    tiles = -(-m // tile)
    if tiles * tile != m:
        rows = jnp.pad(rows, ((0, tiles * tile - m), (0, 0)))
    steps = k // block

    def at(v, kk, count):                                                  # a visit past the count fetches nothing new
        return jnp.where(v < count[0], kk, steps - 1)

    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles + groups - 1, steps),
            in_specs=[pl.BlockSpec((tile, block), lambda v, kk, expert, tile_of, bounds, count:
                                   (tile_of[v], at(v, kk, count))),
                      pl.BlockSpec((1, block, n), lambda v, kk, expert, tile_of, bounds, count:
                                   (expert[v], at(v, kk, count), 0))],
            out_specs=pl.BlockSpec((tile, n), lambda v, kk, expert, tile_of, bounds, count: (tile_of[v], 0)),
            scratch_shapes=[pltpu.VMEM((tile, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_matmul",
    )(*_visits(group_sizes, tile, tiles), rows, kernel)
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(rows, kernel, group_sizes, interpret):
    return _call(rows, kernel, group_sizes, interpret, _block_in(*kernel.shape[1:]))


def _fwd(rows, kernel, group_sizes, interpret):
    return _call(rows, kernel, group_sizes, interpret, _block_in(*kernel.shape[1:])), (rows, kernel, group_sizes)


def _bwd(interpret, saved, cotangent):
    # no cell trains through the kernel, and the backward of a grouped product
    # is two more grouped products that ``ragged_dot`` already has
    rows, kernel, group_sizes = saved
    _, vjp = jax.vjp(lambda r, w: jax.lax.ragged_dot(r, w, group_sizes), rows, kernel)
    return vjp(cotangent) + (None,)


_grouped_matmul.defvjp(_fwd, _bwd)


def grouped_matmul(rows, kernel, group_sizes, *, interpret: Optional[bool] = None):
    """``rows [m, in]`` sorted by group times ``kernel [groups, in, out]``: row
    ``r`` of group ``g`` (``group_sizes [groups]`` int32 in order) gives ``rows[r]
    @ kernel[g]``, ``[m, out]`` in the rows' dtype.  Rows past
    ``sum(group_sizes)`` are unspecified (``ragged_dot``'s contract on the
    TPU): select them away, never weigh them by 0.

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    return _grouped_matmul(rows, kernel, group_sizes, not _platform_compiles() if interpret is None else interpret)

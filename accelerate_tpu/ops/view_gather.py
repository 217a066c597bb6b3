"""A decode window's gathered cache view filled by one Pallas page copy.

:func:`~accelerate_tpu.serving.pool._gather_view` gives the model a contiguous
per-lane view ``[L, N, H * D, P * page]`` (rows flat, positions minor) of the
page pool ``[L, NP, H, page, D]`` through the lanes' block tables ``[N, P]``.
Written in XLA it is a zero fill of the view and one ``dynamic_update_slice`` a
(lane, page slot): thousands of updates a window (2 x (8 x 256 + 8 x 37) in the
long-document cell), each a fixed cost of a few microseconds whatever its
bytes, and each a few more operations to trace, lower and compile.

:func:`gather_pages` is the same view as one kernel: a grid over (layer block,
lane, slot block) whose index maps read the page ids from the prefetched
tables, so the pipeline fetches each page block into fast memory and writes it
out as the view's columns ``p * page ..``.  Nothing is filled first: every block
of the view is written once, a slot the tables mark ``NULL_PAGE`` with the null
page's contents, as the update form writes it.  A lane's dead slots all name
the null page, and a pipeline does not fetch again a block it fetched for the
step before, so the null page is read once a lane and slot position.

The view's layout is the one the scan carries, positions minor, and the kernel
takes the pool in the layout the program holds it in, which the chip picks from
the widths: a pool whose head width is whole lanes (``D`` 128) is ``D``-minor,
and each ``[page, D]`` tile is transposed in fast memory; a narrower one (``D``
64) is ``page``-minor, a page is then ``[H * D, page]`` as it lies, and the
kernel copies it.  Neither form puts a copy of the pool or of the view in front
of the kernel or behind it (``tests/test_tpu_compile.py``).

``docs/kernels/view_gather.md`` has the plan, the bytes and the measurements.
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
from .view_attention import _xla_form

#: bytes of the pool a grid step moves at most: layers and pages are put
#: together up to it, so that a step's fixed cost is small against its bytes
STEP_BYTES = 2 * 1024 * 1024
#: four blocks of a step in flight (in and out, double-buffered) and the
#: transposed tiles
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: rows of a bfloat16 tile: the flattened ``H * D`` rows are whole tiles of
#: each head's ``D`` only where ``D`` is a multiple of it
_SUBLANES = 16


def _page_minor(d: int) -> bool:
    """Whether the chip holds a pool ``[.., H, page, D]`` with ``page`` minor:
    where the head width is not whole lanes the chip puts the page's 128
    positions minor, which pads nothing (GPT-2-XL's 64-wide heads); where it is,
    the array stays as written, ``D`` minor."""
    return d % NUM_LANES != 0


def _plan(layers: int, slots: int, block_bytes: int):
    """``(layers a block, pages a step)``: the most layers, then the most pages,
    that divide the array's and keep a step's pool bytes within
    :data:`STEP_BYTES` (``block_bytes``: one page of one layer)."""
    fit = lambda n, each: max(x for x in range(1, n + 1) if n % x == 0 and x * each <= STEP_BYTES)
    per_block = fit(layers, block_bytes)
    return per_block, fit(slots, per_block * block_bytes)


def view_gather_applies(pages, interpret: Optional[bool] = None) -> bool:
    """Whether the kernel fills the flat view of ``pages [L, NP, H, page, D]``
    (an array or its shape and dtype): bfloat16, pages of whole lanes, heads
    whose rows are whole tiles, one page of one layer within a step's bytes, on a
    TPU (or wherever a caller says how to run it: ``interpret=True`` is the CPU
    tests' way) and not under :func:`~accelerate_tpu.ops.view_attention.xla_form`
    (a pool sharded over key/value heads: a ``pallas_call`` has no partitioning
    rule).  The zero fill and page-wide updates serve everything else."""
    _, _, h, page, d = pages.shape
    return (pages.dtype == jnp.bfloat16 and page % NUM_LANES == 0 and d % _SUBLANES == 0
            and h * d * page * pages.dtype.itemsize <= STEP_BYTES
            and not _xla_form.get()
            and (interpret is not None or _platform_compiles()))


def _kernel(tables_ref, *refs, transpose: bool):
    *sources, out_ref = refs
    for i, src in enumerate(sources):
        if transpose:
            # [lb, 1, H, page, D] -> [lb, H * D, page]: each head's tile turned
            lb, _, h, page, d = src.shape
            for layer in range(lb):
                for head in range(h):
                    out_ref[layer, 0, head * d:(head + 1) * d, i * page:(i + 1) * page] = src[layer, 0, head].T
        else:
            page = src.shape[3]
            out_ref[:, :, :, i * page:(i + 1) * page] = src[...]


@functools.partial(jax.jit, static_argnames=("interpret", "layer"))
def _call(pages, tables, *, interpret, layer):
    # jitted: a program gathers K and V (and each kind's arrays) of a few
    # shapes, traced and lowered once a shape and not once a call
    layers, num_pages, h, page, d = pages.shape
    n, slots = tables.shape
    out_layers, first = (layers, 0) if layer is None else (1, layer)
    per_block, per_step = _plan(out_layers, slots, h * d * page * pages.dtype.itemsize)
    ids = jnp.clip(tables, 0, num_pages - 1).reshape(-1)      # as ``dynamic_slice`` clamps
    transpose = not _page_minor(d)
    if transpose:
        source, block = pages, (per_block, 1, h, page, d)
    else:
        # [.., H, D, page] is the pool as the chip holds it: a bitcast
        source, block = pages.swapaxes(3, 4).reshape(layers, num_pages, h * d, page), (per_block, 1, h * d, page)
    zeros = (0,) * (len(block) - 2)

    def page_of(i):
        return lambda lb, lane, sb, ids: (first + lb, ids[lane * slots + sb * per_step + i], *zeros)

    view = pl.pallas_call(
        functools.partial(_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(out_layers // per_block, n, slots // per_step),
            in_specs=[pl.BlockSpec(block, page_of(i)) for i in range(per_step)],
            out_specs=pl.BlockSpec((per_block, 1, h * d, per_step * page),
                                   lambda lb, lane, sb, ids: (lb, lane, 0, sb)),
        ),
        out_shape=jax.ShapeDtypeStruct((out_layers, n, h * d, slots * page), pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="view_gather",
    )(ids, *([source] * per_step))
    return view if layer is None else view[0]


def gather_pages(pages, tables, *, layer: Optional[int] = None, interpret: Optional[bool] = None):
    """``pages [L, NP, H, page, D]`` gathered through ``tables [N, P]`` into the
    flat view ``[L, N, H * D, P * page]``: lane ``n``'s columns ``p * page ..``
    hold page ``tables[n, p]`` (an id past the pool clamped to its last page,
    as ``dynamic_slice`` clamps), bit for bit :func:`~accelerate_tpu.serving.pool
    ._gather_view`'s update form.  With ``layer`` the view of that layer alone,
    ``[N, H * D, P * page]``: a program that carries each layer's view as an
    array of its own reads it whole, where a layer of the stacked view is a
    static slice that the compiler may copy out.  :func:`view_gather_applies`
    says where it runs.

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if interpret is None:
        interpret = not _platform_compiles()
    return _call(pages, tables.astype(jnp.int32), interpret=interpret, layer=layer)

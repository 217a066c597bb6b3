"""A prefill chunk's attention over a positions-minor cache view: a Pallas flash kernel.

:func:`~accelerate_tpu.models.transformer.cached_attention` gets ``q [B, S, Hq,
D]`` and one layer's view ``k``/``v [B, Hkv * D, M]``, ``M`` the most a lane may
ever hold (32,768 columns in the long-document cell).  Written in XLA it forms
float32 logits ``[B, Hkv, rep, S, M]`` over all of the view, masks them and runs
a softmax: three passes over gigabytes of scores through HBM, most of them over
columns that hold nothing yet (a chunk at ``base`` can see ``base + S`` keys).

:func:`view_flash_attention` is the same attention as one kernel that

* visits only the key blocks that can hold a visible key: how many there are
  (and, under a band, where they start) is worked out from ``q_positions`` on
  the device and handed over as scalar-prefetch arguments, which the K and V
  index maps read (the pattern :mod:`.grouped_matmul` uses for its visits): a
  dead block is never fetched, its grid step does nothing;
* keeps the scores, the running maximum and sum and the accumulator in fast
  memory (online softmax, as :mod:`.flash_attention`), so no ``[rows, keys]``
  array touches HBM;
* reads the view as it lies: ``K [D, block]`` is the right-hand side of ``q K``
  and ``P V`` contracts the minor dimension of both, so there is no transposed
  copy of a view; ``q`` and the output are read and written as ``[S, rep * D]``
  column blocks of ``[B, S, Hq * D]``, so there is no transposed copy of them
  either.  The ``rep`` query heads of a key/value head share its K and V blocks.

The masks are ``cached_attention``'s three: ``j <= position``; the band ``j >
position - window``; and the ring's ``held = hi - ((hi - j) mod M)`` with ``held
>= 0``.  Each is a row vector of the position a column holds (``_held``) compared
with the query rows' positions, worked out once a block for all ``rep`` heads,
and only in the blocks that need one: a block every query row sees whole takes
the unmasked body.  float32 scores, sums and accumulator; probabilities are cast
to the view's dtype for ``P V`` and normalised once at the end.

A decode step (one query row a lane) is bound by the view's bytes, not by
scores: written in XLA it reads every column of every lane's view twice (the
score reduction, then ``P V``), whatever the lanes hold.
:func:`view_decode_attention` reads only the key blocks that hold a key the
lane's query sees, all key/value heads of a lane's block at once, in one kernel
a layer that walks the list of live (lane, key block) pairs with its own
double-buffered copies: no grid step is spent on a dead block.

``docs/kernels/view_attention.md`` has the grid, the block sizes and the
measurements.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_MASK_VALUE, NUM_LANES
from .retention import _platform_compiles

#: columns of a key block; a view's width need not divide by it (the ring of 37
#: pages of 128 does not): the last block's tail is masked.  Measured 256 to
#: 2,048: 1,024 is within 1 % of the best for a 512-chunk and a fifth faster
#: than 512 for a 128-chunk, whose steps are short
KEY_BLOCK = 1024
#: query rows of a block: a chunk bucket is one block, a longer prefill several
_ROW_BLOCK = 512
#: the narrowest view the kernel takes: under it XLA's one pass is cheap enough
_MIN_VIEW = 2048
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
#: a column that holds no position: no query position reaches it
_NOTHING = 2 ** 30

# rows of the prefetched scalars, one column a (lane, row block)
_FIRST, _COUNT, _LOW, _HIGH, _HI, _HI_COLUMN = range(6)

_xla_form = contextvars.ContextVar("view_attention_xla_form", default=False)


@contextlib.contextmanager
def xla_form():
    """Trace what is inside with ``cached_attention``'s masked einsum whatever
    the shapes.  For a program whose views are sharded over key/value heads (the
    engine under ``tp > 1``): a ``pallas_call`` has no partitioning rule, so the
    compiler would gather every view whole onto every chip to run it."""
    token = _xla_form.set(True)
    try:
        yield
    finally:
        _xla_form.reset(token)


def view_flash_applies(q, k_view, interpret: Optional[bool] = None) -> bool:
    """Whether the kernel takes ``q [B, S, Hq, D]`` against ``k_view [B, Hkv * D,
    M]`` (arrays or their shapes and dtypes): bfloat16, a chunk's worth of rows
    (``S >= 128``: never a decode or verify window), heads of whole lanes, a view
    of whole lanes at least 2,048 wide, on a TPU (or wherever a caller says how
    to run it: ``interpret=True`` is the CPU tests' way) and not under
    :func:`xla_form`.  The masked einsum serves everything else."""
    s, d = q.shape[1], q.shape[3]
    m = k_view.shape[2]
    return (q.dtype == jnp.bfloat16 and k_view.dtype == jnp.bfloat16
            and s >= NUM_LANES and d % NUM_LANES == 0
            and m % NUM_LANES == 0 and m >= _MIN_VIEW
            and not _xla_form.get()
            and (interpret is not None or _platform_compiles()))


def _held(j, hi, hi_column, m: int, ring: bool):
    """The position column ``j`` holds (scalars or a row vector of columns): its
    own in a view written from 0; in a ring the newest one congruent to it that
    has been written, ``hi - ((hi - j) mod m)``, negative where none has.
    ``hi_column = hi mod m`` spares the vector unit a division."""
    if not ring:
        return j
    return hi - hi_column + j - jnp.where(j > hi_column, m, 0)


def _kernel(meta_ref, q_ref, k_ref, v_ref, pos_ref, out_ref, acc_ref, m_ref, l_ref, *,
            scale: float, m_cols: int, window: Optional[int], ring: bool):
    rep, _, d = acc_ref.shape
    block = k_ref.shape[2]
    kb = pl.program_id(3)
    at = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)
    first, count = meta_ref[_FIRST, at], meta_ref[_COUNT, at]
    low, high = meta_ref[_LOW, at], meta_ref[_HIGH, at]             # the row block's least and greatest position
    hi, hi_column = meta_ref[_HI, at], meta_ref[_HI_COLUMN, at]
    tail = m_cols % block != 0
    j0 = (first + kb) * block

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(masked: bool):
        k, v = k_ref[0], v_ref[0]                                                    # [D, block]
        mask = None
        if masked:
            j = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            held = _held(j, hi, hi_column, m_cols, ring)
            if ring:
                held = jnp.where(held < 0, _NOTHING, held)
            if tail:
                # past the view's last column the block holds whatever lay there
                held = jnp.where(j >= m_cols, _NOTHING, held)
                v = jnp.where(j < m_cols, v, jnp.zeros_like(v))
            pos = jnp.tile(pos_ref[0], (1, block // NUM_LANES))                      # [rows, block]
            mask = held <= pos
            if window is not None:
                mask &= held > pos - window
        for g in range(rep):
            # the rep query heads of this key/value head against the one K / V block
            q = q_ref[0, :, g * d:(g + 1) * d]
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[g]                                                        # [rows, 128], lanes alike
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - jnp.tile(m_next, (1, block // NUM_LANES)))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[g] = m_next
            pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)             # [rows, D]
            acc_ref[g] = acc_ref[g] * jnp.tile(alpha, (1, d // NUM_LANES)) + pv

    @pl.when(kb < count)
    def _():
        # whether any (row, column) of the block is masked: from the positions at
        # the block's two ends, which bound the rest unless the ring's seam (the
        # oldest column, right of the newest) lies inside
        j1 = j0 + block - 1
        oldest, newest = (_held(j, hi, hi_column, m_cols, ring) for j in (j0, j1))
        needs = newest > low
        if window is not None:
            needs |= oldest <= high - window
        if ring:
            needs |= (oldest < 0) | ((j0 <= hi_column) & (hi_column < j1))
        if tail:
            needs |= j1 >= m_cols
        pl.when(needs)(lambda: visit(True))
        pl.when(jnp.logical_not(needs))(lambda: visit(False))

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        for g in range(rep):
            # every query sees a key (its own), so the sum is at least exp(0)
            out_ref[0, :, g * d:(g + 1) * d] = (
                acc_ref[g] * jnp.tile(1.0 / l_ref[g], (1, d // NUM_LANES))).astype(out_ref.dtype)


def _plan(q_positions, rows: int, block: int, m_cols: int, window: Optional[int], ring: bool):
    """The prefetched scalars ``[6, B * row_blocks]``: for each (lane, row block)
    the first key block that can hold a visible key and how many follow it, the
    block's least and greatest query position, and the ring's ``hi`` and ``hi mod
    M``.  A view is written from 0, so its live blocks end at the greatest
    position's; a band starts them at ``least - window + 1``; a ring that has
    wrapped is live everywhere."""
    b, s = q_positions.shape
    blocks = q_positions.reshape(b, s // rows, rows)
    low, high = blocks.min(axis=2), blocks.max(axis=2)
    hi = jnp.broadcast_to(q_positions[:, -1:], low.shape)
    if ring:
        first = jnp.zeros_like(low)
        last = jnp.minimum(hi, m_cols - 1) // block
    else:
        first = jnp.zeros_like(low) if window is None else jnp.maximum(low - window + 1, 0) // block
        last = jnp.minimum(high, m_cols - 1) // block
    count = jnp.maximum(last - first + 1, 1)
    meta = jnp.stack([first, count, low, high, hi, hi % m_cols])
    return meta.reshape(6, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "ring", "interpret", "block"))
def _call(q, k_view, v_view, q_positions, *, window, ring, interpret, block):
    # jitted: a program's layers call it with the same shapes, and are traced
    # and lowered to the Mosaic kernel once a (rows, view, mask) and not a layer
    b, s, n_q, d = q.shape
    m_cols = k_view.shape[2]
    n_kv = k_view.shape[1] // d
    rep = n_q // n_kv
    q_positions = q_positions.astype(jnp.int32)
    # whole row blocks: the rows added repeat the last one and are cut off again
    padded = -(-s // NUM_LANES) * NUM_LANES
    rows = next(r for r in (_ROW_BLOCK, 256, NUM_LANES) if padded % r == 0)
    q2 = q.reshape(b, s, n_q * d)
    if padded != s:
        q2 = jnp.pad(q2, ((0, 0), (0, padded - s), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, padded - s)), mode="edge")
    row_blocks = padded // rows
    key_blocks = -(-m_cols // block)
    meta = _plan(q_positions, rows, block, m_cols, window, ring)
    pos = jax.lax.broadcast_in_dim(q_positions, (b, padded, NUM_LANES), (0, 1))

    def key_block(lane, head, rb, kb, meta):
        at = lane * row_blocks + rb
        return lane, head, meta[_FIRST, at] + jnp.minimum(kb, meta[_COUNT, at] - 1)  # a dead step fetches nothing new

    a_row_block = pl.BlockSpec((1, rows, rep * d), lambda lane, head, rb, kb, meta: (lane, rb, head))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=d ** -0.5, m_cols=m_cols, window=window, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kv, row_blocks, key_blocks),
            in_specs=[a_row_block,
                      pl.BlockSpec((1, d, block), key_block),
                      pl.BlockSpec((1, d, block), key_block),
                      pl.BlockSpec((1, rows, NUM_LANES), lambda lane, head, rb, kb, meta: (lane, rb, 0))],
            out_specs=a_row_block,
            scratch_shapes=[pltpu.VMEM((rep, rows, d), jnp.float32),
                            pltpu.VMEM((rep, rows, NUM_LANES), jnp.float32),
                            pltpu.VMEM((rep, rows, NUM_LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q2.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="view_flash_attention",
    )(meta, q2, k_view, v_view, pos)
    return out[:, :s].reshape(b, s, n_q, d)


def view_flash_attention(q, k_view, v_view, q_positions, *, window: Optional[int] = None,
                         ring: bool = False, interpret: Optional[bool] = None,
                         block: int = KEY_BLOCK):
    """Attention of ``q [B, S, Hq, D]`` against one layer's cache view ``k_view``
    / ``v_view [B, Hkv * D, M]`` (positions minor, ``D`` and ``M`` multiples of
    128), ``q_positions [B, S]``: ``cached_attention``'s result for its causal,
    banded (``window``) and ring (``window`` and ``ring``: position ``p`` in
    column ``p % M``, the lane's last query position the newest written) masks,
    ``[B, S, Hq, D]`` in ``q``'s dtype.  Every query must see a key (its own).

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if ring and window is None:
        raise ValueError("a ring view is a window layer's: pass window=")
    d, m_cols = q.shape[3], k_view.shape[2]
    if d % NUM_LANES or m_cols % NUM_LANES or block % NUM_LANES or k_view.shape[1] % d:
        raise ValueError(f"view_flash_attention wants heads, view and key block of whole lanes: "
                         f"q {q.shape}, view {k_view.shape}, block {block}")
    if interpret is None:
        interpret = not _platform_compiles()
    return _call(q, k_view, v_view, q_positions, window=window, ring=ring,
                 interpret=interpret, block=min(block, m_cols))


# ------------------------------------------------------------------- decode
#: columns of a decode key block: all key/value heads of a lane's block are
#: read at once, ``[Hkv * D, block]`` (1 MB a block of K on Trinity's view).
#: Measured 512 and 1,024 on one v5e: 512 skips more of a ring behind its band
#: and of a lane's last block, 2 % faster for Trinity's window and 11-20 % for
#: Mellum2's views at the cell's depths, within 5 % where every block is live
DECODE_BLOCK = 512
#: the narrowest view a decode step reads in the kernel: from 1,024 columns up
#: the kernel was as fast as the einsum or faster at the cells' depths (one
#: v5e, 128-wide heads); narrower views were not measured
_MIN_DECODE_VIEW = 1024
#: query rows a key/value head's ``rep`` heads are padded to: the sublanes of a
#: bfloat16 tile
_DECODE_ROWS = 16
#: fast memory the decode kernel's buffers may take (:func:`_decode_fast_bytes`):
#: the rest of ``_VMEM_LIMIT_BYTES`` is left to a block's scores and to the
#: compiler's own
_DECODE_FAST_BYTES = 48 * 1024 * 1024
#: the most live (lane, key block) pairs the plan may list in scalar memory: the
#: longest list compiled for a v5e (100 lanes of 512 blocks), longer than any
#: whose views fit the chip's HBM at 128-wide heads
_DECODE_MAX_PAIRS = 51200
#: rows of the decode plan, one column a lane
_POSITION, _POSITION_COLUMN, _PAIRS_FROM, _PAIRS_TO = range(4)


def _decode_fast_bytes(lanes: int, n_kv: int, rep: int, d: int, m_cols: int, itemsize: int) -> int:
    """The decode kernel's fast memory: a key block of K and of V twice over,
    every lane's query and output, and one lane's float32 accumulator, running
    maximum and sum (lane-replicated: an f32 column takes a tile's 128 lanes
    whatever its width)."""
    rows = -(-rep // _DECODE_ROWS) * _DECODE_ROWS
    block = min(DECODE_BLOCK, m_cols)
    return (2 * 2 * n_kv * d * block * itemsize
            + 2 * lanes * n_kv * rows * d * itemsize
            + n_kv * rows * (d + 2 * NUM_LANES) * 4)


def view_decode_applies(q, k_view, interpret: Optional[bool] = None) -> bool:
    """Whether the decode kernel takes ``q [B, S, Hq, D]`` against ``k_view [B,
    Hkv * D, M]`` (arrays or their shapes and dtypes): bfloat16, one query row
    a lane (``S == 1``: a decode step, never a verify window), heads of whole
    lanes, a view of whole lanes at least 1,024 wide, lanes few enough that
    their queries and outputs fit the kernel's fast memory and their key blocks
    its list (``_DECODE_FAST_BYTES``, ``_DECODE_MAX_PAIRS``), on a TPU (or
    wherever a caller says how to run it) and not under :func:`xla_form`.  The
    masked einsum serves everything else."""
    lanes, s, n_q, d = q.shape
    m = k_view.shape[2]
    if not (q.dtype == jnp.bfloat16 and k_view.dtype == jnp.bfloat16
            and s == 1 and d % NUM_LANES == 0
            and m % NUM_LANES == 0 and m >= _MIN_DECODE_VIEW):
        return False
    n_kv = k_view.shape[1] // d
    return (_decode_fast_bytes(lanes, n_kv, n_q // n_kv, d, m, 2) <= _DECODE_FAST_BYTES
            and lanes * -(-m // DECODE_BLOCK) <= _DECODE_MAX_PAIRS
            and not _xla_form.get()
            and (interpret is not None or _platform_compiles()))


def live_key_blocks(last, m_cols: int, block: int, window: Optional[int], ring: bool, xp=jnp):
    """``bool [..., ceil(M / block)]``: the key blocks of a view ``M`` wide that
    hold a key a query at position ``last [...]`` sees (``xp``: ``jnp`` on the
    device, ``numpy`` on the host).  A view written from 0 is live from the
    band's first block (0 without a band) to the query's; a ring's block is live
    where the newest position it holds, ``_held`` at its last column or the
    query's own where the ring's seam lies inside, has been written and lies in
    the band: a ring that has wrapped is live everywhere but in the blocks that
    lie wholly behind the band."""
    starts = xp.arange(-(-m_cols // block)) * block
    ends = xp.minimum(starts + block, m_cols) - 1
    last = xp.asarray(last)[..., None]
    if not ring:
        live = starts <= last
        return live if window is None else live & (ends > last - window)
    column = last % m_cols
    held_end = last - column + ends - xp.where(ends > column, m_cols, 0)
    newest = xp.where((starts <= column) & (column <= ends), last, held_end)
    return (newest >= 0) & (newest > last - window)


def _decode_kernel(pairs_ref, meta_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf, sems,
                   acc_ref, m_ref, l_ref, *, scale: float, m_cols: int, window: Optional[int],
                   ring: bool):
    lanes = q_ref.shape[0]
    n_kv, _, d = acc_ref.shape
    block = k_buf.shape[2]
    total = meta_ref[_PAIRS_TO, lanes - 1]
    # the view's last block is read as the block that ends at the view's end;
    # its columns that belong to the block before are masked
    start_of = lambda kb: pl.multiple_of(jnp.minimum(kb * block, m_cols - block), NUM_LANES)

    def copies(t, slot):
        lane, start = pairs_ref[0, t], start_of(pairs_ref[1, t])
        return [pltpu.make_async_copy(view.at[lane, :, pl.ds(start, block)], buf.at[slot], sems.at[i, slot])
                for i, (view, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]

    for copy in copies(0, 0):
        copy.start()

    def attend(lane, carry):
        pos, pos_column = meta_ref[_POSITION, lane], meta_ref[_POSITION_COLUMN, lane]

        def visit(t, carry):
            slot = t % 2

            # the next pair's copy, the next lane's first where this is a lane's last
            @pl.when(t + 1 < total)
            def _():
                for copy in copies(t + 1, 1 - slot):
                    copy.start()

            for copy in copies(t, slot):
                copy.wait()
            kb = pairs_ref[1, t]
            j = start_of(kb) + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            held = _held(j, pos, pos_column, m_cols, ring)
            # one query row a lane: one mask for all its heads
            mask = (held <= pos) & (j >= kb * block)
            if window is not None:
                mask &= held > pos - window
            if ring:
                mask &= held >= 0
            for h in range(n_kv):
                k = k_buf[slot, h * d:(h + 1) * d, :]                                    # [D, block]
                v = v_buf[slot, h * d:(h + 1) * d, :]
                s = jax.lax.dot_general(q_ref[lane, h], k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32) * scale       # [rows, block]
                s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
                m_prev = m_ref[h]                                                         # [rows, 128], lanes alike
                m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - jnp.tile(m_next, (1, block // NUM_LANES)))
                alpha = jnp.exp(m_prev - m_next)
                l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
                m_ref[h] = m_next
                pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)              # [rows, D]
                acc_ref[h] = acc_ref[h] * jnp.tile(alpha, (1, d // NUM_LANES)) + pv
            return carry

        # one lane's state at a time: the list holds the pairs lane by lane
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        jax.lax.fori_loop(meta_ref[_PAIRS_FROM, lane], meta_ref[_PAIRS_TO, lane], visit, 0)
        # every lane sees a key (its own), so every sum is at least exp(0)
        out_ref[lane] = (acc_ref[...] * jnp.tile(1.0 / l_ref[...], (1, 1, d // NUM_LANES))).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, lanes, attend, 0)


def _decode_plan(positions, m_cols: int, window: Optional[int], ring: bool):
    """``(pairs [2, B * blocks], meta [4, B])``: the live (lane, key block)
    pairs lane by lane (the list's tail repeats the first pair and is never
    visited), and for each lane its position, the position's column ``mod M``
    and where its pairs start and end in the list."""
    live = live_key_blocks(positions, m_cols, DECODE_BLOCK, window, ring)            # [B, blocks]
    blocks = live.shape[1]
    (flat,) = jnp.nonzero(live.reshape(-1), size=live.size, fill_value=0)
    pairs = jnp.stack([flat // blocks, flat % blocks])
    counts = jnp.sum(live, axis=1)
    end = jnp.cumsum(counts)
    return pairs.astype(jnp.int32), jnp.stack([positions, positions % m_cols, end - counts, end]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "ring", "interpret"))
def _decode_call(q, k_view, v_view, q_positions, *, window, ring, interpret):
    b, _, n_q, d = q.shape
    m_cols = k_view.shape[2]
    n_kv = k_view.shape[1] // d
    rep = n_q // n_kv
    rows = -(-rep // _DECODE_ROWS) * _DECODE_ROWS
    q4 = q.reshape(b, n_kv, rep, d)
    if rows != rep:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, rows - rep), (0, 0)))
    pairs, meta = _decode_plan(q_positions[:, 0].astype(jnp.int32), m_cols, window, ring)
    smem, vmem = (pl.BlockSpec(memory_space=space) for space in (pltpu.SMEM, pltpu.VMEM))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = min(DECODE_BLOCK, m_cols)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=d ** -0.5, m_cols=m_cols, window=window, ring=ring),
        in_specs=[smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((2, n_kv * d, block), k_view.dtype),
                        pltpu.VMEM((2, n_kv * d, block), v_view.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((n_kv, rows, d), jnp.float32),
                        pltpu.VMEM((n_kv, rows, NUM_LANES), jnp.float32),
                        pltpu.VMEM((n_kv, rows, NUM_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="view_decode_attention",
    )(pairs, meta, q4, k_view, v_view)
    return out[:, :, :rep].reshape(b, 1, n_q, d)


def view_decode_attention(q, k_view, v_view, q_positions, *, window: Optional[int] = None,
                          ring: bool = False, interpret: Optional[bool] = None):
    """A decode step's attention of ``q [B, 1, Hq, D]`` against one layer's
    cache view ``k_view`` / ``v_view [B, Hkv * D, M]`` (positions minor, ``D``
    and ``M`` multiples of 128), ``q_positions [B, 1]``: ``cached_attention``'s
    result under the same masks as :func:`view_flash_attention`, ``[B, 1, Hq,
    D]`` in ``q``'s dtype.  Only the key blocks (``DECODE_BLOCK`` columns) that
    hold a key a lane's query sees are read (:func:`live_key_blocks`).

    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if ring and window is None:
        raise ValueError("a ring view is a window layer's: pass window=")
    d, m_cols = q.shape[3], k_view.shape[2]
    if q.shape[1] != 1 or d % NUM_LANES or m_cols % NUM_LANES or k_view.shape[1] % d:
        raise ValueError(f"view_decode_attention wants one query row and heads and a view of whole "
                         f"lanes: q {q.shape}, view {k_view.shape}")
    if interpret is None:
        interpret = not _platform_compiles()
    return _decode_call(q, k_view, v_view, q_positions, window=window, ring=ring, interpret=interpret)

"""Paged KV allocator: one physical page pool behind every lane AND the prefix cache.

The slot pool gives each lane a contiguous ``max_len`` KV slab — worst-case
memory reserved up front, so mixed-length traffic caps concurrency at
``HBM / max_len`` lanes even when most requests are short.  vLLM's
PagedAttention breaks that: KV lives in fixed-size *pages*, a lane owns a
block table mapping logical positions to physical pages, pages are allocated
as the lane grows, and refcounting lets many lanes alias the same physical
page.  The TPU-native translation here keeps every device program fixed-shape
(:mod:`.pool` grows exactly one gather/scatter executable per existing shape)
while all allocation, refcounting, and copy-on-write stay host-side numpy:

* :class:`PageAllocator` — the refcounted free list.  Page id ``0`` is the
  reserved **null page**: freed or frozen lanes' garbage writes land there
  (their block-table rows are reset to null), so no compiled program ever
  needs a "has pages?" branch.
* :class:`PagedKVPool` — the device-resident page arrays
  ``[L, num_pages, Hkv, page_size, Dh]`` plus per-lane block tables
  (host ``[num_slots, pages_per_lane]`` int32, uploaded per cycle — a few KB).
  ``pages_per_lane * page_size == max_len`` exactly: the gathered per-lane
  view has the *same* width as ``generate``'s contiguous cache, so paged
  decode runs the bitwise-identical attention program (a wider view would
  change the softmax reduction shape and with it the last-ulp rounding —
  measured, not hypothetical).

* :class:`MixedKVPool` — the pool of a stack of two kinds of layer
  (``config.layer_types``), one retention rule a kind: the "full" layers keep
  every position of a lane, as above; the "window" layers keep a **ring** of
  pages a lane, and a page that falls wholly behind every position a later
  query can see goes back to the free list.
* :class:`StatePool` — the pool of a model that keeps no rows a token (a
  recurrent state of fixed size a lane): no pages, no tables, no allocator.

Sharing model: the prefix cache pins pages (one allocator ref per caching
node), every lane aliasing a cached prefix takes its own ref per page, and a
page returns to the free list only at refcount zero.  Copy-on-write happens in
exactly one place — the page holding a lane's first decode-write position
(``prompt_len - 1``) when that page is shared — everything a lane writes after
that lands in pages it owns alone.

Telemetry (documented in ``docs/usage/observability.md``):
``serve/kv_pages_in_use``, ``serve/kv_pages_free`` and
``serve/kv_bytes_shared`` published by :meth:`PagedKVPool.publish_gauges`;
``serve/preemptions_total`` is counted by the engine when page pressure forces
a lane to release its pages and requeue for replay.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..telemetry import MetricsRegistry, get_registry

#: Reserved garbage-sink page id. Never allocated, never freed; block-table
#: rows of inactive lanes point here so frozen-lane writes have a harmless
#: destination and gathers read finite (zero-initialised) values.
NULL_PAGE = 0


class PageAllocator:
    """Refcounted free-list allocator over ``num_pages`` physical pages.

    Page 0 is the permanently-pinned null page (:data:`NULL_PAGE`).  The free
    list hands out ascending ids deterministically — allocation order is part
    of the engine's reproducibility story (same workload, same tables).
    """

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError(f"need at least 2 pages (null + 1), got {num_pages}")
        self.refs = np.zeros(self.num_pages, np.int64)
        self.refs[NULL_PAGE] = 1  # never allocatable, never freed
        # pop() takes from the tail: ids come out ascending (1, 2, 3, ...)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Allocated pages (null excluded)."""
        return self.num_pages - 1 - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` pages (refcount 1 each) or ``None`` — all-or-nothing, so
        a partial grab under pressure never leaks pages."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self.refs[ids] += 1
        return ids

    def ref(self, ids: Sequence[int]) -> None:
        """One more reference on each of ``ids`` (aliasing a shared prefix)."""
        for p in ids:
            if self.refs[p] <= 0:
                raise RuntimeError(f"ref() on unallocated page {p}")
            self.refs[p] += 1

    def deref(self, ids: Sequence[int]) -> int:
        """Drop one reference per page; pages hitting zero return to the free
        list.  Returns how many pages were actually freed."""
        freed = 0
        for p in ids:
            if p == NULL_PAGE:
                continue
            self.refs[p] -= 1
            if self.refs[p] < 0:
                raise RuntimeError(f"page {p} refcount underflow")
            if self.refs[p] == 0:
                self._free.append(p)
                freed += 1
        return freed

    def shared_extra_refs(self) -> int:
        """Σ max(refs - 1, 0) over real pages: how many page-copies sharing is
        saving right now (the ``serve/kv_bytes_shared`` numerator)."""
        return int(np.maximum(self.refs[1:] - 1, 0).sum())


class PagedKVPool:
    """Device page arrays + host block tables for ``num_slots`` lanes.

    Parameters
    ----------
    config: the model's ``TransformerConfig`` (layer/head/dim geometry; pages
        use ``config.dtype`` unless ``kv_dtype`` says otherwise).
    num_slots: lane count (the decode batch dimension).
    max_len: per-lane logical KV capacity.  Must be a multiple of
        ``page_size`` — the gathered view is exactly this wide, which is what
        makes paged decode bitwise-identical to a contiguous cache.
    page_size: tokens per page (the prefix-cache chunk granularity must be a
        multiple of it; the engine uses gcd(prefill buckets) by default).
    num_pages: physical pages including the null page.  Must be at least
        ``max_len // page_size + 1`` so a single lane can always run to its
        capacity even with nothing else to reclaim.
    kv_dtype: page storage format — ``None`` keeps ``config.dtype`` (the
        token-identical path), ``"bf16"`` stores bf16, ``"int8"`` / ``"fp8"``
        store quantized pages with one f32 dequantization scale per
        (layer, page, kv-head) written at scatter time
        (:func:`accelerate_tpu.ops.paged_attention.paged_quantized_insert`).
        Scale arrays exist for every format (ones when direct-store) so the
        compiled window signature does not fork on the dtype knob.
    """

    def __init__(self, config, num_slots: int, max_len: int, page_size: int,
                 num_pages: int, registry: Optional[MetricsRegistry] = None,
                 kv_dtype: Optional[str] = None, mesh=None,
                 tp_axis: str = "tp", num_layers: Optional[int] = None):
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size {page_size} "
                f"(the gathered view must match the legacy slab width exactly)"
            )
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.num_slots = int(num_slots)
        self.pages_per_lane = self.max_len // self.page_size
        self.num_pages = int(num_pages)
        if self.num_pages < self.pages_per_lane + 1:
            raise ValueError(
                f"num_pages {num_pages} cannot hold one full lane "
                f"({self.pages_per_lane} pages) plus the null page"
            )
        cfg = config
        from ..ops.paged_attention import kv_qmax, kv_storage_dtype

        self.kv_dtype = kv_dtype
        self.storage_dtype = kv_storage_dtype(kv_dtype, cfg.dtype)
        self.quantized = kv_qmax(self.storage_dtype) is not None
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            from ..parallel.mesh import mesh_axis_size

            self.tp_degree = mesh_axis_size(mesh, tp_axis)
        else:
            self.tp_degree = 1
        if self.tp_degree > 1 and cfg.num_kv_heads % self.tp_degree != 0:
            raise ValueError(
                f"num_kv_heads {cfg.num_kv_heads} must divide evenly over "
                f"tp={self.tp_degree} to shard the page pool on the head axis"
            )
        # kv-head axis OUTSIDE the page: a (page, Dh) tile per (page, head)
        # is the block shape the TPU kernels in ops/paged_attention.py need.
        # The row shapes are the configuration's: K and V of every kv head,
        # or a latent-attention model's one latent and one rope key a token.
        (k_heads, k_width), (v_heads, v_width) = cfg.cache_row_shapes
        # the layers whose rows these arrays hold: all of them, or the one
        # kind of a two-rule pool (:class:`MixedKVPool`)
        num_layers = cfg.num_layers if num_layers is None else int(num_layers)
        lead = (num_layers, self.num_pages)
        k_shape = lead + (k_heads, self.page_size, k_width)
        v_shape = lead + (v_heads, self.page_size, v_width)
        if mesh is not None:
            # head-axis NamedSharding: each device holds Hkv/tp heads of every
            # page.  Block tables / refcounts stay host-side and whole.
            from jax.sharding import NamedSharding, PartitionSpec

            ax = tp_axis if self.tp_degree > 1 else None
            kv_sh = NamedSharding(mesh, PartitionSpec(None, None, ax, None, None))
            sc_sh = NamedSharding(mesh, PartitionSpec(None, None, ax))
            # allocated in place on the mesh's own devices: a replica on chip
            # i must never stage its pool through the default device
            self.pages_k = jnp.zeros(k_shape, self.storage_dtype, device=kv_sh)
            self.pages_v = jnp.zeros(v_shape, self.storage_dtype, device=kv_sh)
            self.k_scales = jnp.ones(lead + (k_heads,), jnp.float32, device=sc_sh)
            self.v_scales = jnp.ones(lead + (v_heads,), jnp.float32, device=sc_sh)
        else:
            self.pages_k = jnp.zeros(k_shape, self.storage_dtype)
            self.pages_v = jnp.zeros(v_shape, self.storage_dtype)
            # per-(layer, page, kv-head) dequantization scales; ones (a no-op
            # multiply the direct-store windows never read) when not quantized
            self.k_scales = jnp.ones(lead + (k_heads,), jnp.float32)
            self.v_scales = jnp.ones(lead + (v_heads,), jnp.float32)
        #: bytes of k+v one page holds, scales included — the sharing/HBM
        #: accounting unit
        itemsize = jnp.zeros((), self.storage_dtype).itemsize
        self.page_kv_bytes = int(num_layers * (
            (k_heads * k_width + v_heads * v_width) * self.page_size * itemsize
            + (k_heads + v_heads) * 4
        ))
        self.allocator = PageAllocator(self.num_pages)
        # host block tables: row s maps lane s's logical page slots to
        # physical ids; NULL_PAGE marks unmapped (garbage-sink) entries
        self.tables = np.zeros((self.num_slots, self.pages_per_lane), np.int32)
        self.lane_npages = np.zeros(self.num_slots, np.int32)

        registry = registry if registry is not None else get_registry()
        self._in_use_gauge = registry.gauge(
            "serve/kv_pages_in_use", help="allocated KV pages (null page excluded)"
        )
        self._free_gauge = registry.gauge(
            "serve/kv_pages_free", help="KV pages on the free list"
        )
        self._shared_gauge = registry.gauge(
            "serve/kv_bytes_shared",
            help="KV bytes extra references alias instead of copying "
                 "(sum of (refs-1) * page_bytes over shared pages)",
        )
        registry.gauge(
            "serve/kv_bytes_per_token",
            help="per-device KV HBM one token costs across all layers at the "
                 "pool's storage dtype, amortized per-page scales included "
                 "(the head axis divides exactly over tp when sharded)",
        ).set(self.page_kv_bytes / self.page_size / self.tp_degree)
        self.publish_gauges()

    # -------------------------------------------------------------- lane ops
    def lane_append_owned(self, slot: int, ids: Sequence[int]) -> None:
        """Map freshly allocated pages (refcount already 1, owned by caller —
        ownership transfers to the lane) onto the next logical slots."""
        n = self.lane_npages[slot]
        for i, p in enumerate(ids):
            self.tables[slot, n + i] = p
        self.lane_npages[slot] = n + len(ids)

    def lane_append_shared(self, slot: int, ids: Sequence[int]) -> None:
        """Alias already-resident pages (a prefix-cache hit): takes one new
        reference per page, then maps them.  Zero device work — this IS the
        zero-copy hit path."""
        self.allocator.ref(ids)
        self.lane_append_owned(slot, ids)

    def lane_replace(self, slot: int, page_slot: int, new_id: int) -> int:
        """Copy-on-write bookkeeping: swap one logical slot to ``new_id``
        (already allocated by the caller) and drop the lane's reference on the
        old physical page.  Returns the old id (the copy source)."""
        old = int(self.tables[slot, page_slot])
        self.tables[slot, page_slot] = new_id
        self.allocator.deref([old])
        return old

    def lane_release(self, slot: int) -> int:
        """Unmap the whole lane (finish / cancel / preempt): deref every
        mapped page and reset the row to the null sink.  Returns pages freed."""
        freed = self.allocator.deref(self.lane_detach(slot))
        return freed

    def lane_detach(self, slot: int) -> List[int]:
        """Unmap the lane NOW but keep its page references alive: the row
        resets to the null sink (the next table upload routes any further
        write for this lane to the garbage page) and the physical ids come
        back to the caller, who derefs them later.  This is the async serve
        loop's deferred release: a window dispatched while the lane was live
        still holds the OLD table on device and may write these pages, so
        they must not return to the allocator until that window retires
        (:meth:`~accelerate_tpu.serving.readback.Readback.settle`)."""
        n = int(self.lane_npages[slot])
        held = [int(p) for p in self.tables[slot, :n]]
        self.tables[slot, :] = NULL_PAGE
        self.lane_npages[slot] = 0
        return held

    def chunk_ids(self, slot: int, start_page: int, n: int) -> List[int]:
        """Physical ids backing ``n`` logical page slots from ``start_page``
        (what the prefix cache retains for a freshly prefilled chunk)."""
        return [int(p) for p in self.tables[slot, start_page:start_page + n]]

    def lane_pages(self, slot: int) -> List[int]:
        """Every physical id the lane currently maps, in logical order —
        the block-table row a migration marshals (the ids themselves stay
        behind; only their *content* travels, into pages the destination
        allocator hands out)."""
        return self.chunk_ids(slot, 0, int(self.lane_npages[slot]))

    # ------------------------------------------------------------- accounting
    def kv_bytes(self) -> int:
        """Device HBM held by the page arrays (the whole pool, null included)."""
        return (
            int(self.pages_k.nbytes) + int(self.pages_v.nbytes)
            + int(self.k_scales.nbytes) + int(self.v_scales.nbytes)
        )

    def kv_bytes_per_device(self) -> int:
        """Per-device share of :meth:`kv_bytes`: pages and scales both carry
        the kv-head axis, which splits exactly over the tp degree."""
        return self.kv_bytes() // self.tp_degree

    def chunk_bytes(self, npages: int) -> int:
        """Bytes ``npages`` pages of KV cost — K+V data at the storage dtype
        PLUS both per-page f32 scale slabs.  The ONE accounting unit every
        byte budget that charges per chunk must use (`prefix_cache_mb`,
        `prefix_host_mb`, the shared-bytes gauge): quantized pools carry real
        HBM in the scale slabs, and a budget that counted data bytes only
        would under-charge int8/fp8 entries by ``L * Hkv * 8`` bytes per
        page."""
        return int(npages) * self.page_kv_bytes

    def publish_gauges(self) -> None:
        self._in_use_gauge.set(self.allocator.used_count)
        self._free_gauge.set(self.allocator.free_count)
        self._shared_gauge.set(
            self.allocator.shared_extra_refs() * self.page_kv_bytes
        )


class MixedKVPool(PagedKVPool):
    """The pool of a stack of two kinds of attention layer
    (``config.layer_types``): page arrays, block tables and free list are kept
    a kind, because the two keep different numbers of positions.

    * The **full** layers are a :class:`PagedKVPool` over those layers alone
      (``pages_k`` / ``pages_v`` ``[n_full, num_pages, ...]``, ``tables
      [num_slots, max_len / page]``, ``allocator``): every position of a lane
      stays mapped until the lane ends.
    * The **window** layers keep a ring of ``ring_pages`` pages a lane
      (``ring_k`` / ``ring_v`` ``[n_window, num_slots * ring_pages + 1, ...]``,
      ``ring_tables [num_slots, ring_pages]``, ``ring_allocator``).  Logical
      page ``p`` (positions ``p * page ..``) maps to ring slot ``p %
      ring_pages``; :meth:`ring_advance` hands back every page that lies
      wholly behind the first position the next query can see (``query -
      window + 1``) before it maps the pages the next write needs.  With
      ``ring_pages = ceil((window + largest chunk) / page) + 1`` the pages a
      lane needs at once (a chunk's own, plus the window behind its first
      query) always fit, and the gathered view is the ring's width.

    The ring's array holds ``num_slots`` whole rings, so the window kind is
    never under page pressure and its pages are released the moment the host
    decides: every device program takes the arrays the one before it returned,
    so a page's next owner writes it in a later program than any that still
    reads it.  Page pressure, preemption and the deferred release of a retired
    lane (:meth:`lane_detach`) are the full kind's, as in the base class.
    There is no sharing: the engine builds no prefix cache on this pool (a
    released page cannot be shared)."""

    def __init__(self, config, num_slots: int, max_len: int, page_size: int,
                 num_pages: int, ring_pages: int,
                 registry: Optional[MetricsRegistry] = None):
        n_window, n_full = (config.layer_types.count(kind) for kind in ("window", "full"))
        registry = registry if registry is not None else get_registry()
        # (before the base class, whose constructor publishes the gauges)
        self.ring_pages = int(ring_pages)
        self.ring_allocator = PageAllocator(int(num_slots) * self.ring_pages + 1)
        self._kind_gauges = {
            kind: registry.gauge(
                f"serve/kv_pages_in_use_{kind}",
                help=f"allocated KV pages of the {kind} layers' pool (null page excluded)",
            ) for kind in ("full", "window")
        }
        super().__init__(config, num_slots, max_len, page_size, num_pages,
                         registry=registry, num_layers=n_full)
        self.window = int(config.sliding_window)
        if self.ring_pages * self.page_size < self.window + self.page_size:
            raise ValueError(
                f"a ring of {ring_pages} pages of {page_size} cannot hold a window of "
                f"{self.window} and the page being written"
            )
        (k_heads, k_width), (v_heads, v_width) = config.cache_row_shapes
        lead = (n_window, self.ring_allocator.num_pages)
        self.ring_k = jnp.zeros(lead + (k_heads, self.page_size, k_width), self.storage_dtype)
        self.ring_v = jnp.zeros(lead + (v_heads, self.page_size, v_width), self.storage_dtype)
        self.ring_tables = np.zeros((self.num_slots, self.ring_pages), np.int32)
        #: logical pages ``[ring_lo, ring_hi)`` of each lane are mapped
        self.ring_lo = np.zeros(self.num_slots, np.int64)
        self.ring_hi = np.zeros(self.num_slots, np.int64)

    # ------------------------------------------------------------- the ring
    def ring_advance(self, slot: int, query: int, last: int):
        """Make lane ``slot``'s ring ready for a program whose first query is
        at position ``query`` and whose last write is at ``last``: release the
        pages wholly behind ``query - window + 1`` (no query from here on can
        see them), then map the pages up to ``last``'s.  Returns ``(taken,
        released)`` page counts."""
        page, ring = self.page_size, self.ring_pages
        keep = max(0, query - self.window + 1) // page
        lo, hi = int(self.ring_lo[slot]), int(self.ring_hi[slot])
        behind = [p % ring for p in range(lo, min(keep, hi))]
        self.ring_allocator.deref([int(self.ring_tables[slot, c]) for c in behind])
        self.ring_tables[slot, behind] = NULL_PAGE
        lo = max(lo, keep)
        hi = max(hi, lo)
        need = last // page + 1
        if need - lo > ring:
            raise RuntimeError(
                f"lane {slot} needs pages {lo}..{need - 1} of its window layers at once: "
                f"more than its ring of {ring}"
            )
        ids = self.ring_allocator.alloc(max(need - hi, 0))
        if ids is None:
            raise RuntimeError("the window layers' page pool is exhausted: a ring leaked")
        for p, pid in zip(range(hi, need), ids):
            self.ring_tables[slot, p % ring] = pid
        self.ring_lo[slot], self.ring_hi[slot] = lo, max(hi, need)
        return len(ids), len(behind)

    def ring_held(self, slot: int) -> int:
        """Pages lane ``slot`` holds of the window layers' pool."""
        return int(self.ring_hi[slot] - self.ring_lo[slot])

    # -------------------------------------------------------------- lane ops
    def lane_detach(self, slot: int) -> List[int]:
        """The base class's detach for the full kind (its ids come back to the
        caller, who derefs them when the in-flight window has retired); the
        lane's ring is handed back at once (see the class docstring)."""
        self.ring_allocator.deref([int(p) for p in self.ring_tables[slot]])
        self.ring_tables[slot, :] = NULL_PAGE
        self.ring_lo[slot] = self.ring_hi[slot] = 0
        return super().lane_detach(slot)

    # ------------------------------------------------------------- accounting
    def kv_bytes(self) -> int:
        return super().kv_bytes() + int(self.ring_k.nbytes) + int(self.ring_v.nbytes)

    def publish_gauges(self) -> None:
        super().publish_gauges()
        self._in_use_gauge.set(self.allocator.used_count + self.ring_allocator.used_count)
        self._kind_gauges["full"].set(self.allocator.used_count)
        self._kind_gauges["window"].set(self.ring_allocator.used_count)


class StatePool:
    """The pool of a retention model (``config.retention``): the recurrent
    state of every layer for ``num_slots`` lanes, ``S [L, lanes, Hkv, D, Dh]``
    and ``z [L, lanes, Hkv, D]`` (:mod:`accelerate_tpu.models.retention`), and
    nothing else.  A lane's state has one size whatever its context, so there
    are no pages, no block tables, no allocator and no page pressure: a lane
    is admitted when a slot is free and released by being left (the next
    request's install zeroes it on the device,
    :func:`~accelerate_tpu.serving.pool.make_state_install`).  It answers the
    calls the engine's lane lifecycle makes of :class:`PagedKVPool`."""

    def __init__(self, config, num_slots: int,
                 registry: Optional[MetricsRegistry] = None, sharding=None):
        from ..models.retention import state_shapes
        from ..ops.retention import onepass_applies

        self.num_slots = int(num_slots)
        s_shape, z_shape = state_shapes(config, self.num_slots)
        dtype = config.retention.dtype
        # allocated in place on the replica's own device, as the page pool is
        self.s = jnp.zeros(s_shape, dtype, device=sharding)
        self.z = jnp.zeros(z_shape, dtype, device=sharding)
        registry = registry if registry is not None else get_registry()
        self._bytes_gauge = registry.gauge(
            "serve/state_bytes",
            help="device bytes of the recurrent state pool (every lane, every layer)",
        )
        self._onepass_gauge = registry.gauge(
            "serve/state_step_onepass",
            help="1 where the decode window's retention step is the one-pass kernel, 0 where it is XLA's three passes",
        )
        #: the form ``retention_step_stored`` picks when the window is traced
        self.step_onepass = onepass_applies(self.s, config.retention.degree)
        self.publish_gauges()

    def lane_release(self, slot: int) -> int:
        """Nothing to hand back: the lane's state stays where it is until the
        next install zeroes it."""
        return 0

    def lane_detach(self, slot: int) -> List[int]:
        return []

    def kv_bytes(self) -> int:
        return int(self.s.nbytes) + int(self.z.nbytes)

    def kv_bytes_per_device(self) -> int:
        return self.kv_bytes()

    def publish_gauges(self) -> None:
        self._bytes_gauge.set(self.kv_bytes())
        self._onepass_gauge.set(int(self.step_onepass))


class DraftContextWindow:
    """Host-side sliding context for the draft model — the one piece of
    per-lane drafting state :func:`~accelerate_tpu.serving.spec_exec
    .make_draft_forward` needs.

    The draft forward is stateless (it re-prefills its context every cycle
    into an in-trace scratch cache), so the host only has to hand it the
    last ``width`` visible tokens per lane, right-padded, plus a valid
    length.  Two numpy slabs sized ``[slots, width]`` / ``[slots]`` make
    that a zero-copy dispatch argument: :meth:`begin` seeds a lane from its
    prompt tail, :meth:`push` slides committed tokens in after each verify
    drain, :meth:`retire` zeroes the row.  A bounded window (default 64 in
    the engine) deliberately trades long-range draft context for a fixed,
    small prefill cost — the draft's job is local continuation ranking, and
    tokens beyond the window only reach it through the lane's real KV at
    verify time anyway.
    """

    def __init__(self, slots: int, width: int, pad: int = 0) -> None:
        if width < 1:
            raise ValueError(f"need width >= 1, got {width}")
        self.width = width
        self.pad = pad
        self.tokens = np.full((slots, width), pad, dtype=np.int32)
        self.length = np.zeros(slots, dtype=np.int32)

    def begin(self, slot: int, tokens: Sequence[int]) -> None:
        """Seed ``slot`` from a prompt: keep the last ``width`` tokens."""
        toks = np.asarray(tokens, dtype=np.int32).ravel()[-self.width:]
        self.tokens[slot] = self.pad
        self.tokens[slot, : toks.size] = toks
        self.length[slot] = toks.size

    def push(self, slot: int, tokens: Sequence[int]) -> None:
        """Append committed tokens, sliding the window left on overflow."""
        toks = np.asarray(tokens, dtype=np.int32).ravel()
        if toks.size >= self.width:
            self.tokens[slot] = toks[-self.width:]
            self.length[slot] = self.width
            return
        n = int(self.length[slot])
        spill = n + toks.size - self.width
        if spill > 0:
            self.tokens[slot, : n - spill] = self.tokens[slot, spill:n]
            n -= spill
        self.tokens[slot, n : n + toks.size] = toks
        self.length[slot] = n + toks.size

    def retire(self, slot: int) -> None:
        self.tokens[slot] = self.pad
        self.length[slot] = 0


__all__ = ["NULL_PAGE", "DraftContextWindow", "MixedKVPool", "PageAllocator", "PagedKVPool"]

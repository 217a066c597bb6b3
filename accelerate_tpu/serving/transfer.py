"""Live KV page migration and request-state marshalling between replicas.

Replicas stop being silos here.  The existing recovery path
(:func:`export_inflight` / :func:`adopt`, relocated from ``engine.py``)
moves a request between engines by *throwing the KV away* and re-prefilling
``prompt + generated`` on the adopter — token-exact under greedy, re-seeded
under sampling, and O(prefix) device work every time.  :class:`PageMigrator`
moves the KV itself: a lane's live pages, block-table row, per-page quant
scales, pending token, and RNG stream travel to the destination, which
installs them into its own allocator and continues **bit-identically** —
greedy and sampled alike — at O(pages) copy cost independent of how much
compute produced them.

Two arms, chosen per engine pair (``mode="auto"``):

- **d2d** — both pools live on the same platform with the same sharding
  layout (single-device twins, or tp slices of one mesh): the D2H-shaped
  gather's device outputs are handed straight to the destination's
  scatter-install via ``jax.device_put``, never touching the host.
- **bounce** — anything else (cross-process, cross-platform, mismatched
  meshes): the gather lands in pinned host memory through the one
  sanctioned blocking ``fetch`` and re-uploads with the destination pool's
  placement, exactly like a hierarchical-cache promotion.

Executable discipline: one gather (``serve/migrate_extract``) and one
scatter-install (``serve/migrate_install``) per engine, built lazily on
first migration from the hierarchical cache's factories
(:func:`~.pool.make_spill_extract` / :func:`~.pool.make_promote_install`)
at the pool's full ``pages_per_lane`` width — a lane's live page-id list is
padded with ``NULL_PAGE`` up to that fixed width
(:func:`~.pool.pad_page_ids`), so per-lane page counts never leak into jit
signatures.  On the destination the install enqueues BEHIND any in-flight
decode window per the ``Readback``/``_stale_handles`` depth-1 discipline,
so migration overlaps the destination's decode.  The source drains its own
pipeline first — the migration barrier that makes its host mirrors
(pending token, lane length) and the device-carried RNG row authoritative —
then its other lanes resume overlapped while the gather executes.

Failure semantics: every refusal raises :class:`MigrationError` *before*
any engine state mutates.  ``retriable=True`` (destination slot/page
pressure) means try again next step; ``retriable=False`` (geometry
mismatch, an injected ``migrate_d2d``/``migrate_bounce`` fault) means fall
back to the export/adopt replay path — the source lane is untouched and
the source replica stays healthy.  See ``docs/usage/serving.md``
("Disaggregated prefill/decode") and ``docs/usage/fault_tolerance.md``.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import numpy as np

from ..telemetry import (
    MetricsRegistry,
    RecompileWatchdog,
    get_flight_recorder,
    get_registry,
    get_tracer,
)
from . import faults
from .errors import AdmissionError
from .pool import (
    make_promote_install,
    make_spill_extract,
    pad_page_ids,
    plan_chunks,
)
from .readback import fetch
from .scheduler import Request, RequestState

__all__ = [
    "MigrationError",
    "PageMigrator",
    "adopt",
    "export_inflight",
    "migration_executables",
]

# Migration wall time spans ~10 us (single-page d2d handoff on one chip) to
# ~100 s (a full lane bounced over a congested host link): 20 x2 buckets
# from 10 us in ms units cover it.
_MIGRATE_MS_BUCKETS = tuple(1e-2 * 2.0**i for i in range(20))


class MigrationError(RuntimeError):
    """A migration that could not run; nothing was mutated on either engine.

    ``retriable=True`` — transient destination pressure (no free slot, page
    pool dry): the lane stays where it is and the caller may try again next
    step.  ``retriable=False`` — the pair can never migrate this lane
    (geometry mismatch, lane finished, injected fault): the caller should
    fall back to the export/adopt re-prefill replay path.
    """

    def __init__(self, reason: str, retriable: bool = False):
        super().__init__(reason)
        self.reason = reason
        self.retriable = retriable


# ---------------------------------------------------------------- marshalling
def export_inflight(engine) -> List[Request]:
    """Snapshot every request ``engine`` still owes an answer — running
    lanes, the mid-prefill request, and the waiting queue — detached from
    the engine's state and ready for :func:`adopt` on a survivor.

    Each RUNNING lane exports as ``prompt + generated-so-far`` via
    ``Request.prefill_tokens`` (the preempt-and-replay machinery): replay
    re-prefills the effective prompt and generation resumes exactly where
    it stopped, token-exact under greedy.  Tokens already streamed are
    never re-emitted.  Prefix-cache pins on THIS engine are released and
    the per-engine prefill plan cleared — the adopting engine re-plans
    against its own buckets and cache.  Device state is NOT touched (the
    engine may be poisoned mid-window); ``revive()`` handles teardown.
    Returns requests in rid order — original FCFS submit order."""
    out: List[Request] = []
    for s in range(engine.num_slots):
        req = engine._slot_req[s]
        if req is not None and req.state is RequestState.RUNNING:
            out.append(req)
    for hd in (engine._prev_handle, engine._inflight):
        if hd is None:
            continue
        # a pre-freed lane's request left _slot_req when its final window
        # dispatched but is still owed that window's tokens from the
        # drain this engine will never run — it lives only on the handle
        for s in hd.prefreed:
            req = hd.reqs[s]
            if (req is not None and req.state is RequestState.RUNNING
                    and not any(req is r for r in out)):
                out.append(req)
    out.extend(engine.scheduler.take_prefills())
    out.extend(engine.scheduler.queue)
    engine.scheduler.queue.clear()
    for req in out:
        if engine.prefix_cache is not None and req.cache_nodes:
            engine.prefix_cache.release(req.cache_nodes)
        req.cache_nodes = []
        req.cached_chunks = 0
        req.cache_chain_broken = False
        req.chunks = ()
        req.next_chunk = 0
        req.slot = None
        req.state = RequestState.QUEUED
    out.sort(key=lambda r: r.rid)
    for req in out:
        if req.trace is not None:
            req.trace.annotate("export_inflight", rid=req.rid,
                               generated=len(req.tokens))
    engine.recorder.record(
        "serve/export_inflight", count=len(out), step=engine._step_count,
    )
    return out


def adopt(engine, request: Request) -> Request:
    """Admit a request exported from a dead replica, at the FRONT of
    ``engine``'s queue (it already waited its FCFS turn once).  The
    effective prompt is ``prefill_tokens`` — greedy lanes replay
    token-exact; sampled lanes resume on a re-seeded stream (the fresh rid
    folds into this engine's base rng at install), distribution-correct
    but not sample-exact.  Raises a non-retriable :class:`AdmissionError`
    when the effective prompt cannot fit this engine's geometry; never
    refused for queue depth — survivors absorb a dead peer's load."""
    eff = len(request.prefill_tokens)
    if eff > engine.max_prompt_len:
        raise AdmissionError(
            f"replayed prompt+generated length {eff} > max_prompt_len "
            f"{engine.max_prompt_len}",
            queue_depth=engine.scheduler.queue_depth, retriable=False,
        )
    span = max(engine.window, engine._spec_span)
    remaining = max(request.config.max_new_tokens - len(request.tokens), 1)
    if eff + remaining + span > engine.max_len:
        raise AdmissionError(
            f"replayed length {eff} + remaining {remaining} + span {span} "
            f"exceeds slot capacity {engine.max_len}",
            queue_depth=engine.scheduler.queue_depth, retriable=False,
        )
    padded = sum(b for b, _ in plan_chunks(eff, engine.buckets))
    if padded > engine.max_len:
        raise AdmissionError(
            f"replayed length {eff} pads to {padded} prefill tokens under "
            f"buckets {engine.buckets}, exceeding capacity {engine.max_len}",
            queue_depth=engine.scheduler.queue_depth, retriable=False,
        )
    old_rid = request.rid
    request.rid = engine._next_rid
    engine._next_rid += 1
    if request.trace is not None:
        # the SAME trace crosses replicas: close the ejection-to-adoption
        # interval as a failover phase and re-index under the new rid —
        # the waterfall continues rather than restarting
        request.trace.phase(
            "failover", from_engine=request.trace.engine,
            to_engine=engine.engine_id, old_rid=old_rid, rid=request.rid,
            generated=len(request.tokens),
        )
        engine.reqtrace.rebind(request.trace, engine.engine_id, request.rid)
    engine.scheduler.requeue(request)
    engine._bump("requests_submitted")
    engine._bump("requests_replayed")
    # the tenant label rides the Request across the failover — the
    # adopting engine keeps the caller's books exact
    engine._bump_tenant(request.tenant, "requests_submitted")
    engine._bump_tenant(request.tenant, "requests_replayed")
    if request.deadline_s is not None:
        engine._has_deadlines = True
    engine.recorder.record(
        "serve/adopt", rid=request.rid, old_rid=old_rid,
        effective_len=eff, generated=len(request.tokens),
    )
    return request


# ----------------------------------------------------------------- executables
def migration_executables(engine):
    """The engine's ``(extract, install)`` migration pair, built lazily on
    first use and cached — ``serve/migrate_extract`` (D2H-shaped page
    gather) and ``serve/migrate_install`` (donated H2D-shaped scatter), one
    of each per engine at the pool's full ``pages_per_lane`` width.  Lazy
    because most engines never migrate: the compiled budget only grows on
    the replicas that actually participate, and by exactly this documented
    set (``compiled_executable_counts``)."""
    if engine._migrate_extract is None:
        npages = engine.kv.pages_per_lane
        engine._migrate_extract = RecompileWatchdog(
            make_spill_extract(npages, shardings=engine._shardings),
            name="serve/migrate_extract", budget=1, registry=engine.metrics,
        )
        engine._migrate_install = RecompileWatchdog(
            make_promote_install(npages, shardings=engine._shardings),
            name="serve/migrate_install", budget=1, registry=engine.metrics,
        )
    return engine._migrate_extract, engine._migrate_install


# ------------------------------------------------------------------- migrator
class PageMigrator:
    """Move live decode lanes between :class:`ServingEngine` replicas.

    Stateless apart from telemetry: every :meth:`migrate` call is one
    complete lane move (or a clean :class:`MigrationError` refusal), so one
    migrator instance can serve a whole router.  Pass the same private
    ``registry`` the engines use to keep bench arms isolated."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.metrics = registry if registry is not None else get_registry()
        self.recorder = get_flight_recorder().tagged(engine="migrator")
        self.tracer = get_tracer()
        self._migrations = self.metrics.counter(
            "serve/migrations_total",
            help="live lanes moved between replicas with their KV pages "
                 "(d2d and host-bounce arms both); replay fallbacks do not "
                 "count — they bump serve/requests_replayed_total instead",
        )
        self._bytes = self.metrics.counter(
            "serve/migrate_bytes_total",
            help="KV payload bytes migrated between replicas (live pages + "
                 "quant scales, at storage dtype) — the crossover input of "
                 "the migrate-vs-replay A/B",
        )
        self._handoffs = self.metrics.counter(
            "serve/prefill_handoffs_total",
            help="lanes handed off prefill-role -> decode-role right after "
                 "their last prefill chunk landed (disaggregated policy); a "
                 "subset of serve/migrations_total",
        )
        self._ms_hist = self.metrics.histogram(
            "serve/migrate_ms",
            buckets=_MIGRATE_MS_BUCKETS,
            help="wall time per lane migration, source drain barrier through "
                 "destination lane install dispatch (the install itself "
                 "overlaps the destination's decode)",
        )

    # ------------------------------------------------------------ feasibility
    @staticmethod
    def compatible(src, dst) -> Optional[str]:
        """``None`` when lanes can migrate ``src -> dst``; else the blocking
        reason.  The pools must agree on page geometry and storage dtype so
        the gathered chunk feeds the destination's install bit-for-bit."""
        if src is dst:
            return "source and destination are the same engine"
        if src.config.latent_attention is not None or dst.config.latent_attention is not None:
            return "page migration is not ported to a latent-attention cache yet"
        if src.config.retention is not None or dst.config.retention is not None:
            return "page migration is not ported to a recurrent state yet"
        if src.kv.page_size != dst.kv.page_size:
            return (f"page_size differs ({src.kv.page_size} vs "
                    f"{dst.kv.page_size})")
        if src.kv.pages_per_lane != dst.kv.pages_per_lane:
            return (f"pages_per_lane differs ({src.kv.pages_per_lane} vs "
                    f"{dst.kv.pages_per_lane})")
        if src.kv.storage_dtype != dst.kv.storage_dtype:
            return (f"KV storage dtype differs ({src.kv.storage_dtype} vs "
                    f"{dst.kv.storage_dtype})")
        if src.kv.pages_k.shape[0] != dst.kv.pages_k.shape[0] \
                or src.kv.pages_k.shape[2:] != dst.kv.pages_k.shape[2:]:
            return "KV pool geometry (layers/heads/head_dim) differs"
        return None

    @staticmethod
    def resolve_mode(src, dst) -> str:
        """``"d2d"`` when the gather's outputs can feed the destination
        install without a host round trip — same platform AND the same
        sharding structure (both unsharded, or both meshes, where
        ``device_put`` re-lays the chunk onto the destination mesh) —
        else ``"bounce"``."""
        sdev = next(iter(src.kv.pages_k.devices()))
        ddev = next(iter(dst.kv.pages_k.devices()))
        if sdev.platform != ddev.platform:
            return "bounce"
        if (src._shardings is None) != (dst._shardings is None):
            return "bounce"
        return "d2d"

    # -------------------------------------------------------------- migration
    def migrate(self, src, dst, slot: int, mode: str = "auto",
                reason: str = "rebalance") -> Request:
        """Move the RUNNING lane in ``src`` slot ``slot`` to ``dst``,
        KV pages included, and return its request — which continues on the
        destination bit-identically (greedy AND sampled: the live RNG row
        travels, unlike :func:`adopt`'s re-seed).  Raises
        :class:`MigrationError` with nothing mutated otherwise."""
        req = src._slot_req[slot]
        if req is None or req.state is not RequestState.RUNNING \
                or not src._active[slot]:
            raise MigrationError(f"no running lane in slot {slot}")
        why = self.compatible(src, dst)
        if why is not None:
            raise MigrationError(why)
        if mode == "auto":
            mode = self.resolve_mode(src, dst)
        if mode not in ("d2d", "bounce"):
            raise MigrationError(f"unknown migration mode {mode!r}")
        if dst._next_free_slot() is None:
            raise MigrationError("destination has no free slot",
                                 retriable=True)
        t0 = time.perf_counter()
        # the source-side migration barrier: drain the depth-1 pipeline so
        # the host mirrors (pending token, lane length) are current and the
        # device-carried RNG row is the lane's live stream.  The source's
        # other lanes resume overlapped decode the very next step.
        src._drain_inflight()
        if not src._active[slot] or src._slot_req[slot] is not req:
            raise MigrationError("lane finished while draining the source")
        lane_len = int(src._lane_len[slot])
        span = max(dst.window, dst._spec_span)
        remaining = max(req.config.max_new_tokens - len(req.tokens), 1)
        if lane_len + 1 + remaining + span > dst.max_len:
            raise MigrationError(
                f"lane length {lane_len} + remaining {remaining} + span "
                f"{span} exceeds destination capacity {dst.max_len}")
        page_ids = src.kv.lane_pages(slot)
        npages = len(page_ids)
        pending = int(src._pending_tok[slot])
        if src._lane_device is not None:
            # the sampling stream rides the device between windows; with
            # the pipeline drained this sanctioned fetch returns without a
            # real wait, and the row transfers the stream bit-exactly
            rng = np.asarray(fetch(src._lane_device[-1])[slot], np.uint32)
        else:
            rng = np.asarray(src._rngs[slot], np.uint32)
        point = f"migrate_{mode}"
        if faults.ACTIVE is not None and faults.ACTIVE.fire(point):
            self.recorder.record(
                "serve/fault", point=point, rid=req.rid, slot=int(slot),
                src=src.engine_id, dst=dst.engine_id,
            )
            raise MigrationError(f"injected {point} fault")
        new_ids = dst.kv.allocator.alloc(npages)
        if new_ids is None:
            if dst._reclaim_pages(npages, allow_preempt=False):
                new_ids = dst.kv.allocator.alloc(npages)
            if new_ids is None:
                raise MigrationError("destination page pool exhausted",
                                     retriable=True)
        extract, _ = migration_executables(src)
        _, install = migration_executables(dst)
        behind = dst._inflight is not None or dst._prev_handle is not None
        skv, dkv = src.kv, dst.kv
        with self.tracer.span("serve/migrate", mode=mode, pages=npages,
                              behind_window=behind):
            handles = extract(
                skv.pages_k, skv.pages_v, skv.k_scales, skv.v_scales,
                src._put(pad_page_ids(page_ids, skv.pages_per_lane)),
            )
            if mode == "bounce":
                # the pinned-host bounce: the one sanctioned fetch, waiting
                # only on the gather just dispatched (source pipeline is
                # empty), then re-uploaded with the destination placement
                ck, cv, cks, cvs = fetch(*handles)
                ck, cv = dst._put_kv_chunk(ck), dst._put_kv_chunk(cv)
                cks = dst._put_scale_chunk(cks)
                cvs = dst._put_scale_chunk(cvs)
            else:
                ck, cv, cks, cvs = handles
                if dst._shardings is not None:
                    # same platform, different mesh handles: re-lay the
                    # gathered chunk onto the destination's sharding —
                    # device-to-device, never through the host
                    ck = jax.device_put(ck, dst._shardings.pages)
                    cv = jax.device_put(cv, dst._shardings.pages)
                    cks = jax.device_put(cks, dst._shardings.scales)
                    cvs = jax.device_put(cvs, dst._shardings.scales)
            # the install donates the destination pool handles, which any
            # in-flight destination window still consumes: park them until
            # its drain, per the depth-1 discipline (_stale_handles)
            dst._stale_handles += [dkv.pages_k, dkv.pages_v,
                                   dkv.k_scales, dkv.v_scales]
            (dkv.pages_k, dkv.pages_v, dkv.k_scales,
             dkv.v_scales) = install(
                dkv.pages_k, dkv.pages_v, dkv.k_scales, dkv.v_scales,
                ck, cv, cks, cvs,
                dst._put(pad_page_ids(new_ids, dkv.pages_per_lane)),
            )
        # source teardown: the lane's page refs drop now — the device runs
        # in dispatch order, so any later source prefill recycling these
        # pages is ordered BEHIND the gather (the spill discipline)
        src._retire_lane(slot)
        dst_slot = self._install_lane(dst, req, new_ids, lane_len, pending,
                                      rng)
        old_rid = req.rid
        req.rid = dst._next_rid
        dst._next_rid += 1
        req.slot = dst_slot
        if req.trace is not None:
            # the SAME trace crosses replicas, like failover — the
            # waterfall gains a migrate phase instead of restarting
            req.trace.phase(
                "migrate", from_engine=src.engine_id,
                to_engine=dst.engine_id, old_rid=old_rid, rid=req.rid,
                mode=mode, pages=npages, generated=len(req.tokens),
            )
            dst.reqtrace.rebind(req.trace, dst.engine_id, req.rid)
        self._reestablish_prefix(dst, req, new_ids, lane_len)
        nbytes = skv.chunk_bytes(npages)
        self._migrations.inc()
        self._bytes.inc(nbytes)
        self._ms_hist.observe((time.perf_counter() - t0) * 1e3)
        self.recorder.record(
            "serve/migrate", rid=req.rid, old_rid=old_rid, mode=mode,
            src=src.engine_id, dst=dst.engine_id, slot=int(slot),
            dst_slot=dst_slot, pages=npages, bytes=nbytes,
            behind_window=behind, reason=reason,
        )
        return req

    def handoff(self, src, dst, slot: int, mode: str = "auto") -> Request:
        """Prefill handoff: migrate a freshly prefilled lane off a
        prefill-role replica onto a decode-role one — the disaggregated
        steady state.  Same mechanics as :meth:`migrate`; counted
        separately because handoffs are the *policy* (every lane, once)
        where rebalance migrations are the *exception* (hot spots only)."""
        req = self.migrate(src, dst, slot, mode=mode,
                           reason="prefill_handoff")
        self._handoffs.inc()
        self.recorder.record(
            "serve/prefill_handoff", rid=req.rid, src=src.engine_id,
            dst=dst.engine_id, generated=len(req.tokens),
        )
        return req

    # -------------------------------------------------------------- internals
    @staticmethod
    def _install_lane(dst, req: Request, new_ids: List[int], lane_len: int,
                      pending: int, rng: np.ndarray) -> int:
        """Wire the migrated lane into ``dst`` — ``_install``'s twin minus
        the re-prefill: the block-table row points at the freshly installed
        pages, the host mirrors take the TRANSFERRED lane length, pending
        token, and RNG row (not a re-fold of the base rng — that is what
        makes continuation bit-identical where :func:`adopt` is only
        distribution-correct), and the one-slot lane-install scatter edits
        the device mirror behind any in-flight window without a sync."""
        s = dst._next_free_slot()
        dst.kv.lane_append_owned(s, new_ids)
        gen = req.config
        eos_v = -1 if gen.eos_token_id is None else gen.eos_token_id
        top_k_v = 0 if gen.top_k is None else gen.top_k
        top_p_v = 1.0 if gen.top_p is None else gen.top_p
        if dst._lane_device is not None:
            ld = dst._lane_device
            # the replaced handles are inputs of the scatter (and outputs
            # of any in-flight window): park them until the next drain so
            # their destructors never wait on pending device work
            dst._stale_handles += [ld[0], ld[1], ld[2], ld[3], ld[4],
                                   ld[5], ld[6], ld[8]]
            (ld[0], ld[1], ld[2], ld[3], ld[4], ld[5], ld[6],
             ld[8]) = dst._lane_install(
                ld[0], ld[1], ld[2], ld[3], ld[4], ld[5], ld[6], ld[8],
                dst._put(np.int32(s)), dst._put(np.int32(pending)),
                dst._put(np.int32(eos_v)),
                dst._put(np.bool_(gen.do_sample)),
                dst._put(np.float32(gen.temperature)),
                dst._put(np.int32(top_k_v)), dst._put(np.float32(top_p_v)),
                dst._put(rng),
            )
        dst._pending_tok[s] = pending
        dst._active[s] = True
        dst._eos[s] = eos_v
        dst._do_sample[s] = gen.do_sample
        dst._temperature[s] = gen.temperature
        dst._top_k[s] = top_k_v
        dst._top_p[s] = top_p_v
        dst._rngs[s] = rng
        dst._lane_len[s] = lane_len
        if dst._draft_window is not None:
            # seed the draft context from the full sequence tail: its last
            # token IS the lane's pending token, the tree-root invariant
            dst._draft_window.begin(s, req.output_ids)
        if dst._slot_ever_used[s]:
            dst._bump("slots_reused")
        dst._slot_ever_used[s] = True
        dst._slot_req[s] = req
        dst._reserved_slots.discard(s)
        if req.deadline_s is not None:
            dst._has_deadlines = True
        req.state = RequestState.RUNNING
        return s

    @staticmethod
    def _reestablish_prefix(dst, req: Request, new_ids: List[int],
                            lane_len: int) -> None:
        """Re-establish prefix-cache pins on the destination: the migrated
        prompt chunks alias the lane's NEW physical pages zero-copy, each
        full chunk inserted with its own allocator reference exactly like
        ``_populate_cache`` — so future destination requests sharing the
        prefix hit instead of re-prefilling.  (The source side needs no
        step: ``_retire_lane`` dropped the lane's refs, while the source
        cache's own nodes — and their refs — stay resident and servable.)
        Chunks whose pages reach the lane's write frontier are skipped:
        decode keeps writing there, and a cached page must be immutable."""
        if dst.prefix_cache is None or not req.cache_prefix:
            return
        ptoks = np.asarray(req.prompt, np.int32).reshape(-1)
        page = dst.page_size
        frontier = (lane_len // page) * page
        parent = None
        start = 0
        for bucket, valid in plan_chunks(len(ptoks), dst.buckets):
            if valid != bucket or start + bucket > frontier:
                break
            npg = bucket // page
            first = start // page
            ids = list(new_ids[first:first + npg])
            node = dst.prefix_cache.insert_pages(
                parent, ptoks[start:start + bucket], ids,
                nbytes=dst.kv.chunk_bytes(npg),
            )
            if node is None:
                break
            if node.pages == tuple(ids):
                # a NEW node: the cache holds its own reference per page
                # (dropped by _on_prefix_evict); a deduped re-insert keeps
                # the resident node's pages and refs untouched
                dst.kv.allocator.ref(ids)
            parent = node
            start += bucket

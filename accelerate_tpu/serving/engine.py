"""Continuous-batching serving engine over the paged KV pool.

The static ``generate`` path is one whole-batch program: every request starts
together and runs exactly ``max_new_tokens`` steps, so at mixed request
lengths the batch's tokens/s collapses to the longest request's schedule.
:class:`ServingEngine` instead runs iteration-level scheduling (Orca-style)
against a fixed set of compiled executables (:mod:`.pool`):

1. a request queue admits FCFS into freed slots, prefilling chunked under a
   per-step token budget (:mod:`.scheduler`);
2. a masked decode window advances every occupied slot; EOS or the length cap
   frees a slot the same step it fires;
3. freed slots are reused by queued requests without disturbing running lanes.

Everything dynamic lives on the host; the device only ever sees
``1 + len(prefill_buckets) + 2`` shapes (decode window, per-bucket prefill,
lane install, the copy-on-write page copy), plus one verify-window shape when
``speculate_k > 0`` (or a tree-verify + draft-forward pair when
``draft_model`` is set), plus a spill/promote pair per bucket when the host
prefix tier is on.  See ``docs/usage/serving.md``.

Speculative decoding (``speculate_k > 0``): each cycle the host proposes K
draft tokens per lane by n-gram prompt-lookup (:mod:`.spec` — incrementally
indexed per lane, O(K) per cycle) and, when at least one lane drafts, ONE
verify forward over ``[slots, K+1]`` positions
(:func:`.pool.make_paged_verify_window`) lands 1..K+1 tokens per lane — greedy
outputs token-exact vs plain decode, sampled outputs distribution-exact
(Leviathan accept/resample).  Cycles with no draft fall back to the decode
window, so non-repetitive workloads never regress.

Tree speculation (``draft_model=``): an on-device draft model — by default a
truncated-layer head of the served model (:func:`.spec_exec.build_draft`) —
drafts a ``1 + tree_width * tree_depth``-node token tree per lane in ONE
small jitted forward (:func:`.spec_exec.make_draft_forward`), and a tree
verify window (:func:`.pool.make_paged_tree_verify_window`) scores all nodes under
the ancestor attention mask and commits the best root-to-leaf path:
Leviathan acceptance generalized to branch selection, so outputs stay
token-exact (greedy) / distribution-exact (sampled).  Unlike n-gram lookup,
the draft model speculates on *non-repetitive* text; the compiled budget
grows by exactly two shapes: ``draft_forward`` and ``tree_verify_window``
(which replaces the linear verify window).  See ``docs/usage/serving.md``.

Prefix caching (:mod:`.prefix_cache`): the pages of freshly prefilled full
chunks are retained (one allocator reference each) in a radix tree keyed by
the token prefix; later requests sharing that prefix alias the pages through
their block tables instead of re-running prefill, with no copy.  Outputs are
token-exact with the cache on or off — only redundant prefill compute is
skipped; the decode path never changes.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..logging import get_logger
from ..models.generation import GenerationConfig
from ..models.transformer import Transformer
from ..ops.latent_view_attention import latent_flash_applies
from ..ops.view_attention import (
    DECODE_BLOCK,
    KEY_BLOCK,
    live_key_blocks,
    view_decode_applies,
    view_flash_applies,
)
from ..ops.view_gather import view_gather_applies
from ..telemetry import (
    CostTable,
    MetricsRegistry,
    RecompileWatchdog,
    detect_device_peaks,
    get_flight_recorder,
    get_registry,
    get_reqtrace,
    get_tracer,
    slo_tick,
    start_debug_server,
)
from . import faults, transfer
from .errors import AdmissionError
from .paging import NULL_PAGE, DraftContextWindow, MixedKVPool, PagedKVPool, StatePool
from .pool import (
    ServeShardings,
    audit_donation,
    jit_cache_sizes,
    make_copy_page,
    make_lane_install,
    make_paged_decode_window,
    make_paged_prefill_chunk,
    make_paged_tree_verify_window,
    make_paged_verify_window,
    make_promote_install,
    make_spill_extract,
    make_mixed_decode_window,
    make_mixed_prefill_chunk,
    make_state_decode_window,
    make_state_install,
    make_state_prefill_chunk,
    plan_chunks,
)
from .prefix_cache import PrefixCache
from .readback import Readback, fetch
from .scheduler import Request, RequestState, Scheduler
from .spec_exec import (
    NgramDrafter,
    TreeDrafter,
    TreeSpec,
    build_draft,
    make_draft_forward,
)

logger = get_logger(__name__)

# Serving latencies live between ~100 us (a CPU-test decode step) and ~100 s
# (a deep queue on a loaded pool): 24 x2 buckets from 100 us cover it.
_LATENCY_BUCKETS = tuple(1e-4 * 2.0**i for i in range(24))

# The counters whose movement over one step ``serve/step`` closes with, as
# ``window`` and ``live``, ``chunks``, ``chunk_tokens`` and ``emitted``.
_STEP_CARRIED = ("decode_steps", "occupied_lane_steps", "prefill_chunks",
                 "prefill_tokens", "tokens_generated")
# A routed engine's two more, as ``experts_hit`` and ``expert_slots``.
_STEP_CARRIED_MOE = ("moe_experts_hit", "moe_expert_slots")

# Process-wide replica ids ("e0", "e1", ...): every flight-recorder event and
# request-trace phase an engine emits is tagged with its id so multi-replica
# rings stay disambiguable (the process-global recorder bit PR 14's bench).
_ENGINE_IDS = itertools.count()


class _Stats(dict):
    """``ServingEngine.stats``: a plain numeric dict (benches reset it in
    place, ``ReplicaRouter.stats`` sums its items) that is *also* callable —
    ``engine.stats()`` returns a copy augmented with the per-request trace
    rollup under ``"requests"``."""

    def __call__(self) -> dict:
        out = dict(self)
        engine = getattr(self, "engine", None)
        out["requests"] = (
            get_reqtrace().summary(engine_id=engine.engine_id)
            if engine is not None else {}
        )
        out["tenants"] = (
            {t: dict(v) for t, v in engine._tenant_stats.items()}
            if engine is not None else {}
        )
        return out


def _refuse_unported(cfg, *, kv_dtype, speculate_k, draft_model, decode_kernel,
                     prefill_kernel, prefix_host_mb, prefix_disk_mb, role, mesh,
                     tp_axis, prefix_cache_mb=None) -> None:
    """Refuse at construction, by name, each option that a latent cache
    (``config.latent_attention``), routed experts (``config.experts``), a
    recurrent state (``config.retention``) or a pool of two retention rules
    (``config.layer_types``) do not have yet.  Everything else — chunked
    prefill, cancellation, the scheduler, failover replay — is the same code
    for them."""
    asked = {}
    if cfg.layer_types is not None:
        # a window layer's pages go back to the free list while the lane
        # lives: a released page cannot be shared with a later prompt, rolled
        # back to, spilled or shipped; the ring is read through the gathered
        # view only, in the model's dtype, on one device
        asked.update({
            "prefix_cache_mb": bool(prefix_cache_mb),
            "kv_dtype": kv_dtype is not None,
            "speculate_k": bool(speculate_k),
            "draft_model": draft_model is not None,
            "decode_kernel": decode_kernel != "xla",
            "prefill_kernel": prefill_kernel not in (None, "xla"),
            "prefix_host_mb": bool(prefix_host_mb),
            "prefix_disk_mb": bool(prefix_disk_mb),
            "role": role != "both",
        })
    if cfg.retention is not None:
        # the state has no rows to quantise, no page to read in place, to
        # spill or to ship, and no way yet to roll back past a rejected draft
        asked.update({
            "kv_dtype": kv_dtype is not None,
            "speculate_k": bool(speculate_k),
            "draft_model": draft_model is not None,
            "decode_kernel": decode_kernel != "xla",
            "prefill_kernel": prefill_kernel not in (None, "xla"),
            "prefix_host_mb": bool(prefix_host_mb),
            "prefix_disk_mb": bool(prefix_disk_mb),
            "role": role != "both",
        })
    if cfg.latent_attention is not None:
        # the latent and the rope key live in the gathered view's two arrays;
        # everything below reads K and V per kv head from the pool in place,
        # ships them between hosts, or verifies rows under a tree mask
        asked.update({
            "kv_dtype": kv_dtype is not None,
            "speculate_k": bool(speculate_k),
            "draft_model": draft_model is not None,
            "decode_kernel": decode_kernel != "xla",
            "prefill_kernel": prefill_kernel not in (None, "xla"),
            "prefix_host_mb": bool(prefix_host_mb),
            "role": role != "both",
        })
    if (cfg.latent_attention is not None or cfg.experts is not None
            or cfg.retention is not None or cfg.layer_types is not None):
        from ..parallel.mesh import mesh_axis_size

        asked["mesh"] = mesh is not None and mesh_axis_size(mesh, tp_axis) > 1
    for name, used in asked.items():
        if used:
            what = ("a recurrent state" if cfg.retention is not None
                    else "a latent-attention cache" if cfg.latent_attention is not None
                    else "a pool of window and full layers" if cfg.layer_types is not None
                    else "routed experts")
            raise ValueError(
                f"{name} is not ported to {what} yet: serve this model with the "
                f"default {name} (see ROADMAP.md Reach for what is missing)"
            )


class ServingEngine:
    """Serve many requests through one page pool with in-flight admission.

    The KV pool is a refcounted *page pool* with per-lane block tables
    (:mod:`.paging`): pages are allocated as lanes grow, prefix-cache hits
    alias shared pages with ZERO copies (copy-on-write only on a shared tail
    page), and page pressure preempts the youngest lane, which requeues for
    replay through the prefix cache.  Greedy outputs are token-identical to
    ``generate``, after preemption too; a preempted *sampled* lane resumes on
    a restarted RNG stream (re-seeded from the request id at install):
    distribution-correct, not sample-exact, as under speculative decoding.

    A retention model (``config.retention``) has no pages: its pool is the
    lanes' recurrent state (:class:`~accelerate_tpu.serving.paging.StatePool`),
    a lane is admitted when a slot is free and its state zeroed on the device
    when the slot is taken, no prefix cache is built, and the options a state
    does not have yet are refused by name (:func:`_refuse_unported`).  Chunked
    prefill, the scheduler, cancellation, failover replay and streaming are
    the same code.

    A stack of window and full attention layers (``config.layer_types``) gets
    a pool of two retention rules
    (:class:`~accelerate_tpu.serving.paging.MixedKVPool`): whole block tables
    for the full layers, a ring of pages a lane for the window layers, whose
    pages go back to the free list as they fall behind the window
    (``serve/page_release``; ``docs/usage/mixed_attention_stack.md``).  It
    shares nothing between lanes, so ``prefix_cache_mb`` must be 0; the other
    options it does not have yet are refused by name too.

    Parameters
    ----------
    model, params: the flagship ``Transformer`` and its (HBM-resident) params.
    num_slots: concurrent request lanes (rows of the block table).
    max_len: per-lane KV capacity (default ``config.max_seq_len``).  A request
        needs ``prompt_len + max_new_tokens + decode_window <= max_len``.
    prefill_buckets: fixed chunk sizes for chunked prefill — one compiled
        prefill shape per bucket.  Defaults to ``(128, 512)`` clipped to
        ``max_prompt_len``.
    max_prompt_len: upper bound of the prefill buckets; defaults to
        ``max_len``.
    prefill_token_budget: max prefill tokens charged per engine step (bounds
        decode-latency jitter while prompts stream in); default: the largest
        bucket.
    decode_window: decode steps fused per engine step (one ``lax.scan``
        executable).  Larger windows amortize host round-trips; a request
        finishing mid-window wastes at most ``window - 1`` masked lane-steps.
    slot_order: optional slot-id preference for admission (tests permute this
        to pin down lane independence).
    prefix_cache_mb: byte budget (MiB) for the chunk-granular prefix KV cache
        (:mod:`.prefix_cache`); ``0``/``None`` disables it.  Requests opt out
        per-request via ``submit(..., cache_prefix=False)``.
    prefix_host_mb: byte budget (MiB) for the host-RAM spill tier behind the
        device prefix cache.  Device-tier evictions demote
        their pages host-side via an async D2H gather instead of dropping
        them; a later hit on a spilled prefix promotes it back with an H2D
        scatter-install enqueued BEHIND the in-flight decode window, charging
        zero prefill budget.  ``0`` (the default) disables the tier and keeps
        every existing code path byte-identical.
    prefix_disk_mb: optional disk ring (MiB) behind the host tier; host-tier
        evictions of landed payloads park as ``.npz`` files instead of
        dropping.  Requires ``prefix_host_mb > 0`` and ``prefix_disk_dir``.
    prefix_disk_dir: directory for the disk ring's page files.
    speculate_k: draft length K for self-speculative decoding; ``0`` (the
        default) disables it.  Cycles where at least one lane has an n-gram
        draft run one verify forward over ``[slots, K+1]`` positions instead
        of the decode window, landing 1..K+1 tokens per lane; draftless
        cycles fall back to the decode window.  Greedy outputs are
        token-exact either way; sampled outputs preserve the distribution
        but not the sample stream.  Adds exactly one compiled executable.
        Per-request opt-out: ``submit(..., speculate=False)``.
    speculate_ngram: longest trailing n-gram the draft proposer tries
        (:func:`~accelerate_tpu.serving.spec.propose_ngram_draft`).
    draft_model: switch speculation to an on-device draft model verified
        over a token tree.  ``int n`` — self-speculation: the first ``n``
        layers of the served model (re-sliced on every :meth:`swap_params`);
        ``str path`` — a HF checkpoint dir streamed through
        :mod:`~accelerate_tpu.models.hf_compat` (optionally ``"dir#n"`` to
        truncate to ``n`` layers); ``(cfg, params)`` — an explicit pre-built
        draft.  Replaces the linear verify window with the tree verify
        window plus one draft-forward executable; requires a full-causal
        model (no sliding window / alibi).
    tree_width: sibling branches at the tree's branch point (draft-model
        top-k candidates); ``1`` (default) drafts a single greedy chain —
        the linear window shape, still verified through the tree machinery.
        Requires ``draft_model``.
    tree_depth: draft chain length below each branch candidate; defaults to
        ``speculate_k`` when set, else 4.  The tree verifies
        ``1 + tree_width * tree_depth`` nodes per lane and commits at most
        ``tree_depth + 1`` tokens.  Under ``decode_kernel="pallas"`` the
        node count must stay <= 32 (ancestor masks pack into uint32 rows).
    draft_ctx: host-side sliding context window the stateless draft forward
        re-prefills each cycle (:class:`~.paging.DraftContextWindow`).
    metrics_port: start (or join) the process-wide debug server
        (``/metrics``, ``/healthz``, ``/debug/flight``, ``/debug/stacks``)
        on this port; ``0`` binds an ephemeral port, ``None`` defers to
        ``ATPU_METRICS_PORT`` (off when unset).
    paged: accepted for compatibility only and selects nothing.  ``True``
        (what the benchmark's workload files pass) is a no-op; ``False``
        raises — the per-lane slab pool it used to select was removed.
    page_size: tokens per KV page.  Must divide every prefill
        bucket and ``max_len``; default ``gcd(prefill_buckets)`` — the prefix
        cache's chunk granularity.
    num_pages: physical pages in the pool, the knob that trades
        HBM for concurrency: lanes only consume pages they actually use, so
        ``num_pages`` can be far below ``num_slots * max_len / page_size``
        under mixed-length traffic.  Default is the no-preemption worst case
        (``num_slots * max_len / page_size + 1``).
    decode_kernel: attention program for the paged decode/verify windows.
        ``"xla"`` (default) gathers each lane's pages into a ``max_len``-wide
        view and runs the model's attention einsum over it (the program
        ``generate`` runs).  ``"pallas"`` reads KV pages *in place* through the
        block tables (:mod:`accelerate_tpu.ops.paged_attention`): no gather
        temporary, no padding reads — one grid program per (lane, kv-head)
        with an online softmax over each lane's live pages only.  Same
        compiled-shape budget (the kernel replaces the decode executables, it
        does not add any); greedy outputs are token-identical in practice
        (asserted by ``tests/test_paged_attention.py``) but the online
        softmax is not bitwise the full-view softmax.  Full-causal
        rope/learned models only.
    prefill_kernel: attention program for the paged *prefill chunk*
        executables.  ``None`` (default) follows the resolved
        ``decode_kernel`` — a pool that decodes through the Pallas kernel
        prefills through its chunk-wide twin
        (:func:`~accelerate_tpu.ops.paged_attention.paged_flash_prefill`),
        a pool on the XLA reference stays on it.  ``"pallas"`` reads prior
        pages in place with a q-blocked flash online softmax and writes the
        chunk's K/V straight into the page pool (scatter-time quantization
        included) — no gather temporary, no scatter round-trip.  ``"xla"``
        forces the gather/scatter reference path (the only arm under tp>1,
        and the bisection knob when a prefill divergence is suspected).  Same
        compiled-shape budget either way (the kernel replaces the per-bucket
        prefill executables' attention, it adds none).  Full-causal
        rope/learned models only.
    interleave_prefill: dispatch each step's prefill chunks *behind* the
        decode window instead of ahead of it.
        The decode window is issued first and its tokens stay in flight
        (``async_depth=1``) while the host schedules and enqueues the cycle's
        chunks back-to-back behind it; the scheduler charges decode tokens
        and prefill tokens against ONE joint per-cycle budget
        (:meth:`.Scheduler.begin_step`), so decode lanes never skip a cycle
        while a long prompt prefills, and up to ``num_slots`` requests may
        be mid-prefill at once with chunks picked shortest-remaining-first —
        a chat prompt lands its one chunk next cycle even while a 100k-token
        prompt streams.  Greedy/sampled outputs are token-identical to the
        default prefill-ahead ordering (lane RNG folds from the request id,
        never from arrival order).
    kv_dtype: KV page storage format.  ``None``
        keeps the model dtype (token-identical); ``"bf16"`` stores bf16;
        ``"int8"`` / ``"fp8"`` quantize pages with per-(page, kv-head) f32
        scales written at scatter time and dequantized at attention — about
        4x (fp32 models) / 2x (bf16) less KV HBM per token, so the same pool
        bytes hold proportionally more concurrent lanes.  Quantized KV is
        lossy: outputs track the native path within a logit tolerance
        (``serve/kv_quant_error`` gauges the per-cycle round-trip error).
    mesh: a named :class:`jax.sharding.Mesh` for tensor-parallel serving
        (``None``, the default, keeps single-chip behavior byte-for-byte).
        With a ``tp_axis`` of size > 1: params shard by the
        :data:`~accelerate_tpu.parallel.tensor_parallel.DEFAULT_TP_RULES`,
        the page pool shards on the kv-head axis, and every
        window executable compiles with explicit in/out shardings
        (:class:`~accelerate_tpu.serving.pool.ServeShardings`) — one model
        spans the axis while block tables, scheduler, prefix-cache radix
        tree, and telemetry stay host-side and replicated.  Greedy outputs
        are token-identical to tp=1 at every (kernel, kv_dtype)
        combination and the compiled-executable budget is unchanged; both
        are pinned by ``tests/test_serving_mesh.py``.  ``decode_kernel=
        "pallas"`` is refused at construction under tp > 1 (the Pallas grid
        reads whole head tiles of an unsharded pool; the XLA einsum
        partitions head-parallel) — it never silently becomes ``"xla"``.
        Head counts must divide the tp degree.
    tp_axis: mesh axis name the KV heads and weight matrices shard over
        (default ``"tp"``); axes absent from the mesh count as size 1.
    async_depth: ``1`` (the default) runs the depth-1 pipelined loop: each
        decode window's tokens stay on device in a :class:`.readback.Readback`
        handle while the host runs ``_emit``, streaming callbacks, and the
        next step's admission, and the NEXT window is dispatched before the
        previous one's tokens are materialized — host work overlaps device
        compute instead of alternating with it.  Outputs are token-identical
        to ``async_depth=0`` (today's strictly synchronous loop) for every
        sampling mode; the observable differences are lag semantics only: a
        lane that hits EOS at window N is retired one cycle later (it may
        execute one extra masked window whose tokens are discarded — written
        to the null page),
        ``finish_step`` lands one step later, and ``cancel`` of a running
        lane drops the in-flight window's tokens.  Speculative cycles
        synchronize on the previous window before dispatching (drafts and the
        verify token block need its tokens), so with ``speculate_k > 0`` the
        overlap covers scheduling/admission but not ``_emit``.  Set
        ``async_depth=0`` when callbacks must observe tokens the same step
        the device produced them, or to bisect a suspected pipelining bug.
        See ``docs/usage/serving.md`` ("Async pipelined serving").
    max_queue: admission backpressure bound — a ``submit`` that would push
        the waiting queue past this raises a *retriable*
        :class:`~accelerate_tpu.serving.errors.AdmissionError` (queue depth
        + retry-after hint attached) instead of queueing unboundedly.  The
        HTTP front door maps it to 429; the
        :class:`~accelerate_tpu.serving.router.ReplicaRouter` failover
        ladder tries the next replica.  ``None`` (default) keeps the queue
        unbounded.  Preemption replay re-enters at the queue FRONT and is
        never refused.
    weights_version: operator-facing label for the parameter set currently
        served — surfaced by ``/v1/models`` and rotated by
        :meth:`swap_params` during zero-downtime weight hot-swap.
    """

    def __init__(
        self,
        model: Transformer,
        params: Any,
        num_slots: int = 4,
        max_len: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_prompt_len: Optional[int] = None,
        prefill_token_budget: Optional[int] = None,
        decode_window: int = 4,
        pad_token_id: int = 0,
        rng_seed: int = 0,
        slot_order: Optional[Sequence[int]] = None,
        registry: Optional[MetricsRegistry] = None,
        prefix_cache_mb: Optional[float] = 64.0,
        prefix_host_mb: Optional[float] = 0.0,
        prefix_disk_mb: Optional[float] = 0.0,
        prefix_disk_dir: Optional[str] = None,
        metrics_port: Optional[int] = None,
        speculate_k: int = 0,
        speculate_ngram: int = 3,
        draft_model: Any = None,
        tree_width: int = 1,
        tree_depth: Optional[int] = None,
        draft_ctx: int = 64,
        paged: bool = True,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        decode_kernel: str = "xla",
        prefill_kernel: Optional[str] = None,
        interleave_prefill: bool = False,
        kv_dtype: Optional[str] = None,
        mesh=None,
        tp_axis: str = "tp",
        async_depth: int = 1,
        max_queue: Optional[int] = None,
        weights_version: str = "v0",
        role: str = "both",
    ):
        cfg = model.config
        self.model = model
        self.params = params
        self.config = cfg
        _refuse_unported(cfg, kv_dtype=kv_dtype, speculate_k=speculate_k,
                         draft_model=draft_model, decode_kernel=decode_kernel,
                         prefill_kernel=prefill_kernel, prefix_host_mb=prefix_host_mb,
                         prefix_disk_mb=prefix_disk_mb, role=role, mesh=mesh,
                         tp_axis=tp_axis, prefix_cache_mb=prefix_cache_mb)
        #: routed experts: decode windows and prefill chunks return the
        #: ``moe_*`` counters, fetched with the window's tokens
        self._routed = cfg.experts is not None
        #: the held experts a decode step's expert layers could read
        self._expert_slots_a_step = (
            cfg.experts.num_held * (cfg.num_layers - cfg.experts.dense_layers) if self._routed else 0)
        #: the counters ``serve/step`` closes with (``_STEP_CARRIED``, and a
        #: routed engine's ``experts_hit`` / ``expert_slots``)
        self._step_carried = _STEP_CARRIED + (_STEP_CARRIED_MOE if self._routed else ())
        #: a retention model: the pool is the lanes' recurrent state
        #: (:class:`StatePool`), its windows return the ``state_*`` counters,
        #: and every page-speaking step of the lane lifecycle has nothing to do
        self._stateful = cfg.retention is not None
        #: window and full layers in one stack: the pool keeps a ring of pages
        #: a lane for the one kind and whole tables for the other
        #: (:class:`MixedKVPool`), its windows return the ``kv_rows_*`` counters
        self._mixed = cfg.layer_types is not None
        self.num_slots = int(num_slots)
        self.max_len = int(max_len if max_len is not None else cfg.max_seq_len)
        self.max_prompt_len = int(
            max_prompt_len if max_prompt_len is not None else self.max_len
        )
        if self.max_prompt_len > self.max_len:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} > slot capacity {self.max_len}"
            )
        if prefill_buckets is None:
            prefill_buckets = [b for b in (128, 512) if b <= self.max_prompt_len]
            if not prefill_buckets:
                prefill_buckets = [self.max_prompt_len]
        self.buckets = tuple(sorted(set(int(b) for b in prefill_buckets)))
        if self.buckets[-1] > self.max_prompt_len:
            raise ValueError(
                f"largest prefill bucket {self.buckets[-1]} exceeds "
                f"max_prompt_len {self.max_prompt_len}"
            )
        self.window = int(decode_window)
        self.speculate_k = int(speculate_k)
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        self.speculate_ngram = int(speculate_ngram)
        self.pad_token_id = int(pad_token_id)
        if slot_order is None:
            slot_order = range(self.num_slots)
        self.slot_order = tuple(int(s) for s in slot_order)
        if sorted(self.slot_order) != list(range(self.num_slots)):
            raise ValueError(
                f"slot_order must permute range({self.num_slots}), got {self.slot_order}"
            )
        self.async_depth = int(async_depth)
        if self.async_depth not in (0, 1):
            raise ValueError(
                f"async_depth must be 0 (synchronous) or 1 (depth-1 pipeline), "
                f"got {async_depth}"
            )
        #: the at-most-one in-flight window handle (depth-1 pipeline); None
        #: when the pipeline is empty (always, under async_depth=0)
        self._inflight: Optional[Readback] = None
        #: the PREVIOUS window's handle, parked between this cycle's dispatch
        #: and its drain at the end of _step_impl — non-None only inside that
        #: span, so admission work running in between (interleaved prefill)
        #: can reach it and any forced flush drains oldest-first
        self._prev_handle: Optional[Readback] = None

        if not paged:
            raise ValueError(
                "paged=False: the per-lane slab KV pool was removed; the page "
                "pool is the engine's only pool (drop the argument)"
            )
        if decode_kernel not in ("xla", "pallas"):
            raise ValueError(
                f"decode_kernel must be 'xla' or 'pallas', got {decode_kernel!r}"
            )
        if prefill_kernel not in (None, "xla", "pallas"):
            raise ValueError(
                f"prefill_kernel must be None, 'xla' or 'pallas', "
                f"got {prefill_kernel!r}"
            )
        self.interleave_prefill = bool(interleave_prefill)
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got {role!r}"
            )
        #: "prefill" runs chunked prefill only — freshly installed lanes
        #: never dispatch a decode window here, they wait for the router's
        #: prefill handoff (serving/transfer.py) onto a decode-role peer.
        #: "decode" replicas receive migrated lanes (and can still prefill
        #: adopted replays — role shapes steady-state policy, not recovery).
        self.role = role
        from ..ops.paged_attention import (
            kv_qmax,
            kv_storage_dtype,
            resolve_paged_kernel,
        )

        # shard-aware kernel check: under a tp>1 mesh the Pallas grid would
        # read whole (kv-head, page) tiles of a head-sharded pool, so asking
        # for "pallas" there raises — the engine runs the kernel it was asked
        # for or does not start
        decode_kernel = resolve_paged_kernel(decode_kernel, mesh, tp_axis)
        self.decode_kernel = decode_kernel
        # prefill follows the decode kernel unless forced: a pool decoding
        # through Pallas prefills through its chunk-wide twin
        if prefill_kernel is None:
            prefill_kernel = decode_kernel
        self.prefill_kernel = resolve_paged_kernel(
            prefill_kernel, mesh, tp_axis, role="prefill"
        )
        self.kv_dtype = kv_dtype

        self.quantized = kv_qmax(kv_storage_dtype(kv_dtype, cfg.dtype)) is not None
        # "direct" windows thread the page pool through the model
        # (PagedKVCache) instead of the gather/scatter sandwich: required for
        # in-place Pallas attention and for scale-aware quantized writes.
        # Native-dtype XLA stays on the gathered path: the model's own
        # attention over a max_len-wide view, plus the live-page gather mask.
        self._direct = self.quantized or decode_kernel == "pallas"
        # the prefill-side twin of the flag: quantized pools and the flash
        # prefill kernel both need the chunk forward to own the page writes
        self._prefill_direct = self.quantized or self.prefill_kernel == "pallas"
        # ------------------------------------------------- tree speculation
        self._draft_spec = draft_model
        self.tree_width = int(tree_width)
        self.tree_depth = int(
            tree_depth if tree_depth is not None
            else (self.speculate_k if self.speculate_k else 4)
        )
        self.draft_ctx = int(draft_ctx)
        self.tree: Optional[TreeSpec] = None
        if draft_model is None:
            if self.tree_width != 1:
                raise ValueError(
                    "tree_width > 1 needs a draft model to rank sibling "
                    "branches; pass draft_model="
                )
        else:
            if self.draft_ctx < 1:
                raise ValueError(f"draft_ctx must be >= 1, got {draft_ctx}")
            if cfg.sliding_window is not None or cfg.positional == "alibi":
                raise ValueError(
                    "tree speculation needs a full-causal model: the ancestor "
                    "mask replaces the causal row mask, which sliding_window "
                    "and alibi models reshape"
                )
            self.tree = TreeSpec(self.tree_width, self.tree_depth)
            if decode_kernel == "pallas" and self.tree.nodes > 32:
                raise ValueError(
                    f"tree has {self.tree.nodes} nodes but the Pallas tree "
                    f"kernel packs ancestor masks into uint32 rows (<= 32 "
                    f"nodes); shrink tree_width/tree_depth or use "
                    f"decode_kernel='xla'"
                )
        # widest device pass this engine can run in one cycle: a tree verify
        # writes all S node positions at the lane frontier (committing at
        # most depth + 1), a linear verify writes speculate_k + 1
        self._spec_span = (
            self.tree.nodes if self.tree is not None else self.speculate_k + 1
        )
        self._spec_any = self.tree is not None or self.speculate_k > 0
        self.page_size = int(
            page_size if page_size is not None
            else math.gcd(*self.buckets) if len(self.buckets) > 1
            else self.buckets[0]
        )
        for b in self.buckets:
            if b % self.page_size != 0:
                raise ValueError(
                    f"page_size {self.page_size} must divide every prefill "
                    f"bucket, got {self.buckets}"
                )
        if self.max_len % self.page_size != 0:
            raise ValueError(
                f"page_size {self.page_size} must divide max_len {self.max_len}"
            )
        self.num_pages = int(
            num_pages if num_pages is not None
            else self.num_slots * (self.max_len // self.page_size) + 1
        )
        # ------------------------------------------------------ mesh / tp
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            from ..parallel.mesh import mesh_axis_size
            from ..parallel.sharding import shard_pytree_with_path
            from ..parallel.tensor_parallel import (
                SERVING_TP_RULES,
                make_tp_sharding_fn,
            )

            self.tp_degree = mesh_axis_size(mesh, tp_axis)
            if self.tp_degree > 1 and (
                cfg.num_heads % self.tp_degree != 0
                or cfg.num_kv_heads % self.tp_degree != 0
            ):
                raise ValueError(
                    f"num_heads {cfg.num_heads} / num_kv_heads "
                    f"{cfg.num_kv_heads} must divide evenly over "
                    f"tp={self.tp_degree}"
                )
            # SERVING_TP_RULES, not DEFAULT_TP_RULES: row-parallel psum would
            # break bitwise token identity vs tp=1 (see tensor_parallel.py)
            self.params, param_shardings = shard_pytree_with_path(
                params,
                make_tp_sharding_fn(
                    mesh, axis_name=tp_axis, rules=SERVING_TP_RULES
                ),
            )
            self._shardings = ServeShardings(
                mesh, param_shardings, tp_axis=tp_axis
            )
        else:
            self.tp_degree = 1
            self._shardings = None
        self.metrics = registry if registry is not None else get_registry()
        # device state: the shared page pool + host block tables.  There is
        # no prefill scratch: a chunk gathers the lane's own view, shared
        # prefix pages included, and writes freshly filled pages back
        if self._stateful:
            self.kv = StatePool(
                cfg, self.num_slots, registry=self.metrics,
                sharding=None if self._shardings is None else self._shardings.replicated,
            )
        elif self._mixed:
            # the ring: the window, the largest chunk written behind it, and
            # the page a decode step is writing
            ring_pages = -(-(cfg.sliding_window + self.buckets[-1]) // self.page_size) + 1
            self.kv = MixedKVPool(
                cfg, self.num_slots, self.max_len, self.page_size,
                self.num_pages, ring_pages, registry=self.metrics,
            )
        else:
            self.kv = PagedKVPool(
                cfg, self.num_slots, self.max_len, self.page_size,
                self.num_pages, registry=self.metrics, kv_dtype=kv_dtype,
                mesh=mesh, tp_axis=tp_axis,
            )
        self.tracer = get_tracer()
        # Forensics + cost accounting (docs/usage/observability.md): request
        # lifecycle events land in the process flight recorder, per-executable
        # FLOP/HBM signatures in a private cost table (filled lazily by
        # analyze_costs / a /metrics scrape — never in the serve loop).
        # Every event this engine (and its scheduler) records carries the
        # replica id; the per-request trace registry keys its waterfalls on
        # the same id across failover.
        self.engine_id = f"e{next(_ENGINE_IDS)}"
        self.recorder = get_flight_recorder().tagged(engine=self.engine_id)
        self.reqtrace = get_reqtrace()
        self.cost_table = CostTable(self.metrics)
        self.device_peaks = detect_device_peaks()
        self.debug_server = start_debug_server(
            metrics_port, registry=self.metrics, recorder=self.recorder
        )
        if self.debug_server is not None:
            self.debug_server.add_collector(self.analyze_costs)
        # Window models: the direct paged windows run a Transformer whose
        # config selects the attention kernel.  The
        # fields carry no parameters, so the engine's params serve every
        # variant.  The prefill model picks its own kernel: the chunk-wide
        # flash kernel under prefill_kernel="pallas", the XLA reference
        # otherwise — either way the page writes go through the same insert
        # path, so the written KV is identical across kernels.
        wmodel = pmodel = model
        if self._direct:
            wmodel = Transformer(dataclasses.replace(cfg, paged_kernel=decode_kernel))
        if self._prefill_direct:
            pmodel = Transformer(dataclasses.replace(
                cfg,
                paged_kernel=("flash_prefill" if self.prefill_kernel == "pallas"
                              else "xla"),
            ))
        # budget=1 per executable: the engine's whole design promises exactly
        # one compiled shape each — any second signature is a bug worth a warning
        if self._stateful:
            decode_fn = make_state_decode_window(wmodel, self.window, shardings=self._shardings)
            self._state_install = RecompileWatchdog(
                make_state_install(shardings=self._shardings),
                name="serve/state_install", budget=1, registry=self.metrics,
            )
        elif self._mixed:
            decode_fn = make_mixed_decode_window(wmodel, self.window)
        else:
            decode_fn = make_paged_decode_window(
                wmodel, self.window, direct=self._direct, shardings=self._shardings)
        if self._direct:
            # nested watchdog: serve/paged_attn accounts the in-place paged
            # attention executable itself (budget 1 — the kernel REPLACES the
            # decode executable, it must never add shapes); serve/decode_window
            # keeps its usual accounting on top.  Attribute forwarding lets
            # jit_cache_sizes read straight through both layers.
            decode_fn = RecompileWatchdog(
                decode_fn, name="serve/paged_attn", budget=1,
                registry=self.metrics,
            )
        self._decode = RecompileWatchdog(
            decode_fn, name="serve/decode_window", budget=1, registry=self.metrics,
        )
        self._prefill = {
            b: RecompileWatchdog(
                make_state_prefill_chunk(pmodel, shardings=self._shardings)
                if self._stateful else
                make_mixed_prefill_chunk(pmodel, b, self.page_size)
                if self._mixed else
                make_paged_prefill_chunk(
                    pmodel, b, self.page_size, direct=self._prefill_direct,
                    shardings=self._shardings,
                ),
                name=f"serve/prefill_{b}", budget=1, registry=self.metrics,
            )
            for b in self.buckets
        }
        self._lane_install = RecompileWatchdog(
            make_lane_install(shardings=self._shardings),
            name="serve/lane_install", budget=1, registry=self.metrics,
        )
        if self.tree is not None:
            # tree mode REPLACES the linear verify window: the compiled
            # budget grows by exactly {draft_forward, tree_verify_window}
            self._verify = RecompileWatchdog(
                make_paged_tree_verify_window(
                    wmodel, self.tree, direct=self._direct,
                    shardings=self._shardings,
                ),
                name="serve/tree_verify_window", budget=1,
                registry=self.metrics,
            )
            draft_cfg, draft_host = build_draft(
                cfg, self.params, draft_model,
                draft_ctx=self.draft_ctx, depth=self.tree_depth,
            )
            # the draft head is small: replicate it rather than shard — tp
            # collectives would serialize its many tiny dispatches
            self._draft_params = (
                jax.device_put(draft_host) if self._shardings is None
                else jax.device_put(draft_host, self._shardings.replicated)
            )
            self._draft_cfg = draft_cfg
            self._draft_fwd = RecompileWatchdog(
                make_draft_forward(Transformer(draft_cfg), self.tree,
                                   self.draft_ctx, shardings=self._shardings),
                name="serve/draft_forward", budget=1, registry=self.metrics,
            )
            self._draft_window = DraftContextWindow(
                self.num_slots, self.draft_ctx, pad=self.pad_token_id
            )
            self._ngram = None
            self.drafter = TreeDrafter(self.tree, draft_cfg, self._draft_fwd)
        elif self.speculate_k:
            self._verify = RecompileWatchdog(
                make_paged_verify_window(
                    wmodel, self.speculate_k, direct=self._direct,
                    shardings=self._shardings,
                ),
                name="serve/verify_window", budget=1, registry=self.metrics,
            )
            self._draft_fwd = None
            self._draft_window = None
            self._ngram = NgramDrafter(max_ngram=self.speculate_ngram)
            self.drafter = self._ngram
        else:
            self._verify = None
            self._draft_fwd = None
            self._draft_window = None
            self._ngram = None
            self.drafter = None
        self._copy_page = RecompileWatchdog(
            make_copy_page(shardings=self._shardings),
            name="serve/copy_page", budget=1, registry=self.metrics,
        )
        self.prefix_host_bytes = int((prefix_host_mb or 0.0) * 2**20)
        prefix_disk_bytes = int((prefix_disk_mb or 0.0) * 2**20)
        if self.prefix_host_bytes and not prefix_cache_mb:
            raise ValueError(
                "prefix_host_mb spills the prefix cache's pages; it requires "
                "an enabled prefix cache (prefix_cache_mb > 0)"
            )
        if prefix_disk_bytes and not self.prefix_host_bytes:
            raise ValueError(
                "prefix_disk_mb sits behind the host ring; set prefix_host_mb"
            )
        if self.prefix_host_bytes:
            # one D2H gather + one H2D scatter-install shape per prefill
            # bucket: the documented compiled-budget growth of the host tier
            self._spill_extract = {
                b: RecompileWatchdog(
                    make_spill_extract(b // self.page_size,
                                       shardings=self._shardings),
                    name=f"serve/spill_{b}", budget=1, registry=self.metrics,
                )
                for b in self.buckets
            }
            self._promote_install = {
                b: RecompileWatchdog(
                    make_promote_install(b // self.page_size,
                                         shardings=self._shardings),
                    name=f"serve/promote_{b}", budget=1, registry=self.metrics,
                )
                for b in self.buckets
            }
        else:
            self._spill_extract = {}
            self._promote_install = {}
        # a state has no pages for a hit to map: a retention model builds no
        # prefix cache, whatever ``prefix_cache_mb`` says
        if prefix_cache_mb and not self._stateful:
            self.prefix_cache: Optional[PrefixCache] = PrefixCache(
                int(prefix_cache_mb * 2**20), registry=self.metrics,
                on_evict=self._on_prefix_evict,
                host_capacity_bytes=self.prefix_host_bytes,
                spill=self._spill_node if self.prefix_host_bytes else None,
                disk_capacity_bytes=prefix_disk_bytes,
                disk_dir=prefix_disk_dir,
            )
        else:
            self.prefix_cache = None

        self.scheduler = Scheduler(
            self.buckets,
            prefill_token_budget if prefill_token_budget is not None else self.buckets[-1],
            prefix_cache=self.prefix_cache,
            recorder=self.recorder,
            max_queue=max_queue,
            # interleaved mode keeps up to one open prefill per slot so a
            # short prompt's chunk can land SRTF ahead of a long one's
            max_prefills=self.num_slots if self.interleave_prefill else 1,
        )
        #: label of the parameter set currently served; rotated by swap_params
        self.weights_version = str(weights_version)
        #: True while a drain / hot-swap holds new prefills back (queued
        #: requests stay queued; in-flight lanes run to completion)
        self.admission_paused = False

        n = self.num_slots
        # host-side per-slot lane state, shipped to the decode window each step
        self._slot_req: List[Optional[Request]] = [None] * n
        self._slot_ever_used = np.zeros(n, bool)
        self._pending_tok = np.zeros(n, np.int32)
        self._active = np.zeros(n, bool)
        self._eos = np.full(n, -1, np.int32)
        self._do_sample = np.zeros(n, bool)
        self._temperature = np.ones(n, np.float32)
        self._top_k = np.zeros(n, np.int32)
        self._top_p = np.ones(n, np.float32)
        self._rngs = np.zeros((n, 2), np.uint32)
        # host mirror of each lane's KV write index: install sets
        # it to prompt_len - 1, decode/verify advance it by exactly what the
        # device committed — integer arithmetic, so the mirror is always exact
        self._lane_len = np.zeros(n, np.int32)
        #: high-water mark of simultaneously active lanes
        self.peak_active_lanes = 0
        self._base_rng = jax.random.PRNGKey(rng_seed)
        # slots held for requests mid-prefill (one per open prefill; a set
        # because interleaved mode keeps several prefills in flight at once)
        self._reserved_slots: set = set()
        # device-resident mirror of the lane vectors above (uploaded once,
        # then edited in place: decode/verify carry pending/rng device-side,
        # installs scatter one slot, frees re-upload the active mask) —
        # lane state never round-trips through the host mid-serve
        self._lane_device: Optional[list] = None

        self._next_rid = 0
        self._step_count = 0
        # ``stats`` stays a plain mutable dict — benches reset it in place —
        # while ``_bump`` mirrors every increment into cumulative counters.
        # (_Stats additionally answers ``stats()`` with a trace summary.)
        self.stats = _Stats({
            "requests_submitted": 0,
            "requests_completed": 0,
            "tokens_generated": 0,
            "prefill_chunks": 0,
            "prefill_tokens": 0,
            "interleaved_chunks": 0,
            "decode_steps": 0,
            "occupied_lane_steps": 0,
            "slots_reused": 0,
            "prefix_hit_tokens": 0,
            "prefix_hit_tokens_host": 0,
            "prefix_miss_tokens": 0,
            "cancelled": 0,
            "spec_drafted": 0,
            "spec_accepted": 0,
            "preemptions": 0,
            "cow_copies": 0,
            "prefreed_lanes": 0,
            "hot_swaps": 0,
            "deadline_shed": 0,
            "requests_replayed": 0,
        })
        if self._routed:
            # token-expert choices made, those that fell on experts held
            # here, and (decode windows) held experts that got a row, summed
            # over steps and layers: live lanes and valid prompt rows only;
            # and what that last one is out of, held experts x expert layers
            # x the steps of each decode window drained (counted on the host)
            self.stats.update(moe_pairs_total=0, moe_pairs_here=0, moe_experts_hit=0,
                              moe_expert_slots=0)
        if self._stateful:
            # lane-steps whose state a decode window read and rewrote (lanes x
            # steps), those that emitted a token, and lanes zeroed at install
            self.stats.update(state_lane_steps=0, state_live_lane_steps=0, state_installs=0)
        if self._mixed:
            # pages the allocators handed out (both kinds) and window-layer
            # pages handed back because they fell behind the window (counted
            # on the host, where the allocator acts); the keys a live lane's
            # decode step could see, in all layers and in the window layers,
            # summed over lane-steps (counted on the device, in the window)
            self.stats.update(kv_pages_taken=0, kv_pages_released_window=0,
                              kv_rows_live=0, kv_rows_live_window=0)
        #: the widths of the views a chunk's attention reads (the
        #: ``max_len``-wide one first): none where the chunk reads pages in
        #: place (the Pallas prefill kernel) or a recurrent state
        self._chunk_views = ()
        if not (self._stateful or self.prefill_kernel == "pallas"):
            self._chunk_views = self._gathered_views()
            # key blocks (``ops/view_attention.py`` ``KEY_BLOCK`` columns) of the
            # ``max_len``-wide view that a dispatched chunk could see a key in,
            # and that the view has: counted on the host, where ``base`` is known
            self.stats.update(chunk_key_blocks_live=0, chunk_key_blocks_view=0)
        #: whether the decode window gathers flat views of its pages (not where
        #: it reads pages in place, latent rows or a recurrent state)
        self._gathers_view = not (self._stateful or self._direct or cfg.latent_attention is not None)
        if self._gathers_view:
            # (lane, page slot) blocks the decode windows' flat views were
            # filled with, over every such array, and those copied from a live
            # page: counted on the host at dispatch, from the tables and indices;
            # and the key blocks (``DECODE_BLOCK`` columns) of the ``max_len``-wide
            # view that hold a key the window's steps see, and that the view has
            self.stats.update(view_slots_live=0, view_slots=0,
                              decode_key_blocks_live=0, decode_key_blocks_view=0)
        self.stats.engine = self
        self._counters = {
            k: self.metrics.counter(f"serve/{k}_total") for k in self.stats
        }
        self._ttft_hist = self.metrics.histogram(
            "serve/ttft_s", buckets=_LATENCY_BUCKETS,
            help="submit-to-first-token wall time",
        )
        # per-traffic-class TTFT histograms, created lazily on the first
        # request carrying each class label (serve/ttft_s_class_<class>)
        self._class_ttft_hists: dict = {}
        # tenant attribution: per-tenant counter/histogram families, created
        # lazily on the first request carrying each tenant label (same
        # pattern as the class hists) —
        # serve/<key>_tenant_<tenant>_total and serve/ttft_s_tenant_<tenant>.
        # ``_tenant_stats`` mirrors the bumps numerically so
        # ``stats()["tenants"]`` is a lock-free rollup that sums EXACTLY to
        # the global counters (every _bump_tenant site sits beside a _bump).
        self._tenant_counters: dict = {}
        self._tenant_ttft_hists: dict = {}
        self._tenant_stats: dict = {}
        self._tenant_kv_gauges: dict = {}
        self._token_hist = self.metrics.histogram(
            "serve/token_latency_s", buckets=_LATENCY_BUCKETS,
            help="inter-token wall time (first token = TTFT)",
        )
        # Derived per-phase histograms, observed as request-trace phases close
        # (telemetry/reqtrace.py): together they decompose serve/ttft_s.
        self._queue_wait_hist = self.metrics.histogram(
            "serve/queue_wait_s", buckets=_LATENCY_BUCKETS,
            help="submit to first prefill chunk taken (trace queue_wait phase)",
        )
        self._prefill_phase_hist = self.metrics.histogram(
            "serve/prefill_compute_s", buckets=_LATENCY_BUCKETS,
            help="per-chunk prefill share of a request's waterfall "
                 "(fresh compute, cached replay, or promoted chunks alike)",
        )
        self._decode_tok_hist = self.metrics.histogram(
            "serve/decode_s_per_token", buckets=_LATENCY_BUCKETS,
            help="per-request decode-window share amortized over the tokens "
                 "the window committed (closes at drain, async-depth-aware)",
        )
        self._promote_wait_hist = self.metrics.histogram(
            "serve/promote_wait_s", buckets=_LATENCY_BUCKETS,
            help="host-tier promotion dispatch to landed-at-drain wait",
        )
        self._queue_gauge = self.metrics.gauge(
            "serve/queue_depth", help="requests queued or mid-prefill"
        )
        self._occupancy_gauge = self.metrics.gauge(
            "serve/slot_occupancy", help="fraction of slots active this window"
        )
        self._hit_rate_gauge = self.metrics.gauge(
            "serve/prefix_hit_rate",
            help="prefix_hit_tokens / (hit + miss) over cache-eligible prefill",
        )
        self._hit_rate_device_gauge = self.metrics.gauge(
            "serve/prefix_hit_rate_device",
            help="device-tier share of the prefix hit rate: tokens served by "
                 "zero-copy page aliasing / (hit + miss)",
        )
        self._hit_rate_host_gauge = self.metrics.gauge(
            "serve/prefix_hit_rate_host",
            help="spilled-tier share of the prefix hit rate: tokens served by "
                 "host/disk promotion (H2D install, no prefill FLOPs) / "
                 "(hit + miss)",
        )
        self._decode_flops_gauge = self.metrics.gauge(
            "serve/decode_flops_per_token",
            help="decode-window XLA FLOPs / (window * num_slots)",
        )
        self._hbm_gauge = self.metrics.gauge(
            "serve/hbm_peak_bytes",
            help="largest per-executable HBM peak across the serving pool, "
                 "per device (divided by the tp degree when sharded)",
        )
        self._accept_rate_gauge = self.metrics.gauge(
            "serve/spec_accept_rate",
            help="accepted / proposed draft tokens (cumulative) under "
                 "speculative decoding",
        )
        self._accept_len_hist = self.metrics.histogram(
            "serve/spec_accept_len",
            buckets=tuple(float(i) for i in range(33)),
            help="accepted draft tokens per drafted lane per verify cycle "
                 "(0..K linear, 0..tree_depth along the winning tree path); "
                 "the distribution the acceptance-vs-speedup curve samples",
        )
        self._draft_ms_hist = self.metrics.histogram(
            "serve/draft_ms",
            buckets=tuple(1e-2 * 2.0**i for i in range(20)),
            help="host wall time per cycle to assemble + dispatch the draft "
                 "forward (tree speculation only; device time hides under "
                 "the verify dispatch that follows)",
        )
        self._tree_nodes_counter = self.metrics.counter(
            "serve/spec_tree_nodes",
            help="token-tree nodes verified (occupied lanes x tree nodes, "
                 "cumulative) — the tree verify window's work volume",
        )
        self.metrics.gauge(
            "serve/decode_kernel",
            help="info gauge: decode attention program — 1 = pallas "
                 "(in-place paged kernel), 0 = xla (gather reference)",
        ).set(1.0 if self.decode_kernel == "pallas" else 0.0)
        self.metrics.gauge(
            "serve/prefill_kernel",
            help="info gauge: prefill attention program — 1 = pallas "
                 "(paged flash prefill), 0 = xla (gather/scatter reference)",
        ).set(1.0 if self.prefill_kernel == "pallas" else 0.0)
        self._pf_rate_gauge = self.metrics.gauge(
            "serve/prefill_tokens_per_s",
            help="prefill throughput over the trailing steps that ran at "
                 "least one chunk (valid tokens / wall time between them)",
        )
        self._interleave_gauge = self.metrics.gauge(
            "serve/prefill_interleave_ratio",
            help="fraction of prefill chunks dispatched BEHIND a same-cycle "
                 "decode window (interleaved chunked prefill); 0 by "
                 "definition under the default prefill-ahead ordering",
        )
        # trailing-rate state for serve/prefill_tokens_per_s
        self._pf_last_t: Optional[float] = None
        self._pf_last_tokens = 0
        # device quant-error handles from this cycle's prefill chunks; they
        # attach to the next dispatched window's Readback and are folded into
        # the quant-error gauge at drain (fetching here would sync the pipe)
        self._pending_prefill_qerr: List = []
        # ``moe_*`` counter handles of this cycle's prefill chunks, attached
        # and fetched the same way (with the window's tokens, in its one fetch)
        self._pending_moe_counts: List = []
        # hierarchical prefix cache deferrals, same discipline: spill gathers
        # enqueued at eviction time (``(node, handles)``) land their payloads
        # at the next drain; promotion-install records are acknowledged there.
        # Fetching either eagerly would sync the pipeline mid-cycle.
        self._pending_spills: List = []
        self._pending_promotions: List = []
        # tokens charged by the decode window dispatched this cycle; _admit
        # subtracts it from the scheduler's joint per-cycle budget when the
        # interleaved ordering dispatched decode first
        self._cycle_decode_tokens = 0
        self.metrics.gauge(
            "serve/tp_degree",
            help="info gauge: tensor-parallel degree the params and KV pool "
                 "shard over (1 = single-chip)",
        ).set(float(self.tp_degree))
        self.metrics.gauge(
            "serve/role",
            help="info gauge: disaggregated serving role — 0 = both "
                 "(monolithic), 1 = prefill-only, 2 = decode-only",
        ).set({"both": 0.0, "prefill": 1.0, "decode": 2.0}[self.role])
        if self._routed:
            from ..parallel.moe import held_experts_grouped

            #: the form the experts' products take when the programs are traced
            self.moe_grouped_kernel = held_experts_grouped(cfg)
            self.metrics.gauge(
                "serve/moe_grouped_kernel",
                help="1 where the held experts' products run in the Pallas grouped "
                     "matmul, 0 where they are jax.lax.ragged_dot",
            ).set(float(self.moe_grouped_kernel))
        #: whether a prefill chunk's attention over its gathered views runs in
        #: a Pallas flash kernel (``ops/view_attention.py``, or
        #: ``ops/latent_view_attention.py`` for latent rows): what the
        #: attention will see when the chunk programs are traced
        self.chunk_attention_kernel = self.tp_degree == 1 and cfg.positional != "alibi" and any(
            self._chunk_kernel_applies(b, m) for b in self.buckets for m in self._chunk_views)
        self.metrics.gauge(
            "serve/chunk_attention_kernel",
            help="1 where the prefill chunks' attention over the gathered views runs "
                 "in the Pallas flash kernel, 0 where it is XLA's masked softmax",
        ).set(float(self.chunk_attention_kernel))
        #: whether the decode window's flat views are built by the Pallas page
        #: copy (``ops/view_gather.py``): what ``_gather_view`` will see when the
        #: window is traced; never under ``tp > 1`` (``pool._kernel_form``)
        self.view_gather_kernel = self._gathers_view and self.tp_degree == 1 and all(
            view_gather_applies(pages) for pages in
            ((self.kv.pages_k, self.kv.ring_k) if self._mixed else (self.kv.pages_k,)))
        self.metrics.gauge(
            "serve/view_gather_kernel",
            help="1 where the decode window's gathered views are filled by the Pallas "
                 "page copy, 0 where by a zero fill and one update a page",
        ).set(float(self.view_gather_kernel))
        #: whether a decode step's attention over the window's gathered views
        #: runs in the Pallas decode kernel (``ops/view_attention.py``) for some
        #: kind of view: what ``cached_attention`` will see when the window is
        #: traced; never under ``tp > 1``
        self.decode_attention_kernel = self._gathers_view and self.tp_degree == 1 and (
            cfg.positional != "alibi") and any(
            view_decode_applies(
                jax.ShapeDtypeStruct((self.num_slots, 1, cfg.num_heads, cfg.resolved_head_dim), cfg.dtype),
                jax.ShapeDtypeStruct((self.num_slots, cfg.num_kv_heads * cfg.resolved_head_dim, m),
                                     self.kv.pages_k.dtype))
            for m in self._gathered_views())
        self.metrics.gauge(
            "serve/decode_attention_kernel",
            help="1 where a decode step's attention over the window's gathered views runs "
                 "in the Pallas decode kernel, 0 where it is XLA's masked softmax",
        ).set(float(self.decode_attention_kernel))
        self._kv_quant_gauge = (
            self.metrics.gauge(
                "serve/kv_quant_error",
                help="max abs KV round-trip quantization error of the values "
                     "written this cycle (an upper-bound logit-divergence "
                     "proxy) — only published under quantized kv_dtype",
            )
            if self.quantized
            else None
        )
        # pipeline overlap accounting (async_depth=1): host_s accumulates the
        # dispatch->drain host-work time each window, wait_s the blocking tail
        # of each fetch; their ratio is the fraction of host work the device
        # covered.
        self._overlap_host_s = 0.0
        self._overlap_wait_s = 0.0
        # set when a lane is freed while its window is still in flight: the
        # active mask is host-authoritative, so the next dispatch refreshes
        # just that one device vector instead of a full (blocking) resync
        self._mask_stale = False
        # old device handles replaced by a lane-install scatter or a mask
        # re-upload while a window is in flight.  They must not be *dropped*
        # yet — releasing the last reference to a handle a pending
        # computation consumes blocks until that computation finishes — so
        # they stage here and ride out on the next window's Readback, dying
        # only after its drain.
        self._stale_handles: List = []
        self._overlap_gauge = self.metrics.gauge(
            "serve/host_overlap_ratio",
            help="fraction of serve-loop host work (emit/callbacks/admission) "
                 "hidden under device execution: host_s / (host_s + "
                 "readback_wait_s), cumulative; 0 under async_depth=0",
        )
        # lane-migration gather/scatter pair, built lazily by
        # serving/transfer.py on this engine's first migration (most
        # replicas never migrate; the compiled budget grows only on the
        # ones that do, by exactly this documented set)
        self._migrate_extract: Optional[RecompileWatchdog] = None
        self._migrate_install: Optional[RecompileWatchdog] = None
        # fault containment: the first exception to escape a step parks here
        # and every later step() re-raises it — a poisoned engine never
        # half-runs.  The router supervisor reads it to trigger ejection.
        self._poisoned: Optional[BaseException] = None
        # deadline shedding: EMA of request wall time (admission's
        # queue-depth feasibility estimate) and a flag that keeps the
        # per-step deadline sweep off the hot path until a deadline exists
        self._service_ema = 0.0
        self._has_deadlines = False

    def _gathered_views(self) -> Tuple[int, ...]:
        """The widths of the views a chunk or a decode window gathers: the
        ``max_len``-wide one, and a two-rule pool's ring."""
        return (self.kv.tables.shape[1] * self.page_size,) + (
            (self.kv.ring_pages * self.page_size,) if self._mixed else ())

    def _chunk_kernel_applies(self, rows: int, m: int) -> bool:
        """Whether a chunk of ``rows`` attends over an ``m``-wide view in a
        flash kernel, by the rule its attention applies when traced: the latent
        kernel's for latent rows, the view kernel's for the others."""
        cfg, shape = self.config, jax.ShapeDtypeStruct
        la = cfg.latent_attention
        if la is not None:
            h = cfg.num_heads
            return latent_flash_applies(
                shape((1, rows, h, la.nope_dim), cfg.dtype), shape((1, rows, h, la.rope_dim), cfg.dtype),
                shape((1, m, la.kv_rank), self.kv.pages_k.dtype),
                shape((la.kv_rank, h * (la.nope_dim + la.v_dim)), cfg.dtype))
        return view_flash_applies(
            shape((1, rows, cfg.num_heads, cfg.resolved_head_dim), cfg.dtype),
            shape((1, cfg.num_kv_heads * cfg.resolved_head_dim, m),
                  cfg.dtype if self.quantized else self.kv.pages_k.dtype))

    def _bump(self, key: str, n: int = 1) -> None:
        self.stats[key] += n
        self._counters[key].inc(n)

    def _bump_tenant(self, tenant: Optional[str], key: str, n: int = 1) -> None:
        """Mirror a ``_bump`` into the caller tenant's lazily created counter
        family (``serve/<key>_tenant_<tenant>_total``) and the numeric rollup
        behind ``stats()["tenants"]``.  Steady-state cost is two dict lookups;
        ``tenant=None`` (untenanted traffic) is one ``is None`` check."""
        if tenant is None:
            return
        counters = self._tenant_counters.get(tenant)
        if counters is None:
            counters = self._tenant_counters[tenant] = {}
            self._tenant_stats[tenant] = {}
        counter = counters.get(key)
        if counter is None:
            counter = counters[key] = self.metrics.counter(
                f"serve/{key}_tenant_{tenant}_total"
            )
            self._tenant_stats[tenant][key] = 0
        self._tenant_stats[tenant][key] += n
        counter.inc(n)

    def _tenant_ttft(self, tenant: Optional[str], value: float) -> None:
        """Per-tenant TTFT histogram family (``serve/ttft_s_tenant_<t>``),
        created lazily like the per-class family."""
        if tenant is None:
            return
        hist = self._tenant_ttft_hists.get(tenant)
        if hist is None:
            hist = self._tenant_ttft_hists[tenant] = self.metrics.histogram(
                f"serve/ttft_s_tenant_{tenant}", buckets=_LATENCY_BUCKETS,
            )
        hist.observe(value)

    def _put(self, x):
        """Upload host data for a window call.  Under a mesh every control
        operand must be *replicated over the mesh's devices* — a plain
        ``jnp.asarray`` commits to one device, which the explicitly-sharded
        executables reject as an incompatible placement.

        numpy inputs are copied first: the host mirrors (``_active``,
        ``_lane_len``, the paged block tables) stay mutable while a window
        is in flight, and CPU ``device_put`` may alias an aligned numpy
        buffer zero-copy — without the copy, a post-dispatch host mutation
        (lane retirement, ``_lane_len`` advance, ``lane_detach`` nulling a
        table row) could be read mid-execution by the in-flight window."""
        if isinstance(x, np.ndarray):
            x = x.copy()
        if self._shardings is None:
            return jnp.asarray(x)
        # straight from the host to the mesh's own devices: staging through
        # jnp.asarray would land every operand on the default device first
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return jax.device_put(x, self._shardings.replicated)

    # ------------------------------------------------------------- submission
    def submit(
        self,
        prompt,
        config: Optional[GenerationConfig] = None,
        on_token: Optional[Callable[[Request, int], None]] = None,
        cache_prefix: bool = True,
        speculate: bool = True,
        deadline_s: Optional[float] = None,
        request_class: Optional[str] = None,
        tenant: Optional[str] = None,
        **overrides: Any,
    ) -> Request:
        """Queue one request; returns its :class:`Request` handle (filled in
        as the engine runs).  ``overrides`` patch the ``GenerationConfig``
        exactly like :func:`~accelerate_tpu.models.generation.generate`.
        ``cache_prefix=False`` opts this request out of prefix-KV reuse and
        population (e.g. prompts carrying secrets that must not be retained);
        ``speculate=False`` opts it out of n-gram drafting (it still rides
        along in verify windows other lanes trigger — with pad drafts, which
        verification rejects).  ``deadline_s`` is an SLO budget from submit:
        admission sheds (retriable refusal) when the queue-depth estimate
        says it cannot be met, and the per-step deadline sweep cancels the
        request (``deadline_exceeded`` set) if a running lane blows it.
        ``request_class`` is a free-form traffic label (e.g. ``"chat"``,
        ``"batch"``): TTFT is additionally observed into a per-class
        histogram ``serve/ttft_s_class_<class>`` so one tenant's long
        prompts can't hide another's latency regression in the blended
        percentile.  ``tenant`` attributes this request to a caller: every
        global counter the request moves (submissions, tokens, preemptions,
        sheds, completions, replays) is mirrored into
        ``serve/<key>_tenant_<tenant>_total`` and the
        ``stats()["tenants"]`` rollup, and TTFT additionally lands in
        ``serve/ttft_s_tenant_<tenant>`` — the accounting substrate for
        fair-share enforcement."""
        gen = config or GenerationConfig()
        if overrides:
            gen = dataclasses.replace(gen, **overrides)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size > self.max_prompt_len:
            raise AdmissionError(
                f"prompt length {prompt.size} > max_prompt_len {self.max_prompt_len}",
                queue_depth=self.scheduler.queue_depth,
                retriable=False,
            )
        # headroom for the widest device pass this engine can run: a verify
        # cycle writes speculate_k + 1 KV positions in one forward, a tree
        # verify all tree.nodes node positions at the lane frontier
        span = max(self.window, self._spec_span)
        need = prompt.size + gen.max_new_tokens + span
        if need > self.max_len:
            raise AdmissionError(
                f"prompt {prompt.size} + max_new_tokens {gen.max_new_tokens} + "
                f"max(decode_window, speculation span) {span} = {need} exceeds "
                f"slot capacity {self.max_len}",
                queue_depth=self.scheduler.queue_depth,
                retriable=False,
            )
        # the chunk plan pads the final chunk up to its bucket; that padding
        # must still fit the lane's view or the tail writes would silently
        # clamp/corrupt
        padded = sum(b for b, _ in plan_chunks(prompt.size, self.buckets))
        if padded > self.max_len:
            raise AdmissionError(
                f"prompt {prompt.size} pads to {padded} prefill tokens under "
                f"buckets {self.buckets}, exceeding capacity {self.max_len}",
                queue_depth=self.scheduler.queue_depth,
                retriable=False,
            )
        if deadline_s is not None:
            # feasibility check against the waiting line: each queued request
            # costs ~one observed end-to-end service time (EMA) before this
            # one's lane even starts.  Optimistic before the first completion
            # (EMA 0 admits everything); a shed is retriable — the queue
            # drains, the same deadline may be meetable in a moment.
            est = self.scheduler.queue_depth * self._service_ema
            if est > float(deadline_s):
                self._bump("deadline_shed")
                self._bump_tenant(tenant, "deadline_shed")
                self.recorder.record(
                    "serve/deadline_shed", where="admission",
                    deadline_s=float(deadline_s), estimate_s=est,
                    queue_depth=self.scheduler.queue_depth,
                )
                raise AdmissionError(
                    f"deadline {deadline_s}s unmeetable: ~{est:.2f}s of queued "
                    f"work ahead ({self.scheduler.queue_depth} requests)",
                    queue_depth=self.scheduler.queue_depth,
                    retry_after_s=min(30.0, max(est - float(deadline_s), 0.1)),
                    retriable=True,
                )
        now = time.perf_counter()
        req = Request(rid=self._next_rid, prompt=prompt, config=gen, on_token=on_token,
                      submit_step=self._step_count, submit_time=now, last_token_time=now,
                      cache_prefix=bool(cache_prefix), speculate=bool(speculate),
                      deadline_s=None if deadline_s is None else float(deadline_s),
                      request_class=request_class, tenant=tenant)
        self._next_rid += 1
        # the waterfall opens here: queue_wait runs until the first prefill
        # chunk is taken (None when tracing is off — every hook guards on it)
        req.trace = self.reqtrace.begin(
            rid=req.rid, engine=self.engine_id,
            prompt_len=int(prompt.size), submit_t=now,
        )
        self.scheduler.submit(req)
        self._bump("requests_submitted")
        self._bump_tenant(tenant, "requests_submitted")
        if deadline_s is not None:
            self._has_deadlines = True
        return req

    def cancel(self, request) -> bool:
        """Cancel a queued OR running request (a :class:`Request` or its rid).

        Queued requests are dropped before burning any prefill budget; a
        RUNNING lane is frozen immediately — it stops decoding this very
        step, its slot frees for the next admission, and every
        KV page it held returns to the allocator (shared prefix pages survive
        under the cache's own references).  Tokens already streamed stay
        streamed.  Returns True when the request was cancelled (state becomes
        ``CANCELLED``); False when it is mid-prefill, done, or unknown."""
        rid = request.rid if isinstance(request, Request) else int(request)
        req = self.scheduler.cancel(rid)
        if req is not None:
            self._bump("cancelled")
            self.reqtrace.complete(req.trace, status="cancelled")
            return True
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if req is None or req.rid != rid or not self._active[s]:
                continue
            # with a window in flight the lane's tokens from that window are
            # dropped at drain (ownership check in _emit); its KV pages stay
            # held until the window retires (lane_detach deferral)
            self._retire_lane(s)
            req.state = RequestState.CANCELLED
            req.finish_step = self._step_count
            self._bump("cancelled")
            self.recorder.record(
                "serve/cancel_running", rid=rid, slot=s, step=self._step_count,
                tokens=len(req.tokens),
            )
            self.reqtrace.complete(req.trace, status="cancelled")
            return True
        return False

    # ------------------------------------------------------- drain / hot-swap
    def pause_admission(self) -> None:
        """Stop starting new prefills.  Queued requests stay queued, a
        request already mid-prefill finishes its chunks, and active lanes
        decode to completion — after enough ``step()`` calls the engine
        reaches quiescence (:attr:`drained`).  The drain-replica and weight
        hot-swap paths both start here."""
        self.admission_paused = True

    def resume_admission(self) -> None:
        """Re-open admission; queued requests start prefilling next step."""
        self.admission_paused = False

    @property
    def drained(self) -> bool:
        """True when no lane is active, no prefill is mid-flight, and no
        decode window is in the pipeline — the quiescence :meth:`swap_params`
        requires.  Queued requests do NOT block drain: they have no device
        state and run under whatever weights are live when admission
        resumes."""
        return (
            not self._active.any()
            and self._inflight is None
            and self._prev_handle is None
            and not self.scheduler.prefills
            and not self._reserved_slots
        )

    def swap_params(self, params: Any, version: Optional[str] = None) -> None:
        """Zero-downtime weight hot-swap: rebind this engine's parameters.

        Requires quiescence (:attr:`drained` — pause admission and ``step()``
        until lanes finish); raises ``RuntimeError`` otherwise rather than
        splice weights mid-request.  The new params ride the same upload path
        as ``__init__`` (tp-sharded under a mesh via ``SERVING_TP_RULES``),
        so every compiled executable — prefill buckets, decode windows, copy
        chunks — is REUSED as-is: a swap costs one host-to-device transfer,
        never a recompile.  The prefix cache is flushed first (queued pins
        dropped): retained KV was computed under the old weights, and
        replaying it would silently corrupt tokens.  Queued requests survive
        and decode under the new weights.  Admission stays wherever the
        caller put it — resume explicitly after cutover.
        """
        if not self.drained:
            raise RuntimeError(
                "swap_params requires a drained engine (pause_admission, then "
                "step until engine.drained): active lanes or an in-flight "
                "window would mix weight versions mid-request"
            )
        if faults.ACTIVE is not None and faults.ACTIVE.fire("hot_swap_upload"):
            # fail BEFORE touching any state: a torn upload must leave the
            # engine serving the old weights intact, cache included
            raise faults.FaultInjected(
                "injected hot-swap upload failure (weights unchanged)"
            )
        if self.prefix_cache is not None:
            # queued requests hold pins from admission-time matching; drop
            # them (they re-match against fresh KV at prefill) so flush can
            # take every node
            self.scheduler.drop_cache_pins()
            flushed = self.prefix_cache.flush()
        else:
            flushed = 0
        if self.mesh is not None:
            from ..parallel.sharding import shard_pytree_with_path
            from ..parallel.tensor_parallel import (
                SERVING_TP_RULES,
                make_tp_sharding_fn,
            )

            self.params, _ = shard_pytree_with_path(
                params,
                make_tp_sharding_fn(
                    self.mesh, axis_name=self.tp_axis, rules=SERVING_TP_RULES
                ),
            )
        else:
            self.params = jax.device_put(params)
        if self.tree is not None and isinstance(self._draft_spec, int):
            # self-speculative draft: re-slice the head from the NEW weights
            # so the draft keeps tracking the served model across the swap
            # (a stale head would only cost acceptance, but why pay it)
            _, draft_host = build_draft(
                self.config, self.params, self._draft_spec,
                draft_ctx=self.draft_ctx, depth=self.tree_depth,
            )
            self._draft_params = (
                jax.device_put(draft_host) if self._shardings is None
                else jax.device_put(draft_host, self._shardings.replicated)
            )
        old = self.weights_version
        if version is not None:
            self.weights_version = str(version)
        self._bump("hot_swaps")
        self.recorder.record(
            "serve/hot_swap", old_version=old, new_version=self.weights_version,
            step=self._step_count, cache_nodes_flushed=flushed,
        )

    # -------------------------------------------------------- fault tolerance
    def kill(self, reason: str = "replica killed") -> None:
        """Poison this engine as if its device vanished mid-window: every
        subsequent :meth:`step` raises without touching the pool.  The router
        supervisor sees ``_poisoned``, exports the in-flight requests, and
        replays them on surviving replicas.  Chaos tests and the
        ``replica_kill`` fault point call this; :meth:`revive` undoes it."""
        self._poisoned = faults.FaultInjected(reason)
        self.recorder.record(
            "serve/engine_poisoned", error=reason, step=self._step_count,
        )

    def export_inflight(self) -> List[Request]:
        """Snapshot every request this engine still owes an answer, detached
        and ready for :meth:`adopt` on a survivor.  The marshalling lives in
        :func:`serving.transfer.export_inflight` — the state-movement module
        shared with live page migration; this method is its engine-facing
        entry point."""
        return transfer.export_inflight(self)

    def adopt(self, request: Request) -> Request:
        """Admit a request exported from a dead replica, at the FRONT of the
        queue.  Greedy lanes replay token-exact; sampled lanes resume on a
        re-seeded stream (distribution-correct, not sample-exact — live
        migration via :class:`serving.transfer.PageMigrator` is the
        bit-identical alternative when the source's pages are readable).
        The marshalling lives in :func:`serving.transfer.adopt`."""
        return transfer.adopt(self, request)

    def revive(self) -> None:
        """Tear a poisoned engine back down to a serviceable idle state.

        The half-open circuit breaker's probe path: settle whatever the dead
        step left in flight (a failed fetch is recorded, not fatal — the
        window's pages still settle), retire every lane, drop the prefill
        plan and any stragglers in the queue, flush the prefix cache (its
        retained KV may be torn mid-write), and clear the poison.  The lane
        device mirrors are dropped wholesale — the next dispatch re-uploads
        them fresh rather than trusting vectors a dying window may have
        corrupted."""
        handles = [h for h in (self._prev_handle, self._inflight)
                   if h is not None]
        self._prev_handle = self._inflight = None
        for hd in handles:
            try:
                fetch(hd.toks)  # sync: proves the window's writes landed
            except Exception as exc:
                self.recorder.record(
                    "serve/revive_fetch_failed", error=repr(exc),
                )
            if hd.deferred_pages:
                hd.settle(self.kv.allocator)
        self._stale_handles.clear()
        self._pending_prefill_qerr.clear()
        self._pending_moe_counts.clear()
        try:
            self._settle_spills(self._pending_spills)
        except Exception as exc:
            # the gathers rode the poisoned dispatch stream: their payloads
            # can't be trusted, so the nodes drop instead of staying spilled
            self.recorder.record("serve/revive_spill_failed", error=repr(exc))
            if self.prefix_cache is not None:
                for node, handles in self._pending_spills:
                    if node.host is handles:
                        self.prefix_cache.discard_spilled(node)
        self._pending_spills = []
        self._pending_promotions = []
        self._cycle_decode_tokens = 0
        for s in range(self.num_slots):
            if self._active[s] or self._slot_req[s] is not None:
                self._retire_lane(s)
        self.scheduler.take_prefills()
        self._reserved_slots.clear()
        for req in list(self.scheduler.queue):
            # export_inflight normally emptied this; anything left has no
            # owner to stream to — drop it cleanly with its pins
            self.scheduler.cancel(req.rid)
        if self.prefix_cache is not None:
            self.scheduler.drop_cache_pins()
            self.prefix_cache.flush()
        self._lane_device = None
        self._mask_stale = False
        self._poisoned = None
        self.admission_paused = False
        self.recorder.record("serve/revive", step=self._step_count)

    def _shed_blown_deadlines(self) -> None:
        """Per-step deadline sweep (only runs while a deadline is live):
        cancel running lanes and queued requests past their ``deadline_s``,
        marking ``deadline_exceeded`` so the API layer answers 504."""
        now = time.perf_counter()
        any_live = False
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if req is None or req.deadline_s is None or not self._active[s]:
                continue
            elapsed = now - req.submit_time
            if elapsed <= req.deadline_s:
                any_live = True
                continue
            self._retire_lane(s)
            req.deadline_exceeded = True
            req.state = RequestState.CANCELLED
            req.finish_step = self._step_count
            self._bump("deadline_shed")
            self._bump_tenant(req.tenant, "deadline_shed")
            self.recorder.record(
                "serve/deadline_shed", where="running", rid=req.rid, slot=s,
                deadline_s=req.deadline_s, elapsed_s=elapsed,
                tokens=len(req.tokens),
            )
            if req.trace is not None:
                req.trace.annotate("deadline_shed", where="running",
                                   deadline_s=req.deadline_s)
                self.reqtrace.complete(req.trace, status="shed")
        for req in list(self.scheduler.queue):
            if req.deadline_s is None:
                continue
            elapsed = now - req.submit_time
            if elapsed <= req.deadline_s:
                any_live = True
                continue
            self.scheduler.cancel(req.rid)
            req.deadline_exceeded = True
            self._bump("deadline_shed")
            self._bump_tenant(req.tenant, "deadline_shed")
            self.recorder.record(
                "serve/deadline_shed", where="queued", rid=req.rid,
                deadline_s=req.deadline_s, elapsed_s=elapsed,
            )
            if req.trace is not None:
                req.trace.annotate("deadline_shed", where="queued",
                                   deadline_s=req.deadline_s)
                self.reqtrace.complete(req.trace, status="shed")
        if any(r.deadline_s is not None for r in self.scheduler.prefills):
            any_live = True  # finishes its chunks; the running sweep catches it
        self._has_deadlines = any_live

    # -------------------------------------------------------------- admission
    def _next_free_slot(self) -> Optional[int]:
        # a lane freed while its window is still in flight is immediately
        # admissible: the host mask/slot_req are authoritative (the stale
        # device mask only costs the dead lane one extra masked window), and
        # in-flight writes to the slot are overwritten by insert/prefill,
        # which queue behind the window on device
        for s in self.slot_order:
            if (not self._active[s] and self._slot_req[s] is None
                    and s not in self._reserved_slots):
                return s
        return None

    def _admit(self) -> None:
        chunks, tokens = self.stats["prefill_chunks"], self.stats["prefill_tokens"]
        with self.tracer.span("serve/admit") as span:
            self._admit_impl()
            span["chunks"] = self.stats["prefill_chunks"] - chunks
            span["prefill_tokens"] = self.stats["prefill_tokens"] - tokens

    def _admit_impl(self) -> None:
        # paused admission (drain / hot-swap): never START a prefill, but a
        # request already mid-prefill finishes — abandoning it would leak its
        # reserved slot and cache pins
        if self.admission_paused and not self.scheduler.prefills:
            return
        # joint per-cycle budget: in interleaved mode the decode window
        # dispatched before admission and charged its tokens; the default
        # ordering charges zero (decode dispatches after)
        budget = self.scheduler.begin_step(self._cycle_decode_tokens)
        while True:
            if not self.admission_paused:
                # open prefills up to the scheduler's cap (1, or one per slot
                # in interleaved mode) while slots and pages allow
                while (self.scheduler.queue
                       and len(self.scheduler.prefills)
                       < self.scheduler.max_prefills):
                    slot = self._next_free_slot()
                    if slot is None:
                        break
                    if not self._admission_pages_ok(self.scheduler.queue[0]):
                        break
                    req = self.scheduler.start_next(slot)
                    self._reserved_slots.add(slot)
                    if self._stateful:
                        self._zero_lane_state(slot, req)
            if not self.scheduler.prefills:
                return
            took = self.scheduler.take_chunk(
                budget, ready=self._ensure_prefill_pages,
            )
            if took is None:
                return  # budget spent or page pressure: retry next step
            req, bucket, valid, start, cached = took
            tr = req.trace
            if tr is not None and not tr.queue_done:
                # first chunk taken: the queue_wait phase ends here
                self._queue_wait_hist.observe(
                    tr.close_queue(self.scheduler.queue_depth)
                )
            ptoks = req.prefill_tokens
            if cached:
                node = req.cache_nodes[req.next_chunk - 1]
                spilled = node.tier != "device"
                if spilled and not self._promote_node(req, node, bucket):
                    # degraded promotion (fault, page pressure, or a torn
                    # payload): fall through to a plain cache miss — the chunk
                    # re-prefills below, charging budget, and _populate_cache
                    # heals the node with the fresh pages.  Token-identical:
                    # the lane's KV is recomputed, never partially installed.
                    cached = False
                    self.recorder.record(
                        "serve/promote_degraded", rid=req.rid, bucket=bucket,
                        step=self._step_count,
                    )
                elif not spilled:
                    # the zero-copy hit: alias the node's physical pages
                    # into this lane's block table — no device work at all
                    self.kv.lane_append_shared(req.slot, node.pages)
                if cached:
                    self._bump("prefix_hit_tokens", valid)
                    if spilled:
                        self._bump("prefix_hit_tokens_host", valid)
            if not cached:
                chunk = np.zeros(bucket, np.int32)
                chunk[:valid] = ptoks[start:start + valid]
                self._paged_prefill_chunk(req, bucket, valid, chunk, start)
                budget -= bucket
                self._bump("prefill_chunks")
                if self.interleave_prefill and self._cycle_decode_tokens:
                    # a decode window was dispatched this same cycle and this
                    # chunk queued behind it: the interleave actually happened
                    self._bump("interleaved_chunks")
                if self.prefix_cache is not None and req.cache_prefix:
                    self._bump("prefix_miss_tokens", valid)
                    self._populate_cache(req, bucket, valid, start, ptoks)
            self._bump("prefill_tokens", valid)
            if tr is not None:
                # one tiled phase per admitted chunk with hit-tier attribution
                # (a degraded promotion re-entered the fresh path above)
                source = ("fresh" if not cached
                          else "promoted" if spilled else "cached")
                self._prefill_phase_hist.observe(tr.phase(
                    "prefill", chunk=req.next_chunk - 1, bucket=bucket,
                    tokens=valid, source=source,
                ))
            done = self.scheduler.finish_prefill()
            if done is not None:
                self._install(done)

    # ---------------------------------------------------------- page admission
    def _on_prefix_evict(self, node) -> None:
        """Prefix-cache eviction hook: drop the cache's allocator
        reference on each retained page.  Pages still aliased by running lanes
        survive; unreferenced ones return to the free list.  Spilled nodes
        arrive here with ``pages = None`` — their refs were already dropped at
        demotion time by :meth:`_spill_node`."""
        if node.pages:
            self.kv.allocator.deref(node.pages)

    # ----------------------------------------------------- hierarchical cache
    def _spill_node(self, node):
        """PrefixCache ``spill`` hook: demote a device-tier node into the
        host ring.  Enqueues the bucket's D2H page gather and releases the
        cache's page refs immediately — the device executes in dispatch
        order, so any later prefill recycling those pages is ordered BEHIND
        the gather and the extracted payload is exact.  Nothing blocks here:
        the gather's device handles become the node's interim payload and the
        actual host copy lands at the next drain (``Readback.spills``).
        Returns ``None`` (node drops instead) when the node's page count
        matches no prefill bucket."""
        bucket = len(node.pages) * self.page_size
        if bucket not in self._spill_extract:
            return None
        kv = self.kv
        ids = self._put(np.asarray(node.pages, np.int32))
        with self.tracer.span("serve/spill_d2h", bucket=bucket):
            handles = self._spill_extract[bucket](
                kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales, ids,
            )
        self.kv.allocator.deref(node.pages)
        self._pending_spills.append((node, handles))
        self.recorder.record(
            "serve/spill", bucket=bucket, step=self._step_count,
            behind_window=self._inflight is not None
            or self._prev_handle is not None,
        )
        return handles

    def _put_kv_chunk(self, x: np.ndarray):
        """Upload one spilled chunk's page data with the pool's placement
        (head-axis sharded under a mesh, so the promote install's donated
        in-place aliasing holds per shard)."""
        if self._shardings is not None:
            return jax.device_put(np.ascontiguousarray(x), self._shardings.pages)
        return jnp.asarray(x)

    def _put_scale_chunk(self, x: np.ndarray):
        if self._shardings is not None:
            return jax.device_put(
                np.ascontiguousarray(x), self._shardings.scales
            )
        return jnp.asarray(x)

    def _promote_node(self, req: Request, node, bucket: int) -> bool:
        """Promote one spilled prefix chunk host -> device for ``req``:
        allocate fresh pages, upload the payload, and enqueue the
        scatter-install BEHIND the in-flight decode window — the depth-1
        discipline: the old pool handles park on ``_stale_handles`` and ride
        out on the next window's ``Readback.consumed``, and completion is
        acknowledged at that window's drain (``Readback.promotions``).  Never
        syncs.  Returns False — degrading the chunk to a plain miss, with
        NOTHING installed and the engine state untouched — on an injected
        ``promote_h2d`` fault, a torn payload, or unrecoverable page
        pressure."""
        if faults.ACTIVE is not None and faults.ACTIVE.fire("promote_h2d"):
            self.recorder.record(
                "serve/fault", point="promote_h2d", rid=req.rid,
                step=self._step_count,
            )
            return False
        payload = self.prefix_cache.node_payload(node)
        if payload is None:
            return False
        npg = bucket // self.page_size
        ids = self.kv.allocator.alloc(npg)
        if ids is None:
            if not self._reclaim_pages(npg, allow_preempt=False):
                return False
            ids = self.kv.allocator.alloc(npg)
            if ids is None:
                return False
        kv = self.kv
        ck, cv, cks, cvs = payload
        if isinstance(ck, np.ndarray):
            # landed (or disk-reloaded) payload: H2D upload, pool placement
            ck, cv = self._put_kv_chunk(ck), self._put_kv_chunk(cv)
            cks = self._put_scale_chunk(cks)
            cvs = self._put_scale_chunk(cvs)
        # else: the spill gather hasn't drained yet — its device outputs feed
        # the install directly, ordered behind the gather by dispatch order
        behind = self._inflight is not None or self._prev_handle is not None
        # admission may run under an in-flight window that consumes the pool
        # handles: park them so the rebind below never drops a consumed handle
        self._stale_handles += [kv.pages_k, kv.pages_v,
                                kv.k_scales, kv.v_scales]
        with self.tracer.span("serve/promote_h2d", bucket=bucket,
                              behind_window=behind):
            (kv.pages_k, kv.pages_v, kv.k_scales,
             kv.v_scales) = self._promote_install[bucket](
                kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                ck, cv, cks, cvs, self._put(np.asarray(ids, np.int32)),
            )
        self.kv.lane_append_owned(req.slot, ids)  # lane takes the alloc ref
        if self.prefix_cache.promote_node(node, ids):
            # re-admitted to the device tier: the cache holds its own ref per
            # page (dropped again by _on_prefix_evict); on failure the node
            # stays spilled and only the lane owns the pages
            self.kv.allocator.ref(ids)
        self._pending_promotions.append({
            "rid": req.rid, "bucket": bucket, "behind_window": behind,
            "step": self._step_count, "trace": req.trace,
        })
        if req.trace is not None:
            req.trace.annotate("promote_dispatch", bucket=bucket,
                               behind_window=behind)
        self.recorder.record(
            "serve/promote_h2d", rid=req.rid, bucket=bucket,
            behind_window=behind, step=self._step_count,
        )
        return True

    def _settle_spills(self, entries: list) -> None:
        """Land pending spill payloads (drain side): the producing gathers
        retired behind the window that just drained, so each fetch returns
        without a real wait.  Entries whose node moved on (promoted, healed,
        or dropped while the gather was in flight) are fetched and discarded
        — fetching first keeps the handle-drop from ever blocking on a
        consumer still in flight."""
        for node, handles in entries:
            arrays = fetch(*handles)
            if self.prefix_cache is not None and node.host is handles:
                self.prefix_cache.settle_payload(node, arrays)

    def _zero_lane_state(self, slot: int, req: Request) -> None:
        """A retention model's install: zero the lane's state on the device
        (enqueued behind whatever window still runs over the lane), before the
        request's first prefill chunk folds its prompt into it."""
        kv = self.kv
        audit_donation(kv.s, kv.z)
        # the in-flight window consumes these handles: park them until its
        # drain, so that the rebind below never drops a consumed handle
        self._stale_handles += [kv.s, kv.z]
        with self.tracer.span("serve/state_install", slot=slot, req=req.trace_id):
            kv.s, kv.z = self._state_install(kv.s, kv.z, self._put(np.int32(slot)))
        self._bump("state_installs")

    def _admission_pages_ok(self, req: Request) -> bool:
        """Can the queue head's whole prefill be paged in?  Conservative
        (cached chunks alias pages and cost nothing; the count uses the match
        from submit, which admission may improve).  Reclaims WITHOUT
        preemption — evicting a running lane to admit behind it would invert
        FCFS and can livelock under steady overload.  A state pool has no
        page pressure: a free slot is all a request needs."""
        if self._stateful:
            return True
        padded = sum(b for b, _ in req.chunks)
        # only device-tier cached chunks alias for free; spilled chunks
        # promote into freshly allocated pages and must be charged
        cached = sum(
            b for i, (b, _) in enumerate(req.chunks[:req.cached_chunks])
            if i < len(req.cache_nodes) and req.cache_nodes[i].tier == "device"
        )
        need = (padded - cached) // self.page_size
        if self._mixed and (min(padded // self.page_size, self.kv.ring_pages)
                            > self.kv.ring_allocator.free_count):
            return False      # both rules' pages are counted; a whole ring a slot, so it has them
        if self.kv.allocator.free_count >= need:
            return True
        return self._reclaim_pages(need, allow_preempt=False)

    def _ensure_prefill_pages(self, req: Request) -> bool:
        """Pages for ``req``'s NEXT chunk (the scheduler's ``ready`` predicate
        inside ``take_chunk``).  False skips this request for this engine step
        — running lanes keep decoding, their completions free pages, and the
        stalled chunk retries next step (or SRTF picks a smaller prefill)."""
        if self._stateful or req.next_chunk >= len(req.chunks):
            return True
        if req.next_chunk < req.cached_chunks:
            node = (req.cache_nodes[req.next_chunk]
                    if req.next_chunk < len(req.cache_nodes) else None)
            if node is None or node.tier == "device":
                return True  # device-tier hit: aliases pages, allocates none
            # spilled chunk: promotion scatter-installs into fresh pages
        bucket, _ = req.chunks[req.next_chunk]
        need = bucket // self.page_size
        if self.kv.allocator.free_count >= need:
            return True
        return self._reclaim_pages(need, allow_preempt=False)

    def _paged_prefill_chunk(self, req: Request, bucket: int, valid: int,
                             chunk: np.ndarray, start: int) -> None:
        """Prefill one fresh chunk straight into newly allocated lane pages.
        The executable gathers the lane's full view — shared prefix pages
        included, which is how a partial hit feeds context to the chunks after
        it — and scatters back only the chunk's own (page-aligned) span."""
        s = req.slot
        if self._stateful:
            # the prompt's last token stays out of the state: the first decode
            # step feeds it as the lane's pending token and folds it in then
            last = start + valid == len(req.prefill_tokens)
            kv = self.kv
            args = (self.params, chunk[None], kv.s, kv.z, self._put(np.int32(s)),
                    self._put(np.int32(start)), self._put(np.int32(valid - last)))
            self.cost_table.capture(f"serve/prefill_{bucket}", self._prefill[bucket], args)
            with self.tracer.span("serve/prefill_chunk", bucket=bucket, valid=valid,
                                  req=req.trace_id):
                kv.s, kv.z = self._prefill[bucket](*args)
            return
        ids = self.kv.allocator.alloc(bucket // self.page_size)
        if ids is None:  # _ensure_prefill_pages runs first; this cannot happen
            raise RuntimeError("KV page pool exhausted mid-prefill")
        self.kv.lane_append_owned(s, ids)
        kv = self.kv
        if self._chunk_views:
            self._bump("chunk_key_blocks_live", -(-(start + bucket) // KEY_BLOCK))
            self._bump("chunk_key_blocks_view", -(-self._chunk_views[0] // KEY_BLOCK))
        table = self._put(kv.tables[s])
        base = self._put(jnp.int32(start))
        if self._mixed:
            # the window layers' ring: pages behind the chunk's first query's
            # window go back, the chunk's own are mapped
            with self.tracer.span("serve/page_release"):
                taken, released = kv.ring_advance(s, start, start + bucket - 1)
            self._bump("kv_pages_taken", len(ids) + taken)
            self._bump("kv_pages_released_window", released)
            args = (self.params, chunk[None], kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v,
                    table, self._put(kv.ring_tables[s]), base)
            if self._routed:
                args += (self._put(jnp.int32(valid)),)
            self.cost_table.capture(f"serve/prefill_{bucket}", self._prefill[bucket], args)
            with self.tracer.span("serve/prefill_chunk", bucket=bucket, valid=valid,
                                  req=req.trace_id):
                kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v, *counts = self._prefill[bucket](*args)
            self._pending_moe_counts.extend(counts)
            return
        if self._prefill_direct:
            args = (self.params, chunk[None], kv.pages_k, kv.pages_v,
                    kv.k_scales, kv.v_scales, table, base)
            self.cost_table.capture(
                f"serve/prefill_{bucket}", self._prefill[bucket], args,
            )
            with self.tracer.span("serve/prefill_chunk", bucket=bucket, valid=valid,
                                  req=req.trace_id):
                (kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                 qerr) = self._prefill[bucket](*args)
            if self.quantized:
                # don't fetch() here — that would sync the pipeline right
                # behind the chunk; park the handle and fold it into the
                # gauge when the next window drains
                self._pending_prefill_qerr.append(qerr)
            return
        args = (self.params, chunk[None], kv.pages_k, kv.pages_v, table, base)
        if self._routed:
            args += (self._put(jnp.int32(valid)),)
        self.cost_table.capture(f"serve/prefill_{bucket}", self._prefill[bucket], args)
        with self.tracer.span("serve/prefill_chunk", bucket=bucket, valid=valid,
                              req=req.trace_id):
            kv.pages_k, kv.pages_v, *counts = self._prefill[bucket](*args)
        self._pending_moe_counts.extend(counts)

    def _reclaim_pages(self, need: int, allow_preempt: bool) -> bool:
        """Recover free pages until at least ``need`` are available.  The
        ladder, cheapest first: (1) evict unpinned prefix-cache leaves —
        dropping the cache's reference frees any page no lane still aliases;
        (2) drain the in-flight window so pages parked on its deferral list
        (lanes freed/preempted after it dispatched) return to the pool — one
        pipeline sync, but nothing running is sacrificed; (3) preempt the
        youngest running lane (its pages free NOW; it requeues at the front
        and replays through the cache); (4) strip queued requests' cache pins
        so step 1 can reach more leaves.  Returns False when the ladder is
        exhausted short of ``need``."""
        while self.kv.allocator.free_count < need:
            if self.prefix_cache is not None and self.prefix_cache.evict_one():
                continue
            if ((self._inflight is not None and self._inflight.deferred_pages)
                    or (self._prev_handle is not None
                        and self._prev_handle.deferred_pages)):
                self._drain_inflight()
                continue
            if allow_preempt and self._preempt():
                continue
            if self.scheduler.drop_cache_pins() > 0:
                continue
            return False
        return True

    def _preempt(self) -> bool:
        """Preempt the youngest replayable running lane: release its pages,
        requeue it at the FRONT for replay over prompt + generated tokens
        (ideally hitting the cache chunks it populated in its first life).
        Youngest-first keeps FCFS intact — the last admitted is the first
        sacrificed.  Greedy replay is token-exact; a sampled victim resumes
        on a re-seeded RNG stream (``_install`` folds the base rng with the
        rid again), so its continuation is distribution-correct but not
        sample-exact.  Returns False with no replayable victim."""
        victims = sorted(
            (s for s in np.nonzero(self._active)[0] if self._slot_req[s] is not None),
            key=lambda s: self._slot_req[s].rid, reverse=True,
        )
        for s in victims:
            req = self._slot_req[s]
            eff = len(req.prefill_tokens)
            padded = sum(b for b, _ in plan_chunks(eff, self.buckets))
            if eff > self.max_prompt_len or padded > self.max_len:
                continue  # grew past replayability (max_prompt_len < max_len)
            # tokens the in-flight window lands for the victim are dropped at
            # drain and regenerated by the replay (token-exact under greedy)
            freed = self._retire_lane(s)
            self.scheduler.requeue(req)
            self._bump("preemptions")
            self._bump_tenant(req.tenant, "preemptions")
            self.recorder.record(
                "serve/preempt", rid=req.rid, slot=int(s), step=self._step_count,
                pages_freed=freed, effective_len=eff,
            )
            if req.trace is not None:
                req.trace.annotate("preempt", slot=int(s), pages_freed=freed,
                                   generated=len(req.tokens))
            return True
        return False

    def _ensure_decode_capacity(self, width: int) -> None:
        """Map pages for every active lane's next ``width`` KV writes
        (positions ``lane_len .. lane_len + width - 1``).  Under pressure the
        full reclaim ladder runs, preemption included — the youngest lane
        funds the older ones, and if a lane preempts ITSELF the loop simply
        moves on (its pages are already free)."""
        if self._stateful:
            return
        page = self.page_size
        for s in np.nonzero(self._active)[0]:
            need = (int(self._lane_len[s]) + width - 1) // page + 1
            while self._active[s]:
                missing = need - int(self.kv.lane_npages[s])
                if missing <= 0:
                    break
                ids = self.kv.allocator.alloc(missing)
                if ids is not None:
                    self.kv.lane_append_owned(s, ids)
                    if self._mixed:
                        self._bump("kv_pages_taken", missing)
                    break
                if not self._reclaim_pages(missing, allow_preempt=True):
                    raise RuntimeError(
                        "KV page pool exhausted: no cache leaf, lane, or pin "
                        "left to reclaim for a decoding lane"
                    )
        if self._mixed:
            # the window layers' rings: what fell behind each lane's window
            # goes back before the pages of its next ``width`` writes are mapped
            taken = released = 0
            with self.tracer.span("serve/page_release") as span:
                for s in np.nonzero(self._active)[0]:
                    n = int(self._lane_len[s])
                    t, r = self.kv.ring_advance(int(s), n, n + width - 1)
                    taken, released = taken + t, released + r
                span["released"] = released
            self._bump("kv_pages_taken", taken)
            self._bump("kv_pages_released_window", released)

    def _populate_cache(self, req: Request, bucket: int, valid: int, start: int,
                        ptoks: np.ndarray) -> None:
        """Retain a freshly prefilled FULL chunk in the prefix cache.

        Zero copies — the cache node records the lane's own physical page
        ids and takes one allocator reference per page, so the KV outlives
        the lane.  Padded final chunks
        are skipped — their KV past ``valid`` is garbage — and once one chunk
        fails to retain (budget or collision) the rest of the request's chain
        is abandoned: a child without its ancestors could never be matched.
        """
        if valid != bucket or req.cache_chain_broken:
            return
        parent = req.cache_nodes[-1] if req.cache_nodes else None
        npg = bucket // self.page_size
        ids = self.kv.chunk_ids(req.slot, start // self.page_size, npg)
        node = self.prefix_cache.insert_pages(
            parent, ptoks[start:start + bucket], ids,
            nbytes=self.kv.chunk_bytes(npg),
        )
        if node is not None and node.pages == tuple(ids):
            # a NEW node was created: the cache holds its own reference
            # per page (dropped by _on_prefix_evict); a deduped re-insert
            # keeps the resident node's pages and refs untouched
            self.kv.allocator.ref(ids)
        if node is None:
            req.cache_chain_broken = True
        else:
            self.prefix_cache.acquire([node])
            req.cache_nodes.append(node)

    def _cow_tail_page(self, s: int, plen: int) -> None:
        """Copy-on-write for the single spot sharing and writing can collide:
        the page holding position ``plen - 1``, the lane's first decode-write
        target.  Chunk starts are page-aligned (buckets are multiples of the
        page size), so every OTHER shared page lies strictly before the write
        frontier and every later page is freshly allocated.  Re-checks after
        each reclaim — eviction can dissolve the sharing and make the copy
        unnecessary."""
        if self._stateful:
            return
        pslot = (plen - 1) // self.page_size
        pid = int(self.kv.tables[s, pslot])
        while int(self.kv.allocator.refs[pid]) > 1:
            new = self.kv.allocator.alloc(1)
            if new is None:
                if not self._reclaim_pages(1, allow_preempt=True):
                    raise RuntimeError("KV page pool exhausted during copy-on-write")
                continue
            kv = self.kv
            # admission runs under the previous step's in-flight window, which
            # consumes these page handles: park them until its drain so the
            # rebind below never drops a consumed handle (see _stale_handles)
            self._stale_handles += [kv.pages_k, kv.pages_v,
                                    kv.k_scales, kv.v_scales]
            with self.tracer.span("serve/copy_page", src=pid, dst=new[0]):
                kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales = self._copy_page(
                    kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                    self._put(jnp.int32(pid)), self._put(jnp.int32(new[0]))
                )
            kv.lane_replace(s, pslot, new[0])
            self._bump("cow_copies")
            return

    def _install(self, req: Request) -> None:
        """Hand a fully prefilled request its lane.  The lane's pages ARE
        the prefilled KV — nothing moves; only the shared tail page (if any)
        is copy-on-write duplicated before decode starts writing at
        ``plen - 1``."""
        s = req.slot
        ptoks = req.prefill_tokens
        plen = len(ptoks)
        self._cow_tail_page(s, plen)
        self._lane_len[s] = plen - 1
        self.recorder.record(
            "serve/install", rid=req.rid, slot=s, step=self._step_count,
            prompt_len=plen,
        )
        gen = req.config
        rng = np.asarray(jax.random.fold_in(self._base_rng, req.rid), np.uint32)
        eos_v = -1 if gen.eos_token_id is None else gen.eos_token_id
        top_k_v = 0 if gen.top_k is None else gen.top_k
        top_p_v = 1.0 if gen.top_p is None else gen.top_p
        if self._lane_device is not None:
            # Admission must not sync the pipeline: pending/rng are carried
            # on device between windows, and fetching them here would block
            # on the in-flight window.  A one-slot device-side scatter edits
            # the carried vectors instead — it enqueues behind the in-flight
            # window and costs the host only a dispatch.
            ld = self._lane_device
            # the replaced handles are inputs of the scatter (and outputs of
            # the in-flight window): park them until the next drain so their
            # destructors never wait on pending device work
            self._stale_handles += [ld[0], ld[1], ld[2], ld[3], ld[4],
                                    ld[5], ld[6], ld[8]]
            (ld[0], ld[1], ld[2], ld[3], ld[4], ld[5], ld[6],
             ld[8]) = self._lane_install(
                ld[0], ld[1], ld[2], ld[3], ld[4], ld[5], ld[6], ld[8],
                self._put(np.int32(s)), self._put(np.int32(ptoks[-1])),
                self._put(np.int32(eos_v)), self._put(np.bool_(gen.do_sample)),
                self._put(np.float32(gen.temperature)),
                self._put(np.int32(top_k_v)), self._put(np.float32(top_p_v)),
                self._put(rng),
            )
        self._pending_tok[s] = ptoks[-1]
        if self._draft_window is not None:
            # seed the draft context from the prompt tail: its last token IS
            # the lane's pending token, which the draft forward echoes as the
            # tree root — the invariant the tree verify's tokens[:, 0] needs
            self._draft_window.begin(s, ptoks)
        self._active[s] = True
        self._eos[s] = eos_v
        self._do_sample[s] = gen.do_sample
        self._temperature[s] = gen.temperature
        self._top_k[s] = top_k_v
        self._top_p[s] = top_p_v
        self._rngs[s] = rng
        if self._slot_ever_used[s]:
            self._bump("slots_reused")
        self._slot_ever_used[s] = True
        self._slot_req[s] = req
        self._reserved_slots.discard(s)
        # the lane's block table holds its own reference on every page now;
        # the radix nodes this request read or populated can be evicted
        # without affecting it
        if self.prefix_cache is not None and req.cache_nodes:
            self.prefix_cache.release(req.cache_nodes)
            req.cache_nodes = []
        req.state = RequestState.RUNNING

    # ----------------------------------------------------------------- decode
    def _lane_arrays(self) -> list:
        """Device-resident lane vectors in decode/verify argument order
        (pending, active, eos, do_sample, temperature, top_k, top_p, pad,
        rngs).  Uploaded from the host mirrors once; after that the
        pending-token and rng entries are refreshed in place from each
        window's device-side outputs, installs edit one slot via the
        ``lane_install`` scatter, and a lane freed since the last dispatch
        re-uploads just the active mask — steady-state cycles upload
        nothing and nothing ever blocks on an in-flight window."""
        if self._lane_device is None:
            self._lane_device = [
                self._put(self._pending_tok), self._put(self._active),
                self._put(self._eos), self._put(self._do_sample),
                self._put(self._temperature), self._put(self._top_k),
                self._put(self._top_p),
                self._put(jnp.full((self.num_slots,), self.pad_token_id, jnp.int32)),
                self._put(self._rngs),
            ]
            self._mask_stale = False
        elif self._mask_stale:
            # a lane was freed while its window was in flight.  The active
            # mask is host-authoritative (no executable writes it), so the
            # dead lane is masked out by re-uploading this one vector — no
            # device sync, and the lane ran exactly one extra masked window.
            self._stale_handles.append(self._lane_device[1])
            self._lane_device[1] = self._put(self._active)
            self._mask_stale = False
        return self._lane_device

    def _retire_lane(self, slot: int) -> int:
        """Tear down one running lane (finish / cancel / preempt), deferring
        whatever the in-flight window still needs.  If the window was
        dispatched believing this lane live, its KV pages move to the
        window's deferral list (they free at drain, after the window's
        masked writes provably landed) and the device active mask is
        refreshed at the next dispatch instead of forcing a blocking mirror
        resync.  Returns pages freed *now* (0 when deferred)."""
        freed = 0
        inflight = self._inflight
        if inflight is not None and inflight.lane_live(slot):
            self._mask_stale = True
            inflight.deferred_pages.extend(self.kv.lane_detach(slot))
        else:
            # no window holds this lane: pages free immediately, and the
            # device mirror only needs its active bit dropped (the dead
            # lane's pending/rng entries are masked out until reinstall)
            self._mask_stale = True
            freed = self.kv.lane_release(slot)
        self._active[slot] = False
        self._slot_req[slot] = None
        if self._ngram is not None:
            self._ngram.retire(slot)
        if self._draft_window is not None:
            self._draft_window.retire(slot)
        self._lane_len[slot] = 0
        return freed

    def _free(self, slot: int, req: Request) -> None:
        self._retire_lane(slot)
        self._finish_request(slot, req)

    def _finish_request(self, slot: int, req: Request) -> None:
        req.state = RequestState.DONE
        req.finish_step = self._step_count
        # end-to-end service time EMA: the per-queued-request cost behind
        # submit()'s deadline feasibility estimate
        dur = max(time.perf_counter() - req.submit_time, 0.0)
        self._service_ema = (
            dur if self._service_ema == 0.0
            else 0.8 * self._service_ema + 0.2 * dur
        )
        self._bump("requests_completed")
        self._bump_tenant(req.tenant, "requests_completed")
        self.recorder.record(
            "serve/finish", rid=req.rid, slot=slot, step=self._step_count,
            tokens=len(req.tokens), steps=self._step_count - req.submit_step,
        )
        if req.trace is not None:
            req.trace.tokens = len(req.tokens)
            self.reqtrace.complete(req.trace, status="done")

    def _prefree_exhausted(self) -> None:
        """Retire lanes whose in-flight window provably exhausts their token
        budget — BEFORE this step's admission, so the slot refills this cycle
        instead of next.

        Without this, the depth-1 pipeline pays an occupancy lag the sync
        loop doesn't: a lane finishing inside window N is only discovered at
        N's drain, which runs after window N+1 dispatched AND after this
        step's admission — the slot sits dead for a full extra window.  But
        completion by length cap is host-arithmetic: a lane with no EOS
        configured lands exactly ``width`` tokens per decode window, so
        ``len(tokens) + width >= max_new_tokens`` proves death in flight.
        Such lanes retire here (pages deferred to the window, exactly the
        cancel-mid-flight path) and their slot admits a new request whose
        prefill/insert/scatter chain behind the in-flight window on device —
        the async admission schedule converges to the sync loop's.  The
        window's tokens still land at drain via the ``prefreed`` mark on the
        handle.  EOS-configured lanes and speculative lanes (commit counts
        are decided on device) keep the conservative one-window lag."""
        hd = self._inflight
        if hd is None or hd.kind != "decode":
            return
        for s in np.nonzero(self._active)[0]:
            s = int(s)
            req = self._slot_req[s]
            if req is None or not hd.lane_live(s) or hd.reqs[s] is not req:
                continue
            if self._eos[s] >= 0 or (self._spec_any and req.speculate):
                continue
            if len(req.tokens) + hd.width >= req.config.max_new_tokens:
                hd.prefreed.add(s)
                self._retire_lane(s)
                self._bump("prefreed_lanes")

    def _dispatch_decode(self) -> Optional["Readback"]:
        with self.tracer.span("serve/dispatch") as span:
            hd = self._dispatch_decode_impl()
            span["occupied"] = int(self._active.sum())
        return hd

    def _dispatch_decode_impl(self) -> Optional["Readback"]:
        """Dispatch one decode phase over the pool — a speculative verify
        cycle when any lane has an n-gram draft, the plain decode window
        otherwise — and return the handle the caller must drain (the
        *previous* window under the depth-1 pipeline, this window itself
        under ``async_depth=0``, ``None`` when the pool is idle).

        Dispatch and drain are split so the step loop can run admission
        between them: with ``interleave_prefill`` the prefill chunk enqueues
        *behind* the window dispatched here, decode lanes never skip a cycle
        while a long prompt prefills, and the chunk still finishes under the
        host work of draining the previous window.  Speculative cycles drain
        first instead: drafting and the verify token block need the previous
        window's tokens.

        Side effect: ``self._cycle_decode_tokens`` is set to the token count
        charged by this cycle's window (0 when idle) — ``_admit`` subtracts
        it from the scheduler's joint per-cycle budget."""
        self._cycle_decode_tokens = 0
        if self._spec_any and self._inflight is not None:
            self._drain_inflight()
        if not self._active.any():
            self._drain_inflight()
            return None
        # map pages for the widest pass this cycle could run (the same
        # span the admission check reserved headroom for); this may
        # preempt the youngest lane under pressure, so re-check occupancy
        self._ensure_decode_capacity(max(self.window, self._spec_span))
        if not self._active.any():
            self._drain_inflight()
            return None
        n_occupied = int(self._active.sum())
        self.peak_active_lanes = max(self.peak_active_lanes, n_occupied)
        self._occupancy_gauge.set(n_occupied / self.num_slots)
        if faults.ACTIVE is not None and faults.ACTIVE.fire("decode_dispatch"):
            raise faults.FaultInjected(
                f"injected decode-window dispatch failure "
                f"(step {self._step_count}, {n_occupied} lanes)"
            )
        if self.tree is not None:
            drafted = self._tree_lanes()
            hd = (
                self._tree_cycle(drafted, n_occupied) if drafted.any()
                else self._decode_cycle(n_occupied)
            )
        else:
            drafts = self._propose_drafts() if self.speculate_k else None
            if drafts is not None:
                hd = self._verify_cycle(*drafts, n_occupied=n_occupied)
            else:
                hd = self._decode_cycle(n_occupied)
        self._cycle_decode_tokens = n_occupied * hd.width
        if self.async_depth == 0:
            return hd
        prev, self._inflight = self._inflight, hd
        return prev

    def _decode_window(self) -> None:
        """Dispatch one decode phase and drain the handle it returns — the
        non-interleaved step ordering (admission already ran)."""
        prev = self._dispatch_decode()
        if prev is not None:
            self._drain(prev)

    def _update_prefill_gauges(self) -> None:
        """Publish prefill throughput and the interleave ratio.

        ``serve/prefill_tokens_per_s`` is valid prompt tokens through the
        prefill executables over wall time between steps that made prefill
        progress (idle stretches slide the window start so they don't dilute
        the rate).  ``serve/prefill_interleave_ratio`` is the fraction of
        forward-pass prefill chunks dispatched in the same cycle as a decode
        window — ~1.0 means long prompts rode along under decode; ~0.0 means
        chunks ran on an otherwise idle device (no interleaving to do, or
        ``interleave_prefill`` off)."""
        chunks = self.stats["prefill_chunks"]
        if chunks:
            self._interleave_gauge.set(
                self.stats["interleaved_chunks"] / chunks
            )
        tokens = self.stats["prefill_tokens"]
        now = time.perf_counter()
        if self._pf_last_t is None or tokens < self._pf_last_tokens:
            self._pf_last_t, self._pf_last_tokens = now, tokens
            return
        if tokens == self._pf_last_tokens:
            self._pf_last_t = now  # no prefill this step: slide the window
            return
        dt = now - self._pf_last_t
        if dt > 0.0:
            self._pf_rate_gauge.set((tokens - self._pf_last_tokens) / dt)
        self._pf_last_t, self._pf_last_tokens = now, tokens

    def _drain_inflight(self) -> None:
        """Flush the pipeline: materialize the in-flight window (if any) and
        land its tokens.  Called before speculative cycles, when the pool
        goes idle, and by the page-reclaim ladder to settle deferred pages.
        Oldest first: a previous window parked mid-step (interleaved
        admission runs between dispatch and drain) lands before the window
        dispatched after it, or tokens would interleave out of order."""
        prev, self._prev_handle = self._prev_handle, None
        if prev is not None:
            self._drain(prev)
        hd, self._inflight = self._inflight, None
        if hd is not None:
            self._drain(hd)

    def _drain(self, hd: Readback) -> None:
        """Land one window's deferred outputs: the ONE blocking readback per
        window, then all host-side bookkeeping against the window's
        dispatch-time lane snapshot (a lane freed/cancelled/preempted or
        re-installed since dispatch fails the ownership check in ``_emit``
        and its tokens are dropped — exactly what the sync loop would never
        have produced)."""
        try:
            with self.tracer.span("serve/drain", kind=hd.kind):
                self._drain_impl(hd)
        except BaseException:
            # a failed drain poisons this engine (step()'s wrapper) with the
            # handle already detached from ``_inflight`` — a pre-freed lane's
            # request lives ONLY on that handle, so requeue it here or
            # export_inflight never sees it and its caller waits forever
            for s in hd.prefreed:
                req = hd.reqs[s]
                if req is not None and req.state is RequestState.RUNNING:
                    self.scheduler.requeue(req)
            raise

    def _drain_impl(self, hd: Readback) -> None:
        if faults.ACTIVE is not None:
            if faults.ACTIVE.fire("fetch_slow"):
                time.sleep(faults.ACTIVE.slow_ms / 1e3)  # stalled interconnect
            if faults.ACTIVE.fire("fetch_fail"):
                raise faults.FaultInjected(
                    f"injected readback failure (step {self._step_count})"
                )
        t0 = time.perf_counter()
        with self.tracer.span("serve/readback", kind=hd.kind,
                              occupied=hd.n_occupied):
            # the window's ONE fetch: its tokens, a verify's commit counts,
            # and the moe counters parked on it (its own and its cycle's
            # prefill chunks'), which retired no later than it did
            commits = [hd.counts] if hd.kind == "verify" else []
            got = fetch(hd.toks, *commits, *hd.moe_counts)
            got = got if isinstance(got, tuple) else (got,)
            toks = got[0]
            counts = got[1] if commits else np.full(self.num_slots, hd.width)
            moe = got[1 + len(commits):]
        t1 = time.perf_counter()
        if self._stateful:
            for steps, live in moe:
                self._bump("state_lane_steps", int(steps))
                self._bump("state_live_lane_steps", int(live))
            moe = ()
        if self._mixed:
            # a decode window's own vector ends with the rows its live lanes'
            # steps could see (after the experts' three, where it has those);
            # a chunk's carries none
            width = 2 + 3 * self._routed
            for c in moe:
                if len(c) == width:
                    self._bump("kv_rows_live", int(c[-2]))
                    self._bump("kv_rows_live_window", int(c[-1]))
            moe = [c[:3] for c in moe] if self._routed else ()
        for total, here, hit in moe:
            self._bump("moe_pairs_total", int(total))
            self._bump("moe_pairs_here", int(here))
        if moe and hd.kind == "decode":
            # experts hit is a decode-step quantity (a chunk of hundreds of
            # rows hits every expert): the window's own counter, parked first
            self._bump("moe_experts_hit", int(moe[0][2]))
            self._bump("moe_expert_slots", self._expert_slots_a_step * hd.width)
        hd.moe_counts = []
        # overlap accounting: host work since dispatch ran under the device;
        # the blocking tail is what the pipeline failed to hide.  Under
        # async_depth=0 the drain follows dispatch immediately, so host ~ 0
        # and the ratio publishes ~0 — the honest baseline.
        host = max(t0 - hd.dispatch_t, 0.0)
        wait = max(t1 - t0, 0.0)
        self._overlap_host_s += host
        self._overlap_wait_s += wait
        denom = self._overlap_host_s + self._overlap_wait_s
        if denom > 0.0:
            self._overlap_gauge.set(self._overlap_host_s / denom)
        self.recorder.record(
            "serve/readback", step=self._step_count, window=hd.kind,
            wait_ms=wait * 1e3, overlapped_ms=host * 1e3,
        )
        hd.consumed.clear()
        if hd.spills:
            # the producing gathers retired behind the window that just
            # drained: land the host payloads now, off the device
            self._settle_spills(hd.spills)
            hd.spills = []
        for rec in hd.promotions:
            # install retired with the window it was enqueued behind
            tr = rec.pop("trace", None)
            if tr is not None and not tr.finished:
                self._promote_wait_hist.observe(
                    tr.phase("promote_wait", bucket=rec["bucket"])
                )
            self.recorder.record("serve/promote_land", **rec)
        hd.promotions = []
        if hd.qerr is not None and self._kv_quant_gauge is not None:
            self._kv_quant_gauge.set(float(fetch(hd.qerr)))
        if hd.prefill_qerrs and self._kv_quant_gauge is not None:
            # chunks attached to this handle dispatched no later than the
            # cycle after it, so their quant errors are (nearly) landed here;
            # publish the worst chunk of the batch
            self._kv_quant_gauge.set(
                max(float(fetch(e)) for e in hd.prefill_qerrs)
            )
            hd.prefill_qerrs = []
        if hd.kind == "verify":
            # the write-index mirror advances by what the device actually
            # committed — but only for lanes still owned by the request
            # the window was dispatched for (a cancelled lane's mirror
            # was reset to 0 and must stay there)
            for s in np.nonzero(hd.active)[0]:
                if hd.reqs[s] is not None and self._slot_req[s] is hd.reqs[s]:
                    self._lane_len[s] += int(counts[s])
            accepted = int(np.maximum(counts[hd.drafted] - 1, 0).sum())
            self._bump("spec_accepted", accepted)
            for s in np.nonzero(hd.drafted)[0]:
                self._accept_len_hist.observe(
                    float(max(int(counts[s]) - 1, 0))
                )
            if self.stats["spec_drafted"]:
                self._accept_rate_gauge.set(
                    self.stats["spec_accepted"] / self.stats["spec_drafted"]
                )
            self.recorder.record(
                "serve/verify", step=self._step_count,
                drafted_lanes=hd.n_drafted, committed=int(counts.sum()),
                accepted=accepted,
            )
        self._trace_drain(hd, counts, t0, t1)
        # one span a call: nothing is opened inside the per-token loop
        with self.tracer.span("serve/emit") as span:
            span["tokens"], span["lanes"] = self._emit(
                toks, counts, mask=hd.active, reqs=hd.reqs, eos=hd.eos,
                prefreed=hd.prefreed)
        if hd.deferred_pages:
            # fetch() above proved the window retired: its masked writes to
            # detached lanes' pages have landed, so the pages can recycle
            hd.settle(self.kv.allocator)

    def _trace_drain(self, hd: Readback, counts: np.ndarray,
                     t0: float, t1: float) -> None:
        """Close per-request decode/spec_verify waterfall phases at DRAIN —
        under ``async_depth=1`` a window's cost is only known when its
        readback lands, so this is where attribution is honest.  Each live
        lane's phase spans its trace cursor to ``t1`` (the blocking fetch
        tail included, so tiled phases keep summing to wall time); the tail
        rides along as the phase's ``wait_s`` attribute, from which the
        debug endpoints synthesize the ``readback_wait`` overlay — one dict
        per lane per window here, not two.  Runs before ``_emit`` so the
        phases land ahead of the first-token mark."""
        phase = "spec_verify" if hd.kind == "verify" else "decode"
        wait = max(t1 - t0, 0.0)
        for s, req in hd.live_requests():
            tr = req.trace
            if tr is None or tr.finished:
                continue
            dur = tr.phase(phase, now=t1, step=self._step_count,
                           lanes=hd.n_occupied, wait_s=wait)
            n = max(int(counts[s]), 1)
            self._decode_tok_hist.observe(dur / n, n)

    def _count_view_slots(self) -> None:
        """The (lane, page slot) blocks the decode window about to be dispatched
        fills its flat views with, K's and V's arrays each, and those copied
        from a live page: a full table's slots as ``pool._live_tables`` masks
        them with the lanes' indices, a ring's whole; the rest hold the null
        page.  And the key blocks of the ``max_len``-wide view its steps see
        (:func:`~accelerate_tpu.ops.view_attention.live_key_blocks`), of all the
        view's: what the decode kernel reads of it, whichever form runs."""
        kv = self.kv
        live = (self._lane_len + self.window - 1) // self.page_size + 1
        held = int(np.count_nonzero((np.arange(kv.tables.shape[1]) < live[:, None])
                                    & (kv.tables != NULL_PAGE)))
        slots = kv.tables.size
        if self._mixed:
            held += int(np.count_nonzero(kv.ring_tables != NULL_PAGE))
            slots += kv.ring_tables.size
        self._bump("view_slots_live", 2 * held)
        self._bump("view_slots", 2 * slots)
        # every lane's view up to the window's last position (a lane that is
        # not decoding stays at its index: one block)
        last = np.where(self._active, self._lane_len + self.window - 1, self._lane_len)
        blocks = live_key_blocks(last, self._gathered_views()[0], DECODE_BLOCK, None, False, xp=np)
        self._bump("decode_key_blocks_live", int(blocks.sum()))
        self._bump("decode_key_blocks_view", blocks.size)

    def _decode_cycle(self, n_occupied: int) -> Readback:
        """Dispatch one decode window and return its in-flight handle.  The
        tokens stay on device: the caller decides when to drain (immediately
        under ``async_depth=0``, one cycle later under the pipeline).  The
        window's KV/pending/rng outputs rebind here, at dispatch — so the
        next dispatch donates the new handles, never a buffer the in-flight
        window still owns."""
        lanes = self._lane_arrays()
        qerr = None
        if self._stateful:
            kv = self.kv
            audit_donation(kv.s, kv.z)
            index = self._put(self._lane_len)
            consumed = [kv.s, kv.z, lanes[0], lanes[-1], index]
            args = (self.params, kv.s, kv.z, index, *lanes)
            if not self.cost_table.captured("serve/decode_window"):
                self.cost_table.capture("serve/decode_window", self._decode, args)
            with self.tracer.span("serve/decode_window", occupied=n_occupied,
                                  steps=self.window):
                kv.s, kv.z, toks, pending, rngs, *moe = self._decode(*args)
            self._lane_len[self._active] += self.window
        elif self._mixed:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v)
            consumed = [kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v, lanes[0], lanes[-1]]
            tables, ring_tables = self._put(kv.tables), self._put(kv.ring_tables)
            index = self._put(self._lane_len)
            consumed += [tables, ring_tables, index]
            args = (self.params, kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v, tables,
                    ring_tables, index, *lanes)
            self._count_view_slots()
            if not self.cost_table.captured("serve/decode_window"):
                self.cost_table.capture("serve/decode_window", self._decode, args)
            with self.tracer.span("serve/decode_window", occupied=n_occupied,
                                  steps=self.window):
                (kv.pages_k, kv.pages_v, kv.ring_k, kv.ring_v, toks, pending, rngs,
                 *moe) = self._decode(*args)
            self._lane_len[self._active] += self.window
        elif self._direct:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales)
            consumed = [kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                        lanes[0], lanes[-1]]
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index]
            args = (self.params, kv.pages_k, kv.pages_v, kv.k_scales,
                    kv.v_scales, tables, index, *lanes)
            if not self.cost_table.captured("serve/decode_window"):
                self.cost_table.capture("serve/decode_window", self._decode, args)
            with self.tracer.span("serve/decode_window", occupied=n_occupied,
                                  steps=self.window):
                with self.tracer.span("serve/paged_attn", kernel=self.decode_kernel):
                    (kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales, toks,
                     pending, rngs, qerr, *moe) = self._decode(*args)
            self._lane_len[self._active] += self.window
        else:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v)
            consumed = [kv.pages_k, kv.pages_v, lanes[0], lanes[-1]]
            # block tables + write indices ride up fresh each cycle (a few KB
            # of int32 — allocation is host-side and can change every cycle)
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index]
            if self._gathers_view:
                self._count_view_slots()
            if not self.cost_table.captured("serve/decode_window"):
                self.cost_table.capture(
                    "serve/decode_window", self._decode,
                    (self.params, kv.pages_k, kv.pages_v, tables, index, *lanes),
                )
            with self.tracer.span("serve/decode_window", occupied=n_occupied,
                                  steps=self.window):
                kv.pages_k, kv.pages_v, toks, pending, rngs, *moe = self._decode(
                    self.params, kv.pages_k, kv.pages_v, tables, index, *lanes
                )
            self._lane_len[self._active] += self.window
        # the carried pending token / rng live on into the next cycle without
        # touching the host (the host pending mirror is refreshed by _emit)
        lanes[0], lanes[-1] = pending, rngs
        self._bump("decode_steps", self.window)
        self._bump("occupied_lane_steps", n_occupied * self.window)
        consumed += self._stale_handles
        self._stale_handles = []
        return Readback(
            kind="decode", toks=toks, width=self.window, qerr=qerr,
            moe_counts=moe,
            active=self._active.copy(), reqs=list(self._slot_req),
            eos=self._eos.copy(), n_occupied=n_occupied, consumed=consumed,
        )

    def _propose_drafts(self):
        """Host-side n-gram drafts for this cycle: ``(drafts [N, K], drafted
        [N])`` or ``None`` when no active opted-in lane found a match (the
        cycle falls back to the plain decode window).  Lanes without a match
        carry pad drafts — verification rejects them, and the lane still
        lands its >= 1 guaranteed token from the verify forward.

        Drafting goes through the per-lane incremental suffix index
        (:class:`~accelerate_tpu.serving.spec.NgramIndex` via
        :class:`~accelerate_tpu.serving.spec_exec.NgramDrafter`): each call
        feeds the index only the tokens committed since the previous cycle,
        so the host cost is O(K) per lane regardless of context length —
        token-identical to the O(context) rescan it replaced."""
        k = self.speculate_k
        drafts = np.full((self.num_slots, k), self.pad_token_id, np.int32)
        drafted = np.zeros(self.num_slots, bool)
        for s in np.nonzero(self._active)[0]:
            req = self._slot_req[s]
            if req is None or not req.speculate:
                continue
            d = self._ngram.propose(int(s), req.output_ids, k)
            if d is not None:
                drafts[s] = d
                drafted[s] = True
        if not drafted.any():
            return None
        return drafts, drafted

    def _tree_lanes(self) -> np.ndarray:
        """Active lanes opted into speculation this cycle (tree mode).  The
        draft model drafts for every lane in the batch anyway; this mask only
        scopes the accounting (``spec_drafted``/accept stats) and the
        all-opted-out fallback to the plain decode window."""
        drafted = np.zeros(self.num_slots, bool)
        for s in np.nonzero(self._active)[0]:
            req = self._slot_req[s]
            if req is not None and req.speculate:
                drafted[s] = True
        return drafted

    def _tree_cycle(self, drafted: np.ndarray, n_occupied: int) -> Readback:
        """Dispatch one draft forward + tree verify window pair; returns the
        verify handle.  The draft's ``[N, S]`` token tree never touches the
        host — the draft forward's output handle feeds the verify window
        directly, so the host cost of a tree cycle is two dispatches plus
        the usual control-state uploads.

        The draft context window's tail token equals each active lane's
        pending token (seeded at install, advanced in ``_emit``), so the
        draft output's column 0 — the tree root — is exactly the pending
        token the verify forward must score first.  Inactive lanes carry
        garbage roots; their writes are masked (paged: NULL_PAGE-routed)
        and their commits never emit."""
        tree = self.tree
        lanes = self._lane_arrays()
        t0 = time.perf_counter()
        dw = self._draft_window
        ctx = self._put(dw.tokens)
        length = self._put(dw.length)
        if not self.cost_table.captured("serve/draft_forward"):
            self.cost_table.capture(
                "serve/draft_forward", self._draft_fwd,
                (self._draft_params, ctx, length),
            )
        with self.tracer.span("serve/draft_forward", occupied=n_occupied):
            tokens = self.drafter.propose_device(self._draft_params, ctx, length)
        self._draft_ms_hist.observe((time.perf_counter() - t0) * 1e3)
        n_drafted = int(drafted.sum())
        qerr = None
        if self._direct:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales)
            consumed = [kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                        lanes[0], lanes[-1]]
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index, tokens]
            args = (self.params, kv.pages_k, kv.pages_v, kv.k_scales,
                    kv.v_scales, tables, index, tokens, *lanes[1:])
            if not self.cost_table.captured("serve/tree_verify_window"):
                self.cost_table.capture(
                    "serve/tree_verify_window", self._verify, args
                )
            with self.tracer.span("serve/tree_verify_window", occupied=n_occupied,
                                  steps=tree.depth + 1, drafted=n_drafted):
                with self.tracer.span("serve/paged_attn",
                                      kernel=self.decode_kernel):
                    (kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales, out,
                     n_commit, pending, rngs, qerr) = self._verify(*args)
        else:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v)
            consumed = [kv.pages_k, kv.pages_v, lanes[0], lanes[-1]]
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index, tokens]
            if not self.cost_table.captured("serve/tree_verify_window"):
                self.cost_table.capture(
                    "serve/tree_verify_window", self._verify,
                    (self.params, kv.pages_k, kv.pages_v, tables, index,
                     tokens, *lanes[1:]),
                )
            with self.tracer.span("serve/tree_verify_window", occupied=n_occupied,
                                  steps=tree.depth + 1, drafted=n_drafted):
                kv.pages_k, kv.pages_v, out, n_commit, pending, rngs = (
                    self._verify(
                        self.params, kv.pages_k, kv.pages_v, tables, index,
                        tokens, *lanes[1:]
                    )
                )
        lanes[0], lanes[-1] = pending, rngs
        self._bump("decode_steps", tree.depth + 1)
        self._bump("occupied_lane_steps", n_occupied * (tree.depth + 1))
        # accounting uses depth (the max acceptable along one path), not
        # tree nodes: accept rate stays in [0, 1] and comparable across
        # linear and tree arms; node volume has its own counter
        self._bump("spec_drafted", n_drafted * tree.depth)
        self._tree_nodes_counter.inc(n_occupied * tree.nodes)
        consumed += self._stale_handles
        self._stale_handles = []
        return Readback(
            kind="verify", toks=out, width=tree.depth + 1, counts=n_commit,
            qerr=qerr, active=self._active.copy(), reqs=list(self._slot_req),
            eos=self._eos.copy(), n_occupied=n_occupied,
            drafted=drafted.copy(), n_drafted=n_drafted, consumed=consumed,
        )

    def _verify_cycle(self, drafts: np.ndarray, drafted: np.ndarray,
                      n_occupied: int) -> Readback:
        """Dispatch one speculative verify window; returns its in-flight
        handle.  ``n_commit`` stays on device with the tokens — the paged
        write-index mirror therefore advances at *drain*, which is why
        speculative cycles drain the previous window before dispatching."""
        k = self.speculate_k
        lanes = self._lane_arrays()
        # the host pending mirror is always fresh here (a pending verify
        # handle was drained before drafting); only the [N, K+1] token block
        # uploads per verify cycle
        tokens = self._put(
            np.concatenate([self._pending_tok[:, None], drafts], axis=1)
        )
        n_drafted = int(drafted.sum())
        qerr = None
        if self._direct:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales)
            consumed = [kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales,
                        lanes[0], lanes[-1]]
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index, tokens]
            args = (self.params, kv.pages_k, kv.pages_v, kv.k_scales,
                    kv.v_scales, tables, index, tokens, *lanes[1:])
            if not self.cost_table.captured("serve/verify_window"):
                self.cost_table.capture("serve/verify_window", self._verify, args)
            with self.tracer.span("serve/verify_window", occupied=n_occupied,
                                  steps=k + 1, drafted=n_drafted):
                with self.tracer.span("serve/paged_attn", kernel=self.decode_kernel):
                    (kv.pages_k, kv.pages_v, kv.k_scales, kv.v_scales, out,
                     n_commit, pending, rngs, qerr) = self._verify(*args)
        else:
            kv = self.kv
            audit_donation(kv.pages_k, kv.pages_v)
            consumed = [kv.pages_k, kv.pages_v, lanes[0], lanes[-1]]
            tables = self._put(kv.tables)
            index = self._put(self._lane_len)
            consumed += [tables, index, tokens]
            if not self.cost_table.captured("serve/verify_window"):
                self.cost_table.capture(
                    "serve/verify_window", self._verify,
                    (self.params, kv.pages_k, kv.pages_v, tables, index,
                     tokens, *lanes[1:]),
                )
            with self.tracer.span("serve/verify_window", occupied=n_occupied,
                                  steps=k + 1, drafted=n_drafted):
                kv.pages_k, kv.pages_v, out, n_commit, pending, rngs = self._verify(
                    self.params, kv.pages_k, kv.pages_v, tables, index,
                    tokens, *lanes[1:]
                )
        lanes[0], lanes[-1] = pending, rngs
        self._bump("decode_steps", k + 1)
        self._bump("occupied_lane_steps", n_occupied * (k + 1))
        self._bump("spec_drafted", n_drafted * k)
        consumed += self._stale_handles
        self._stale_handles = []
        return Readback(
            kind="verify", toks=out, width=k + 1, counts=n_commit, qerr=qerr,
            active=self._active.copy(), reqs=list(self._slot_req),
            eos=self._eos.copy(), n_occupied=n_occupied,
            drafted=drafted.copy(), n_drafted=n_drafted, consumed=consumed,
        )

    def _emit(self, toks: np.ndarray, counts: np.ndarray,
              mask: Optional[np.ndarray] = None,
              reqs: Optional[List[Optional[Request]]] = None,
              eos: Optional[np.ndarray] = None,
              prefreed: Optional[set] = None) -> Tuple[int, int]:
        """Land device-produced tokens on their requests. ``toks[s, :counts[s]]``
        is lane ``s``'s output this cycle (a full decode window, or a verify
        cycle's committed prefix).  Per-lane take counts — EOS cut plus the
        per-request length cap — are computed in one numpy pass so host time
        stays flat in window size / speculate_k; only genuine per-request
        bookkeeping (streaming callbacks, histograms, frees) runs in Python.

        ``mask``/``reqs``/``eos`` are the window's dispatch-time snapshots
        (:class:`Readback`): under the pipeline the live lane state may have
        moved on — a lane freed/cancelled/preempted since dispatch no longer
        owns its slot, so the ownership check drops its tokens.  Returns the
        tokens landed and the lanes they landed on (the ``serve/emit`` span's
        counts)."""
        if mask is None:
            mask = self._active
        if reqs is None:
            reqs = self._slot_req
        if eos is None:
            eos = self._eos
        width = toks.shape[1]
        pos = np.arange(width)[None, :]
        valid = (pos < np.asarray(counts).reshape(-1, 1)) & mask[:, None]
        is_eos = valid & (toks == eos[:, None]) & (eos >= 0)[:, None]
        has_eos = is_eos.any(axis=1)
        first_eos = np.where(has_eos, is_eos.argmax(axis=1), width)
        n_take = np.minimum(valid.sum(axis=1), first_eos + 1)
        now = time.perf_counter()
        landed = lanes = 0
        for s in np.nonzero(n_take > 0)[0]:
            req = reqs[s]
            if req is None:
                continue
            owner = self._slot_req[s] is req
            # a slot with a new owner normally drops this window's tokens
            # (the lane was cancelled/preempted) — unless the lane was
            # PRE-FREED: retired early because this very window provably
            # finishes it, in which case its tokens are the request's tail
            if not owner and not (
                prefreed and int(s) in prefreed
                and req.state is RequestState.RUNNING
            ):
                continue
            # the device can land more than the request's remaining budget in
            # one verify cycle; the cap truncation below keeps outputs exactly
            # what sequential decode would have produced
            n = min(int(n_take[s]), req.config.max_new_tokens - len(req.tokens))
            if n <= 0:
                continue
            if not req.tokens:
                self._ttft_hist.observe(now - req.submit_time)
                if req.trace is not None:
                    req.trace.mark_first_token(now)
                if req.request_class:
                    hist = self._class_ttft_hists.get(req.request_class)
                    if hist is None:
                        hist = self.metrics.histogram(
                            f"serve/ttft_s_class_{req.request_class}",
                            buckets=_LATENCY_BUCKETS,
                        )
                        self._class_ttft_hists[req.request_class] = hist
                    hist.observe(now - req.submit_time)
                self._tenant_ttft(req.tenant, now - req.submit_time)
            for t in toks[s, :n]:
                req.emit(int(t))
            if owner and self._draft_window is not None:
                # keep the draft context's tail == the lane's pending token
                # (the committed suffix ends with the next pending token)
                self._draft_window.push(int(s), toks[s, :n])
            landed += n
            lanes += 1
            self._bump("tokens_generated", n)
            self._bump_tenant(req.tenant, "tokens_generated", n)
            # a cycle lands n tokens on this lane at once: each is charged its
            # amortized share of the wall time since the lane's last arrival
            self._token_hist.observe(max(now - req.last_token_time, 0.0) / n, n)
            req.last_token_time = now
            hit_eos = bool(has_eos[s]) and n == int(n_take[s])
            if hit_eos or len(req.tokens) >= req.config.max_new_tokens:
                if owner:
                    self._free(s, req)
                else:
                    # pre-freed: the lane was already retired and the slot
                    # reassigned — only the request itself completes here
                    self._finish_request(int(s), req)
            elif owner:
                self._pending_tok[s] = int(toks[s, n - 1])
        return landed, lanes

    # ------------------------------------------------------------------ drive
    def step(self) -> None:
        """One engine iteration: budgeted chunked-prefill admission, then one
        masked decode window over the pool.

        Fault containment: the first exception to escape the step body parks
        in ``_poisoned`` and re-raises — this engine never half-runs again
        until :meth:`revive`.  The router supervisor treats a poisoned
        replica as dead, exports its in-flight requests, and replays them on
        survivors (:meth:`export_inflight` / :meth:`adopt`)."""
        if self._poisoned is not None:
            raise self._poisoned
        try:
            stats = self.stats
            before = [stats[k] for k in self._step_carried]
            with self.tracer.span(
                "serve/step", queue=self.scheduler.queue_depth
            ) as span:
                self._step_impl()
                span["occupied"] = int(self._active.sum())
                # what this step carried, from the counters its parts bumped:
                # at most one window a step, so the lanes live at its dispatch
                # are its lane-steps over its width
                steps, lane_steps, chunks, chunk_tokens, emitted, *moe = (
                    stats[k] - b for k, b in zip(self._step_carried, before))
                span["window"] = int(steps > 0)
                span["live"] = lane_steps // steps if steps else 0
                span["chunks"], span["chunk_tokens"] = chunks, chunk_tokens
                span["emitted"] = emitted
                if moe:
                    # the held experts the windows it drained read, of those
                    # their steps could have read
                    span["experts_hit"], span["expert_slots"] = moe
        except Exception as exc:
            self._poisoned = exc
            self.recorder.record(
                "serve/engine_poisoned", error=repr(exc), step=self._step_count,
            )
            raise

    def _step_impl(self) -> None:
        if self._has_deadlines:
            self._shed_blown_deadlines()
        if (faults.ACTIVE is not None and self._active.any()
                and faults.ACTIVE.fire("page_exhaustion")):
            # stand-in for the pool running dry: run the reclaim ladder's
            # last resort (preempt the youngest lane for front-of-queue
            # replay) exactly as _ensure_decode_capacity would under pressure.
            # Drain first, as the ladder's step 2 does: with the prior window
            # still in flight the victim could re-install into its old slot
            # before the drain, and the stale window's tokens would pass the
            # ownership check and land twice.
            if self._inflight is not None:
                self._drain_inflight()
            self._preempt()
        queue_depth = self.scheduler.queue_depth
        self._queue_gauge.set(queue_depth)
        self._prefree_exhausted()
        if self.role == "prefill":
            # disaggregated prefill replica: chunked prefill only.  Lanes
            # whose last chunk landed sit installed-but-undecoded until the
            # router hands them off to a decode replica (transfer.handoff);
            # dispatching a decode window here would both waste the step and
            # advance lanes the destination expects at their prefill
            # frontier.  No window means nothing to charge decode for.
            self._cycle_decode_tokens = 0
            self._admit()
            self._prev_handle = None
        elif self.interleave_prefill:
            # decode-interleaved chunked prefill: dispatch this cycle's
            # window FIRST, then admit — the chunk enqueues *behind* the
            # window, so decode lanes never skip a cycle while a long
            # prompt prefills, and the chunk runs under the host work of
            # draining the previous window
            # the previous window parks on the engine while admission runs:
            # any forced flush inside _admit (page-reclaim ladder) must land
            # it BEFORE the window just dispatched
            self._prev_handle = self._dispatch_decode()
            self._admit()
        else:
            # decode dispatches after admission: charge it nothing (the
            # counter still holds LAST cycle's width otherwise)
            self._cycle_decode_tokens = 0
            self._admit()
            self._prev_handle = self._dispatch_decode()
        tgt = (self._inflight if self._inflight is not None
               else self._prev_handle)
        if self._pending_prefill_qerr:
            # hand the chunk quant-error handles to a window that retires
            # no earlier than the chunks do — fetched at ITS drain
            if tgt is not None:
                tgt.prefill_qerrs.extend(self._pending_prefill_qerr)
                self._pending_prefill_qerr.clear()
        if self._pending_moe_counts and tgt is not None:
            tgt.moe_counts.extend(self._pending_moe_counts)
            self._pending_moe_counts.clear()
        if self._pending_spills or self._pending_promotions:
            # same discipline for hierarchical-cache traffic: spill payloads
            # land, and promotions are acknowledged, at the drain of a window
            # that provably retires after them
            if tgt is not None:
                tgt.spills.extend(self._pending_spills)
                tgt.promotions.extend(self._pending_promotions)
            else:
                # no window in flight (idle engine / async_depth=0 gap):
                # nothing to hide the fetch behind, settle on the spot
                self._settle_spills(self._pending_spills)
                for rec in self._pending_promotions:
                    tr = rec.pop("trace", None)
                    if tr is not None and not tr.finished:
                        self._promote_wait_hist.observe(
                            tr.phase("promote_wait", bucket=rec["bucket"])
                        )
                    self.recorder.record("serve/promote_land", **rec)
            self._pending_spills = []
            self._pending_promotions = []
        prev, self._prev_handle = self._prev_handle, None
        if prev is not None:
            self._drain(prev)
        if self.prefix_cache is not None:
            covered = self.stats["prefix_hit_tokens"] + self.stats["prefix_miss_tokens"]
            if covered:
                hit = self.stats["prefix_hit_tokens"]
                host_hit = self.stats["prefix_hit_tokens_host"]
                self._hit_rate_gauge.set(hit / covered)
                self._hit_rate_device_gauge.set((hit - host_hit) / covered)
                self._hit_rate_host_gauge.set(host_hit / covered)
        self._update_prefill_gauges()
        self.kv.publish_gauges()
        self._step_count += 1
        # Progress heartbeat for the stall detector / /healthz; also the
        # ring's per-step record of what the pool looked like.
        self.recorder.heartbeat(
            "serve/step", step=self._step_count, queue=queue_depth,
            occupied=int(self._active.sum()),
        )

    @property
    def has_work(self) -> bool:
        # an in-flight window is work: its tokens haven't landed yet, so the
        # driver keeps stepping until the pipeline flushes (the trailing step
        # finds no active lane and drains)
        return (self.scheduler.has_queued or bool(self._active.any())
                or self._inflight is not None)

    def _update_tenant_kv_gauges(self) -> None:
        """Per-tenant KV occupancy gauges (``serve/kv_pages_tenant_<t>``):
        pages held by each tenant's active lanes.  Walks the slot array —
        metrics-tick cadence only, never the per-step hot path.  A tenant with no live lane reads 0
        (the gauge is not deleted: dashboards want the series to zero, not
        vanish)."""
        if not self._tenant_stats or self._stateful:
            return
        held: dict = {}
        for s in range(self.num_slots):
            req = self._slot_req[s]
            if req is None or req.tenant is None:
                continue
            held[req.tenant] = (held.get(req.tenant, 0)
                                + int(self.kv.lane_npages[s]))
        for tenant in self._tenant_stats:
            gauge = self._tenant_kv_gauges.get(tenant)
            if gauge is None:
                gauge = self._tenant_kv_gauges[tenant] = self.metrics.gauge(
                    f"serve/kv_pages_tenant_{tenant}"
                )
            gauge.set(held.get(tenant, 0))

    def _log_health(self, dt: float, d_tokens: int) -> None:
        """One-line serve-health summary (the ``metrics_interval`` heartbeat)."""
        queued = self.scheduler.queue_depth
        occupancy = float(self._active.mean()) if self.num_slots else 0.0
        p99_ms = self._token_hist.percentile(99) * 1e3
        logger.info(
            f"serve health: queue={queued} occupancy={occupancy:.2f} "
            f"tokens/s={d_tokens / dt if dt > 0 else 0.0:.1f} "
            f"token_p99={p99_ms:.2f}ms "
            f"completed={self.stats['requests_completed']}"
            f"/{self.stats['requests_submitted']}"
        )

    def run(
        self,
        max_steps: Optional[int] = None,
        metrics_interval: Optional[float] = None,
    ) -> None:
        """Drive :meth:`step` until every submitted request completes.

        ``metrics_interval`` (seconds) logs a one-line health summary — queue
        depth, slot occupancy, tokens/s, p99 token latency — at that cadence
        through :func:`~accelerate_tpu.logging.get_logger`.  Off by default.
        """
        steps = 0
        last_log = time.perf_counter()
        last_tokens = self.stats["tokens_generated"]
        while self.has_work:
            self.step()
            steps += 1
            if metrics_interval is not None:
                now = time.perf_counter()
                if now - last_log >= metrics_interval:
                    self._log_health(now - last_log,
                                     self.stats["tokens_generated"] - last_tokens)
                    # the fleet-health layer rides the same tick: refresh the
                    # per-tenant KV gauges, then sample/evaluate the SLO
                    # engine if one is installed (a no-op branch otherwise)
                    self._update_tenant_kv_gauges()
                    slo_tick()
                    last_log = now
                    last_tokens = self.stats["tokens_generated"]
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")

    def serve(
        self,
        prompts: Sequence,
        configs=None,
        on_token: Optional[Callable[[Request, int], None]] = None,
        metrics_interval: Optional[float] = None,
    ) -> List[Request]:
        """Convenience: submit every prompt (``configs`` is one shared or a
        per-request list of ``GenerationConfig``), run to completion, return
        the requests in submission order.  ``metrics_interval`` is forwarded
        to :meth:`run` (periodic health logging; off by default)."""
        reqs = []
        for i, p in enumerate(prompts):
            cfg = configs[i] if isinstance(configs, (list, tuple)) else configs
            reqs.append(self.submit(p, config=cfg, on_token=on_token))
        self.run(metrics_interval=metrics_interval)
        return reqs

    # ------------------------------------------------------------------ stats
    def mean_slot_occupancy(self) -> float:
        """Occupied lane-steps / total lane-steps across decode windows."""
        total = self.stats["decode_steps"] * self.num_slots
        return self.stats["occupied_lane_steps"] / total if total else 0.0

    def prefix_cache_stats(self) -> dict:
        """Prefix-cache health: residency + hit/miss token counts (zeros when
        the cache is disabled)."""
        out = {"prefix_hit_tokens": self.stats["prefix_hit_tokens"],
               "prefix_miss_tokens": self.stats["prefix_miss_tokens"]}
        covered = out["prefix_hit_tokens"] + out["prefix_miss_tokens"]
        out["hit_rate"] = out["prefix_hit_tokens"] / covered if covered else 0.0
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        if self._stateful:
            # a recurrent state has no pages for a hit to map
            out["built"] = False
        return out

    def analyze_costs(self) -> dict:
        """XLA cost/memory analysis over every executable the pool has run
        (decode window, hit prefill/copy buckets, insert) and publish the
        ``serve/decode_flops_per_token`` / ``serve/hbm_peak_bytes`` gauges.

        Best-effort and idempotent — re-lowers from recorded abstract
        signatures, so call it off the serve loop (benches do; the debug
        server runs it as a scrape collector).  Returns the cost-table
        snapshot."""
        snap = self.cost_table.analyze_all()
        decode_flops = self.cost_table.flops("serve/decode_window")
        if decode_flops:
            self._decode_flops_gauge.set(
                decode_flops / (self.window * self.num_slots)
            )
        hbm = self.cost_table.max_hbm_peak_bytes()
        if hbm:
            # per-device: XLA's analysis sees logical (whole-array) shapes;
            # under tp the KV pool and weights split evenly across the axis
            self._hbm_gauge.set(hbm / self.tp_degree)
        return snap

    def kv_pool_bytes(self) -> int:
        """PER-DEVICE HBM the page pool occupies (the knob ``num_pages``
        sizes it).  Under a tp mesh the pool shards on the kv-head axis, so
        each device holds exactly ``1 / tp_degree`` of the logical bytes."""
        return self.kv.kv_bytes_per_device()

    def compiled_executable_counts(self) -> dict:
        """Per-executable jit-cache sizes — the no-retrace contract: after any
        workload each entry is at most 1 (the verify_window entry exists only
        when ``speculate_k > 0`` and stays 0 until the first drafted cycle;
        tree speculation swaps it for exactly two entries,
        ``tree_verify_window`` and ``draft_forward``).  ``copy_page`` stays 0
        until the first copy-on-write; cache hits alias pages, so the hit
        path adds no executable at all.  ``lane_install`` is the one-slot
        lane-vector scatter admissions enqueue once the device mirror exists — 0 when
        every install landed before the first window.  The host spill tier
        (``prefix_host_mb > 0``) adds exactly one ``spill_<bucket>`` D2H
        gather and one ``promote_<bucket>`` H2D scatter-install per prefill
        bucket — the documented, bounded growth of the compiled budget; each
        stays 0 until the first spill/promotion of that bucket.  Live lane
        migration adds exactly one ``migrate_extract`` D2H/D2D gather and one
        ``migrate_install`` donated scatter at full ``pages_per_lane`` width
        (page-id padding keeps the signature fixed) — built lazily by
        ``serving.transfer.migration_executables``, so engines that never
        participate in a migration gain neither entry."""
        out = {"decode_window": jit_cache_sizes(self._decode),
               "lane_install": jit_cache_sizes(self._lane_install),
               "copy_page": jit_cache_sizes(self._copy_page)}
        if self._stateful:
            out["state_install"] = jit_cache_sizes(self._state_install)
        if self._verify is not None:
            out["tree_verify_window" if self.tree is not None
                else "verify_window"] = jit_cache_sizes(self._verify)
        if self._draft_fwd is not None:
            out["draft_forward"] = jit_cache_sizes(self._draft_fwd)
        for b, f in self._prefill.items():
            out[f"prefill_{b}"] = jit_cache_sizes(f)
        for b, f in self._spill_extract.items():
            out[f"spill_{b}"] = jit_cache_sizes(f)
        for b, f in self._promote_install.items():
            out[f"promote_{b}"] = jit_cache_sizes(f)
        if self._migrate_extract is not None:
            out["migrate_extract"] = jit_cache_sizes(self._migrate_extract)
        if self._migrate_install is not None:
            out["migrate_install"] = jit_cache_sizes(self._migrate_install)
        return out

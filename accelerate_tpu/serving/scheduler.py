"""Host-side request scheduling for the continuous-batching engine.

The device side (:mod:`.pool`) is a fixed set of compiled executables; the
scheduler is everything dynamic: a FCFS request queue, per-request
:class:`~accelerate_tpu.models.generation.GenerationConfig`, chunked-prefill
progress, and an admission policy bounded by a **prefill-token budget per
engine step** — the Orca/Sarathi knob that keeps decode-step latency jitter
bounded while new prompts stream in.

With a :class:`~accelerate_tpu.serving.prefix_cache.PrefixCache` attached, the
scheduler also resolves prefix reuse: ``submit`` walks the radix tree for the
longest cached chunk-aligned prefix (pinning the matched nodes so eviction
cannot pull them out from under the queued request), ``start_next`` refreshes
the walk — requests admitted earlier may have populated chunks this request
can now reuse — and ``take_chunk`` charges cached chunks at ZERO cost against
the prefill-token budget, so every hit also frees budget for cold prompts in
the same engine step.
"""

from __future__ import annotations

import dataclasses
import enum
from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..models.generation import GenerationConfig
from ..telemetry import get_flight_recorder
from .errors import AdmissionError
from .pool import plan_chunks


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    RUNNING = "running"
    DONE = "done"
    CANCELLED = "cancelled"


@dataclasses.dataclass
class Request:
    """One serving request: prompt + per-request generation config + progress.

    ``on_token(request, token)`` streams each generated token as the engine
    observes it (window granularity); ``tokens`` accumulates the final
    generated ids (EOS included when hit, never the post-EOS padding).
    """

    rid: int
    prompt: np.ndarray                      # [S] int32
    config: GenerationConfig
    on_token: Optional[Callable[["Request", int], None]] = None
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    # chunked-prefill progress
    chunks: Tuple[Tuple[int, int], ...] = ()
    next_chunk: int = 0
    # prefix-cache state: the first ``cached_chunks`` entries of ``chunks``
    # are CACHED (replayed from retained KV slabs instead of prefilled);
    # ``cache_nodes`` holds the pinned radix nodes backing them plus any nodes
    # this request itself populates (released on insertion or cancel), and
    # ``cache_chain_broken`` stops population once a chunk could not be
    # retained (a later chunk without its ancestors would be unreachable).
    cache_prefix: bool = True
    # per-request speculative-decoding opt-out: when False the engine never
    # drafts for this request's lane even with ``speculate_k > 0`` (it still
    # rides along in verify windows other lanes trigger — with pad drafts,
    # which verification simply rejects)
    speculate: bool = True
    cached_chunks: int = 0
    cache_nodes: List[Any] = dataclasses.field(default_factory=list)
    cache_chain_broken: bool = False
    submit_step: int = -1
    finish_step: int = -1
    # wall-clock stamps (time.perf_counter) for TTFT / per-token latency
    submit_time: float = 0.0
    last_token_time: float = 0.0
    # replica index a :class:`~accelerate_tpu.serving.router.ReplicaRouter`
    # placed this request on (None when submitted straight to an engine)
    replica: Optional[int] = None
    # stable replica identity: unlike ``replica`` (a position in
    # ``router.engines``, which shifts when an earlier replica detaches),
    # this id survives elastic add/drain — cancel resolves through it first
    replica_id: Optional[int] = None
    # SLO deadline in seconds from submit (None = no deadline).  Admission
    # sheds when the queue-depth estimate says it is unmeetable; the engine's
    # deadline sweep cancels a running lane that blows it and sets
    # ``deadline_exceeded`` so the API layer can answer 504 instead of 500
    deadline_s: Optional[float] = None
    deadline_exceeded: bool = False
    # traffic-class label ("chat", "batch", ...) for per-class TTFT
    # histograms; None stays out of the per-class series entirely
    request_class: Optional[str] = None
    # tenant attribution label (X-Tenant header / API-key prefix at the front
    # door).  Rides the Request through preemption, export_inflight, and
    # failover ``adopt`` exactly like ``trace`` does, so per-tenant counters
    # stay exact across replays; None stays out of every per-tenant family
    tenant: Optional[str] = None
    # the front door's id for this request (``TokenStream.rid``: what the API
    # echoes as X-Request-Id and ``http/stream_write`` carries); None when
    # submitted straight to an engine.  Unlike ``rid`` it survives adoption.
    key: Optional[int] = None
    # per-request latency waterfall (telemetry.reqtrace.RequestTrace; None
    # when tracing is off).  The SAME object rides through preemption,
    # export_inflight, and failover adoption, so the waterfall spans replicas
    # instead of restarting — ``adopt`` appends a ``failover`` phase to it.
    trace: Optional[Any] = dataclasses.field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return self.state is RequestState.DONE

    @property
    def trace_id(self) -> int:
        """The ``req`` this request's spans and records carry on the tracer."""
        return self.rid if self.key is None else self.key

    @property
    def output_ids(self) -> np.ndarray:
        """Prompt + generated tokens (the ``generate`` row, pad tail trimmed)."""
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])

    @property
    def prefill_tokens(self) -> np.ndarray:
        """What prefill must process for this request *now*: the prompt, plus
        — after a preemption — every token already generated and streamed.
        Replay re-prefills the whole effective prompt (ideally via prefix-cache
        hits on the chunks this request populated in its first life) and
        generation resumes exactly where it stopped; ``tokens`` is never
        re-emitted.  Identical to ``prompt`` for a never-preempted request."""
        if not self.tokens:
            return self.prompt
        return self.output_ids

    def emit(self, token: int) -> None:
        self.tokens.append(int(token))
        if self.on_token is not None:
            self.on_token(self, int(token))

    def finished(self, token: int) -> bool:
        """Would emitting ``token`` complete this request?"""
        eos = self.config.eos_token_id
        return (eos is not None and int(token) == eos) or (
            len(self.tokens) + 1 >= self.config.max_new_tokens
        )


class Scheduler:
    """FCFS admission with a per-step prefill-token budget.

    By default one request prefills at a time (the legacy scratch cache is
    batch-1); its chunks are charged against ``prefill_token_budget`` each
    engine step, so a long prompt spreads across steps instead of stalling
    every running request for its whole prefill (chunked prefill,
    Sarathi-style).

    ``max_prefills > 1`` (the interleaved engine) keeps several
    requests mid-prefill at once: admission is still FCFS, but
    :meth:`take_chunk` picks the chunk to run each step
    shortest-remaining-first among the open prefills, so a short chat prompt
    arriving behind a 100k-token prompt finishes its one chunk next step
    instead of waiting out the giant — iteration-level scheduling on the
    prefill side, with the budget still the single jitter bound.
    """

    def __init__(self, prefill_buckets: Sequence[int], prefill_token_budget: int,
                 prefix_cache=None, recorder=None,
                 max_queue: Optional[int] = None, max_prefills: int = 1):
        self.buckets = tuple(sorted(set(int(b) for b in prefill_buckets)))
        if not self.buckets:
            raise ValueError("need at least one prefill bucket")
        self.budget = int(prefill_token_budget)
        if self.budget < self.buckets[0]:
            raise ValueError(
                f"prefill_token_budget {self.budget} cannot fit the smallest "
                f"bucket {self.buckets[0]} — no prompt would ever be admitted"
            )
        # admission backpressure: with ``max_queue`` set, a submit that would
        # push the waiting line past it raises a *retriable* AdmissionError —
        # the signal the HTTP front door maps to 429 and the router's failover
        # ladder uses to try a less-loaded replica.  None = unbounded (the
        # in-process benches/tests drive their own queue depth).
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_prefills = int(max_prefills)
        if self.max_prefills < 1:
            raise ValueError(f"max_prefills must be >= 1, got {max_prefills}")
        self.queue: deque = deque()
        # requests mid-prefill, in admission order; bounded by max_prefills
        self._prefills: List[Request] = []
        # did a forward-pass chunk dispatch since begin_step? (the first one
        # per cycle is exempt from the joint budget — anti-starvation)
        self._chunk_this_step = False
        self.prefix_cache = prefix_cache
        # request-lifecycle events for post-mortems (a no-op ring append when
        # telemetry is disabled); the engine passes the process recorder
        self.recorder = recorder if recorder is not None else get_flight_recorder()

    @property
    def prefills(self) -> Tuple[Request, ...]:
        """Every request currently mid-prefill, admission order."""
        return tuple(self._prefills)

    @property
    def prefilling(self) -> Optional[Request]:
        """The oldest open prefill (the only one under ``max_prefills=1``) —
        kept for the single-prefill callers; multi-prefill code should use
        :attr:`prefills`."""
        return self._prefills[0] if self._prefills else None

    @prefilling.setter
    def prefilling(self, req: Optional[Request]) -> None:
        self._prefills = [] if req is None else [req]

    def take_prefills(self) -> List[Request]:
        """Detach and return every open prefill (replica export: the engine
        hands them to the router for replay on a survivor)."""
        out, self._prefills = self._prefills, []
        return out

    def _match_prefix(self, request: Request) -> None:
        """(Re)walk the radix tree for ``request``'s longest cached prefix and
        pin the matched chain.  Pins taken by an earlier walk are released
        *after* the new chain is acquired — the old nodes are still resident
        during the re-walk, so the fresh match can only be equal or longer."""
        if self.prefix_cache is None or not request.cache_prefix:
            return
        nodes = self.prefix_cache.match(request.prefill_tokens, request.chunks)
        self.prefix_cache.acquire(nodes)
        if request.cache_nodes:
            self.prefix_cache.release(request.cache_nodes)
        request.cache_nodes = list(nodes)
        request.cached_chunks = len(nodes)

    def submit(self, request: Request) -> None:
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # retry hint: the queue drains one request per freed slot; a rough
            # half-second per queued request is deliberately conservative —
            # callers treat it as "not before", not as a promise
            depth = self.queue_depth
            raise AdmissionError(
                f"admission queue full ({len(self.queue)} >= max_queue "
                f"{self.max_queue})",
                queue_depth=depth,
                retry_after_s=min(30.0, 0.5 * depth),
                retriable=True,
            )
        request.chunks = plan_chunks(len(request.prefill_tokens), self.buckets)
        self._match_prefix(request)
        self.queue.append(request)
        self.recorder.record(
            "serve/submit", rid=request.rid, prompt_len=len(request.prompt),
            chunks=len(request.chunks), cached_chunks=request.cached_chunks,
            queue_depth=len(self.queue),
        )

    def requeue(self, request: Request) -> None:
        """Put a preempted RUNNING request back at the FRONT of the queue for
        replay (it already waited its FCFS turn once).  Its effective prompt
        is ``prefill_tokens`` — original prompt plus everything generated —
        re-planned into chunks and re-matched against the prefix cache, so
        replay aliases/reuses whatever this request populated in its first
        life instead of recomputing it."""
        request.state = RequestState.QUEUED
        request.slot = None
        request.chunks = plan_chunks(len(request.prefill_tokens), self.buckets)
        request.next_chunk = 0
        request.cached_chunks = 0
        request.cache_chain_broken = False
        self._match_prefix(request)
        self.queue.appendleft(request)
        if request.trace is not None:
            request.trace.annotate(
                "requeue", effective_len=len(request.prefill_tokens),
                cached_chunks=request.cached_chunks,
            )
        self.recorder.record(
            "serve/requeue", rid=request.rid,
            effective_len=len(request.prefill_tokens),
            cached_chunks=request.cached_chunks, queue_depth=len(self.queue),
        )

    def drop_cache_pins(self) -> int:
        """Release every *queued* request's prefix-cache pins (the
        engine's last-resort page reclaim: pinned nodes block eviction, and a
        queued request can always re-match at admission).  Returns how many
        requests were unpinned."""
        dropped = 0
        if self.prefix_cache is None:
            return 0
        for req in self.queue:
            if req.cache_nodes:
                self.prefix_cache.release(req.cache_nodes)
                req.cache_nodes = []
                req.cached_chunks = 0
                dropped += 1
        return dropped

    def cancel(self, rid: int) -> Optional[Request]:
        """Drop a still-QUEUED request (not yet prefilling) from the queue.

        Returns the cancelled :class:`Request` (state ``CANCELLED``, its
        pinned prefix-cache nodes released) or ``None`` when ``rid`` is not
        queued — already prefilling, running, done, or unknown.  Cancelling
        before admission is the cheap case worth optimizing: the request has
        consumed no prefill budget and holds no slot.
        """
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                if self.prefix_cache is not None and req.cache_nodes:
                    self.prefix_cache.release(req.cache_nodes)
                    req.cache_nodes = []
                req.state = RequestState.CANCELLED
                self.recorder.record("serve/cancel", rid=rid)
                return req
        return None

    @property
    def has_queued(self) -> bool:
        return bool(self.queue) or bool(self._prefills)

    @property
    def queue_depth(self) -> int:
        """Requests waiting or mid-prefill — the ``serve/queue_depth`` gauge
        and the router's load signal.  Admission runs against the engine's
        HOST lane state, which under the pipelined loop (``async_depth=1``)
        is authoritative even while a window is in flight: a lane retired at
        drain frees its slot immediately, one step after the sync loop would
        have (the documented EOS lag), so queue depth can read one step
        higher than ``async_depth=0`` under churn — never lower."""
        return len(self.queue) + len(self._prefills)

    def begin_step(self, decode_tokens: int = 0) -> int:
        """Fresh prefill-token budget for this engine step.

        ``decode_tokens`` is what the decode window already dispatched this
        cycle (interleaved mode: occupied lanes x window width).  Decode and
        prefill share one per-cycle token budget — the Sarathi/Orca joint
        bound — so a busy pool shrinks what prefill may add on top, keeping
        total step latency flat.  Anti-starvation lives in
        :meth:`take_chunk`, not here: the first forward-pass chunk of each
        cycle dispatches even over budget (or a chunk wider than the
        post-decode remainder could never run while any lane decodes, and a
        full pool under a long prompt livelocks admission); the budget
        throttles every chunk after it."""
        self._chunk_this_step = False
        if decode_tokens <= 0:
            return self.budget
        return max(self.budget - int(decode_tokens), 0)

    def start_next(self, slot: int) -> Optional[Request]:
        """Pop the FCFS head into PREFILL state, bound for ``slot``.  Up to
        ``max_prefills`` requests may be mid-prefill at once; admission order
        stays FCFS even though :meth:`take_chunk` picks among them SRTF."""
        if len(self._prefills) >= self.max_prefills or not self.queue:
            return None
        req = self.queue.popleft()
        req.state = RequestState.PREFILL
        req.slot = slot
        # refresh the prefix match: requests admitted since submit may have
        # populated exactly the chunks this one needs (the batch-submit case)
        self._match_prefix(req)
        self._prefills.append(req)
        self.recorder.record(
            "serve/prefill_start", rid=req.rid, slot=slot,
            chunks=len(req.chunks), cached_chunks=req.cached_chunks,
        )
        return req

    @staticmethod
    def _remaining_compute(req: Request) -> int:
        """Tokens still needing a forward pass: cached chunks replay for
        free, so they don't count toward shortest-remaining-first."""
        skip = max(req.next_chunk, req.cached_chunks)
        return sum(v for _, v in req.chunks[skip:])

    def take_chunk(self, budget: int, ready=None,
                   ) -> Optional[Tuple[Request, int, int, int, bool]]:
        """Next prefill chunk fitting ``budget``:
        ``(request, bucket_len, valid_len, start, cached)`` or None.

        With several open prefills the pick is shortest-remaining-first
        (remaining *compute* tokens; FCFS rid breaks ties) among those whose
        next chunk fits the budget — a chat prompt's single chunk lands ahead
        of a mega-prompt's hundredth without starving it (every candidate
        stays eligible each step).  ``ready`` is an optional per-request
        gate — the engine passes its page-reservation check, so a
        request short on pages this step doesn't block a smaller one that
        fits.

        A CACHED chunk (``cached=True``: covered by a pinned prefix-cache
        node) charges nothing against the budget — replaying retained KV is
        one ``dynamic_update_slice``, not a forward pass — so hits both skip
        compute and leave the whole budget to cold prompts this step.

        The FIRST forward-pass chunk since :meth:`begin_step` ignores the
        budget check: the joint decode+prefill bound may leave a remainder
        smaller than the pending bucket every single cycle, and without this
        carve-out such a chunk would starve until the pool idles.
        """
        best = None
        best_key = None
        for req in self._prefills:
            if req.next_chunk >= len(req.chunks):
                continue
            bucket, _ = req.chunks[req.next_chunk]
            cached = req.next_chunk < req.cached_chunks
            if not cached and bucket > budget and self._chunk_this_step:
                continue
            if ready is not None and not ready(req):
                continue
            key = (self._remaining_compute(req), req.rid)
            if best_key is None or key < best_key:
                best, best_key = req, key
        if best is None:
            return None
        bucket, valid = best.chunks[best.next_chunk]
        cached = best.next_chunk < best.cached_chunks
        start = sum(v for _, v in best.chunks[: best.next_chunk])
        best.next_chunk += 1
        if not cached:
            self._chunk_this_step = True
        return best, bucket, valid, start, cached

    def finish_prefill(self) -> Optional[Request]:
        """If an open prefill has run every chunk, hand it over for insertion
        and clear its prefill lane (at most one per call — the engine installs
        each finished request before taking the next chunk)."""
        for i, req in enumerate(self._prefills):
            if req.next_chunk >= len(req.chunks):
                del self._prefills[i]
                return req
        return None

"""Deferred device->host readback for the pipelined serve loop.

The synchronous engine loop materializes every window's tokens immediately
after dispatch, so the device idles while the host runs ``_emit``, streaming
callbacks, drafting, and admission — and the host idles while the device
computes.  With ``ServingEngine(async_depth=1)`` the engine instead parks the
window's device-side outputs in a :class:`Readback` handle, dispatches the
NEXT window first, and only then materializes the previous window's tokens:
JAX's async dispatch queues the new window behind the old one, so the
blocking :func:`fetch` returns as soon as the *old* window finishes while the
new one keeps the device busy under the host's emit/scheduling work.

:func:`fetch` is the ONE sanctioned blocking device->host transfer in the
serving hot path — atpu-lint's ``blocking-readback`` rule lints every other
``jax.device_get`` / ``block_until_ready`` out of ``accelerate_tpu/serving``
so a stray eager readback cannot silently re-serialize the pipeline.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import numpy as np

__all__ = ["Readback", "fetch"]


def fetch(*arrays):
    """Materialize device arrays on the host (blocking).

    Blocks until the computation producing each array has finished; all
    outputs of one jitted window materialize together, so fetching a window's
    tokens also guarantees its KV writes have landed — the invariant the
    deferred page release in :meth:`Readback.settle` relies on.
    """
    out = tuple(np.asarray(jax.device_get(a)) for a in arrays)  # noqa: blocking-readback
    return out[0] if len(out) == 1 else out


@dataclasses.dataclass
class Readback:
    """One in-flight decode/verify window: the device handles to its outputs
    plus the dispatch-time host state needed to land them later.

    The handle is created at dispatch and drained at most one cycle later
    (depth-1 pipeline).  ``active``/``reqs``/``eos`` snapshot the lane state
    the window was dispatched under: between dispatch and drain the host may
    cancel a lane, preempt it, or install a new request into a slot the
    window still considers live, so ``_emit`` must mask by what the *device*
    saw, and retire-by-identity (``engine._slot_req[s] is reqs[s]``) rather
    than by slot number.
    """

    kind: str                      # "decode" | "verify"
    toks: Any                      # device [slots, width] token block
    width: int                     # decode window width / speculate_k + 1
    counts: Any = None             # device [slots] n_commit (verify only)
    qerr: Any = None               # device KV quantization round-trip error
    #: device ``int32 [3]`` counters of a routed-experts model (pairs chosen,
    #: pairs on experts held here, held experts hit): this window's own first,
    #: then those of the prefill chunks dispatched in its cycle — all fetched
    #: with ``toks`` in the window's one blocking fetch
    moe_counts: list = dataclasses.field(default_factory=list)
    active: Optional[np.ndarray] = None   # dispatch-time active mask (copy)
    reqs: Optional[list] = None           # dispatch-time _slot_req snapshot
    eos: Optional[np.ndarray] = None      # dispatch-time per-lane EOS ids
    n_occupied: int = 0
    drafted: Optional[np.ndarray] = None  # verify: lanes that proposed drafts
    n_drafted: int = 0
    dispatch_t: float = dataclasses.field(default_factory=time.perf_counter)
    #: physical KV page ids whose deref was deferred because this window may
    #: still write through the block table it was dispatched with; settled
    #: (dereffed) only after :func:`fetch` proves the window retired.
    deferred_pages: List[int] = dataclasses.field(default_factory=list)
    #: slots retired *predictively* after this window dispatched: their lane
    #: provably exhausts its length budget inside this window (no EOS
    #: configured, fixed decode width), so the engine freed the slot for
    #: re-admission one cycle early.  ``_emit`` lands these lanes' tokens
    #: even though the slot has a new owner — the pre-freed request is DONE
    #: at drain, not dropped.
    prefreed: set = dataclasses.field(default_factory=set)
    #: device handles this window (or a lane edit enqueued just before it)
    #: consumed: the previous cycle's donated pool/pending/rng and any lane
    #: vectors replaced by an install scatter.  Dropping the last Python
    #: reference to such a handle *blocks until the consuming computation
    #: finishes* — exactly the stall the pipeline exists to avoid — so the
    #: engine parks the old references here and lets them die with the
    #: handle, after :func:`fetch` proved the window retired.
    consumed: list = dataclasses.field(default_factory=list)
    #: device quant-error scalars from prefill chunks dispatched in this
    #: window's cycle (interleaved chunked prefill): fetching one eagerly
    #: would sync the pipeline right after the chunk enqueued, so the engine
    #: parks the handles here and folds them into the quant-error gauge at
    #: drain — by which point the chunks have long retired behind the window.
    prefill_qerrs: list = dataclasses.field(default_factory=list)
    #: pending prefix-cache spills riding this window: ``(node, handles)``
    #: pairs whose D2H gathers were enqueued before this window dispatched.
    #: Fetching a gather eagerly would sync the pipeline at eviction time, so
    #: the engine parks the handles here and lands them into the node's host
    #: payload at drain — behind the same blocking point everything else
    #: syncs at.
    spills: list = dataclasses.field(default_factory=list)
    #: spilled-prefix promotions dispatched behind this window (host -> device
    #: H2D install records): completion is acknowledged at drain, where the
    #: install has provably retired with the window it was enqueued behind.
    promotions: list = dataclasses.field(default_factory=list)

    def lane_live(self, slot: int) -> bool:
        """Was ``slot`` active when this window was dispatched?  A live lane's
        pages must not return to the allocator until the window retires."""
        return self.active is not None and bool(self.active[slot])

    def live_requests(self):
        """``(slot, request)`` pairs for lanes live at dispatch — the lanes
        this window owes tokens to (pre-freed lanes included: they were
        active when the window dispatched).  Drain-side per-request
        attribution (``engine._trace_drain``) iterates these against the
        dispatch-time snapshot, not the possibly-moved-on live state."""
        if self.active is None or self.reqs is None:
            return
        for s in np.nonzero(self.active)[0]:
            req = self.reqs[s]
            if req is not None:
                yield int(s), req

    def settle(self, allocator) -> int:
        """Deref every deferred page (call only after :func:`fetch` on this
        window's outputs — i.e. after its KV writes provably landed)."""
        if not self.deferred_pages:
            return 0
        freed = allocator.deref(self.deferred_pages)
        self.deferred_pages = []
        return freed

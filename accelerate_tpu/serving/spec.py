"""Host-side draft proposal for self-speculative decoding.

Decode is memory-bandwidth-bound: one model forward per emitted token per
lane reads the full weight set to produce a single row of logits, leaving the
MXU idle (`serve/decode_flops_per_token` vs the chip's HBM peak makes the gap
visible).  Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding") closes it by *verifying* K cheaply
drafted tokens in ONE batched forward: the verify pass computes the true
next-token distribution at every drafted position, and an accept/commit rule
keeps the output distribution exactly what non-speculative decode would have
produced — for greedy decode, token-for-token identical.

The drafter here is **prompt-lookup / n-gram matching** (the draft-model-free
scheme popularized by vLLM's ngram speculator): each lane's draft is the
continuation of the most recent earlier occurrence of its trailing n-gram in
its own context (prompt + generated tokens).  No second model, no extra
params, no device work — a numpy suffix match per lane per cycle.  It shines
on repetitive or structured output (code, JSON, extraction, long quotes of
the prompt) where the continuation literally already appears in the context,
and degrades to nothing on high-entropy text — which is why the engine falls
back to the plain decode window whenever no lane drafts.

Device-side verification lives in :func:`~.pool.make_paged_verify_window`; the
engine (:mod:`.engine`) wires the two together per cycle.

Drafting is the one serve-loop stage that is *inherently sequential* with
the previous window: a lane's draft extends its own freshest context, so the
pipelined loop (``ServingEngine(async_depth=1)``) drains the in-flight
window before calling :func:`propose_ngram_draft` — speculative cycles
overlap scheduling/admission with device compute, but not drafting or
``_emit``.  Keep the per-lane cost here strictly O(context) numpy with no
device interaction: this function runs on the host's critical path between
a drain and the next dispatch.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


def propose_ngram_draft(
    context: np.ndarray,
    k: int,
    max_ngram: int = 3,
    min_ngram: int = 1,
    pad: int = 0,
) -> Optional[np.ndarray]:
    """Draft ``k`` tokens by prompt-lookup: find the most recent earlier
    occurrence of the longest trailing n-gram of ``context`` and return the
    tokens that followed it.

    Tries n-gram sizes from ``max_ngram`` down to ``min_ngram`` (longer
    matches draft with higher acceptance).  The match must end strictly
    before the context's tail (the trailing n-gram itself never matches) and
    have at least one following token.

    A match at lag ``L`` from the tail implies the context is locally
    periodic with period ``L``, so the draft extends *cyclically*:
    ``draft[j] = context[start + (j % L)]``.  For matches deep in the
    context this is just the ``k`` literal follower tokens; for the common
    steady-state case — generation locked into a cycle shorter than ``k``,
    where the most recent match sits one period from the tail — it predicts
    whole future periods instead of running out of context (drafting past
    the end and padding would cap acceptance at the cycle length).

    Returns the ``[k]`` int32 draft, or ``None`` when no n-gram recurs —
    the caller falls back to ordinary decode for this lane.  ``pad`` is
    accepted for signature stability but never needed (cyclic extension
    always fills all ``k`` slots).
    """
    context = np.ascontiguousarray(context, dtype=np.int32)
    n_ctx = int(context.size)
    if k <= 0 or min_ngram < 1 or n_ctx < min_ngram + 1:
        return None
    for n in range(min(max_ngram, n_ctx - 1), min_ngram - 1, -1):
        tail = context[n_ctx - n:]
        # candidate windows start at 0..n_ctx-n-2: they end strictly before
        # the tail starts a new copy AND leave >= 1 token to draft from
        windows = np.lib.stride_tricks.sliding_window_view(context[: n_ctx - 1], n)
        hits = np.nonzero((windows == tail).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1]) + n          # most recent match wins
            lag = n_ctx - start                # local period implied by the match
            return context[start + (np.arange(k) % lag)]
    return None


class NgramIndex:
    """Incremental per-lane suffix index: :func:`propose_ngram_draft` without
    the per-cycle O(context) rescan.

    The brute-force matcher re-walks the whole context every verify cycle to
    find the most recent earlier occurrence of the trailing n-gram.  This
    index instead keeps, for every n-gram size, a dict mapping each window
    (as a token tuple) to the *latest* start position where it occurs —
    maintained by :meth:`append` in O(max_ngram) per committed token, so
    steady-state drafting is O(k) per cycle regardless of context length.

    Equivalence with the rescan: the brute force takes ``hits[-1]`` (the
    largest matching start over windows of ``context[:n_ctx - 1]``), and the
    dict records each start exactly once in increasing order, so its value
    IS the largest start seen.  :meth:`append` records the window *ending
    just before* the new token, which keeps the trailing n-gram itself out of
    the index until a later token makes it an "earlier" occurrence — the
    same strict-before-the-tail rule the sliding-window scan enforces.
    Token-identical by construction; ``TestNgramDraft`` pins both paths to
    the same goldens.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1) -> None:
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got [{min_ngram}, {max_ngram}]"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self._ctx: list = []
        self._idx: Dict[int, Dict[Tuple[int, ...], int]] = {
            n: {} for n in range(min_ngram, max_ngram + 1)
        }

    def __len__(self) -> int:
        return len(self._ctx)

    def append(self, token: int) -> None:
        """Commit one token: index every window that *ends* at the old tail
        (the new token is its follower), then grow the context."""
        ctx, L = self._ctx, len(self._ctx)
        for n in range(self.min_ngram, min(self.max_ngram, L) + 1):
            self._idx[n][tuple(ctx[L - n:])] = L - n
        ctx.append(int(token))

    def extend(self, tokens) -> None:
        for t in np.asarray(tokens, dtype=np.int32).ravel():
            self.append(int(t))

    def propose(self, k: int) -> Optional[np.ndarray]:
        """O(k) draft: longest trailing n-gram whose latest earlier start is
        on record, extended cyclically exactly like the rescan path."""
        ctx, n_ctx = self._ctx, len(self._ctx)
        if k <= 0 or n_ctx < self.min_ngram + 1:
            return None
        for n in range(min(self.max_ngram, n_ctx - 1), self.min_ngram - 1, -1):
            s = self._idx[n].get(tuple(ctx[n_ctx - n:]))
            if s is not None:
                start = s + n
                lag = n_ctx - start
                return np.asarray(
                    [ctx[start + (j % lag)] for j in range(k)], dtype=np.int32
                )
        return None

"""The fixed-shape compiled executables behind the serving engine.

Iteration-level scheduling (Orca) and block-structured KV management (vLLM)
win their 2-10x serving throughput by decoupling request lifetimes from the
batch program: a request that finishes frees its KV capacity *immediately* and
a queued request takes its place without restarting anyone else.  The TPU-first
translation keeps everything inside a handful of fixed-shape executables — no
per-request retracing — over ONE KV store, the shared page pool
``[L, num_pages, Hkv, page, Dh]`` of :mod:`.paging`, reached through per-lane
block tables:

* **decode window** (:func:`make_paged_decode_window`) — ONE jitted
  executable: ``lax.scan`` over ``window`` masked decode steps
  (:func:`_decode_scan`).  Per-request sampling knobs (eos / temperature /
  top-k / top-p) enter as traced *vectors*, so a new request never forces a
  retrace.  Inactive or EOS-done lanes are frozen: their index stops advancing
  and their emissions are masked to the pad token.  Greedy lanes take the same
  argmax ``generate`` takes — token-exact.
* **prefill chunks** (:func:`make_paged_prefill_chunk`) — one executable per
  chunk *bucket* (e.g. 128/512).  A prompt prefills into its own lane's pages
  in fixed-size chunks; only the final chunk is padded, and padded positions
  are never attended (the causal mask is the valid-entry mask).  A
  prefix-cache hit (:mod:`.prefix_cache`) aliases the cached pages into the
  lane's block table on the host — no executable runs.
* **verify window** (:func:`make_paged_verify_window`) — one executable per
  configured ``speculate_k``: a single forward over ``[slots, K+1]`` drafted
  positions (pending token + K host-drafted tokens, :mod:`.spec`), the
  token-exact acceptance prefix per lane, and an index rollback past the
  first rejected draft (:func:`_verify_body`).  Lands a variable 1..K+1
  tokens per lane per call while preserving exactly the tokens sequential
  decode would emit.  Model-based tree speculation (``draft_model=``) swaps
  it for exactly two: the tree verify window
  (:func:`make_paged_tree_verify_window` — the ``[slots, tree_nodes]`` bucket
  is static per engine, never call-varying) and one draft forward
  (:func:`~accelerate_tpu.serving.spec_exec.make_draft_forward`).
* **lane install** (:func:`make_lane_install`) and **copy page**
  (:func:`make_copy_page`, copy-on-write of a shared tail page) — one
  executable each; the host prefix tier adds a spill/promote pair per bucket
  (:func:`make_spill_extract`, :func:`make_promote_install`).

Each window and chunk has two arms.  The gathered arm (``direct=False``)
gathers every lane's pages into a contiguous ``max_len``-wide view once a
call, runs the model on it as a plain
:class:`~accelerate_tpu.models.transformer.KVCache` — the attention program
``generate`` runs — then stores the pages the call wrote into back whole
(:func:`_store_span_pages`; a prefill chunk's span is page-aligned and is its
pages).  The view has the layout of the ``KVCache`` that the model's attention
kind reads (:func:`_flat_view`, from the configuration).  For the per-head
block it is ``[L, N, Hkv * Dh, max_len]``, rows flat and positions minor: the
chip tiles an array's two minor dimensions, so ``(Hkv, Dh)`` minor padded
GPT-2-XL's 25 heads to 32 sublanes and its 64 values to 128 lanes, 2.56 x the
bytes in every pass and every decode step, while ``Hkv * Dh`` by ``max_len``
pads nothing and is the pool's own order on the chip (``[.., Hkv, Dh, page]``,
``page`` minor): a page IS a block of the view's columns, put in and cut out
by one ``dynamic_update_slice`` / ``dynamic_slice`` each, with no pass over the
view between the pool and the model.  Latent rows (one of 512 values a
position) keep the position-major view ``[L, N, max_len, 1, width]``, which
tiles exactly; it goes through the compiler's gather and one layout pass.
What the view costs is once a CALL: the gather, a view-sized temporary for K
and for V, and a write-back of two pages a lane, stored in the pool's own
layout.  Rows are never stored singly: with
``page`` minor a row is a strided write, and the compiler would copy the whole
pool into a row-minor layout and back to serve it.  Inside the call the model
writes the view in place — each layer its new rows, each scan step of a decode
window 2 x L small updates of the carried view; nothing of the view's size
is copied per step.  The in-place arm (``direct=True``) hands the model the
pool itself (:class:`~accelerate_tpu.models.transformer.PagedKVCache`): no
view, the same in-place write through the block tables, pages read where they
lie.

A model whose cache is a recurrent state a lane (``config.retention``,
:mod:`~accelerate_tpu.models.retention`) has neither arm: nothing to gather,
nothing to write back.  Its three programs (:func:`make_state_decode_window`,
:func:`make_state_prefill_chunk`, :func:`make_state_install`) hand the model the
donated state of :class:`~accelerate_tpu.serving.paging.StatePool` as a
:class:`~accelerate_tpu.models.retention.StateCache`; the decode window is the
same :func:`_decode_scan`, which rewrites every lane's state in place at every
step and leaves a frozen lane's as it was.

A stack of two kinds of attention layer (``config.layer_types``) has the
gathered arm twice over (:func:`make_mixed_decode_window`,
:func:`make_mixed_prefill_chunk`): the pool keeps page arrays and block tables
a kind (:class:`~accelerate_tpu.serving.paging.MixedKVPool`), the full layers'
view is ``max_len`` wide as above, the window layers' view is the lane's ring
of pages, position ``p`` in column ``p % width``
(:class:`~accelerate_tpu.models.transformer.MixedKVCache`), and the pages a
call wrote into go back whole into both pools.

Compiled-shape budget for an engine instance: ``1 (decode window) +
len(prefill_buckets) + 1 (lane install) + 1 (copy page)``, plus ``1`` verify
executable when ``speculate_k > 0`` (or the tree pair) — asserted by the
serving tests via the jit cache counters.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import NamedSharding, PartitionSpec

from ..models.generation import sample_tokens_batched
from ..models.retention import StateCache
from ..models.transformer import KVCache, MixedKVCache, PagedKVCache, Transformer
from ..ops.view_attention import xla_form
from ..ops.view_gather import gather_pages, view_gather_applies
from ..parallel.mesh import mesh_axis_size
from ..utils.jax_compat import jit_cache_size
from .paging import NULL_PAGE


class ServeShardings:
    """The engine's placement vocabulary under a tensor-parallel mesh.

    Every serving executable moves arrays from exactly three families: page
    pools and page chunks ``[L, NP, Hkv, page, D]`` (``pages``: kv-head axis
    at dim 2), per-page quantization scales ``[L, NP, Hkv]`` (head axis
    last), and host-side control state (tokens, tables, indices,
    sampling knobs — replicated).  Params carry the :data:`~accelerate_tpu.parallel
    .tensor_parallel.DEFAULT_TP_RULES` placement computed by the engine.

    Factories take ``shardings=None`` (single-chip, plain ``jax.jit``) or an
    instance of this class, in which case every executable compiles with
    explicit in/out shardings — donated KV buffers alias in place per shard,
    and atpu-lint's ``sharding-annotations`` rule pins the discipline.
    """

    def __init__(self, mesh, params, tp_axis: str = "tp"):
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp_degree = mesh_axis_size(mesh, tp_axis)
        ax = tp_axis if self.tp_degree > 1 else None
        self.replicated = NamedSharding(mesh, PartitionSpec())
        self.pages = NamedSharding(mesh, PartitionSpec(None, None, ax, None, None))
        self.scales = NamedSharding(mesh, PartitionSpec(None, None, ax))
        self.params = params

    def rep(self, n: int) -> tuple:
        """``n`` replicated placements — the control-state tail of a signature."""
        return (self.replicated,) * n


def _serve_jit(fn, *, donate_argnums=(), in_shardings=None, out_shardings=None):
    """``jax.jit`` with optional explicit shardings.  ``None`` shardings mean
    single-chip: compile without placement constraints (committed inputs keep
    their devices, exactly the pre-mesh behavior)."""
    if in_shardings is None and out_shardings is None:
        return jax.jit(fn, donate_argnums=donate_argnums)  # noqa: sharding-annotations (single-chip)
    return jax.jit(
        fn,
        donate_argnums=donate_argnums,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
    )


def audit_donation(*trees) -> None:
    """Assert no leaf of ``trees`` has already been donated (its buffer
    deleted by a prior dispatch).  The engine calls this on the KV state it
    is about to donate into a window: under the pipelined loop
    (``async_depth=1``) every window's outputs rebind the page arrays *at
    dispatch*, so the next dispatch always donates the fresh
    handles — this audit turns any future violation of that invariant (a
    double donation, which XLA reports as a use-after-free much later and
    far from the cause) into an immediate, attributable error.  Host-only
    and O(leaves): no device sync."""
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            deleted = getattr(leaf, "is_deleted", None)
            if deleted is not None and deleted():
                raise RuntimeError(
                    "KV buffer was already donated to an earlier dispatch "
                    "(use-after-donation): a window's outputs must be rebound "
                    "before the next window dispatches"
                )


def _routed(model: Transformer) -> bool:
    """Does the model have routed experts (``config.experts``)?  Their decode
    windows and prefill chunks return the ``moe_*`` counters."""
    return getattr(model.config, "experts", None) is not None


def _stateful(model: Transformer) -> bool:
    """Does the model keep a recurrent state for a cache (``config.retention``)?
    Its decode window returns the ``state_*`` counters."""
    return getattr(model.config, "retention", None) is not None


def _two_kinds(model: Transformer) -> bool:
    """Is the stack of two kinds of layer (``config.layer_types``)?  Its pool
    keeps a ring of pages for the window layers and whole tables for the full
    ones, and its decode window returns the ``kv_rows_*`` counters."""
    return getattr(model.config, "layer_types", None) is not None


def _rows_live(config, index, live):
    """``int32 [2]``: the keys the decode step of every live lane can see
    (query at ``index [N]``, itself included), summed over the layers, and
    those of them in window layers: ``min(index + 1, window)`` in each window
    layer, ``index + 1`` in each full one."""
    n_window, n_full = (config.layer_types.count(kind) for kind in ("window", "full"))
    seen = jnp.where(live, index + 1, 0)
    in_window = n_window * jnp.sum(jnp.minimum(seen, config.sliding_window))
    return jnp.stack([in_window + n_full * jnp.sum(seen), in_window]).astype(jnp.int32)


def _forward(model: Transformer, params, tokens, cache, live):
    """``model.apply`` on a cache: ``(logits, cache, counts)``.  For a model
    with routed experts ``counts`` is ``int32 [3]``, computed here on the
    device from what each expert layer sows (``routed_here``) over the rows
    where ``live [B, S]`` holds (a frozen lane's and a chunk's padding rows run
    through the static shapes but are no work): token-expert pairs chosen,
    those that fell on experts held here, and held experts that got at least
    one row, summed over the layers.  ``None`` for every other model, whose
    program is what it was.  For a retention model ``counts`` is ``int32
    [2]``: the lane-steps whose state this call read and rewrote (every lane,
    live or frozen: the shapes are static) and those that were live."""
    if not _routed(model):
        logits, cache = model.apply({"params": params}, tokens, cache=cache)
        counts = None
        if _stateful(model):
            counts = jnp.stack([jnp.int32(live.shape[0]), jnp.sum(live[:, 0])]).astype(jnp.int32)
        return logits, cache, counts
    (logits, cache), sown = model.apply(
        {"params": params}, tokens, cache=cache, mutable=["intermediates"]
    )
    spec = model.config.experts
    total = here = hit = jnp.int32(0)
    for path, local in jax.tree_util.tree_leaves_with_path(sown["intermediates"]):
        if not any(getattr(k, "key", None) == "routed_here" for k in path):
            continue
        held = (local >= 0) & live[..., None]                      # [B, S, k]
        total += jnp.sum(live) * spec.top_k
        here += jnp.sum(held)
        got = jnp.zeros((spec.num_held + 1,), bool).at[
            jnp.where(held, local, spec.num_held)].set(True)
        hit += jnp.sum(got[:-1])
    return logits, cache, jnp.stack([total, here, hit]).astype(jnp.int32)


def _decode_scan(model: Transformer, window: int, params, cache, tokens, active,
                 eos, do_sample, temperature, top_k, top_p, pad, rngs):
    """The masked decode scan shared by the gathered and in-place decode
    windows — one traced program, so the two arms cannot drift apart.
    Returns ``(cache, out_tokens [N, window], pending, rngs, counts)``;
    ``pending`` is the scan's final carry token per lane — the token the next
    window will feed — returned device-side so the engine's lane-state mirrors
    never round-trip through the host between windows; ``counts`` is the
    window's ``moe_*`` counters (:func:`_forward`) or ``None``.

    Semantics per scan step (matching ``generate``'s loop body lane-by-lane):
    the pending token is fed at each lane's own position, its KV is written
    there, the next token is sampled per-lane, and lanes that are inactive or
    have emitted their EOS freeze — index stops advancing and outputs are
    masked to ``pad``.  Frozen lanes still execute (static shapes) but only
    ever overwrite their own dead rows (gathered view) or the null page
    (in-place), so running lanes are untouched."""
    mixed = _two_kinds(model)
    counted = _routed(model) or _stateful(model) or mixed

    def step(carry, _):
        cache, tok, done, rngs, counts = carry
        prev_index = cache.index
        if isinstance(cache, StateCache):
            # a frozen lane's row runs through the static shapes and must leave
            # its state as it is: the lane may be mid-prefill
            cache = cache.replace(live=(~done).astype(jnp.int32))
        if isinstance(cache, PagedKVCache):
            # direct paged cache: route frozen lanes' writes to the null page
            # per step.  In the gathered view a frozen lane harmlessly
            # overwrites its own dead rows, but a quantized page
            # write REQUANTIZES the whole touched page — pad-token garbage
            # must not keep churning a page that still holds real history.
            cache = cache.replace(active=~done)
        logits, cache, seen = _forward(model, params, tok[:, None], cache, ~done[:, None])
        if mixed:
            # a stack of two kinds: the rows its live lanes' step could see,
            # after the experts' counters where it has those too
            rows = _rows_live(model.config, prev_index, ~done)
            seen = rows if seen is None else jnp.concatenate([seen, rows])
        if counted:
            counts = counts + seen
        # model.apply advanced every lane; frozen lanes roll back
        cache = cache.replace(
            index=jnp.where(done, prev_index, prev_index + 1)
        )
        split = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
        nxt = sample_tokens_batched(
            logits[:, -1], split[:, 0],
            do_sample=do_sample, temperature=temperature,
            top_k=top_k, top_p=top_p,
        )
        nxt = jnp.where(done, pad, nxt)
        done = done | ((eos >= 0) & (nxt == eos))
        return (cache, nxt, done, split[:, 1], counts), nxt

    done0 = ~active
    width = 2 if _stateful(model) else 3 * _routed(model) + 2 * mixed
    counts0 = jnp.zeros((width,), jnp.int32) if counted else None
    (cache, tok, _, rngs, counts), toks = jax.lax.scan(
        step, (cache, tokens, done0, rngs, counts0), None, length=window
    )
    return cache, toks.T, tok, rngs, counts


def _with_counts(*outputs):
    """A program's outputs with a trailing ``None`` (no ``moe_*`` counters)
    dropped: models without routed experts keep their signatures."""
    return outputs if outputs[-1] is not None else outputs[:-1]


def _unrouted(model: Transformer, shardings):
    """``shardings`` for a program whose output tuple is fixed: a model with
    routed experts appends its counters, and has no placement rules yet."""
    if shardings is not None and _routed(model):
        raise ValueError("routed experts are served on one device: no mesh placement rules yet")
    return shardings


def _verify_body(model: Transformer, k: int, params, cache, tokens, active, eos,
                 do_sample, temperature, top_k, top_p, pad, rngs):
    """Forward + accept/commit of one speculative verify pass — K+1 positions
    per lane, one forward — shared by the gathered and in-place verify
    windows (one traced program, no numeric drift).

    ``tokens[:, 0]`` is each lane's pending token, ``tokens[:, 1:]`` its K
    host-drafted tokens (:mod:`.spec`).  The single forward writes KV for all
    K+1 positions at each lane's own index and yields the true next-token
    logits at every position; logits at position ``i`` are trustworthy iff
    drafts ``1..i`` were all correct — exactly the prefix the acceptance rule
    commits, so speculation never changes what gets emitted:

    * **greedy lanes** — the committed token at each position is the argmax,
      bitwise the same decision the decode window takes; a draft is accepted
      while it equals that argmax (longest exact match).  Token-exact by
      construction.
    * **sampled lanes** — the Leviathan accept/resample rule specialized to a
      deterministic (point-mass) drafter: draft ``d`` at position ``i`` is
      accepted with probability ``p_i(d)`` under the *filtered* per-lane
      distribution (same temperature/top-k/top-p pipeline as
      :func:`~accelerate_tpu.models.generation.sample_tokens_batched`); on
      rejection the committed token is resampled from ``p_i`` with ``d``
      removed (the renormalized residual ``max(p - q, 0)``), which preserves
      the output distribution exactly.  One bonus token is sampled at the
      final position when every draft is accepted.

    Committed tokens stop at the first emitted EOS; positions past the commit
    point emit ``pad``.  The cache index rolls back to
    ``prev_index + n_commit`` — KV for the pending token and accepted drafts
    stays (it was computed from correct inputs), KV past the first rejection
    is unreachable and gets overwritten by subsequent decode.  Frozen lanes
    (``~active``) commit nothing and keep their index.  Returns ``(cache,
    out [N, K+1], n_commit [N], new_pending [N], new_rngs)``."""
    from ..models.generation import filter_logits_batched

    kp1 = k + 1
    n = tokens.shape[0]
    prev_index = cache.index
    logits, cache = model.apply({"params": params}, tokens, cache=cache)
    logits = logits.astype(jnp.float32)                  # [N, K+1, V]
    vocab = logits.shape[-1]
    drafts = tokens[:, 1:]                               # [N, K]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    use_sample = do_sample & (temperature > 0.0)
    split = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
    draw_rngs, new_rngs = split[:, 0], split[:, 1]

    def _greedy(_):
        return greedy, greedy[:, :k] == drafts

    def _sampled(_):
        rep = lambda x: jnp.repeat(x, kp1, axis=0)
        filt = filter_logits_batched(
            logits.reshape(n * kp1, vocab),
            temperature=rep(temperature), top_k=rep(top_k), top_p=rep(top_p),
        ).reshape(n, kp1, vocab)
        probs = jax.nn.softmax(filt, axis=-1)
        # per lane: K accept draws + K residual resamples + 1 bonus draw
        keys = jax.vmap(lambda r: jax.random.split(r, 2 * k + 1))(draw_rngs)
        u = jax.vmap(lambda ks: jax.vmap(jax.random.uniform)(ks))(keys[:, :k])
        p_draft = jnp.take_along_axis(
            probs[:, :k], drafts[..., None], axis=-1
        )[..., 0]
        accepted = u < p_draft                           # [N, K]
        neg_inf = jnp.finfo(jnp.float32).min
        residual = jnp.where(                            # p with the draft removed
            jax.nn.one_hot(drafts, vocab, dtype=bool), neg_inf, filt[:, :k]
        )
        res = jax.vmap(jax.vmap(jax.random.categorical))(
            keys[:, k:2 * k], residual
        ).astype(jnp.int32)
        bonus = jax.vmap(jax.random.categorical)(
            keys[:, 2 * k], filt[:, k]
        ).astype(jnp.int32)
        emit = jnp.concatenate(
            [jnp.where(accepted, drafts, res), bonus[:, None]], axis=1
        )
        emit = jnp.where(use_sample[:, None], emit, greedy)
        acc = jnp.where(use_sample[:, None], accepted, greedy[:, :k] == drafts)
        return emit, acc

    # all-greedy pools (the common serving mix) skip the full-vocab
    # filtering/sampling machinery at runtime, mirroring sample_tokens_batched
    emit, acc = jax.lax.cond(jnp.any(use_sample), _sampled, _greedy, None)
    n_accept = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
    pos = jnp.arange(kp1)[None, :]
    committable = pos <= n_accept[:, None]
    is_eos = (emit == eos[:, None]) & (eos >= 0)[:, None]
    eos_before = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos) > 0
    commit = committable & ~eos_before & active[:, None]
    n_commit = commit.sum(axis=1).astype(jnp.int32)
    out = jnp.where(commit, emit, pad[:, None])
    # model.apply advanced every lane by K+1; roll back past rejections
    # (and fully, for frozen lanes — their garbage writes are unreachable)
    cache = cache.replace(index=prev_index + n_commit)
    last = jnp.maximum(n_commit - 1, 0)
    new_pending = jnp.take_along_axis(out, last[:, None], axis=1)[:, 0]
    return cache, out, n_commit, new_pending, new_rngs


def _tree_verify_body(model: Transformer, tree, params, cache, tokens, active,
                      eos, do_sample, temperature, top_k, top_p, pad, rngs):
    """Forward + branch-select/commit of one *tree* speculative verify pass —
    ``S = tree.nodes`` drafted tree positions per lane, one forward: the
    generalization of :func:`_verify_body` from a linear ``[slots, K+1]``
    window to a token tree ``[slots, S]``.  Shared by the gathered and
    in-place tree windows (one traced accept program, no numeric drift).
    Returns ``(cache, out [N, D+1], n_commit [N], new_pending [N],
    new_rngs)``.

    ``tree`` is a :class:`~accelerate_tpu.serving.spec_exec.TreeSpec`:
    ``tokens[:, 0]`` is each lane's pending token (tree root), node ``i``'s
    draft token at ``tokens[:, i]`` extends its parent's branch
    (:meth:`TreeSpec` chains topology — ``width`` sibling branches of
    ``depth`` model-drafted tokens).  The single forward writes all ``S``
    nodes' KV contiguously at each lane's frontier, attends under the
    ancestor mask (``tree_mask`` through the model), and the acceptance rule
    selects ONE root-to-leaf path to commit:

    * **greedy lanes** — the branch with the longest exact prefix match
      against the model's argmax chain wins (ties: lowest branch id); the
      committed tokens are the argmaxes along that path, bitwise the tokens
      sequential greedy decode would emit.
    * **sampled lanes** — multi-try speculative sampling at the branch point
      (each sibling candidate is tried against the running residual
      distribution — exact for the point-mass drafts a draft model emits),
      then the linear Leviathan accept/residual-resample down the chosen
      branch; one bonus token at the deepest path node.  Output distribution
      preserved exactly.

    After acceptance the winning path's KV rows are *compacted* to the lane
    frontier (losing branches' rows are overwritten or left dead past the
    rolled-back index) and the index advances by ``n_commit`` — so the cache
    layout a subsequent window sees is byte-for-byte what linear decode would
    have produced."""
    from ..models.generation import filter_logits_batched

    w, depth = tree.width, tree.depth
    s_nodes = tree.nodes
    dp1 = depth + 1
    n = tokens.shape[0]
    prev_index = cache.index
    paths_j = jnp.asarray(tree.paths, jnp.int32)         # [W, D+1]
    # node i sits at sequence position frontier + depth(i); positions must be
    # explicit — consecutive-slot defaults would misplace sibling branches
    positions = prev_index[:, None] + jnp.asarray(tree.depth_arr, jnp.int32)[None, :]
    logits, cache = model.apply(
        {"params": params}, tokens, positions=positions, cache=cache,
        tree_mask=tree.anc,
    )
    logits = logits.astype(jnp.float32)                  # [N, S, V]
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # ok[i]: node i's draft token equals the model's argmax at its parent —
    # the tree analog of ``greedy[:, :k] == drafts``
    ok = tokens == jnp.take(greedy, jnp.asarray(tree.parent, jnp.int32), axis=1)
    chain = jnp.asarray(tree.paths[:, 1:].reshape(-1), jnp.int32)   # [W*D]
    ok_chain = ok[:, chain].reshape(n, w, depth)
    acc_len = jnp.cumprod(ok_chain.astype(jnp.int32), axis=2).sum(axis=2)
    best_greedy = jnp.argmax(acc_len, axis=1).astype(jnp.int32)     # [N]
    use_sample = do_sample & (temperature > 0.0)
    split = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
    draw_rngs, new_rngs = split[:, 0], split[:, 1]

    def _path_emit(best):
        path = jnp.take(paths_j, best, axis=0)                      # [N, D+1]
        emit = jnp.take_along_axis(greedy, path, axis=1)            # [N, D+1]
        acc = jnp.take_along_axis(ok, path[:, 1:], axis=1)          # [N, D]
        return path, emit, acc

    def _greedy(_):
        _, emit, acc = _path_emit(best_greedy)
        return emit, acc, best_greedy

    def _sampled(_):
        rep = lambda x: jnp.repeat(x, s_nodes, axis=0)
        filt = filter_logits_batched(
            logits.reshape(n * s_nodes, vocab),
            temperature=rep(temperature), top_k=rep(top_k), top_p=rep(top_p),
        ).reshape(n, s_nodes, vocab)
        neg_inf = jnp.finfo(jnp.float32).min
        # per lane: W branch tries + 1 branch fallback + (D-1) * (accept draw
        # + residual resample) + 1 bonus draw = W + 2D keys
        keys = jax.vmap(lambda r: jax.random.split(r, w + 2 * depth))(draw_rngs)

        # --- branch point: multi-try speculative sampling over the W sibling
        # candidates.  Trying candidate b against the running residual (all
        # previously tried tokens masked out) and falling through to a final
        # residual sample reproduces the root distribution exactly — the
        # multi-candidate generalization of the Leviathan point-mass rule.
        rem = filt[:, 0]                                 # [N, V]
        acc1 = jnp.zeros(n, bool)
        pick = jnp.zeros(n, jnp.int32)
        tok1 = jnp.zeros(n, jnp.int32)
        for b in range(w):
            d_b = tokens[:, int(tree.paths[b, 1])]
            p_b = jnp.take_along_axis(
                jax.nn.softmax(rem, axis=-1), d_b[:, None], axis=1
            )[:, 0]
            u_b = jax.vmap(jax.random.uniform)(keys[:, b])
            take = (~acc1) & (u_b < p_b)
            pick = jnp.where(take, b, pick)
            tok1 = jnp.where(take, d_b, tok1)
            acc1 = acc1 | take
            rem = jnp.where(jax.nn.one_hot(d_b, vocab, dtype=bool), neg_inf, rem)
        res1 = jax.vmap(jax.random.categorical)(keys[:, w], rem).astype(jnp.int32)
        tok1 = jnp.where(acc1, tok1, res1)
        path_s = jnp.take(paths_j, pick, axis=0)         # [N, D+1]

        # --- down the chosen branch: the linear point-mass accept/resample
        emit_cols = [tok1]
        acc_cols = [acc1]
        for t in range(1, depth):
            node_t = path_s[:, t]
            filt_t = jnp.take_along_axis(
                filt, node_t[:, None, None], axis=1
            )[:, 0]                                      # [N, V]
            d_t = jnp.take_along_axis(
                tokens, path_s[:, t + 1][:, None], axis=1
            )[:, 0]
            p_t = jnp.take_along_axis(
                jax.nn.softmax(filt_t, axis=-1), d_t[:, None], axis=1
            )[:, 0]
            u_t = jax.vmap(jax.random.uniform)(keys[:, w + 2 * t - 1])
            acc_t = u_t < p_t
            resid = jnp.where(jax.nn.one_hot(d_t, vocab, dtype=bool), neg_inf, filt_t)
            res_t = jax.vmap(jax.random.categorical)(
                keys[:, w + 2 * t], resid
            ).astype(jnp.int32)
            emit_cols.append(jnp.where(acc_t, d_t, res_t))
            acc_cols.append(acc_t)
        filt_deep = jnp.take_along_axis(
            filt, path_s[:, depth][:, None, None], axis=1
        )[:, 0]
        bonus = jax.vmap(jax.random.categorical)(
            keys[:, w + 2 * depth - 1], filt_deep
        ).astype(jnp.int32)
        emit_cols.append(bonus)
        emit_s = jnp.stack(emit_cols, axis=1)            # [N, D+1]
        acc_s = jnp.stack(acc_cols, axis=1)              # [N, D]

        _, emit_g, acc_g = _path_emit(best_greedy)
        emit = jnp.where(use_sample[:, None], emit_s, emit_g)
        acc = jnp.where(use_sample[:, None], acc_s, acc_g)
        best = jnp.where(use_sample, pick, best_greedy)
        return emit, acc, best

    emit, acc, best = jax.lax.cond(jnp.any(use_sample), _sampled, _greedy, None)
    path = jnp.take(paths_j, best, axis=0)               # [N, D+1]
    n_accept = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
    pos = jnp.arange(dp1)[None, :]
    committable = pos <= n_accept[:, None]
    is_eos = (emit == eos[:, None]) & (eos >= 0)[:, None]
    eos_before = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos) > 0
    commit = committable & ~eos_before & active[:, None]
    n_commit = commit.sum(axis=1).astype(jnp.int32)
    out = jnp.where(commit, emit, pad[:, None])
    # commit the winning path's KV to the lane frontier, roll back the rest:
    # the layout any later window sees is what linear decode would have built
    if isinstance(cache, PagedKVCache):
        cache = _tree_commit_paged(cache, prev_index, path)
        cache = cache.replace(index=prev_index + n_commit)
    else:
        def _compact(kv):
            def lane(kv_lane, idx, p):                       # [L, H*Dh, M]
                cols = jnp.take(kv_lane, idx + p, axis=2)    # [L, H*Dh, D+1]
                return jax.lax.dynamic_update_slice(kv_lane, cols, (0, 0, idx))

            return jax.vmap(lane, in_axes=(1, 0, 0), out_axes=1)(
                kv, prev_index, path
            )

        cache = cache.replace(
            k=_compact(cache.k), v=_compact(cache.v),
            index=prev_index + n_commit,
        )
    last = jnp.maximum(n_commit - 1, 0)
    new_pending = jnp.take_along_axis(out, last[:, None], axis=1)[:, 0]
    return cache, out, n_commit, new_pending, new_rngs


def make_lane_install(shardings: Optional[ServeShardings] = None):
    """Jitted one-slot edit of the device-resident lane vectors.

    ``(pending [N], active [N], eos [N], do_sample [N], temperature [N],
    top_k [N], top_p [N], rngs [N,2], slot, tok, eos_v, do_sample_v,
    temperature_v, top_k_v, top_p_v, rng [2]) -> (the eight vectors,
    updated at ``slot``)``

    Admission under the pipelined loop must not read lane state back from
    the device: the pending/rng vectors are carried on device between
    windows, so a host round-trip blocks on the in-flight window and turns
    every install into a depth-1 pipeline sync.  This scatter instead
    *enqueues* the edit — it consumes the in-flight window's output handles
    and therefore runs right after that window retires, off the host's
    critical path.  Inputs are not donated: the vectors are a few hundred
    bytes and the in-flight window may still hold them as operands.
    """

    def lane_install(pending, active, eos, do_sample, temperature, top_k,
                     top_p, rngs, slot, tok, eos_v, do_sample_v,
                     temperature_v, top_k_v, top_p_v, rng):
        return (
            pending.at[slot].set(tok),
            active.at[slot].set(True),
            eos.at[slot].set(eos_v),
            do_sample.at[slot].set(do_sample_v),
            temperature.at[slot].set(temperature_v),
            top_k.at[slot].set(top_k_v),
            top_p.at[slot].set(top_p_v),
            rngs.at[slot].set(rng),
        )

    s = shardings
    return _serve_jit(
        lane_install,
        in_shardings=None if s is None else s.rep(16),
        out_shardings=None if s is None else s.rep(8),
    )


def make_state_install(shardings: Optional[ServeShardings] = None):
    """``(s, z, slot) -> (s, z)`` with lane ``slot``'s state zeroed in every
    layer, in place (the pool is donated).  The engine runs it when a slot is
    taken for a request, before the first prefill chunk: a page pool never had
    to clear a lane, because a fresh lane's rows were masked by its index; a
    state is read whole."""

    def state_install(s, z, slot):
        zero = lambda a: jax.lax.dynamic_update_slice_in_dim(
            a, jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype), slot, axis=1)
        return zero(s), zero(z)

    sh = shardings
    return _serve_jit(
        state_install, donate_argnums=(0, 1),
        in_shardings=None if sh is None else sh.rep(3),
        out_shardings=None if sh is None else sh.rep(2),
    )


def make_state_prefill_chunk(model: Transformer,
                             shardings: Optional[ServeShardings] = None):
    """Prefill chunk of a retention model: ``(params, tokens [1, chunk_len], s,
    z, slot, base, valid) -> (s, z)``.  Lane ``slot``'s state is cut out of the
    donated pool, the chunked form carries it over the chunk's first ``valid``
    rows at positions ``base ..`` (the padding of a prompt's last chunk must
    not enter a state, where a page pool let it write rows nobody reads), and
    it is put back.  Logits are discarded, as by the paged chunk.  The engine
    holds back a prompt's last token (``valid`` counts up to it): the decode
    window feeds it as the lane's pending token, and a state, unlike a row
    written twice, would count it twice.  One builder call a bucket: the engine
    holds each bucket's program to one compiled shape."""

    def state_prefill_chunk(params, tokens, s, z, slot, base, valid):
        lane = lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1)
        cache = StateCache(s=lane(s), z=lane(z), index=base, live=valid.reshape(1))
        _, cache = model.apply({"params": params}, tokens, cache=cache)
        put = lambda a, new: jax.lax.dynamic_update_slice_in_dim(a, new, slot, axis=1)
        return put(s, cache.s), put(z, cache.z)

    sh = shardings
    return _serve_jit(
        state_prefill_chunk, donate_argnums=(2, 3),
        in_shardings=None if sh is None else (sh.params, *sh.rep(6)),
        out_shardings=None if sh is None else sh.rep(2),
    )


def make_state_decode_window(model: Transformer, window: int,
                             shardings: Optional[ServeShardings] = None):
    """Decode window of a retention model: ``(params, s, z, index [N], tokens,
    active, eos, do_sample, temperature, top_k, top_p, pad, rngs) -> (s, z,
    out_tokens [N, window], new_pending, new_rngs, counts [2])``.  Nothing is
    gathered and nothing written back: the shared :func:`_decode_scan` carries
    the donated state, every step rewrites it in place, and it comes out as the
    pool.  ``counts`` are the window's ``state_*`` counters
    (:func:`_forward`)."""

    def state_decode_window(params, s, z, index, tokens, active, eos, do_sample,
                            temperature, top_k, top_p, pad, rngs):
        cache = StateCache(s=s, z=z, index=index, live=active.astype(jnp.int32))
        cache, toks, tok, rngs, counts = _decode_scan(
            model, window, params, cache, tokens, active, eos, do_sample,
            temperature, top_k, top_p, pad, rngs,
        )
        return cache.s, cache.z, toks, tok, rngs, counts

    sh = shardings
    return _serve_jit(
        state_decode_window, donate_argnums=(1, 2),
        in_shardings=None if sh is None else (sh.params, *sh.rep(12)),
        out_shardings=None if sh is None else sh.rep(6),
    )


def _kernel_form(shardings: Optional[ServeShardings]):
    """The context a program's gathered arm is traced in.  Under ``tp > 1`` its
    pool and views are sharded over key/value heads and a ``pallas_call`` has no
    partitioning rule, so the chunk's flash kernel and the decode step's kernel
    (``ops/view_attention.py``) and the view's page copy (``ops/view_gather.py``)
    give way to their XLA forms: :func:`~accelerate_tpu.ops.view_attention.xla_form`."""
    return xla_form if shardings is not None and shardings.tp_degree > 1 else contextlib.nullcontext


def _flat_view(model: Transformer) -> bool:
    """Which gathered view the model's attention reads, from its
    configuration: the per-head block's ``[L, N, H * D, M]`` (rows flat,
    positions minor) or latent attention's position-major ``[L, N, M, 1,
    width]``: :class:`~accelerate_tpu.models.transformer.KVCache`."""
    return model.config.latent_attention is None


def _gather_view(pages, tables, flat: bool):
    """``pages [L, NP, H, page, D]`` gathered through ``tables [N, P]`` into a
    contiguous per-lane view: ``[L, N, H * D, P * page]`` if ``flat``, else
    ``[L, N, P * page, H, D]``.

    The flat view is filled a page block at a time: a page is a block ``[L, H
    * D, page]`` of whole tiles that goes into the view's columns ``p * page
    ..``.  Where the platform compiles it (:func:`~accelerate_tpu.ops
    .view_gather.view_gather_applies`) one Pallas kernel writes every block
    (:func:`~accelerate_tpu.ops.view_gather.gather_pages`); elsewhere the view
    is zero-filled and each (lane, page slot) put in by one
    ``dynamic_update_slice``, both offsets static and only the page's id traced.
    Neither is the compiler's own gather, which collects the pages in table
    order first and then passes the whole view into its layout (twice, once to
    transpose and once to re-tile); an update at a traced column is served
    element by element, and for a traced lane the pool is copied page-major
    first."""
    L, _, H, page, D = pages.shape
    N, P = tables.shape
    if not flat:
        return (pages[:, tables]                         # [L, N, P, H, page, D]
                .transpose(0, 1, 2, 4, 3, 5)
                .reshape(L, N, P * page, H, D))

    if view_gather_applies(pages):
        view = gather_pages(pages, tables)
    else:
        view = jnp.zeros((L, N, H * D, P * page), pages.dtype)
        for n in range(N):
            for p in range(P):
                block = jax.lax.dynamic_slice_in_dim(pages, tables[n, p], 1, axis=1)
                block = block.swapaxes(3, 4).reshape(L, 1, H * D, page)
                view = jax.lax.dynamic_update_slice(view, block, (0, n, 0, p * page))
    # as written: left alone the compiler fills the view lane-major (the order
    # it gives the first block's unit axis) and copies it for the model
    return with_layout_constraint(view, Layout(major_to_minor=(0, 1, 2, 3)))


def _gather_layers(pages, tables):
    """:func:`_gather_view`'s flat view as one array a layer, ``[N, H * D, P *
    page]`` each, where the page copy kernel builds it (one call a layer);
    elsewhere the stacked view as written.  The decode scan carries each
    layer's view whole: a layer of a stacked view is a static slice, which the
    compiler copies out of the carried array at every step (the 32-lane cell's
    two full layers: four copies of 268 MB a step)."""
    if not view_gather_applies(pages):
        return _gather_view(pages, tables, True)
    return tuple(gather_pages(pages, tables, layer=layer) for layer in range(pages.shape[0]))


def _gather_columns(pages, tables):
    """:func:`_gather_view`'s flat view ``[L, N, H * D, P * page]`` by the
    compiler's own gather and layout passes, bit-equal to it.  A kind's arrays
    of a two-rule pool hold one to a few layers, so a page is a block of a
    quarter of a megabyte and a lane's table hundreds of slots: filled page by
    page the views cost thousands of ``dynamic_update_slice``s to compile (the
    cell's decode window 163 s here for the described chip and 245 s on it,
    against 22 s; a 512-chunk 23 s against 9).  The mixed PREFILL CHUNK takes
    this form: one lane's views, and it runs as fast either way (19.3 against
    19.8 ms on one TPU v5e).  The mixed DECODE WINDOW takes
    :func:`_gather_view` (the page copy kernel on the chip): alone this gather
    fills 8 lanes of 256 one-layer pages in 2.4 ms against the updates' 6.6,
    but the window that holds it runs 41.5 ms against 35.4 (the gather's output
    passes through two more layouts before the scan takes it)."""
    L, _, H, page, D = pages.shape
    N, P = tables.shape
    return (pages[:, tables]                             # [L, N, P, H, page, D]
            .transpose(0, 1, 3, 5, 2, 4)
            .reshape(L, N, H * D, P * page))


def _live_tables(tables, live):
    """Mask table slots at or past each lane's live page count to the null
    page, so gathers only move pages that can hold a visible key.  ``live``
    is ``[N]`` (or scalar for the single prefill lane).  Bitwise-neutral: a
    masked slot's positions sit past the lane's valid length, and the causal
    mask already replaces their logits before the softmax — this just stops
    the gather from reading whole stale pages to feed positions the mask
    throws away."""
    num_p = tables.shape[-1]
    if jnp.ndim(live) == 0:
        return jnp.where(jnp.arange(num_p) < live, tables, NULL_PAGE)
    return jnp.where(jnp.arange(num_p)[None, :] < live[:, None], tables, NULL_PAGE)


def _store_span_pages(pages, view, tables, start, width: int, active, flat: bool,
                      ring: bool = False):
    """Write positions ``start[n] .. start[n] + width - 1`` of lane ``n``'s
    ``view`` (:func:`_gather_view`) back through its block table, for every
    ACTIVE lane, by storing whole the pages the span touches: a static
    ``(width + page - 2) // page + 1`` a lane.  The
    pool's minor dimension on the chip is ``page``, so one row is a strided
    write that the compiler serves by copying the whole pool into another
    layout and back; a page is a contiguous block, stored in place.  The
    page blocks are cut out of the view by one gather over (lane, page slot)
    and scattered; out of the flat view (its columns ``slot * page ..`` are the
    page as the pool holds it) by one ``dynamic_slice`` a block, each put into
    the pool by a ``dynamic_update_slice``: a gather over page slots would
    have the whole view re-tiled with the slot second-minor first.  The view
    itself is never passed into another layout.

    A touched page is stored over itself plus its new rows: it is live in
    :func:`_live_tables`, so the view's copy of it was gathered from that same
    pool page in this call, and the engine made a decoding lane's tail page
    private to it (``_cow_tail_page``).  Positions are in range by the engine's
    admission check (``prompt + max_new + span <= max_len``).  Stores are
    rerouted to the null page for a slot the span does not reach (no page
    boundary crossed; a slot past the table's end) and for every inactive lane:
    a frozen lane's row may be vacant (all-null already), but a lane
    mid-prefill has REAL pages mapped — possibly shared with the prefix cache —
    and its stale write index must never trample them.

    ``ring`` (the flat view of a window layer kind, ``tables`` a lane's ring of
    ``P`` pages): position ``p`` lies in page slot ``(p // page) % P`` of the
    table and in the view's columns ``p % (P * page)``."""
    L, _, H, page, D = pages.shape                       # K's and V's rows may differ
    N, P = tables.shape
    touched = (width + page - 2) // page + 1
    slot = (start // page)[:, None] + jnp.arange(touched)            # [N, touched]
    if ring:
        reached = active[:, None] & (slot <= ((start + width - 1) // page)[:, None])
        slot = slot % P
    else:
        reached = (active[:, None] & (slot < P)
                   & (slot <= ((start + width - 1) // page)[:, None]))
        slot = jnp.minimum(slot, P - 1)
    ids = jnp.where(reached, jnp.take_along_axis(tables, slot, axis=1), NULL_PAGE)
    if not flat:
        blocks = view.reshape(L, N, P, page, H, D)[:, jnp.arange(N)[:, None], slot]
        return pages.at[:, ids.reshape(-1)].set(
            blocks.reshape(L, N * touched, page, H, D).swapaxes(2, 3))

    for n in range(N):
        for t in range(touched):
            pages = _store_flat_page(pages, view, n, slot[n, t] * page, ids[n, t])
    return pages


def _store_flat_page(pages, view, lane: int, column, page_id):
    """Columns ``column .. column + page - 1`` of lane ``lane`` of the flat
    ``view [L, N, H * D, M]`` (or its tuple of one ``[N, H * D, M]`` a layer)
    stored as page ``page_id`` of ``pages``, in place: on the chip the two
    blocks are the same tiles in the same order."""
    L, _, H, page, D = pages.shape
    if isinstance(view, tuple):              # one view a layer: :func:`_gather_layers`
        block = jnp.stack([jax.lax.dynamic_slice(one, (lane, 0, column), (1, H * D, page))
                           for one in view])
    else:
        block = jax.lax.dynamic_slice(view, (0, lane, 0, column), (L, 1, H * D, page))
    return jax.lax.dynamic_update_slice(
        pages, block.reshape(L, 1, H, D, page).swapaxes(3, 4), (0, page_id, 0, 0, 0))


def make_paged_prefill_chunk(model: Transformer, chunk_len: int, page_size: int,
                             direct: bool = False,
                             shardings: Optional[ServeShardings] = None):
    """Paged prefill: ``(params, tokens [1, chunk_len], pages_k, pages_v,
    table [P], base) -> (pages_k, pages_v)``.

    Gathers the prefilling lane's full view (shared prefix pages included —
    this is how a partial cache hit feeds context to the chunks after it
    without any copy), runs the prefill forward at scalar index ``base``, and
    scatters the chunk's ``chunk_len / page_size`` freshly-written pages back.
    The final chunk of a prompt may be padded past the prompt's end: padded
    positions write garbage KV *beyond* the valid length, which the causal
    mask never lets any later query read and decode progressively overwrites.
    Logits are discarded — the first generated token comes from the shared
    decode step re-processing the last prompt token, so prefill and decode
    share one sampling path.  ``base`` and the chunk span are page-aligned by construction: every
    bucket is a multiple of ``page_size`` and chunk starts are sums of
    buckets, so a chunk never writes into a shared page.

    ``direct=True`` swaps the gather/scatter sandwich for the in-model paged
    cache (:class:`~accelerate_tpu.models.transformer.PagedKVCache`): the
    forward reads pages in place and the write path owns the per-page scales,
    so quantized pools requantize each touched page against fresh content.
    Signature becomes ``(params, tokens, pages_k, pages_v, k_scales, v_scales,
    table [P], base) -> (pages_k, pages_v, k_scales, v_scales, quant_err)``.
    With a ``paged_kernel="flash_prefill"`` model this is the Pallas prefill
    path (``ops/paged_attention.py::paged_flash_prefill``) — no gather, no
    scatter round-trip, the chunk attends over prior pages in place.
    """
    if chunk_len % page_size != 0:
        raise ValueError(
            f"chunk bucket {chunk_len} must be a multiple of page_size {page_size}"
        )
    npg = chunk_len // page_size
    s = _unrouted(model, shardings)
    counted = _routed(model)
    flat = _flat_view(model)
    form = _kernel_form(s)

    if direct:
        def direct_prefill_chunk(params, tokens, pages_k, pages_v, k_scales,
                                 v_scales, table, base):
            cache = PagedKVCache(
                pages_k=pages_k, pages_v=pages_v,
                k_scales=k_scales, v_scales=v_scales,
                tables=table[None], index=base.reshape(1),
                active=jnp.ones((1,), bool), quant_err=jnp.float32(0.0),
            )
            with form():
                _, cache = model.apply({"params": params}, tokens, cache=cache)
            return (cache.pages_k, cache.pages_v, cache.k_scales,
                    cache.v_scales, cache.quant_err)

        return _serve_jit(
            direct_prefill_chunk,
            donate_argnums=(2, 3, 4, 5),
            in_shardings=None if s is None else (
                s.params, s.replicated, s.pages, s.pages, s.scales, s.scales,
                *s.rep(2),
            ),
            out_shardings=None if s is None else (
                s.pages, s.pages, s.scales, s.scales, s.replicated,
            ),
        )

    def chunk(params, tokens, pages_k, pages_v, table, base, valid):
        live = (base + chunk_len - 1) // page_size + 1
        gt = _live_tables(table, live)
        with form():
            cache = KVCache(
                k=_gather_view(pages_k, gt[None], flat),
                v=_gather_view(pages_v, gt[None], flat),
                index=base,
            )
            rows = None if valid is None else jnp.arange(chunk_len)[None, :] < valid
            _, cache, counts = _forward(model, params, tokens, cache, rows)
        ids = jax.lax.dynamic_slice(table, (base // page_size,), (npg,))

        def write_back(pages, view):
            L, _, H, page, D = pages.shape          # K's and V's rows may differ
            if flat:
                for i in range(npg):
                    pages = _store_flat_page(pages, view, 0, base + i * page, ids[i])
                return pages
            w = jax.lax.dynamic_slice(view, (0, 0, base, 0, 0), (L, 1, chunk_len, H, D))
            return pages.at[:, ids].set(w.reshape(L, npg, page, H, D).swapaxes(2, 3))

        return _with_counts(write_back(pages_k, cache.k), write_back(pages_v, cache.v), counts)

    if counted:
        # a model with routed experts: a trailing ``valid`` (the chunk's real
        # rows) in, the ``moe_*`` counters out (:func:`_forward`)
        def paged_prefill_chunk(params, tokens, pages_k, pages_v, table, base, valid):
            return chunk(params, tokens, pages_k, pages_v, table, base, valid)

        return _serve_jit(paged_prefill_chunk, donate_argnums=(2, 3))

    def paged_prefill_chunk(params, tokens, pages_k, pages_v, table, base):
        return chunk(params, tokens, pages_k, pages_v, table, base, None)

    return _serve_jit(
        paged_prefill_chunk,
        donate_argnums=(2, 3),
        in_shardings=None if s is None else (
            s.params, s.replicated, s.pages, s.pages, *s.rep(2),
        ),
        out_shardings=None if s is None else (s.pages, s.pages),
    )


def make_paged_decode_window(model: Transformer, window: int,
                             direct: bool = False,
                             shardings: Optional[ServeShardings] = None):
    """Paged decode: ``(params, pages_k, pages_v, tables [N, P], index [N],
    tokens, active, eos, do_sample, temperature, top_k, top_p, pad, rngs)
    -> (pages_k, pages_v, out_tokens [N, window], new_pending, new_rngs)``.

    Gather view -> the shared :func:`_decode_scan` -> store back whole the
    pages each active lane's ``window`` new positions lie in
    (:func:`_store_span_pages`: two a lane).  The engine tracks each lane's
    index on the host (install/advance arithmetic is exact), so no index
    array needs to round-trip.

    Return packing is readback-friendly by design: ``out_tokens`` is its own
    output leaf (never folded into the carried pages/lane state), so the
    pipelined engine can park just that handle in a :class:`.readback.Readback`
    and dispatch the next window — which donates and rebinds the pages —
    without the deferred token fetch ever touching a donated buffer.  All
    outputs of one call materialize together, so fetching ``out_tokens``
    also proves the window's KV writes landed.

    ``direct=True`` drops the gather/scatter sandwich: the model runs on a
    :class:`~accelerate_tpu.models.transformer.PagedKVCache`, attention reads
    pages in place (``config.paged_kernel`` picks pallas kernel vs XLA
    reference) and writes go through the scale-aware paged insert — the
    quantized-KV and Pallas fast paths.  Same traced ``_decode_scan`` body, so
    sampling/freeze/EOS semantics cannot drift.  Signature gains the scale
    arrays: ``(params, pages_k, pages_v, k_scales, v_scales, tables, index,
    tokens, ...) -> (pages_k, pages_v, k_scales, v_scales, out_tokens,
    new_pending, new_rngs, quant_err)``.  A model with routed experts appends
    its window's ``moe_*`` counters (:func:`_forward`) to either tuple.
    """

    s = _unrouted(model, shardings)
    flat = _flat_view(model)

    if direct:
        def direct_decode_window(params, pages_k, pages_v, k_scales, v_scales,
                                 tables, index, tokens, active, eos, do_sample,
                                 temperature, top_k, top_p, pad, rngs):
            cache = PagedKVCache(
                pages_k=pages_k, pages_v=pages_v,
                k_scales=k_scales, v_scales=v_scales,
                tables=tables, index=index, active=active,
                quant_err=jnp.float32(0.0),
            )
            cache, toks, tok, rngs, counts = _decode_scan(
                model, window, params, cache, tokens, active, eos, do_sample,
                temperature, top_k, top_p, pad, rngs,
            )
            return _with_counts(cache.pages_k, cache.pages_v, cache.k_scales,
                                cache.v_scales, toks, tok, rngs, cache.quant_err,
                                counts)

        return _serve_jit(
            direct_decode_window,
            donate_argnums=(1, 2, 3, 4),
            in_shardings=None if s is None else (
                s.params, s.pages, s.pages, s.scales, s.scales, *s.rep(11),
            ),
            out_shardings=None if s is None else (
                s.pages, s.pages, s.scales, s.scales, *s.rep(4),
            ),
        )

    def paged_decode_window(params, pages_k, pages_v, tables, index, tokens,
                            active, eos, do_sample, temperature, top_k, top_p,
                            pad, rngs):
        page = pages_k.shape[3]
        gt = _live_tables(tables, (index + window - 1) // page + 1)
        with _kernel_form(s)():
            cache = KVCache(
                k=_gather_view(pages_k, gt, flat),
                v=_gather_view(pages_v, gt, flat),
                index=index,
            )
            cache, toks, tok, rngs, counts = _decode_scan(
                model, window, params, cache, tokens, active, eos, do_sample,
                temperature, top_k, top_p, pad, rngs,
            )
        pages_k = _store_span_pages(pages_k, cache.k, tables, index, window, active, flat)
        pages_v = _store_span_pages(pages_v, cache.v, tables, index, window, active, flat)
        return _with_counts(pages_k, pages_v, toks, tok, rngs, counts)

    return _serve_jit(
        paged_decode_window,
        donate_argnums=(1, 2),
        in_shardings=None if s is None else (s.params, s.pages, s.pages, *s.rep(11)),
        out_shardings=None if s is None else (s.pages, s.pages, *s.rep(3)),
    )


def make_mixed_prefill_chunk(model: Transformer, chunk_len: int, page_size: int):
    """Prefill chunk of a stack of two kinds of layer (``config.layer_types``):
    ``(params, tokens [1, chunk_len], pages_k, pages_v, ring_k, ring_v, table
    [P], ring_table [R], base[, valid]) -> (pages_k, pages_v, ring_k, ring_v[,
    counts])``.  The gathered arm of :func:`make_paged_prefill_chunk` twice
    over: the full layers' view is ``max_len`` wide through the lane's whole
    table, the window layers' view is the lane's ring, ``R * page`` wide
    (:class:`~accelerate_tpu.models.transformer.MixedKVCache`), and the chunk's
    pages are stored back whole into both pools: into the table's slots
    ``base // page ..`` and into the ring's slots ``(base // page + i) % R``.
    ``valid`` and the counters are a routed-experts model's (:func:`_forward`)."""
    if chunk_len % page_size != 0:
        raise ValueError(
            f"chunk bucket {chunk_len} must be a multiple of page_size {page_size}"
        )
    npg = chunk_len // page_size

    def chunk(params, tokens, pages_k, pages_v, ring_k, ring_v, table, ring_table, base, valid):
        gt = _live_tables(table, (base + chunk_len - 1) // page_size + 1)
        cache = MixedKVCache(
            k=_gather_columns(pages_k, gt[None]), v=_gather_columns(pages_v, gt[None]),
            k_ring=_gather_columns(ring_k, ring_table[None]),
            v_ring=_gather_columns(ring_v, ring_table[None]),
            index=base, page=page_size,
        )
        rows = None if valid is None else jnp.arange(chunk_len)[None, :] < valid
        _, cache, counts = _forward(model, params, tokens, cache, rows)
        first = base // page_size
        ring_slots = (first + jnp.arange(npg)) % ring_table.shape[0]
        for i in range(npg):
            pages_k = _store_flat_page(pages_k, cache.k, 0, base + i * page_size, table[first + i])
            pages_v = _store_flat_page(pages_v, cache.v, 0, base + i * page_size, table[first + i])
            column, page_id = ring_slots[i] * page_size, ring_table[ring_slots[i]]
            ring_k = _store_flat_page(ring_k, cache.k_ring, 0, column, page_id)
            ring_v = _store_flat_page(ring_v, cache.v_ring, 0, column, page_id)
        return _with_counts(pages_k, pages_v, ring_k, ring_v, counts)

    if _routed(model):
        def mixed_prefill_chunk(params, tokens, pages_k, pages_v, ring_k, ring_v, table,
                                ring_table, base, valid):
            return chunk(params, tokens, pages_k, pages_v, ring_k, ring_v, table, ring_table,
                         base, valid)
    else:
        def mixed_prefill_chunk(params, tokens, pages_k, pages_v, ring_k, ring_v, table,
                                ring_table, base):
            return chunk(params, tokens, pages_k, pages_v, ring_k, ring_v, table, ring_table,
                         base, None)

    return _serve_jit(mixed_prefill_chunk, donate_argnums=(2, 3, 4, 5))


def make_mixed_decode_window(model: Transformer, window: int):
    """Decode window of a stack of two kinds of layer: ``(params, pages_k,
    pages_v, ring_k, ring_v, tables [N, P], ring_tables [N, R], index [N],
    tokens, active, eos, do_sample, temperature, top_k, top_p, pad, rngs) ->
    (pages_k, pages_v, ring_k, ring_v, out_tokens [N, window], new_pending,
    new_rngs, counts)``.  Gather both kinds' views (the window layers' is the
    ring's width, not ``max_len``), the shared :func:`_decode_scan`, store back
    whole the pages each active lane's ``window`` new positions lie in, in both
    pools.  ``counts`` is the experts' ``moe_*`` counters where the model has
    them, then ``[kv_rows_live, kv_rows_live_window]`` (:func:`_rows_live`)."""

    def mixed_decode_window(params, pages_k, pages_v, ring_k, ring_v, tables, ring_tables,
                            index, tokens, active, eos, do_sample, temperature, top_k,
                            top_p, pad, rngs):
        page = pages_k.shape[3]
        gt = _live_tables(tables, (index + window - 1) // page + 1)
        cache = MixedKVCache(
            k=_gather_layers(pages_k, gt), v=_gather_layers(pages_v, gt),
            k_ring=_gather_layers(ring_k, ring_tables),
            v_ring=_gather_layers(ring_v, ring_tables),
            index=index, page=page,
        )
        cache, toks, tok, rngs, counts = _decode_scan(
            model, window, params, cache, tokens, active, eos, do_sample,
            temperature, top_k, top_p, pad, rngs,
        )
        pages_k = _store_span_pages(pages_k, cache.k, tables, index, window, active, True)
        pages_v = _store_span_pages(pages_v, cache.v, tables, index, window, active, True)
        ring_k = _store_span_pages(ring_k, cache.k_ring, ring_tables, index, window, active,
                                   True, ring=True)
        ring_v = _store_span_pages(ring_v, cache.v_ring, ring_tables, index, window, active,
                                   True, ring=True)
        return pages_k, pages_v, ring_k, ring_v, toks, tok, rngs, counts

    return _serve_jit(mixed_decode_window, donate_argnums=(1, 2, 3, 4))


def make_paged_verify_window(model: Transformer, k: int, direct: bool = False,
                             shardings: Optional[ServeShardings] = None):
    """Paged speculative verify: :func:`_verify_body` over a gathered view,
    storing back the pages all ``K+1`` written positions lie in
    (:func:`_store_span_pages`; rejected positions' KV is unreachable past the
    committed index and gets overwritten later).
    ``(params, pages_k, pages_v, tables, index, tokens [N, K+1], ...) ->
    (pages_k, pages_v, out, n_commit, new_pending, new_rngs)`` — the engine
    advances its host index mirror by ``n_commit``.

    ``direct=True``: in-model paged cache (see
    :func:`make_paged_decode_window`); signature gains the scale arrays and a
    trailing ``quant_err``.
    """
    kp1 = k + 1
    s = shardings
    flat = _flat_view(model)

    if direct:
        def direct_verify_window(params, pages_k, pages_v, k_scales, v_scales,
                                 tables, index, tokens, active, eos, do_sample,
                                 temperature, top_k, top_p, pad, rngs):
            cache = PagedKVCache(
                pages_k=pages_k, pages_v=pages_v,
                k_scales=k_scales, v_scales=v_scales,
                tables=tables, index=index, active=active,
                quant_err=jnp.float32(0.0),
            )
            cache, out, n_commit, new_pending, new_rngs = _verify_body(
                model, k, params, cache, tokens, active, eos, do_sample,
                temperature, top_k, top_p, pad, rngs,
            )
            return (cache.pages_k, cache.pages_v, cache.k_scales,
                    cache.v_scales, out, n_commit, new_pending, new_rngs,
                    cache.quant_err)

        return _serve_jit(
            direct_verify_window,
            donate_argnums=(1, 2, 3, 4),
            in_shardings=None if s is None else (
                s.params, s.pages, s.pages, s.scales, s.scales, *s.rep(11),
            ),
            out_shardings=None if s is None else (
                s.pages, s.pages, s.scales, s.scales, *s.rep(5),
            ),
        )

    def paged_verify_window(params, pages_k, pages_v, tables, index, tokens,
                            active, eos, do_sample, temperature, top_k, top_p,
                            pad, rngs):
        page = pages_k.shape[3]
        gt = _live_tables(tables, (index + kp1 - 1) // page + 1)
        with _kernel_form(s)():
            cache = KVCache(
                k=_gather_view(pages_k, gt, flat),
                v=_gather_view(pages_v, gt, flat),
                index=index,
            )
        cache, out, n_commit, new_pending, new_rngs = _verify_body(
            model, k, params, cache, tokens, active, eos, do_sample,
            temperature, top_k, top_p, pad, rngs,
        )
        pages_k = _store_span_pages(pages_k, cache.k, tables, index, kp1, active, flat)
        pages_v = _store_span_pages(pages_v, cache.v, tables, index, kp1, active, flat)
        return pages_k, pages_v, out, n_commit, new_pending, new_rngs

    return _serve_jit(
        paged_verify_window,
        donate_argnums=(1, 2),
        in_shardings=None if s is None else (s.params, s.pages, s.pages, *s.rep(11)),
        out_shardings=None if s is None else (s.pages, s.pages, *s.rep(4)),
    )


def _tree_commit_paged(cache: PagedKVCache, prev_index, path):
    """Commit a tree verify's winning path inside the page pool: gather the
    ``D+1`` path nodes' KV rows through each lane's block table and re-insert
    them contiguously at the lane frontier — the in-place twin of the view
    compaction in :func:`_tree_verify_body`.  Quantized pools dequantize the
    gathered rows and requantize at insert (the same scatter-time scale
    discipline as every other paged write; the round-trip error folds into
    ``quant_err``).  Losing branches' rows past ``frontier + D`` are zeroed by
    the next insert touching their page (stale-slot rule of
    :func:`~accelerate_tpu.ops.paged_attention.paged_quantized_insert`) and
    are never visible to attention (masked past each lane's length)."""
    from ..ops.paged_attention import (
        kv_qmax,
        paged_insert,
        paged_quantized_insert,
    )

    page = cache.pages_k.shape[3]
    p_max = cache.tables.shape[1] - 1
    pos = prev_index[:, None] + path                     # [N, D+1]
    pid = jnp.take_along_axis(
        cache.tables, jnp.clip(pos // page, 0, p_max), axis=1
    )
    off = pos % page
    quantized = kv_qmax(cache.pages_k.dtype) is not None

    def _rows(pages, scales):
        # advanced indices split by a slice lead the result: [N, D+1, L, H, Dh]
        rows = jnp.moveaxis(pages[:, pid, :, off], 2, 0)  # [L, N, D+1, H, Dh]
        if quantized:
            rows = rows.astype(jnp.float32) * scales[:, pid][..., None]
        return rows

    rows_k = _rows(cache.pages_k, cache.k_scales)
    rows_v = _rows(cache.pages_v, cache.v_scales)
    if quantized:
        ins = jax.vmap(
            lambda p, sc, r: paged_quantized_insert(
                p, sc, r, cache.tables, prev_index, cache.active
            )
        )
        pages_k, k_scales, err_k = ins(cache.pages_k, cache.k_scales, rows_k)
        pages_v, v_scales, err_v = ins(cache.pages_v, cache.v_scales, rows_v)
        err = jnp.maximum(jnp.max(err_k), jnp.max(err_v))
        return cache.replace(
            pages_k=pages_k, pages_v=pages_v,
            k_scales=k_scales, v_scales=v_scales,
            quant_err=jnp.maximum(cache.quant_err, err),
        )
    ins = jax.vmap(
        lambda p, r: paged_insert(p, r, cache.tables, prev_index, cache.active)
    )
    return cache.replace(
        pages_k=ins(cache.pages_k, rows_k), pages_v=ins(cache.pages_v, rows_v)
    )


def make_paged_tree_verify_window(model: Transformer, tree,
                                  direct: bool = False,
                                  shardings: Optional[ServeShardings] = None):
    """Paged tree speculative verify — :func:`_tree_verify_body` over the
    page pool.  ``(params, pages_k, pages_v, tables, index,
    tokens [N, S], ...) -> (pages_k, pages_v, out [N, D+1], n_commit,
    new_pending, new_rngs)``.

    ``direct=False`` runs :func:`_tree_verify_body` (including its view
    compaction) over a gathered per-lane view and stores back the pages
    all ``S`` written positions lie in (:func:`_store_span_pages`) — rows past
    the compacted frontier are unreachable garbage, exactly like rejected
    positions in the linear paged verify.
    ``direct=True`` threads the :class:`PagedKVCache` through the model (the
    quantized / pallas-kernel path); the winning path commits via
    :func:`_tree_commit_paged` and the signature gains the scale arrays and a
    trailing ``quant_err``.
    """
    s_nodes = tree.nodes
    s = shardings
    flat = _flat_view(model)

    if direct:
        def direct_tree_verify_window(params, pages_k, pages_v, k_scales,
                                      v_scales, tables, index, tokens, active,
                                      eos, do_sample, temperature, top_k,
                                      top_p, pad, rngs):
            cache = PagedKVCache(
                pages_k=pages_k, pages_v=pages_v,
                k_scales=k_scales, v_scales=v_scales,
                tables=tables, index=index, active=active,
                quant_err=jnp.float32(0.0),
            )
            cache, out, n_commit, new_pending, new_rngs = _tree_verify_body(
                model, tree, params, cache, tokens, active, eos, do_sample,
                temperature, top_k, top_p, pad, rngs,
            )
            return (cache.pages_k, cache.pages_v, cache.k_scales,
                    cache.v_scales, out, n_commit, new_pending, new_rngs,
                    cache.quant_err)

        return _serve_jit(
            direct_tree_verify_window,
            donate_argnums=(1, 2, 3, 4),
            in_shardings=None if s is None else (
                s.params, s.pages, s.pages, s.scales, s.scales, *s.rep(11),
            ),
            out_shardings=None if s is None else (
                s.pages, s.pages, s.scales, s.scales, *s.rep(5),
            ),
        )

    def paged_tree_verify_window(params, pages_k, pages_v, tables, index,
                                 tokens, active, eos, do_sample, temperature,
                                 top_k, top_p, pad, rngs):
        page = pages_k.shape[3]
        gt = _live_tables(tables, (index + s_nodes - 1) // page + 1)
        with _kernel_form(s)():
            cache = KVCache(
                k=_gather_view(pages_k, gt, flat),
                v=_gather_view(pages_v, gt, flat),
                index=index,
            )
        cache, out, n_commit, new_pending, new_rngs = _tree_verify_body(
            model, tree, params, cache, tokens, active, eos, do_sample,
            temperature, top_k, top_p, pad, rngs,
        )
        pages_k = _store_span_pages(pages_k, cache.k, tables, index, s_nodes, active, flat)
        pages_v = _store_span_pages(pages_v, cache.v, tables, index, s_nodes, active, flat)
        return pages_k, pages_v, out, n_commit, new_pending, new_rngs

    return _serve_jit(
        paged_tree_verify_window,
        donate_argnums=(1, 2),
        in_shardings=None if s is None else (s.params, s.pages, s.pages, *s.rep(11)),
        out_shardings=None if s is None else (s.pages, s.pages, *s.rep(4)),
    )


def make_copy_page(shardings: Optional[ServeShardings] = None):
    """Jitted copy-on-write: ``(pages_k, pages_v, k_scales, v_scales, src,
    dst) -> (pages_k, pages_v, k_scales, v_scales)`` duplicates one physical
    page (dequantization scales ride along — a quantized copy is exact, both
    pages decode identically).  Runs only when a lane's first decode write
    lands in a page the prefix cache (or a sibling lane) still references —
    at most once per admitted request, and never on the pure aliasing hit
    path.  One compiled shape per engine, page-size-static.
    """

    def copy_page(pages_k, pages_v, k_scales, v_scales, src, dst):
        pages_k = pages_k.at[:, dst].set(pages_k[:, src])
        pages_v = pages_v.at[:, dst].set(pages_v[:, src])
        k_scales = k_scales.at[:, dst].set(k_scales[:, src])
        v_scales = v_scales.at[:, dst].set(v_scales[:, src])
        return pages_k, pages_v, k_scales, v_scales

    s = shardings
    return _serve_jit(
        copy_page,
        donate_argnums=(0, 1, 2, 3),
        in_shardings=None if s is None else (
            s.pages, s.pages, s.scales, s.scales, *s.rep(2),
        ),
        out_shardings=None if s is None else (s.pages, s.pages, s.scales, s.scales),
    )


def make_spill_extract(npages: int, shardings: Optional[ServeShardings] = None):
    """Jitted D2H-side gather for the hierarchical prefix cache's spill path:
    ``(pages_k, pages_v, k_scales, v_scales, ids [npages]) -> (chunk_k
    [L, npages, Hkv, page, Dh], chunk_v, chunk_k_scales [L, npages, Hkv],
    chunk_v_scales)`` packs one evicted chunk's pages (quant scales ride
    along, so int8/fp8 chunks spill at their quantized density) into dense
    per-chunk arrays the engine fetches at its drain point — the gather is
    enqueued, never synced, and NOTHING is donated: the pool stays live for
    the in-flight decode window.  One compiled shape per prefill bucket
    (``npages = bucket // page_size``), so the compiled budget grows by
    exactly the bucket set.
    """

    def spill_extract(pages_k, pages_v, k_scales, v_scales, ids):
        if ids.shape[0] != npages:
            raise ValueError(
                f"spill_extract compiled for {npages} pages, got {ids.shape[0]}"
            )
        return (jnp.take(pages_k, ids, axis=1),
                jnp.take(pages_v, ids, axis=1),
                jnp.take(k_scales, ids, axis=1),
                jnp.take(v_scales, ids, axis=1))

    s = shardings
    return _serve_jit(
        spill_extract,
        in_shardings=None if s is None else (
            s.pages, s.pages, s.scales, s.scales, s.replicated,
        ),
        out_shardings=None if s is None else (s.pages, s.pages, s.scales, s.scales),
    )


def make_promote_install(npages: int, shardings: Optional[ServeShardings] = None):
    """Jitted H2D-side scatter for the hierarchical prefix cache's promotion
    path: ``(pages_k, pages_v, k_scales, v_scales, chunk_k, chunk_v,
    chunk_k_scales, chunk_v_scales, ids [npages]) -> (pages_k, pages_v,
    k_scales, v_scales)`` installs a spilled chunk's payload into freshly
    allocated pages.  The pool arrays are donated (in-place alias per shard,
    the decode-window discipline), so the engine parks the old handles on the
    in-flight window's ``Readback.consumed`` before rebinding — the install
    enqueues *behind* the window and overlaps the decode it rides with.  One
    compiled shape per prefill bucket, mirroring :func:`make_spill_extract`.
    """

    def promote_install(pages_k, pages_v, k_scales, v_scales,
                        chunk_k, chunk_v, chunk_k_scales, chunk_v_scales, ids):
        if ids.shape[0] != npages:
            raise ValueError(
                f"promote_install compiled for {npages} pages, got {ids.shape[0]}"
            )
        pages_k = pages_k.at[:, ids].set(chunk_k.astype(pages_k.dtype))
        pages_v = pages_v.at[:, ids].set(chunk_v.astype(pages_v.dtype))
        k_scales = k_scales.at[:, ids].set(chunk_k_scales.astype(k_scales.dtype))
        v_scales = v_scales.at[:, ids].set(chunk_v_scales.astype(v_scales.dtype))
        return pages_k, pages_v, k_scales, v_scales

    s = shardings
    return _serve_jit(
        promote_install,
        donate_argnums=(0, 1, 2, 3),
        in_shardings=None if s is None else (
            s.pages, s.pages, s.scales, s.scales,
            s.pages, s.pages, s.scales, s.scales, s.replicated,
        ),
        out_shardings=None if s is None else (s.pages, s.pages, s.scales, s.scales),
    )


def pad_page_ids(ids: Sequence[int], npages: int) -> "np.ndarray":
    """Pad a lane's live page-id list with ``NULL_PAGE`` up to a migration
    executable's fixed ``npages`` width — the sanctioned bucket-padded
    dispatch.  The null page is the pool's garbage sink: the migrate gather
    reads finite (harmless) values from it for the padded rows, and the
    migrate install scatters those padded rows back INTO it, where writes
    are harmless by construction — so one compiled shape serves every
    per-lane page count and nothing ever drifts the jit signature."""
    if len(ids) > npages:
        raise ValueError(
            f"lane holds {len(ids)} pages, exceeding the executable's "
            f"{npages}-page width"
        )
    out = np.full((npages,), NULL_PAGE, np.int32)
    out[:len(ids)] = np.asarray(ids, np.int32)
    return out


def plan_chunks(prompt_len: int, buckets: Sequence[int]) -> Tuple[Tuple[int, int], ...]:
    """Split a prompt into prefill chunks drawn from the fixed bucket sizes.

    Returns ``((bucket_len, valid_len), ...)``: greedy largest-fit, so only
    the final chunk can be padded (``valid_len < bucket_len``).  KV for the
    prompt's last token is still *written* by prefill but re-written by the
    first decode step: the engine installs a lane at ``prompt_len - 1`` and
    leaves the last prompt token pending, so the decode window computes the
    first generated token through the same executable as every later token.
    """
    buckets = sorted(set(int(b) for b in buckets))
    if not buckets or buckets[0] <= 0:
        raise ValueError(f"prefill buckets must be positive, got {buckets}")
    chunks = []
    remaining = prompt_len
    while remaining > 0:
        fit = [b for b in buckets if b <= remaining]
        b = max(fit) if fit else buckets[0]
        chunks.append((b, min(b, remaining)))
        remaining -= min(b, remaining)
    return tuple(chunks)


def jit_cache_sizes(*fns) -> int:
    """Total number of compiled executables across jitted fns — the
    no-per-request-retrace assertion counter (0 until first call).  Reads the
    pjit-internal counter through
    :func:`~accelerate_tpu.utils.jax_compat.jit_cache_size`, which degrades to
    0 rather than crashing if a jax minor bump moves the private attribute."""
    return sum(jit_cache_size(f) or 0 for f in fns)

"""Speculation dispatch stage: drafters, tree topology, and the draft forward.

The engine's speculative machinery used to live inline in ``engine.py``
(``_propose_drafts`` / ``_verify_cycle``); this module extracts it into a
stage with a small **drafter protocol** so the scheduler carve-up planned on
the ROADMAP never has to thread through drafting code.  Three drafters:

* ``ngram`` — :class:`NgramDrafter`: the host-side prompt-lookup drafter
  (:mod:`.spec`), now backed by the *incremental* per-lane
  :class:`~accelerate_tpu.serving.spec.NgramIndex` so steady-state drafting
  is O(k) per cycle instead of re-walking the whole context.  Feeds the
  linear ``[slots, K+1]`` verify window; token-identical to the brute-force
  matcher.
* ``model`` — :class:`TreeDrafter` with ``width == 1``: an on-device draft
  model (a truncated-layer head of the served model, see
  :func:`build_draft`) drafts ``depth`` tokens per lane in ONE small jitted
  forward (:func:`make_draft_forward`) instead of host numpy.  Verification
  still runs the tree window — a width-1 tree is exactly the linear chain.
* ``tree`` — :class:`TreeDrafter` with ``width > 1``: the draft model's
  top-``width`` candidates at the branch point each extend into a greedy
  chain, giving a ``1 + width * depth``-node token tree
  (:class:`TreeSpec`, chains topology) verified in one forward under the
  ancestor mask (SpecInfer/Medusa-style tree attention).

The draft forward is **stateless**: each cycle it re-prefills a bounded
per-lane context window (:class:`~accelerate_tpu.serving.paging
.DraftContextWindow`, host-side) through the truncated head into a scratch
KV created inside the jit.  A persistent draft KV tier was considered and
rejected: it would need its own page class, rollback of losing branches
every cycle, and a second swap/donation discipline — re-prefilling
``draft_ctx`` tokens through a few layers costs less than one verify forward
and keeps the draft a pure function of the visible context.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import KVCache, Transformer, TransformerConfig
from .spec import NgramIndex


class TreeSpec:
    """Static chains-topology token tree for speculative verification.

    ``width`` sibling branches at the branch point, each a greedy chain of
    ``depth`` draft tokens: ``nodes = 1 + width * depth``.  Node 0 is the
    lane's pending token (the tree root, depth 0); branch ``b``'s node at
    level ``s`` (1-based) is ``1 + b * depth + (s - 1)``.  Siblings exist
    only at level 1 — the draft model drafts greedily below its top-``width``
    branch candidates, so deeper fan-out would verify tokens the drafter
    assigns near-zero probability.  All arrays are host numpy constants baked
    into the verify executable (the tree shape is engine-static, never
    call-varying):

    * ``parent [S]`` — parent node id (root's parent is itself)
    * ``depth_arr [S]`` — node depth = sequence-position offset from the
      lane frontier
    * ``anc [S, S]`` — ancestor-or-self visibility, the ``tree_mask``
      threaded through :func:`~accelerate_tpu.models.transformer
      .cached_attention` and the Pallas paged kernel
    * ``paths [W, D+1]`` — row ``b`` = the root-to-leaf node chain of branch
      ``b`` (``[0, node(b, 1), .., node(b, D)]``)
    """

    def __init__(self, width: int, depth: int) -> None:
        if width < 1 or depth < 1:
            raise ValueError(f"need width >= 1 and depth >= 1, got {width}x{depth}")
        self.width = width
        self.depth = depth
        self.nodes = 1 + width * depth
        s = self.nodes
        parent = np.zeros(s, dtype=np.int32)
        depth_arr = np.zeros(s, dtype=np.int32)
        paths = np.zeros((width, depth + 1), dtype=np.int32)
        for b in range(width):
            for lvl in range(1, depth + 1):
                i = 1 + b * depth + (lvl - 1)
                parent[i] = 0 if lvl == 1 else i - 1
                depth_arr[i] = lvl
                paths[b, lvl] = i
        anc = np.zeros((s, s), dtype=bool)
        for i in range(s):
            j = i
            anc[i, j] = True
            while j != 0:
                j = int(parent[j])
                anc[i, j] = True
        self.parent = parent
        self.depth_arr = depth_arr
        self.anc = anc
        self.paths = paths

    def __repr__(self) -> str:
        return f"TreeSpec(width={self.width}, depth={self.depth}, nodes={self.nodes})"


class NgramDrafter:
    """Host-side prompt-lookup drafting over the incremental suffix index.

    One :class:`~accelerate_tpu.serving.spec.NgramIndex` per occupied slot,
    lazily synced to the lane's emitted tokens at propose time — the index
    consumes only the *delta* since the previous cycle (O(new tokens), i.e.
    O(k) in steady state), replacing ``propose_ngram_draft``'s per-cycle
    O(context) rescan while staying token-identical to it (the equivalence
    argument lives on :class:`NgramIndex`; ``TestNgramDraft`` pins both).
    """

    kind = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1) -> None:
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self._idx: Dict[int, NgramIndex] = {}

    def propose(self, slot: int, context, k: int) -> Optional[np.ndarray]:
        """Draft ``k`` tokens for ``slot`` whose emitted tokens are
        ``context`` (a growing sequence; the index appends the unseen tail)."""
        idx = self._idx.get(slot)
        if idx is None or len(idx) > len(context):
            # new lane, or the slot was reused without retire — rebuild
            idx = self._idx[slot] = NgramIndex(self.max_ngram, self.min_ngram)
        idx.extend(context[len(idx):])
        return idx.propose(k)

    def retire(self, slot: int) -> None:
        self._idx.pop(slot, None)


class TreeDrafter:
    """On-device draft-model drafting (``model`` when ``width == 1``,
    ``tree`` when ``width > 1``): owns the jitted draft forward plus the
    engine-facing lifecycle hooks.  The engine feeds it the host context
    window arrays (:class:`~accelerate_tpu.serving.paging
    .DraftContextWindow`) and receives the ``[slots, tree.nodes]`` draft
    token array as a *device handle* — it flows straight into the tree
    verify window without a host round-trip."""

    def __init__(self, tree: TreeSpec, draft_cfg: TransformerConfig,
                 forward) -> None:
        self.tree = tree
        self.draft_cfg = draft_cfg
        self.forward = forward

    @property
    def kind(self) -> str:
        return "tree" if self.tree.width > 1 else "model"

    def propose_device(self, draft_params, ctx, length):
        """Dispatch the draft forward: ``(ctx [N, C], length [N]) ->
        tokens [N, tree.nodes]`` (async device handle)."""
        return self.forward(draft_params, ctx, length)

    def retire(self, slot: int) -> None:  # stateless — context lives host-side
        pass


# ----------------------------------------------------------------- draft model
def _slice_layer_params(params: Dict[str, Any], num_layers: int) -> Dict[str, Any]:
    """First ``num_layers`` decoder layers of a served param tree, both
    layouts: scan (``layers`` with a leading depth axis — slice axis 0) and
    per-layer (``layers_{i}`` — keep ``i < num_layers``).  Non-layer keys
    (embeddings, final norm, lm head) pass through untouched."""
    out: Dict[str, Any] = {}
    for key, val in params.items():
        if key == "layers":
            out[key] = jax.tree_util.tree_map(lambda a: a[:num_layers], val)
            continue
        m = re.fullmatch(r"layers_(\d+)", key)
        if m is None:
            out[key] = val
        elif int(m.group(1)) < num_layers:
            out[key] = val
    return out


def default_draft_layers(num_layers: int) -> int:
    """Default truncation: a quarter of the served depth, at least one layer.
    Shallow heads keep most of next-token agreement on easy tokens (the
    self-speculation observation behind early-exit drafting) while costing a
    small fraction of the verify forward."""
    return max(1, num_layers // 4)


def build_draft(cfg: TransformerConfig, params, draft_model, *,
                draft_ctx: int, depth: int,
                ) -> Tuple[TransformerConfig, Any]:
    """Resolve the engine's ``draft_model`` knob to ``(draft_cfg, host params)``.

    Three forms:

    * **int n** — *self-speculation*: the first ``n`` layers of the served
      model plus its embeddings / final norm / lm head, sliced host-side from
      the served params.  Re-sliced on every ``swap_params`` so the draft
      tracks the served weights through the front door's hot-swap discipline.
    * **str path** — a HF checkpoint dir streamed through
      :mod:`~accelerate_tpu.models.hf_compat`'s mapping one tensor at a time
      (:func:`native_key_map` built for the truncated config only maps the
      head's tensors, so deep layers are never materialized).  An optional
      ``"#n"`` suffix picks the layer count (``"ckpt/dir#4"``); default
      :func:`default_draft_layers`.
    * **(cfg, params) tuple** — explicit draft (tests, pre-built heads).

    The draft config is the served config with the truncated depth, the
    ``xla`` paged kernel (the draft runs a contiguous scratch ``KVCache`` — no pages),
    and a ``max_seq_len`` wide enough for the context window plus the chain
    rollout.  Returned params are host arrays; the engine places them
    replicated (the draft is small — sharding it would serialize its many
    tiny dispatches on cross-chip collectives).
    """
    if isinstance(draft_model, tuple):
        draft_cfg, draft_params = draft_model
        # construction / swap time, engine quiesced — not the serving loop
        draft_params = jax.device_get(draft_params)  # noqa: blocking-readback
        return draft_cfg, draft_params
    if isinstance(draft_model, bool) or not isinstance(draft_model, (int, str)):
        raise ValueError(
            f"draft_model must be int (layer count), str (checkpoint dir) or "
            f"(cfg, params), got {type(draft_model).__name__}"
        )
    min_len = draft_ctx + depth + 1
    if isinstance(draft_model, int):
        n = draft_model
        if not 1 <= n <= cfg.num_layers:
            raise ValueError(
                f"draft_model={n} layers out of range 1..{cfg.num_layers}"
            )
        draft_cfg = dataclasses.replace(
            cfg, num_layers=n, paged_kernel="xla",
            max_seq_len=max(cfg.max_seq_len, min_len),
        )
        inner = params["params"] if "params" in params else params
        sliced = _slice_layer_params(inner, n)
        # construction / swap time, engine quiesced — not the serving loop
        return draft_cfg, jax.device_get(sliced)  # noqa: blocking-readback
    path, _, suffix = draft_model.partition("#")
    from ..models.hf_compat import native_key_map
    from ..models.hf_compat import stream_mapped_tensors
    from ..utils.modeling import unflatten_tree

    base_cfg, _ = native_key_map(path)
    n = int(suffix) if suffix else default_draft_layers(base_cfg.num_layers)
    if not 1 <= n <= base_cfg.num_layers:
        raise ValueError(
            f"draft_model {draft_model!r}: {n} layers out of range "
            f"1..{base_cfg.num_layers}"
        )
    draft_cfg = dataclasses.replace(
        base_cfg, num_layers=n, paged_kernel="xla", scan_layers=False,
        max_seq_len=max(base_cfg.max_seq_len, min_len),
    )
    # a key map built for the truncated config only names the head's tensors;
    # streaming it touches one tensor at a time and never loads deep layers
    _, mapping = native_key_map(path, draft_cfg)
    flat = stream_mapped_tensors(path, mapping)
    return draft_cfg, unflatten_tree(flat)


def make_draft_forward(model: Transformer, tree: TreeSpec, ctx_len: int,
                       shardings=None):
    """One jitted draft forward: ``(params, ctx [N, C], length [N]) ->
    tokens [N, tree.nodes]`` int32 — the whole draft tree in a single
    dispatch.

    Two phases inside one executable, all on a scratch :class:`KVCache`
    created in-trace (zero persistent draft state):

    1. **context prefill** — one forward over the right-padded window;
       positions default to ``arange(C)`` and the causal mask keeps padded
       tail rows invisible.  The logits row at ``length - 1`` yields the
       top-``width`` branch candidates.  The cache index then *rewinds* to
       ``length``: the rollout below overwrites pad rows in place, so no
       pad KV is ever attended.
    2. **chain rollout** — the cache is tiled ``width`` times on the lane
       axis (lane-major, matching the candidates' row-major flatten) and
       ``depth - 1`` greedy single-token steps extend every branch in
       parallel — the branch dimension rides the batch dimension, so the
       rollout costs ``depth - 1`` tiny forwards regardless of width.

    Output layout matches :class:`TreeSpec`: column 0 is the lane's pending
    token (= ``ctx[length - 1]``, the tree root), then branch-major chains.
    Absolute rope positions inside the draft differ from the served model's
    (the window is a suffix) — harmless, rope attends to position
    *differences* and the draft's only job is ranking continuations.
    """
    from .pool import _serve_jit

    width, depth = tree.width, tree.depth
    cfg = model.config

    def draft_forward(params, ctx, length):
        n, c = ctx.shape
        length = jnp.maximum(length.astype(jnp.int32), 1)
        cache = KVCache.create(cfg, n, max_len=c + depth, per_lane_index=True)
        logits, cache = model.apply({"params": params}, ctx, cache=cache)
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None], axis=1
        )[:, 0]                                           # [N, V]
        cand = jax.lax.top_k(last, width)[1].astype(jnp.int32)       # [N, W]
        # rewind to the valid frontier: branch steps write over pad rows
        cache = cache.replace(
            k=jnp.repeat(cache.k, width, axis=1),
            v=jnp.repeat(cache.v, width, axis=1),
            index=jnp.repeat(length, width),
        )
        toks = cand.reshape(n * width)
        chain = [toks]
        for _ in range(depth - 1):
            step_logits, cache = model.apply(
                {"params": params}, toks[:, None], cache=cache
            )
            toks = jnp.argmax(step_logits[:, 0], axis=-1).astype(jnp.int32)
            chain.append(toks)
        tree_tokens = (
            jnp.stack(chain)                              # [D, N*W]
            .reshape(depth, n, width)
            .transpose(1, 2, 0)                           # [N, W, D] branch-major
            .reshape(n, width * depth)
        )
        root = jnp.take_along_axis(ctx, (length - 1)[:, None], axis=1)
        return jnp.concatenate([root.astype(jnp.int32), tree_tokens], axis=1)

    s = shardings
    return _serve_jit(
        draft_forward,
        in_shardings=None if s is None else s.rep(3),
        out_shardings=None if s is None else s.replicated,
    )

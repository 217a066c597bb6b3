"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

Reference surface: DeepSpeed-MoE passthrough only — ``transformer_moe_cls_names``
(``utils/dataclasses.py:792-798``) and ``set_moe_leaf_modules``
(``accelerator.py:1687``); the expert compute/dispatch lives in DeepSpeed CUDA.

TPU-native design (GShard/Switch dense formulation): routing produces static
``[tokens, experts, capacity]`` dispatch/combine tensors, expert ingestion and
combination are einsums (MXU work, no ragged gathers, no dynamic shapes), and
experts are a stacked leading axis sharded over ``ep`` — under jit, XLA lowers
the dispatch einsum against ``ep``-sharded experts to an all-to-all over ICI.
The router runs in fp32 (routing decisions are precision-sensitive) and the
Switch load-balancing aux loss is sown for the trainer to pick up.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..ops.grouped_matmul import grouped_applies, grouped_matmul


def top_k_dispatch(
    router_probs: jax.Array,  # [N, E] fp32
    num_experts_per_tok: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-k routing → dense dispatch/combine tensors.

    Returns ``dispatch [N, E, C]`` (0/1), ``combine [N, E, C]`` (gate-weighted)
    and the Switch aux loss (experts * Σ_e fraction_routed_e * mean_prob_e).
    Tokens beyond an expert's capacity are dropped (their combine weight is 0) —
    the residual connection carries them, standard Switch behavior.
    """
    n_tokens, n_experts = router_probs.shape
    gates, expert_idx = jax.lax.top_k(router_probs, num_experts_per_tok)  # [N, k]
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((n_tokens, n_experts, capacity), dtype=router_probs.dtype)
    combine = jnp.zeros_like(dispatch)
    counts = jnp.zeros((n_experts,), dtype=jnp.int32)
    for j in range(num_experts_per_tok):
        onehot = jax.nn.one_hot(expert_idx[:, j], n_experts, dtype=jnp.int32)  # [N, E]
        # position of each token within its expert's buffer, counting tokens
        # already placed by earlier choices
        pos_in_expert = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]  # [N, E]
        counts = counts + jnp.sum(onehot, axis=0)
        keep = (pos_in_expert < capacity) & (onehot > 0)  # [N, E]
        pos = jnp.sum(jnp.where(keep, pos_in_expert, 0), axis=1)  # [N]
        cap_onehot = jax.nn.one_hot(pos, capacity, dtype=router_probs.dtype)  # [N, C]
        disp_j = keep.astype(router_probs.dtype)[:, :, None] * cap_onehot[:, None, :]
        dispatch = dispatch + disp_j
        combine = combine + gates[:, j][:, None, None] * disp_j

    # Switch aux loss over top-1 assignments (Fedus et al. eq. 4)
    top1 = jax.nn.one_hot(expert_idx[:, 0], n_experts, dtype=router_probs.dtype)
    fraction_routed = jnp.mean(top1, axis=0)           # f_e
    mean_prob = jnp.mean(router_probs, axis=0)         # P_e
    aux_loss = n_experts * jnp.sum(fraction_routed * mean_prob)
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Drop-in MoE replacement for the dense MLP block (SwiGLU experts).

    Expert weights stack on a leading ``[num_experts, ...]`` axis — shard it
    over ``ep`` with :func:`shard_moe_params` and the dispatch einsums become
    all-to-alls under GSPMD.
    """

    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, h = x.shape
        n_tokens = b * s
        xf = x.reshape(n_tokens, h)

        # fp32 router (precision-sensitive; Switch recommendation)
        router_logits = nn.Dense(
            cfg.num_experts,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02),
            name="router",
        )(xf.astype(jnp.float32))
        router_probs = jax.nn.softmax(router_logits, axis=-1)

        capacity = cfg.resolved_expert_capacity(n_tokens)
        dispatch, combine, aux_loss = top_k_dispatch(
            router_probs, cfg.num_experts_per_tok, capacity
        )
        self.sow("intermediates", "router_aux_loss", aux_loss)

        dispatch = dispatch.astype(cfg.dtype)
        combine = combine.astype(cfg.dtype)
        expert_in = jnp.einsum("nec,nh->ech", dispatch, xf.astype(cfg.dtype))

        from ..models.transformer import MLP

        ExpertMLP = nn.vmap(
            MLP,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=0,
            out_axes=0,
            axis_size=cfg.num_experts,
        )
        expert_out = ExpertMLP(cfg, name="experts")(expert_in)  # [E, C, H]
        y = jnp.einsum("nec,ech->nh", combine, expert_out.astype(cfg.dtype))
        return y.reshape(b, s, h).astype(x.dtype)


# ------------------------------------------------------------------- dropless
# The layer the serving engine runs (``TransformerConfig.experts``): no
# capacity, no dropped token, and a device that holds only ``[lo, hi)`` of the
# experts.  Token-expert pairs are sorted by expert and the held experts'
# products are grouped matmuls over the sorted rows; pairs that fall on
# experts held elsewhere sort last and contribute nothing.  The dense dispatch
# above stays for the configurations trained with it on an ``ep`` mesh
# (``num_experts``: its einsums are what GSPMD turns into the all-to-all).


def route_top_k(scores: jax.Array, spec, bias=None) -> Tuple[jax.Array, jax.Array]:
    """``(experts [N, k] int32, gates [N, k] f32)`` from the router's
    ``scores [N, num_routed]`` (softmax or sigmoid, ``spec.score_func``):
    group-limited greedy choice (the ``top_k`` largest among the experts of the
    ``topk_group`` groups whose best score is largest; one group: plain
    top-k), gates ``scaling * score`` or, with ``norm_topk``, renormalised over
    the chosen (and then scaled where ``scale_normed``).  ``bias
    [num_routed]`` (``spec.select_bias``) moves the choice only: the ``top_k``
    largest ``score + bias`` are chosen and gated by their scores without it."""
    n, e = scores.shape
    masked = scores
    if spec.n_group > 1:
        best = jnp.max(scores.reshape(n, spec.n_group, e // spec.n_group), axis=-1)
        _, kept = jax.lax.top_k(best, spec.topk_group)
        allowed = jnp.any(jax.nn.one_hot(kept, spec.n_group, dtype=bool), axis=1)
        masked = jnp.where(jnp.repeat(allowed, e // spec.n_group, axis=1), scores, 0.0)
    if bias is None:
        gates, experts = jax.lax.top_k(masked, spec.top_k)
    else:
        _, experts = jax.lax.top_k(masked + bias, spec.top_k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if spec.norm_topk and spec.top_k > 1:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        if spec.scale_normed:
            gates = gates * spec.scaling
    else:
        gates = gates * spec.scaling
    return experts.astype(jnp.int32), gates


def held_experts_grouped(config) -> bool:
    """Whether the held experts' three products of ``config`` run in the Pallas
    grouped matmul (:mod:`accelerate_tpu.ops.grouped_matmul`) on this platform
    or as ``jax.lax.ragged_dot``: the engine's gauge ``serve/moe_grouped_kernel``.
    Up and down swap ``in`` and ``out``, so one answer holds for all three."""
    spec = config.experts
    return grouped_applies(jax.ShapeDtypeStruct((1, config.hidden_size), config.dtype),
                           jax.ShapeDtypeStruct((spec.num_held, config.hidden_size, spec.width), config.dtype))


class _ExpertProjection(nn.Module):
    """One projection of every held expert, ``kernel [held, in, out]``, applied
    to rows sorted by expert: a grouped matmul.  Its form is picked by what the
    call can observe (``grouped_applies``: bfloat16, widths of whole lanes, a
    TPU): the Pallas kernel, which reads each expert that got a row once and
    visits no row past the groups, or ``jax.lax.ragged_dot``."""

    shape: Tuple[int, int, int]
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, rows, group_sizes):
        kernel = self.param("kernel", nn.initializers.normal(0.02), self.shape, self.param_dtype).astype(self.dtype)
        dot = grouped_matmul if grouped_applies(rows, kernel) else jax.lax.ragged_dot
        return dot(rows, kernel, group_sizes)


class _HeldExperts(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, rows, group_sizes):
        cfg, spec = self.config, self.config.experts
        proj = lambda name, i, o: _ExpertProjection(
            (spec.num_held, i, o), cfg.dtype, cfg.param_dtype, name=name)
        gate = proj("gate_proj", cfg.hidden_size, spec.width)(rows, group_sizes)
        up = proj("up_proj", cfg.hidden_size, spec.width)(rows, group_sizes)
        return proj("down_proj", spec.width, cfg.hidden_size)(nn.silu(gate) * up, group_sizes)


class RoutedExperts(nn.Module):
    """Dropless routed experts plus a shared expert: ``y = sum_{e chosen, held
    here} g_e E_e(x) + S(x)`` (``config.experts``, an
    :class:`~accelerate_tpu.models.transformer.ExpertSpec`).

    The router (float32, highest precision: a choice is a comparison of
    nearly equal numbers) scores all ``num_routed`` experts (softmax or
    sigmoid, with a bias on the choice where the spec says so) and
    :func:`route_top_k` chooses among all of them; of the ``N * top_k`` pairs
    those on ``[lo, hi)`` are computed here, whatever their number: no
    capacity, so a token's result does not depend on what shares its batch.
    What experts held elsewhere would add is left out (on one device of an
    expert-parallel group this is the local partial sum; the exchange that
    completes it is not part of this layer).

    Sows ``routed_here [B, S, top_k]`` (the chosen expert's index among the
    held, ``-1`` where it is held elsewhere) into ``"intermediates"`` for the
    serving programs' counters; nothing is sown unless that collection is
    mutable."""

    config: Any

    @nn.compact
    def __call__(self, x):
        cfg, spec = self.config, self.config.experts
        b, s, h = x.shape
        xf = x.reshape(b * s, h)
        n, k = b * s, spec.top_k
        lo, hi = spec.held
        with jax.named_scope("moe/route"):
            logits = nn.Dense(
                spec.num_routed, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(0.02), precision=jax.lax.Precision.HIGHEST,
                name="router",
            )(xf.astype(jnp.float32))
            if spec.score_func == "sigmoid":
                scores = jax.nn.sigmoid(logits)
            else:
                scores = jax.nn.softmax(logits, axis=-1)
            bias = None
            if spec.select_bias:
                bias = self.param("expert_bias", nn.initializers.zeros,
                                  (spec.num_routed,), jnp.float32)
            # (a router without a bias is called as it always was)
            experts, gates = (route_top_k(scores, spec) if bias is None
                              else route_top_k(scores, spec, bias))
            here = (experts >= lo) & (experts < hi)
            local = jnp.where(here, experts - lo, spec.num_held)          # elsewhere sorts last
            self.sow("intermediates", "routed_here",
                     jnp.where(here, local, -1).reshape(b, s, k))
            order = jnp.argsort(local.reshape(-1), stable=True)           # pairs by held expert
            token_of = order // k
            group_sizes = jnp.sum(
                jax.nn.one_hot(local.reshape(-1), spec.num_held, dtype=jnp.int32), axis=0)
        with jax.named_scope("moe/experts"):
            rows = _HeldExperts(cfg, name="experts")(xf[token_of].astype(cfg.dtype), group_sizes)
            # rows past the groups (pairs held elsewhere) belong to no expert
            # and a ragged matmul leaves them unspecified (the TPU's writes
            # nothing there): select them away, a weight of 0 would keep a NaN
            in_group = jnp.arange(n * k) < jnp.sum(group_sizes)
            weight = gates.reshape(-1)[order]
            routed = jnp.zeros((n, h), jnp.float32).at[token_of].add(
                jnp.where(in_group[:, None], rows.astype(jnp.float32) * weight[:, None], 0.0))
        with jax.named_scope("moe/shared"):
            from ..models.transformer import MLP

            y = routed.astype(x.dtype)
            if spec.shared_width:
                y = y + MLP(cfg, spec.shared_width, name="shared")(xf).astype(x.dtype)
        return y.reshape(b, s, h)


def shard_moe_params(params, mesh: Mesh, *, marker: str = "experts"):
    """Shard stacked expert weights over the mesh's ``ep`` axis (leading expert
    dim, composed with ``fsdp`` on the largest remaining dim); non-expert leaves
    are left untouched.  No-op on meshes without an ``ep`` axis of size > 1.

    This is the standalone form of the placement the :class:`Accelerator`
    applies automatically in ``create_train_state`` — both delegate to
    :func:`..parallel.sharding.expert_partition_spec` for the actual spec.
    """
    from .sharding import expert_partition_spec
    from .tensor_parallel import path_to_str

    ep = mesh.shape.get("ep", 1)
    if ep <= 1:
        return params
    fsdp = mesh.shape.get("fsdp", 1)

    def place(path, x):
        if marker in path_to_str(path).split("/") and hasattr(x, "shape"):
            spec = expert_partition_spec(x.shape, ep, fsdp)
            return jax.device_put(x, NamedSharding(mesh, spec))
        return x

    return jax.tree_util.tree_map_with_path(place, params)


def router_aux_loss(intermediates, coef: float) -> jax.Array:
    """Sum sown ``router_aux_loss`` values * coef (trainer-side hook)."""
    total = jnp.float32(0.0)
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        last = path[-1]
        name = getattr(last, "key", getattr(last, "name", None))
        # sown values arrive as tuples under the 'router_aux_loss' key
        if name == "router_aux_loss" or any(
            getattr(p, "key", getattr(p, "name", None)) == "router_aux_loss" for p in path
        ):
            total = total + jnp.sum(leaf)
    return coef * total

"""Operations and bytes the published GPT-2 algorithm requires, from shapes.

``cfg`` is the ``published`` dict of a configuration file.  Causal attention
is counted once (a query at position p attends to p + 1 keys), nothing
recomputed is counted, and the head is counted only where a token's logits are
needed.  These are the yardstick's counts: whatever implements the step, the
same work is read.
"""

from __future__ import annotations


def _dims(cfg):
    d = cfg["n_embd"]
    return d, cfg["n_layer"], cfg.get("n_inner") or 4 * d, cfg["vocab_size"]


def parameter_count(cfg):
    d, n_layer, inner, vocab = _dims(cfg)
    per_layer = 4 * (d * d + d) + (d * inner + inner) + (inner * d + d) + 4 * d
    return vocab * d + cfg["n_positions"] * d + n_layer * per_layer + 2 * d


def block_matmul_params(cfg):
    d, n_layer, inner, _ = _dims(cfg)
    return n_layer * (4 * d * d + 2 * d * inner)


def forward_flops_token(cfg, context, with_head):
    """Forward FLOPs of one token that attends to ``context`` keys (itself
    included): 2 per matmul weight, 4 x n_embd per key and layer for the
    scores and the weighted sum, 2 x vocab x n_embd for its logits."""
    d, n_layer, _, vocab = _dims(cfg)
    flops = 2 * block_matmul_params(cfg) + 4 * d * context * n_layer
    return flops + (2 * vocab * d if with_head else 0)


def forward_flops_span(cfg, start, stop, heads):
    """Forward FLOPs of the tokens at positions ``start <= p < stop`` of one
    sequence, ``heads`` of which need their logits."""
    d, n_layer, _, vocab = _dims(cfg)
    n = stop - start
    keys = (start + 1 + stop) * n // 2            # sum of (p + 1)
    return 2 * block_matmul_params(cfg) * n + 4 * d * n_layer * keys + 2 * vocab * d * heads


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (3 x forward) FLOPs per trained token at
    ``seq_len``, every position's logits needed."""
    return 3 * forward_flops_span(cfg, 0, seq_len, seq_len) / seq_len


def kv_bytes_per_token(cfg, bytes_per_value=2):
    d, n_layer, _, _ = _dims(cfg)
    return 2 * n_layer * d * bytes_per_value


def weight_bytes(cfg, bytes_per_value=2):
    return parameter_count(cfg) * bytes_per_value


def decode_min_bytes(cfg, contexts, num_slots, bytes_per_value=2):
    """Least HBM bytes to emit one token for each entry of ``contexts`` (the
    keys it attends to): its cache once, and its share of one read of the
    weights by a full batch of ``num_slots`` lanes."""
    kv = kv_bytes_per_token(cfg, bytes_per_value)
    share = weight_bytes(cfg, bytes_per_value) / num_slots
    return sum(c * kv + share for c in contexts)

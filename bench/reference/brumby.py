"""Plain reference of the Brumby-14B block, in ``jax.numpy`` and float32.

Imports nothing of ``accelerate_tpu`` and takes nothing the program made: the
weights come from :func:`init_layer` / :func:`init_top` (the benchmark's own
seeded draw, which the harness also hands to the program through
:func:`to_program_tree`), the inputs from the harness.  ``cfg`` is the
``published`` dict of ``bench/configs/brumby-14b.json``: the keys of the
model's own ``config.json`` as they are run here, plus what the file lists
under ``assumed`` (``power_degree``, ``normaliser_eps``) and, at rehearsal
sizes, ``init_std`` (0.02 where absent).

The model (Brumby-14B-Base, Manifest AI; the layer is the power retention of
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239) is the Qwen3
pre-norm block, RMSNorm eps ``rms_norm_eps``, no biases, swiglu MLP, untied
head, with the softmax attention of EVERY layer replaced.  Per layer, for
``x_t = rmsnorm(h_t)``, query head ``h`` reading key/value head ``j = h //
(heads / kv_heads)``, ``d = head_dim``, ``p = power_degree``:

* ``q_t = W_q x_t``, ``k_t = W_k x_t``, ``v_t = W_v x_t``, ``a_t = W_g x_t`` (one
  gate a key/value head); ``q̂ = rope(rmsnorm_head(q) * w_qn, t)``, ``k̂`` alike
  (the norm over each head's ``d`` with one learned weight shared by the heads,
  then rotate-half rope: Qwen3's order);
* ``log g_t[j] = log_sigmoid(a_t[j])`` in float32;
* ``w[t, i] = exp(sum_{s=i+1..t} log g_s[j]) * (q̂_t[h] . k̂_i[j] / sqrt(d))^p``
  for ``i <= t``; ``y_t[h] = sum_i w[t, i] v_i[j] / (sum_i w[t, i] +
  normaliser_eps)``; ``o_t = W_o concat_h y_t[h]``.

**Attention form only**: the weights ``w`` of every pair are written out, a
block of queries at a time.  No state, no chunks, no cache: the program's
recurrent and chunked forms have to equal this.  The published checkpoint keeps
keys and values up to a switch-over length and a state after it; both are this
sum.

The stack at the published widths does not fit a chip in float32 beside its
own activations at leisure (a layer is 1.3 GB, the head 3.1 GB), so
:func:`forward_by_layer` draws and runs ONE layer at a time over all the rows
it is given; :func:`forward` runs a whole (tiny) model for the tests that hold
the two equal.

``precision`` selects the arithmetic of every matrix multiplication as in
``reference/gpt2.py``: ``"float32"`` (operands at ``Precision.HIGHEST``),
``"bfloat16"``, or ``"fp8"`` (e4m3 under a per-tensor scale, float32
accumulation): the *control*, the nearest precision below bfloat16.  The gate,
the decay and the normalisation stay float32 in all of them.

The counts at the end (``forward_flops_token``, ``forward_flops_span``,
``decode_least_bytes``) are the yardstick's: from shapes, at ``D = d (d + 1) /
2`` entries of the symmetric square (8,256; a layout may pad it), whatever
implements the step.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


# ---------------------------------------------------------------------- shapes
def dims(cfg):
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"], "width": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "degree": cfg.get("power_degree", 2)}


def layer_shapes(cfg):
    m = dims(cfg)
    d, hd = m["d"], m["head"]
    return {"ln1": (d,), "wq": (d, m["heads"] * hd), "wk": (d, m["kv_heads"] * hd), "wv": (d, m["kv_heads"] * hd),
            "wg": (d, m["kv_heads"]), "qn": (hd,), "kn": (hd,), "wo": (m["heads"] * hd, d), "ln2": (d,),
            "w_gate": (d, m["width"]), "w_up": (d, m["width"]), "w_down": (m["width"], d)}


def top_shapes(cfg):
    m = dims(cfg)
    return {"embed": (m["vocab"], m["d"]), "lnf": (m["d"],), "head": (m["d"], m["vocab"])}


def _count(shapes, names=None):
    return int(sum(np.prod(s) for k, s in shapes.items() if names is None or k in names))


def parameter_counts(cfg):
    """What the configuration file states: parameters of one layer, of the
    retention's own projections in it (q, k, v, o, gate), of the embedding, and
    of everything held here."""
    layer, top = layer_shapes(cfg), top_shapes(cfg)
    return {"layer": _count(layer), "retention": _count(layer, ("wq", "wk", "wv", "wo", "wg")),
            "embedding": _count(top, ("embed",)),
            "total": _count(top) + cfg["num_hidden_layers"] * _count(layer)}


def parameter_count(cfg):
    return parameter_counts(cfg)["total"]


def state_entries(cfg):
    """``D``: distinct entries of the feature map of one head."""
    hd = cfg["head_dim"]
    return hd if dims(cfg)["degree"] == 1 else hd * (hd + 1) // 2


def state_bytes_lane(cfg, bytes_per_value=4):
    """The recurrent state of one lane in every layer: ``S [D, d]`` and ``z
    [D]`` a key/value head, float32."""
    m = dims(cfg)
    return m["layers"] * m["kv_heads"] * state_entries(cfg) * (m["head"] + 1) * bytes_per_value


# --------------------------------------------------------------------- weights
_NORMS = ("ln1", "ln2", "lnf", "qn", "kn")


def _draw(key, shapes, cfg, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name in _NORMS:
            out[name] = jnp.ones(shape, dtype)
        else:
            std = cfg.get("init_std", 0.02)
            out[name] = (std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def _key(seed):
    if isinstance(seed, int):
        seed = np.uint32(seed % (2 ** 32))
    return jax.random.PRNGKey(seed)


def init_layer(seed, cfg, layer, dtype):
    """One layer's leaves, drawn from ``fold_in(seed, layer)`` (one key folded
    per leaf name): normal(0.02) for every matrix, the gate's included; ones
    for norm scales.  Traceable; ``layer`` is static."""
    return _draw(jax.random.fold_in(_key(seed), layer), layer_shapes(cfg), cfg, dtype)


def init_top(seed, cfg, dtype):
    """Embedding, final norm and head, from ``fold_in(seed, num_hidden_layers)``."""
    return _draw(jax.random.fold_in(_key(seed), cfg["num_hidden_layers"]), top_shapes(cfg), cfg, dtype)


def init_params(seed, cfg, dtype):
    """The whole model: ``{"top": ..., "layers": [...]}``."""
    return {"top": init_top(seed, cfg, dtype),
            "layers": [init_layer(seed, cfg, i, dtype) for i in range(cfg["num_hidden_layers"])]}


LAYER_PATHS = {
    "ln1": ("input_norm", "scale"), "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wg": ("attn", "g_proj", "kernel"), "qn": ("attn", "q_norm", "scale"),
    "kn": ("attn", "k_norm", "scale"), "wo": ("attn", "o_proj", "kernel"), "ln2": ("post_attn_norm", "scale"),
    "w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
    "w_down": ("mlp", "down_proj", "kernel"),
}
TOP_PATHS = {"embed": ("embed_tokens", "embedding"), "lnf": ("final_norm", "scale"), "head": ("lm_head", "kernel")}


def program_fields(cfg):
    """The program's configuration for ``cfg``, as plain keyword arguments of
    its ``TransformerConfig`` (the nested group as a dict; the types are added
    by whoever builds it)."""
    m = dims(cfg)
    return {
        "vocab_size": m["vocab"], "hidden_size": m["d"], "intermediate_size": m["width"],
        "num_layers": m["layers"], "num_heads": m["heads"], "num_kv_heads": m["kv_heads"], "head_dim": m["head"],
        "max_seq_len": cfg["max_position_embeddings"], "rope_theta": cfg["rope_theta"],
        "rms_norm_eps": cfg["rms_norm_eps"], "qk_norm": True,
        "retention": {"degree": m["degree"], "gate_heads": m["kv_heads"], "state_dtype": "float32",
                      "eps": cfg.get("normaliser_eps", 1e-6)},
    }


def to_program_tree(params, cfg):
    """The leaves of :func:`init_params` under the program's names
    (``layers_<i>/attn/q_proj/kernel`` and so on); nothing is transposed."""
    del cfg
    tree = {}

    def put(path, value):
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = value

    for name, path in TOP_PATHS.items():
        put(path, params["top"][name])
    for i, layer in enumerate(params["layers"]):
        for name, value in layer.items():
            put((f"layers_{i}",) + LAYER_PATHS[name], value)
    return tree


# ------------------------------------------------------------------ arithmetic
def _scaled_round(x, dtype, largest):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


_ROUND = {"float32": lambda x: x,
          "bfloat16": lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
          "fp8": lambda x: _scaled_round(x, jnp.float8_e4m3fn, 448.0)}


def _mm(spec, a, b, precision):
    r = _ROUND[precision]
    return jnp.einsum(spec, r(a), r(b), precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _swiglu(x, w_gate, w_up, w_down, precision):
    gate = _mm("td,di->ti", x, w_gate, precision)
    up = _mm("td,di->ti", x, w_up, precision)
    return _mm("ti,id->td", jax.nn.silu(gate) * up, w_down, precision)


def _rope(x, positions, theta):
    """``x [T, H, d]`` rotated by halves ``(i, i + d/2)`` at ``positions [T]``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------- blocks
def retention(x, p, cfg, precision="float32"):
    """Power retention over one row ``x [T, d]`` at positions ``0 .. T-1``,
    attention form."""
    m = dims(cfg)
    t, heads, kv_heads, hd = x.shape[0], m["heads"], m["kv_heads"], m["head"]
    groups = heads // kv_heads
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    q = _mm("td,de->te", h, p["wq"], precision).reshape(t, heads, hd)
    k = _mm("td,de->te", h, p["wk"], precision).reshape(t, kv_heads, hd)
    v = _mm("td,de->te", h, p["wv"], precision).reshape(t, kv_heads, hd)
    log_g = jax.nn.log_sigmoid(_mm("td,dj->tj", h, p["wg"], precision))          # [T, kv]
    pos = jnp.arange(t)
    q = _rope(_rms_norm(q, p["qn"], eps), pos, cfg["rope_theta"]).reshape(t, kv_heads, groups, hd)
    k = _rope(_rms_norm(k, p["kn"], eps), pos, cfg["rope_theta"])
    b = jnp.cumsum(log_g, axis=0)                                                 # sum_{s<=t} log g_s
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one(args):
        qb, qpos, bq = args                                                       # [Q,kv,G,d], [Q], [Q,kv]
        dots = _mm("qjgd,kjd->jgqk", qb, k, precision) * hd ** -0.5
        seen = pos[None, :] <= qpos[:, None]                                      # [Q, T]
        decay = jnp.exp(jnp.where(seen[None], bq.T[:, :, None] - b.T[:, None, :], -jnp.inf))   # [kv,Q,T]
        w = decay[:, None] * dots ** m["degree"]
        num = _mm("jgqk,kjd->qjgd", w, v, precision)
        den = jnp.sum(w, axis=-1).transpose(2, 0, 1)                              # [Q,kv,G]
        return num / (den[..., None] + cfg.get("normaliser_eps", 1e-6))

    split = lambda a: a.reshape((t // block, block) + a.shape[1:])
    y = jax.lax.map(one, (split(q), split(pos), split(b))).reshape(t, heads * hd)
    return _mm("te,ed->td", y, p["wo"], precision)


def layer_forward(x, p, cfg, precision="float32"):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = x + retention(x, p, cfg, precision)
    h = _rms_norm(x, p["ln2"], cfg["rms_norm_eps"])
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)


def head_logits(x, top, cfg, precision="float32"):
    top = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), top)
    return _mm("td,dv->tv", _rms_norm(x, top["lnf"], cfg["rms_norm_eps"]), top["head"], precision)


def forward(params, ids, cfg, precision="float32"):
    """Logits ``[T, vocab]`` (float32) of one row of token ids ``[T]``, the
    whole stack at once (tiny sizes)."""
    x = params["top"]["embed"].astype(jnp.float32)[ids]
    for p in params["layers"]:
        x = layer_forward(x, p, cfg, precision)
    return head_logits(x, params["top"], cfg, precision)


def json_key(cfg):
    """A hashable form of the ``published`` dict (static argument of a jit)."""
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("cfg_key", "layer", "dtype"))
def _init_layer_jit(seed, cfg_key, layer, dtype):
    return init_layer(seed, json.loads(cfg_key), layer, getattr(jnp, dtype))


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _init_top_jit(seed, cfg_key, dtype):
    return init_top(seed, json.loads(cfg_key), getattr(jnp, dtype))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"), donate_argnums=(0,))
def _layer_jit(x, p, cfg_key, precision):
    return layer_forward(x, p, json.loads(cfg_key), precision)


def forward_by_layer(seed, rows, cfg, dtype="float32", precisions=("float32",)):
    """Final hidden states of every row (all of one length) in every precision,
    one layer drawn and run at a time: ``({precision: [x [T, d]]}, top)``.  The
    weights are :func:`init_layer`'s in ``dtype``, read in float32."""
    key = json_key(cfg)
    seed = np.uint32(seed % (2 ** 32))
    top = _init_top_jit(seed, key, dtype)
    xs = {prec: [top["embed"][jnp.asarray(row)].astype(jnp.float32) for row in rows] for prec in precisions}
    for layer in range(cfg["num_hidden_layers"]):
        p = _init_layer_jit(seed, key, layer, dtype)
        for prec in precisions:
            xs[prec] = [_layer_jit(x, p, key, prec) for x in xs[prec]]
        del p
    return xs, top


# --------------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("cfg_key", "lower"))
def _gaps_jit(x, x_low, top, ids, n_prompt, n_total, cfg_key, lower):
    cfg = json.loads(cfg_key)
    logits = head_logits(x, top, cfg, "float32")
    best = jnp.max(logits, axis=-1)
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    picked = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    pos = jnp.arange(ids.shape[0])
    served = (pos >= n_prompt - 1) & (pos < n_total - 1)
    low = jnp.zeros_like(best)
    if lower is not None:
        low_best = jnp.argmax(head_logits(x_low, top, cfg, lower), axis=-1)
        low = best - jnp.take_along_axis(logits, low_best[:, None], axis=-1)[:, 0]
    return jnp.where(served, best - picked, 0.0), jnp.where(served, low, 0.0), served


def served_token_gaps(seed, samples, cfg, dtype="float32", lower=None, multiple=QUERY_BLOCK):
    """Teacher-forced passes over ``prompt + served`` of every ``(prompt,
    served)`` in ``samples``, all padded to one width (the longest, rounded up
    to ``multiple``; the weights are causal, so the padding stays out of what
    is read).

    Returns a list of ``(gaps, lower_gaps)``: for each served token how far its
    float32 reference logit lies below the reference's best at that position;
    and, where ``lower`` names a precision, the same gap for the token that the
    lower precision puts first there (the control)."""
    width = max(len(p) + len(s) for p, s in samples)
    width = -(-width // multiple) * multiple
    rows = []
    for prompt, served in samples:
        ids = np.zeros((width,), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(served)] = served
        rows.append(ids)
    precisions = ("float32",) + ((lower,) if lower else ())
    xs, top = forward_by_layer(seed, rows, cfg, dtype, precisions)
    out = []
    for i, (prompt, served) in enumerate(samples):
        x_low = xs[lower][i] if lower else xs["float32"][i]
        gaps, low, mask = _gaps_jit(xs["float32"][i], x_low, top, jnp.asarray(rows[i]), len(prompt),
                                    len(prompt) + len(served), json_key(cfg), lower)
        mask = np.asarray(mask)
        out.append((np.asarray(gaps)[mask], np.asarray(low)[mask] if lower else None))
    return out


# ---------------------------------------------------------------------- counts
def matmul_params_token(cfg):
    """Matmul weights one token passes through: the retention's projections and
    the MLP of every layer, and the head."""
    m = dims(cfg)
    counts = parameter_counts(cfg)
    return {"blocks": m["layers"] * (counts["retention"] + 3 * m["d"] * m["width"]), "head": m["d"] * m["vocab"]}


def retention_flops_token(cfg):
    """FLOPs of the retention itself for one token in every layer, as the
    recurrent form needs them: the state's update (``D x d`` multiply-adds a
    key/value head), every query head's read of it, and the normaliser's two.
    Nothing depends on the context."""
    m = dims(cfg)
    entries = state_entries(cfg)
    return m["layers"] * 2 * entries * (m["head"] + 1) * (m["kv_heads"] + m["heads"])


def forward_flops_token(cfg, context, with_head):
    """Forward FLOPs of one token; ``context`` is taken for the harness's sake
    and moves nothing."""
    del context
    w = matmul_params_token(cfg)
    return 2 * w["blocks"] + retention_flops_token(cfg) + (2 * w["head"] if with_head else 0)


def forward_flops_span(cfg, start, stop, heads):
    """Forward FLOPs of the tokens at positions ``start <= p < stop`` of one
    sequence, ``heads`` of which need their logits."""
    w = matmul_params_token(cfg)
    return (2 * w["blocks"] + retention_flops_token(cfg)) * (stop - start) + 2 * w["head"] * heads


def dense_weight_bytes(cfg, bytes_per_value=2):
    """One read of everything a decode step reads of the weights: all held but
    the embedding table (a step reads a row of it a lane)."""
    m = dims(cfg)
    return (parameter_count(cfg) - m["vocab"] * m["d"]) * bytes_per_value


def decode_least_bytes(cfg, contexts, num_slots, bytes_per_value=2):
    """Least HBM bytes to emit one token for each entry of ``contexts`` (only
    counted: nothing depends on a context): its share of one read of the
    weights by a full batch of ``num_slots`` lanes, and one read and one write
    of its lane's float32 state."""
    share = dense_weight_bytes(cfg, bytes_per_value) / num_slots
    return len(contexts) * (share + 2 * state_bytes_lane(cfg))

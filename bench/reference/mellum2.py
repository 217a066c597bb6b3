"""Plain reference of the published Mellum2-12B-A2.5B block (``model_type:
mellum``), in ``jax.numpy`` and float32.

Imports nothing of ``accelerate_tpu`` and takes nothing the program made: the
weights come from :func:`init_layer` / :func:`init_top` (the benchmark's own
seeded draw, which the harness also hands to the program through
:func:`to_program_tree`), the inputs from the harness.  ``cfg`` is the
``published`` dict of ``bench/configs/mellum2-12b.json``: the keys of the
model's own ``config.json`` as they are run here, plus ``experts_held`` (the
``[lo, hi)`` of the routed experts this chip holds; the router keeps
``num_experts`` outputs) and, at rehearsal sizes, ``init_std`` (0.02 where
absent).

The model, per layer ``l`` with input ``x [T, d]`` (RMSNorm eps
``rms_norm_eps`` with a learned scale, no biases, ``h0 = Embed(ids)``):

* **Attention.**  ``a = RMS_in(x)``; ``q = a Wq`` (``num_attention_heads``
  heads of ``head_dim``), ``k = a Wk``, ``v = a Wv`` (``num_key_value_heads``
  heads).  ``q`` and ``k`` are rms-normed over each head's width with one
  learned scale of ``head_dim`` each (Qwen3-MoE's head norm: the file lists it
  under ``assumed``), then rotated, rotate-half over the whole head, by the
  rope of the layer's kind (``rope_parameters[layer_types[l]]``): a
  ``sliding_attention`` layer plain rope at its ``rope_theta``, a
  ``full_attention`` layer YaRN (``rope_type: yarn``: the frequencies
  ``theta^(-2j/D)`` blended towards ``/ factor`` by the linear ramp between
  the floored and ceiled correction dimensions of ``beta_fast`` and
  ``beta_slow`` over ``original_max_position_embeddings``, as Hugging Face's
  ``_compute_yarn_parameters`` computes them; cos and sin both multiplied by
  ``attention_factor``, or ``0.1 ln factor + 1`` where it is absent).  A
  sliding layer's query ``i`` sees key ``j`` iff ``0 <= i - j <
  sliding_window``, a full layer's iff ``j <= i``.  ``o = softmax(q k^T /
  sqrt(head_dim)) v`` in float32, query head ``h`` reading key/value head ``h
  // (heads / kv_heads)``; ``h = x + o Wo``.
* **Experts** (every layer: ``mlp_layer_types`` are all ``sparse``).  ``u =
  RMS_post(h)``; ``p = softmax(u Wr)`` in float32 over all ``num_experts``;
  ``E = top-k(p)``; ``g_e = p_e / sum_{e in E} p_e`` (``norm_topk_prob``);
  ``y = h + sum_{e in E} g_e W2_e(silu(W1_e u) * W3_e u)`` of
  ``moe_intermediate_size``.  No shared expert; no token is dropped.  **The
  share:** only experts ``lo <= e < hi`` are held, the sum runs over the
  chosen experts among them (the cell holds all of them: ``[0, 64)``), by a
  loop over the held experts of masked dense products: no sort, no ragged
  product.
* Final rmsnorm, untied head.

Departures from the published model: no multi-token-prediction head (the
config has no key for it and serving does not read it); no cache, no pages,
no batch.  Everything past the keys and values is a function of one row of
``x``, so a layer runs in blocks of ``QUERY_BLOCK`` queries
(:func:`layer_forward`).  At the published widths eight layers are 15.2 GB in
float32, so :func:`forward_by_layer` draws and runs ONE layer at a time;
:func:`forward` runs a whole (tiny) model for the tests that hold the two
equal.

``precision`` selects the arithmetic of every matrix multiplication as in
``reference/gpt2.py``: ``"float32"`` (operands at ``Precision.HIGHEST``),
``"bfloat16"``, or ``"fp8"`` (e4m3 under a per-tensor scale, float32
accumulation): the *control*, the nearest precision below bfloat16.

The counts at the end (``forward_flops_token``, ``forward_flops_span``,
``decode_least_bytes``) are the yardstick's: from shapes, whatever implements
the step.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
NORMS = ("ln_in", "ln_post", "q_norm", "k_norm", "lnf")


# ---------------------------------------------------------------------- shapes
def dims(cfg):
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"], "window": cfg["sliding_window"],
        "expert_width": cfg["moe_intermediate_size"], "routed": cfg["num_experts"], "lo": int(lo),
        "hi": int(hi), "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def is_window_layer(cfg, layer):
    return cfg["layer_types"][layer] == "sliding_attention"


def layer_shapes(cfg):
    """Leaf name -> shape of one layer (every layer is alike but for its
    attention's kind).  Routed experts are stacked on a leading axis of the
    ``hi - lo`` held here."""
    m = dims(cfg)
    d, q, kv, held, we = m["d"], m["heads"] * m["hd"], m["kv_heads"] * m["hd"], m["hi"] - m["lo"], m["expert_width"]
    return {"ln_in": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d), "q_norm": (m["hd"],),
            "k_norm": (m["hd"],), "ln_post": (d,), "router": (d, m["routed"]),
            "e_gate": (held, d, we), "e_up": (held, d, we), "e_down": (held, we, d)}


def top_shapes(cfg):
    m = dims(cfg)
    return {"embed": (m["vocab"], m["d"]), "lnf": (m["d"],), "head": (m["d"], m["vocab"])}


def _count(shapes, names=None):
    return int(sum(np.prod(s) for k, s in shapes.items() if names is None or k in names))


def parameter_counts(cfg):
    """What the configuration's file states: parameters of the attention of a
    layer (norms apart), of one routed expert, of the router, of a layer, of
    the embedding and head together, and of everything held here."""
    m = dims(cfg)
    layer = layer_shapes(cfg)
    return {"attention": _count(layer, ("wq", "wk", "wv", "wo")),
            "expert": 3 * m["d"] * m["expert_width"],
            "router": _count(layer, ("router",)),
            "norms": _count(layer, ("ln_in", "ln_post", "q_norm", "k_norm")),
            "layer": _count(layer),
            "embed_and_head": _count(top_shapes(cfg), ("embed", "head")),
            "total": _count(top_shapes(cfg)) + m["layers"] * _count(layer)}


def parameter_count(cfg):
    return parameter_counts(cfg)["total"]


# --------------------------------------------------------------------- weights
def _draw(key, shapes, cfg, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name in NORMS:
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
    per leaf name): normal(``init_std``) for every matrix, the router
    included, ones for norm scales.  Traceable; ``layer`` is static."""
    return _draw(jax.random.fold_in(_key(seed), layer), layer_shapes(cfg), cfg, dtype)


def init_top(seed, cfg, dtype):
    """Embedding, final norm and head, from ``fold_in(seed, num_hidden_layers)``."""
    return _draw(jax.random.fold_in(_key(seed), cfg["num_hidden_layers"]), top_shapes(cfg), cfg, dtype)


def init_params(seed, cfg, dtype):
    """The whole model: ``{"top": ..., "layers": [...]}``.  At the published
    widths only in bfloat16 (what the program holds); the float32 reference
    goes layer by layer."""
    return {"top": init_top(seed, cfg, dtype),
            "layers": [init_layer(seed, cfg, i, dtype) for i in range(cfg["num_hidden_layers"])]}


LAYER_PATHS = {
    "ln_in": ("input_norm", "scale"), "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
    "ln_post": ("post_attn_norm", "scale"), "router": ("moe_mlp", "router", "kernel"),
    "e_gate": ("moe_mlp", "experts", "gate_proj", "kernel"), "e_up": ("moe_mlp", "experts", "up_proj", "kernel"),
    "e_down": ("moe_mlp", "experts", "down_proj", "kernel"),
}
TOP_PATHS = {"embed": ("embed_tokens", "embedding"), "lnf": ("final_norm", "scale"), "head": ("lm_head", "kernel")}


def yarn_of(cfg):
    """The full layers' YaRN as the program's ``YarnScaling`` fields."""
    y = cfg["rope_parameters"]["full_attention"]
    return {"factor": y["factor"], "original_max_position": y["original_max_position_embeddings"],
            "beta_fast": y["beta_fast"], "beta_slow": y["beta_slow"]}


def program_fields(cfg):
    """The program's configuration for ``cfg``, as plain keyword arguments of
    its ``TransformerConfig`` (nested groups as dicts; the types are added by
    whoever builds it)."""
    m = dims(cfg)
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    rope = cfg["rope_parameters"]
    return {
        "vocab_size": m["vocab"], "hidden_size": m["d"], "intermediate_size": cfg["intermediate_size"],
        "num_layers": m["layers"], "num_heads": m["heads"], "num_kv_heads": m["kv_heads"], "head_dim": m["hd"],
        "max_seq_len": cfg["max_position_embeddings"], "rope_theta": rope["sliding_attention"]["rope_theta"],
        "rms_norm_eps": cfg["rms_norm_eps"], "sliding_window": m["window"],
        "layer_types": [kinds[k] for k in cfg["layer_types"]], "qk_norm": True,
        "full_rope": {"theta": rope["full_attention"]["rope_theta"], "yarn": yarn_of(cfg)},
        "experts": {"num_routed": m["routed"], "held": [m["lo"], m["hi"]], "top_k": m["top_k"],
                    "width": m["expert_width"], "norm_topk": bool(cfg["norm_topk_prob"]),
                    "score_func": "softmax", "shared_width": 0, "dense_layers": 0},
    }


def to_program_tree(params, cfg):
    """The leaves of :func:`init_params` under the program's names
    (``layers_<i>/attn/q_proj/kernel`` and so on); nothing is transposed."""
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
    return jnp.einsum(spec, r(a.astype(jnp.float32)), r(b.astype(jnp.float32)),
                      precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g.astype(jnp.float32)


def _swiglu(x, w_gate, w_up, w_down, precision):
    gate = _mm("td,di->ti", x, w_gate, precision)
    up = _mm("td,di->ti", x, w_up, precision)
    return _mm("ti,id->td", jax.nn.silu(gate) * up, w_down, precision)


# ------------------------------------------------------------------------ rope
def rope_inv_freq(rope, d):
    """The ``d / 2`` rotary frequencies of one kind's ``rope_parameters``
    entry, in float64: ``theta^(-2j/d)``, and for ``rope_type: yarn`` blended
    towards ``/ factor`` as Hugging Face's ``_compute_yarn_parameters`` does
    (``truncate``: the correction range floored and ceiled)."""
    base = float(rope["rope_theta"])
    inv = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope.get("rope_type", "default") == "default":
        return inv
    length = rope["original_max_position_embeddings"]

    def corr(beta):
        return d * math.log(length / (beta * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    return (inv / rope["factor"]) * (1.0 - extrapolation) + inv * extrapolation


def rope_amplitude(rope):
    """What cos and sin are multiplied by: YaRN's ``attention_factor`` (``0.1
    ln factor + 1`` where the entry has none); 1 for plain rope."""
    if rope.get("rope_type", "default") == "default":
        return 1.0
    if rope.get("attention_factor") is not None:
        return float(rope["attention_factor"])
    return 0.1 * math.log(rope["factor"]) + 1.0


def _rope(x, positions, rope):
    """``x [T, H, D]`` rotated at ``positions [T]``, rotate-half: channel ``i``
    pairs with ``i + D/2``."""
    d = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(rope_inv_freq(rope, d), jnp.float32)
    amp = rope_amplitude(rope)
    cos, sin = (jnp.cos(angles) * amp)[:, None, :], (jnp.sin(angles) * amp)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope_of(cfg, layer):
    return cfg["rope_parameters"][cfg["layer_types"][layer]]


# ---------------------------------------------------------------------- blocks
def keys_values(x, p, cfg, layer, precision="float32"):
    """``(k [T, kv_heads, hd], v)`` of the whole sequence at positions ``0 ..
    T-1``: the head norm on ``k``, then the layer kind's rope."""
    m = dims(cfg)
    t = x.shape[0]
    a = _rms_norm(x, p["ln_in"], cfg["rms_norm_eps"])
    k = _mm("td,de->te", a, p["wk"], precision).reshape(t, m["kv_heads"], m["hd"])
    v = _mm("td,de->te", a, p["wv"], precision).reshape(t, m["kv_heads"], m["hd"])
    k = _rope(_rms_norm(k, p["k_norm"], cfg["rms_norm_eps"]), jnp.arange(t), rope_of(cfg, layer))
    return k, v


def attend(x, positions, k, v, p, cfg, layer, precision="float32"):
    """``o Wo`` of the queries ``x [Q, d]`` at ``positions [Q]`` against the
    whole sequence's ``k``, ``v``: the mask written out."""
    m = dims(cfg)
    n, rep = x.shape[0], m["heads"] // m["kv_heads"]
    a = _rms_norm(x, p["ln_in"], cfg["rms_norm_eps"])
    q = _mm("td,de->te", a, p["wq"], precision).reshape(n, m["heads"], m["hd"])
    q = _rope(_rms_norm(q, p["q_norm"], cfg["rms_norm_eps"]), positions, rope_of(cfg, layer))
    q = q.reshape(n, m["kv_heads"], rep, m["hd"])
    scores = _mm("qhrc,khc->hrqk", q, k, precision) * m["hd"] ** -0.5
    behind = positions[:, None] - jnp.arange(k.shape[0])[None, :]            # i - j
    seen = behind >= 0
    if is_window_layer(cfg, layer):
        seen = seen & (behind < m["window"])
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    o = _mm("hrqk,khc->qhrc", probs, v, precision).reshape(n, m["heads"] * m["hd"])
    return _mm("te,ed->td", o, p["wo"], precision)


def route(probs, cfg):
    """``(experts [T, k], gates [T, k])`` from the router's float32 softmax
    ``probs [T, num_experts]``: the ``k`` largest, gated by their
    probabilities renormalised over the chosen (``norm_topk_prob``)."""
    gates, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return experts, gates


def router_probs(u, p, cfg, precision="float32"):
    return jax.nn.softmax(_mm("td,de->te", u, p["router"], precision), axis=-1)


def expert_layer(u, p, cfg, precision="float32"):
    """``sum_{e chosen, lo <= e < hi} g_e swiglu_e(u)`` and the choices."""
    m = dims(cfg)
    experts, gates = route(router_probs(u, p, cfg, precision), cfg)

    def one(acc, held):
        e, w_gate, w_up, w_down = held
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)                # [T], 0 where not chosen
        return acc + weight[:, None] * _swiglu(u, w_gate, w_up, w_down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(m["lo"], m["hi"]), p["e_gate"], p["e_up"],
                                                      p["e_down"]))
    return routed, experts, gates


def block_forward(x, positions, k, v, p, cfg, layer, precision="float32"):
    """The layer's output for the rows ``x [Q, d]`` at ``positions``, given the
    whole sequence's keys and values."""
    h = x + attend(x, positions, k, v, p, cfg, layer, precision)
    return h + expert_layer(_rms_norm(h, p["ln_post"], cfg["rms_norm_eps"]), p, cfg, precision)[0]


def layer_forward(x, p, cfg, layer, precision="float32"):
    """One layer over the whole sequence ``x [T, d]``: keys and values first,
    then the queries in blocks of ``QUERY_BLOCK`` (all at once where ``T`` is
    no multiple of it)."""
    t = x.shape[0]
    k, v = keys_values(x, p, cfg, layer, precision)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    split = lambda a: a.reshape((t // block, block) + a.shape[1:])
    out = jax.lax.map(lambda args: block_forward(args[0], args[1], k, v, p, cfg, layer, precision),
                      (split(x), split(jnp.arange(t))))
    return out.reshape(t, x.shape[1])


def embed(top, ids):
    return top["embed"].astype(jnp.float32)[ids]


def head_logits(x, top, cfg, precision="float32"):
    return _mm("td,dv->tv", _rms_norm(x, top["lnf"], cfg["rms_norm_eps"]), top["head"], precision)


def forward(params, ids, cfg, precision="float32"):
    """Logits ``[T, vocab]`` (float32) of one row of token ids ``[T]``, the
    whole stack at once (tiny sizes)."""
    x = embed(params["top"], ids)
    for i, p in enumerate(params["layers"]):
        x = layer_forward(x, p, cfg, i, precision)
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


def _kind_layer(cfg, window):
    """The first layer of the cut of this kind: one program for every layer
    of a kind."""
    return next(i for i in range(cfg["num_hidden_layers"]) if is_window_layer(cfg, i) == window)


@functools.partial(jax.jit, static_argnames=("cfg_key", "window", "precision"), donate_argnums=(0,))
def _layer_jit(x, p, cfg_key, window, precision):
    cfg = json.loads(cfg_key)
    return layer_forward(x, p, cfg, _kind_layer(cfg, window), precision)


def forward_by_layer(seed, rows, cfg, dtype="float32", precisions=("float32",)):
    """Final hidden states of every row (all of one length) in every precision,
    one layer drawn and run at a time: ``({precision: [x [T, d]]}, top)``.  The
    weights are :func:`init_layer`'s in ``dtype``, read in float32."""
    key = json_key(cfg)
    seed = np.uint32(seed % (2 ** 32))
    top = _init_top_jit(seed, key, dtype)
    xs = {prec: [embed(top, jnp.asarray(row)) for row in rows] for prec in precisions}
    for layer in range(cfg["num_hidden_layers"]):
        p = _init_layer_jit(seed, key, layer, dtype)
        for prec in precisions:
            xs[prec] = [_layer_jit(x, p, key, is_window_layer(cfg, layer), prec) for x in xs[prec]]
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
    to ``multiple``; causal attention keeps the padding out of what is read).
    Only the head's rows that are read (from the last prompt token on) go
    through the vocabulary.

    Returns a list of ``(gaps, lower_gaps)``: for each served token how far its
    float32 reference logit lies below the reference's best at that position;
    and, where ``lower`` names a precision, the same gap for the token that the
    lower precision puts first there (the control)."""
    width = max(len(p) + len(s) for p, s in samples)
    width = -(-width // multiple) * multiple
    tail = max(len(s) for _, s in samples) + 1
    tail = min(width, -(-tail // multiple) * multiple)
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
        # the head over the ``tail`` rows that hold the served positions
        start = min(max(len(prompt) - 1, 0), width - tail)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, tail, axis=0)
        x_low = xs[lower][i] if lower else xs["float32"][i]
        gaps, low, mask = _gaps_jit(cut(xs["float32"][i]), cut(x_low), top,
                                    jnp.asarray(rows[i][start:start + tail]), len(prompt) - start,
                                    len(prompt) + len(served) - start, json_key(cfg), lower)
        mask = np.asarray(mask)
        out.append((np.asarray(gaps)[mask], np.asarray(low)[mask] if lower else None))
    return out


# ---------------------------------------------------------------------- counts
def layer_kinds(cfg):
    """``(window layers, full layers)`` of the cut."""
    n_window = sum(1 for i in range(cfg["num_hidden_layers"]) if is_window_layer(cfg, i))
    return n_window, cfg["num_hidden_layers"] - n_window


def matmul_params_token(cfg):
    """Matmul weights one token passes through, by part: the attention, the
    router and ``top_k * held / routed`` routed experts of every layer (from
    shapes: the share of the choices that falls here when the router is
    balanced), and the head."""
    m = dims(cfg)
    counts = parameter_counts(cfg)
    here = m["top_k"] * (m["hi"] - m["lo"]) / m["routed"]
    return {"blocks": m["layers"] * (counts["attention"] + counts["router"] + here * counts["expert"]),
            "head": m["d"] * m["vocab"]}


def keys_seen(cfg, context):
    """Keys a query that attends to ``context`` positions (itself included)
    sees, summed over the layers: ``min(context, window)`` in each window
    layer, ``context`` in each full one."""
    n_window, n_full = layer_kinds(cfg)
    return n_window * min(context, cfg["sliding_window"]) + n_full * context


def attention_flops_key(cfg):
    """FLOPs of one query against one key in one layer: the score and the
    weighted sum, every query head."""
    m = dims(cfg)
    return 2 * m["heads"] * 2 * m["hd"]


def forward_flops_token(cfg, context, with_head):
    """Forward FLOPs of one token that attends to ``context`` keys (itself
    included)."""
    w = matmul_params_token(cfg)
    return (2 * w["blocks"] + attention_flops_key(cfg) * keys_seen(cfg, context)
            + (2 * w["head"] if with_head else 0))


def forward_flops_span(cfg, start, stop, heads):
    """Forward FLOPs of the tokens at positions ``start <= p < stop`` of one
    sequence, ``heads`` of which need their logits."""
    w = matmul_params_token(cfg)
    n_window, n_full = layer_kinds(cfg)
    ctx = np.arange(start, stop, dtype=np.int64) + 1
    keys = n_full * int(ctx.sum()) + n_window * int(np.minimum(ctx, cfg["sliding_window"]).sum())
    return 2 * w["blocks"] * (stop - start) + attention_flops_key(cfg) * keys + 2 * w["head"] * heads


def cache_row_bytes(cfg, bytes_per_value=2):
    """The keys and values of one token in ONE layer."""
    m = dims(cfg)
    return 2 * m["kv_heads"] * m["hd"] * bytes_per_value


def expert_bytes(cfg, bytes_per_value=2):
    return parameter_counts(cfg)["expert"] * bytes_per_value


def dense_weight_bytes(cfg, bytes_per_value=2):
    """One read of everything a decode step reads whatever the routing: all
    weights held but the routed experts and the embedding table (a step reads
    a row of it a lane)."""
    m = dims(cfg)
    routed = m["layers"] * (m["hi"] - m["lo"]) * parameter_counts(cfg)["expert"]
    return (parameter_count(cfg) - routed - m["vocab"] * m["d"]) * bytes_per_value


def decode_least_bytes(cfg, contexts, num_slots, experts_hit, rows_live=None, bytes_per_value=2):
    """Least HBM bytes to emit one token for each entry of ``contexts``: the
    rows of the cache its query sees (``min(context, window)`` in each window
    layer, ``context`` in the full ones; or ``rows_live``, the program's own
    count of them summed over the steps, where given), its share of one read
    of the non-expert weights and the head by a full batch of ``num_slots``
    lanes, and one read of each routed expert that got a token
    (``experts_hit``: summed over the steps and layers, the program's own
    counter)."""
    rows = sum(keys_seen(cfg, c) for c in contexts) if rows_live is None else rows_live
    share = dense_weight_bytes(cfg, bytes_per_value) / num_slots
    return (rows * cache_row_bytes(cfg, bytes_per_value) + share * len(contexts)
            + experts_hit * expert_bytes(cfg, bytes_per_value))

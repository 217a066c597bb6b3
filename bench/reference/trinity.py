"""Plain reference of the published Trinity-Large block (``model_type: afmoe``),
in ``jax.numpy`` and float32.

Imports nothing of ``accelerate_tpu`` and takes nothing the program made: the
weights come from :func:`init_layer` / :func:`init_top` (the benchmark's own
seeded draw, which the harness also hands to the program through
:func:`to_program_tree`), the inputs from the harness.  ``cfg`` is the
``published`` dict of ``bench/configs/trinity-large.json``: the keys of the
model's own ``config.json`` as they are run here, plus ``experts_held`` (the
``[lo, hi)`` of the routed experts this chip holds; the router keeps
``num_experts`` outputs), ``router_init_std``, ``expert_bias_std`` and, at
rehearsal sizes, ``init_std`` (0.02 where absent).

The model, per layer ``l`` with input ``x [T, d]`` (RMSNorm eps
``rms_norm_eps``, no biases; ``h0 = Embed(ids) * sqrt(d)`` where
``mup_enabled``):

* **Attention.**  ``a = RMS_in(x)``; ``q = a Wq`` (``num_attention_heads``
  heads of ``head_dim``), ``k = a Wk``, ``v = a Wv`` (``num_key_value_heads``
  heads), ``g = a Wg`` (one value a query head and channel).  ``q`` and ``k``
  are rms-normed over each head's width with one learned scale of ``head_dim``
  each.  A ``sliding_attention`` layer rotates ``q`` and ``k`` (rotate-half
  over the whole head, ``rope_theta``) and query ``i`` sees key ``j`` iff ``0
  <= i - j < sliding_window``; a ``full_attention`` layer has NO positional
  encoding and the causal mask alone.  ``o = softmax(q k^T / sqrt(head_dim))
  v`` in float32, query head ``h`` reading key/value head ``h // (heads /
  kv_heads)``; ``o <- o * sigmoid(g)``; ``h = x + RMS_post_attn(o Wo)``.
* **MLP.**  ``m = RMS_pre_mlp(h)``; ``y = h + RMS_post_mlp(F(m))``.  In the
  ``num_dense_layers`` leading layers ``F`` is a swiglu MLP of
  ``intermediate_size``.  In the others ``s = sigmoid(m Wr)`` in float32 over
  all ``num_experts``; ``E = top-k(s + b)`` (``b`` the selection bias, a
  buffer: it moves the choice and never the gates); ``w_e = s_e / (sum_{e in
  E} s_e + 1e-20) * route_scale``; ``F(m) = sum_{e in E} w_e swiglu_e(m) +
  swiglu_shared(m)``, both of ``moe_intermediate_size``.  No token is dropped.
  **The share:** only experts ``lo <= e < hi`` are held, the sum runs over the
  chosen experts among them, and what the absent ones would have added is
  left out (a loop over the held experts by masked dense products: no sort, no
  ragged product).
* Final rmsnorm, untied head.

Everything past the keys and values is a function of one row of ``x``, so a
layer runs in blocks of ``QUERY_BLOCK`` queries (:func:`layer_forward`): the
keys and values of the whole sequence first (8 heads: small), then block by
block the queries, the band or causal mask written out, the gate, the norms
and the MLP.  At the published widths the stack does not fit a chip in
float32 beside 25 k tokens, so :func:`forward_by_layer` draws and runs ONE
layer at a time; :func:`forward` runs a whole (tiny) model for the tests that
hold the two equal.  No cache, no pages, no batch.

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

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
NORMS = ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp", "q_norm", "k_norm", "lnf")


# ---------------------------------------------------------------------- shapes
def dims(cfg):
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"], "window": cfg["sliding_window"],
        "dense_width": cfg["intermediate_size"], "expert_width": cfg["moe_intermediate_size"],
        "routed": cfg["num_experts"], "lo": int(lo), "hi": int(hi), "top_k": cfg["num_experts_per_tok"],
        "shared_width": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        "dense_layers": cfg["num_dense_layers"], "vocab": cfg["vocab_size"],
    }


def is_expert_layer(cfg, layer):
    return layer >= cfg["num_dense_layers"]


def is_window_layer(cfg, layer):
    return cfg["layer_types"][layer] == "sliding_attention"


def attention_shapes(cfg):
    m = dims(cfg)
    d, q, kv = m["d"], m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    return {"ln_in": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wg": (d, q), "wo": (q, d),
            "q_norm": (m["hd"],), "k_norm": (m["hd"],), "ln_post_attn": (d,), "ln_pre_mlp": (d,),
            "ln_post_mlp": (d,)}


def layer_shapes(cfg, layer):
    """Leaf name -> shape of one layer.  Routed experts are stacked on a leading
    axis of the ``hi - lo`` held here."""
    m = dims(cfg)
    d = m["d"]
    out = attention_shapes(cfg)
    if not is_expert_layer(cfg, layer):
        w = m["dense_width"]
        out.update({"w_gate": (d, w), "w_up": (d, w), "w_down": (w, d)})
        return out
    held, we, ws = m["hi"] - m["lo"], m["expert_width"], m["shared_width"]
    out.update({"router": (d, m["routed"]), "e_bias": (m["routed"],),
                "e_gate": (held, d, we), "e_up": (held, d, we), "e_down": (held, we, d),
                "s_gate": (d, ws), "s_up": (d, ws), "s_down": (ws, d)})
    return out


def top_shapes(cfg):
    m = dims(cfg)
    return {"embed": (m["vocab"], m["d"]), "lnf": (m["d"],), "head": (m["d"], m["vocab"])}


def _count(shapes, names=None):
    return int(sum(np.prod(s) for k, s in shapes.items() if names is None or k in names))


def parameter_counts(cfg):
    """What the table of the configuration states: parameters of the attention
    of a layer (norms apart), of one routed expert, of the dense and of an
    expert layer, and of everything held here (the selection bias is a buffer
    and is counted with the layer that holds it)."""
    m = dims(cfg)
    out = {"attention": _count(attention_shapes(cfg), ("wq", "wk", "wv", "wg", "wo")),
           "expert": 3 * m["d"] * m["expert_width"],
           "total": _count(top_shapes(cfg)) + sum(_count(layer_shapes(cfg, i)) for i in range(m["layers"]))}
    if m["dense_layers"]:
        out["dense_layer"] = _count(layer_shapes(cfg, 0))
    if m["dense_layers"] < m["layers"]:
        out["expert_layer"] = _count(layer_shapes(cfg, m["dense_layers"]))
    return out


def parameter_count(cfg):
    return parameter_counts(cfg)["total"]


# --------------------------------------------------------------------- weights
def _draw(key, shapes, cfg, dtype):
    std = {"router": cfg.get("router_init_std", 0.02), "e_bias": cfg.get("expert_bias_std", 0.0)}
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name in NORMS:
            out[name] = jnp.ones(shape, dtype)
        else:
            scale = std.get(name, cfg.get("init_std", 0.02))
            out[name] = (scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def _key(seed):
    if isinstance(seed, int):
        seed = np.uint32(seed % (2 ** 32))
    return jax.random.PRNGKey(seed)


def init_layer(seed, cfg, layer, dtype):
    """One layer's leaves, drawn from ``fold_in(seed, layer)`` (one key folded
    per leaf name): normal(``init_std``) for every matrix (``router_init_std``
    for the router, ``expert_bias_std`` for the selection bias), ones for norm
    scales.  Traceable; ``layer`` is static."""
    return _draw(jax.random.fold_in(_key(seed), layer), layer_shapes(cfg, layer), cfg, dtype)


def init_top(seed, cfg, dtype):
    """Embedding, final norm and head, from ``fold_in(seed, num_hidden_layers)``."""
    return _draw(jax.random.fold_in(_key(seed), cfg["num_hidden_layers"]), top_shapes(cfg), cfg, dtype)


def init_params(seed, cfg, dtype):
    """The whole model: ``{"top": ..., "layers": [...]}``.  At the published
    widths only in bfloat16 (what the program holds); the float32 reference
    goes layer by layer."""
    return {"top": init_top(seed, cfg, dtype),
            "layers": [init_layer(seed, cfg, i, dtype) for i in range(cfg["num_hidden_layers"])]}


ATTN_PATHS = {
    "ln_in": ("input_norm", "scale"), "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wg": ("attn", "gate_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
    "ln_post_attn": ("attn_out_norm", "scale"), "ln_pre_mlp": ("post_attn_norm", "scale"),
    "ln_post_mlp": ("mlp_out_norm", "scale"),
}
DENSE_PATHS = {"w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
               "w_down": ("mlp", "down_proj", "kernel")}
EXPERT_PATHS = {
    "router": ("moe_mlp", "router", "kernel"), "e_bias": ("moe_mlp", "expert_bias"),
    "e_gate": ("moe_mlp", "experts", "gate_proj", "kernel"), "e_up": ("moe_mlp", "experts", "up_proj", "kernel"),
    "e_down": ("moe_mlp", "experts", "down_proj", "kernel"),
    "s_gate": ("moe_mlp", "shared", "gate_proj", "kernel"), "s_up": ("moe_mlp", "shared", "up_proj", "kernel"),
    "s_down": ("moe_mlp", "shared", "down_proj", "kernel"),
}
TOP_PATHS = {"embed": ("embed_tokens", "embedding"), "lnf": ("final_norm", "scale"), "head": ("lm_head", "kernel")}


def program_fields(cfg):
    """The program's configuration for ``cfg``, as plain keyword arguments of
    its ``TransformerConfig`` (nested groups as dicts; the types are added by
    whoever builds it)."""
    m = dims(cfg)
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    return {
        "vocab_size": m["vocab"], "hidden_size": m["d"], "intermediate_size": m["dense_width"],
        "num_layers": m["layers"], "num_heads": m["heads"], "num_kv_heads": m["kv_heads"], "head_dim": m["hd"],
        "max_seq_len": cfg["max_position_embeddings"], "rope_theta": cfg["rope_theta"],
        "rms_norm_eps": cfg["rms_norm_eps"], "sliding_window": m["window"],
        "layer_types": [kinds[k] for k in cfg["layer_types"]], "rope_full_layers": False,
        "attention_gate": True, "sandwich_norm": True, "qk_norm": True,
        "embed_scale": bool(cfg.get("mup_enabled", False)),
        "experts": {"num_routed": m["routed"], "held": [m["lo"], m["hi"]], "top_k": m["top_k"],
                    "width": m["expert_width"], "scaling": cfg.get("route_scale", 1.0),
                    "norm_topk": bool(cfg.get("route_norm", False)), "scale_normed": True,
                    "score_func": cfg.get("score_func", "sigmoid"), "select_bias": True,
                    "shared_width": m["shared_width"], "dense_layers": m["dense_layers"],
                    "dense_width": m["dense_width"]},
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
        paths = dict(ATTN_PATHS, **(EXPERT_PATHS if is_expert_layer(cfg, i) else DENSE_PATHS))
        for name, value in layer.items():
            put((f"layers_{i}",) + paths[name], value)
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


def _rope(x, positions, theta):
    """``x [T, H, D]`` rotated at ``positions [T]``, rotate-half: channel ``i``
    pairs with ``i + D/2`` at the frequency ``theta^(-2i/D)``."""
    d = x.shape[-1]
    freqs = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------- blocks
def keys_values(x, p, cfg, window_layer, precision="float32"):
    """``(k [T, kv_heads, hd], v)`` of the whole sequence at positions ``0 ..
    T-1``: the head norm on ``k``, rope where the layer is a window layer."""
    m = dims(cfg)
    t = x.shape[0]
    a = _rms_norm(x, p["ln_in"], cfg["rms_norm_eps"])
    k = _mm("td,de->te", a, p["wk"], precision).reshape(t, m["kv_heads"], m["hd"])
    v = _mm("td,de->te", a, p["wv"], precision).reshape(t, m["kv_heads"], m["hd"])
    k = _rms_norm(k, p["k_norm"], cfg["rms_norm_eps"])
    if window_layer:
        k = _rope(k, jnp.arange(t), cfg["rope_theta"])
    return k, v


def attend(x, positions, k, v, p, cfg, window_layer, precision="float32"):
    """``o Wo`` of the queries ``x [Q, d]`` at ``positions [Q]`` against the
    whole sequence's ``k``, ``v``: the mask written out, the gate applied."""
    m = dims(cfg)
    n, rep = x.shape[0], m["heads"] // m["kv_heads"]
    a = _rms_norm(x, p["ln_in"], cfg["rms_norm_eps"])
    q = _mm("td,de->te", a, p["wq"], precision).reshape(n, m["heads"], m["hd"])
    q = _rms_norm(q, p["q_norm"], cfg["rms_norm_eps"])
    if window_layer:
        q = _rope(q, positions, cfg["rope_theta"])
    q = q.reshape(n, m["kv_heads"], rep, m["hd"])
    scores = _mm("qhrc,khc->hrqk", q, k, precision) * m["hd"] ** -0.5
    behind = positions[:, None] - jnp.arange(k.shape[0])[None, :]            # i - j
    seen = behind >= 0
    if window_layer:
        seen = seen & (behind < m["window"])
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
    o = _mm("hrqk,khc->qhrc", probs, v, precision).reshape(n, m["heads"] * m["hd"])
    gate = _mm("td,de->te", a, p["wg"], precision)
    return _mm("te,ed->td", o * jax.nn.sigmoid(gate), p["wo"], precision)


def route(scores, bias, cfg):
    """``(experts [T, k], gates [T, k])`` from the router's float32 sigmoid
    ``scores [T, num_experts]``: the ``k`` largest ``score + bias``, gated by
    the scores WITHOUT the bias, renormalised over the chosen (``route_norm``)
    and multiplied by ``route_scale``."""
    k = cfg["num_experts_per_tok"]
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :], k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("route_norm", True):
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return experts, gates * cfg.get("route_scale", 1.0)


def router_scores(h, p, cfg, precision="float32"):
    logits = _mm("td,de->te", h, p["router"], precision)
    if cfg.get("score_func", "sigmoid") == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def routed_part(h, p, cfg, precision="float32"):
    """``sum_{e chosen, lo <= e < hi} w_e swiglu_e(h)`` and the choices."""
    m = dims(cfg)
    experts, gates = route(router_scores(h, p, cfg, precision), p["e_bias"], cfg)

    def one(acc, held):
        e, w_gate, w_up, w_down = held
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)                # [T], 0 where not chosen
        return acc + weight[:, None] * _swiglu(h, w_gate, w_up, w_down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(m["lo"], m["hi"]), p["e_gate"], p["e_up"],
                                                      p["e_down"]))
    return routed, experts, gates


def shared_part(h, p, cfg, precision="float32"):
    return _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], precision)


def expert_layer(h, p, cfg, precision="float32"):
    routed, experts, gates = routed_part(h, p, cfg, precision)
    return routed + shared_part(h, p, cfg, precision), experts, gates


def block_forward(x, positions, k, v, p, cfg, layer, precision="float32"):
    """The layer's output for the rows ``x [Q, d]`` at ``positions``, given the
    whole sequence's keys and values."""
    eps = cfg["rms_norm_eps"]
    h = x + _rms_norm(attend(x, positions, k, v, p, cfg, is_window_layer(cfg, layer), precision),
                      p["ln_post_attn"], eps)
    m = _rms_norm(h, p["ln_pre_mlp"], eps)
    if is_expert_layer(cfg, layer):
        f = expert_layer(m, p, cfg, precision)[0]
    else:
        f = _swiglu(m, p["w_gate"], p["w_up"], p["w_down"], precision)
    return h + _rms_norm(f, p["ln_post_mlp"], eps)


def layer_forward(x, p, cfg, layer, precision="float32"):
    """One layer over the whole sequence ``x [T, d]``: keys and values first,
    then the queries in blocks of ``QUERY_BLOCK`` (all at once where ``T`` is
    no multiple of it)."""
    t = x.shape[0]
    k, v = keys_values(x, p, cfg, is_window_layer(cfg, layer), precision)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    split = lambda a: a.reshape((t // block, block) + a.shape[1:])
    out = jax.lax.map(lambda args: block_forward(args[0], args[1], k, v, p, cfg, layer, precision),
                      (split(x), split(jnp.arange(t))))
    return out.reshape(t, x.shape[1])


def embed(top, ids, cfg):
    x = top["embed"].astype(jnp.float32)[ids]
    return x * cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled", False) else x


def head_logits(x, top, cfg, precision="float32"):
    return _mm("td,dv->tv", _rms_norm(x, top["lnf"], cfg["rms_norm_eps"]), top["head"], precision)


def forward(params, ids, cfg, precision="float32"):
    """Logits ``[T, vocab]`` (float32) of one row of token ids ``[T]``, the
    whole stack at once (tiny sizes)."""
    x = embed(params["top"], ids, cfg)
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


def _kind_layer(cfg, expert, window):
    """The first layer of the cut that is of this kind of MLP and attention:
    one program for every layer of a kind."""
    for i in range(cfg["num_hidden_layers"]):
        if is_expert_layer(cfg, i) == expert and is_window_layer(cfg, i) == window:
            return i
    raise ValueError(f"no layer with expert={expert}, window={window}")


@functools.partial(jax.jit, static_argnames=("cfg_key", "expert", "window", "precision"), donate_argnums=(0,))
def _layer_jit(x, p, cfg_key, expert, window, precision):
    cfg = json.loads(cfg_key)
    return layer_forward(x, p, cfg, _kind_layer(cfg, expert, window), precision)


def forward_by_layer(seed, rows, cfg, dtype="float32", precisions=("float32",)):
    """Final hidden states of every row (all of one length) in every precision,
    one layer drawn and run at a time: ``({precision: [x [T, d]]}, top)``.  The
    weights are :func:`init_layer`'s in ``dtype``, read in float32."""
    key = json_key(cfg)
    seed = np.uint32(seed % (2 ** 32))
    top = _init_top_jit(seed, key, dtype)
    xs = {prec: [embed(top, jnp.asarray(row), cfg) for row in rows] for prec in precisions}
    for layer in range(cfg["num_hidden_layers"]):
        p = _init_layer_jit(seed, key, layer, dtype)
        for prec in precisions:
            xs[prec] = [_layer_jit(x, p, key, is_expert_layer(cfg, layer), is_window_layer(cfg, layer), prec)
                        for x in xs[prec]]
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
    """Matmul weights one token passes through, by part: the attention and the
    MLPs of every layer with ``top_k * held / routed`` routed experts a token
    and expert layer (from shapes: the share of the choices that falls here
    when the router is balanced), and the head."""
    m = dims(cfg)
    n_expert_layers = max(m["layers"] - m["dense_layers"], 0)
    n_dense = m["layers"] - n_expert_layers
    here = m["top_k"] * (m["hi"] - m["lo"]) / m["routed"]
    per_expert_layer = m["d"] * m["routed"] + 3 * m["d"] * m["shared_width"] + here * 3 * m["d"] * m["expert_width"]
    return {"blocks": m["layers"] * parameter_counts(cfg)["attention"]
                      + n_dense * 3 * m["d"] * m["dense_width"] + n_expert_layers * per_expert_layer,
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
    n_expert_layers = max(m["layers"] - m["dense_layers"], 0)
    routed = n_expert_layers * (m["hi"] - m["lo"]) * parameter_counts(cfg)["expert"]
    return (parameter_count(cfg) - routed - m["vocab"] * m["d"]) * bytes_per_value


def decode_least_bytes(cfg, contexts, num_slots, experts_hit, rows_live=None, bytes_per_value=2):
    """Least HBM bytes to emit one token for each entry of ``contexts``: the
    rows of the cache its query sees (``min(context, window)`` in each window
    layer, ``context`` in the full ones; or ``rows_live``, the program's own
    count of them summed over the steps, where given), its share of one read
    of the non-expert weights and the head slice by a full batch of
    ``num_slots`` lanes, and one read of each routed expert that got a token
    (``experts_hit``: summed over the steps and layers, the program's own
    counter)."""
    rows = sum(keys_seen(cfg, c) for c in contexts) if rows_live is None else rows_live
    share = dense_weight_bytes(cfg, bytes_per_value) / num_slots
    return (rows * cache_row_bytes(cfg, bytes_per_value) + share * len(contexts)
            + experts_hit * expert_bytes(cfg, bytes_per_value))

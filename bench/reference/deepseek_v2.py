"""Plain reference of the published DeepSeek-V2 block, in ``jax.numpy`` and float32.

Imports nothing of ``accelerate_tpu`` and takes nothing the program made: the
weights come from :func:`init_layer` / :func:`init_top` (the benchmark's own
seeded draw, which the harness also hands to the program through
:func:`to_program_tree`), the inputs from the harness.  ``cfg`` is the
``published`` dict of ``bench/configs/deepseek-v2.json``: the keys of the
model's own ``config.json`` as they are run here, plus ``experts_held`` (the
``[lo, hi)`` of the routed experts this chip holds; the router keeps
``n_routed_experts`` outputs), ``router_init_std`` and, at rehearsal sizes,
``init_std`` (0.02 where absent: at tiny widths normal(0.02) leaves every
attention score near 0 and a wrong softmax scale would not show).

The model (DeepSeek-V2, arXiv 2405.04434, and ``modeling_deepseek.py``), per
layer, pre-norm residual blocks, RMSNorm eps ``rms_norm_eps``, no biases:

* **MLA.**  ``c_q = norm(W_DQ h)``; per head ``[q_nope ; q_pe] = W_UQ c_q``;
  ``[c_kv_raw ; k_pe_raw] = W_DKV h``; ``c_kv = norm(c_kv_raw)``; per head
  ``[k_nope ; v] = W_UKV c_kv``; rope on ``q_pe`` and on the ONE ``k_pe`` all
  heads share, pairs ``(2j, 2j+1)``, YaRN frequencies; ``score = (q_nope .
  k_nope + q_pe . k_pe) * scale`` with YaRN's ``mscale_all_dim`` squared in
  the scale; causal softmax in float32; ``out = W_O [o_1 .. o_H]``.  Only the
  decompressed form is written here (the program's absorbed form has to equal
  it).
* **Experts** (layers ``first_k_dense_replace`` on): ``s = softmax(W_g h)`` in
  float32 over all ``n_routed_experts``; the best ``topk_group`` of
  ``n_group`` groups by their largest ``s``; the ``num_experts_per_tok``
  largest ``s`` among those groups' experts; ``g_e = routed_scaling_factor *
  s_e`` (renormalised instead where ``norm_topk_prob``); ``y = sum_e g_e
  E_e(h) + S(h)``, ``E_e`` a swiglu MLP of ``moe_intermediate_size``, ``S`` one
  of ``n_shared_experts`` times that.  No token is dropped.  **The share:**
  only experts ``lo <= e < hi`` are held, the sum runs over the chosen experts
  among them, and what the absent ones would have added is left out (a loop
  over the held experts by masked dense products: no sort, no ragged product).
* Leading layers: a swiglu MLP of ``intermediate_size``.

The stack at the published widths does not fit a chip in float32 (an expert
layer is 4.6 GB), so :func:`forward_by_layer` draws and runs ONE layer at a
time over all the rows it is given, attention in blocks of queries;
:func:`forward` runs a whole (tiny) model for the tests that hold the two
equal.

``precision`` selects the arithmetic of every matrix multiplication as in
``reference/gpt2.py``: ``"float32"`` (operands at ``Precision.HIGHEST``),
``"bfloat16"``, or ``"fp8"`` (e4m3 under a per-tensor scale, float32
accumulation): the *control*, the nearest precision below bfloat16.

The counts at the end (``forward_flops_token``, ``forward_flops_span``,
``decode_least_bytes``) are the yardstick's: from shapes, whatever
implements the step.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


# ---------------------------------------------------------------------- shapes
def dims(cfg):
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"], "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"], "routed": cfg["n_routed_experts"],
        "lo": int(lo), "hi": int(hi), "top_k": cfg["num_experts_per_tok"],
        "shared_width": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "dense_layers": cfg["first_k_dense_replace"], "vocab": cfg["vocab_size"],
    }


def is_expert_layer(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def attention_shapes(cfg):
    m = dims(cfg)
    d, h = m["d"], m["heads"]
    return {"ln1": (d,), "w_dq": (d, m["q_rank"]), "q_norm": (m["q_rank"],),
            "w_uq": (m["q_rank"], h * (m["nope"] + m["rope"])),
            "w_dkv": (d, m["kv_rank"] + m["rope"]), "kv_norm": (m["kv_rank"],),
            "w_ukv": (m["kv_rank"], h * (m["nope"] + m["v"])), "wo": (h * m["v"], d), "ln2": (d,)}


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
    out.update({"router": (d, m["routed"]),
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
    of a layer (norms apart), of layer 0, of one routed expert, of an expert
    layer, and of everything held here."""
    m = dims(cfg)
    first_expert = m["dense_layers"]
    att = _count(attention_shapes(cfg), ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo"))
    out = {"attention": att, "expert": 3 * m["d"] * m["expert_width"],
           "total": _count(top_shapes(cfg)) + sum(_count(layer_shapes(cfg, i)) for i in range(m["layers"]))}
    if m["dense_layers"]:
        out["dense_layer"] = _count(layer_shapes(cfg, 0))
    if first_expert < m["layers"]:
        out["expert_layer"] = _count(layer_shapes(cfg, first_expert))
    return out


def parameter_count(cfg):
    return parameter_counts(cfg)["total"]


# --------------------------------------------------------------------- weights
def _draw(key, shapes, cfg, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name in ("ln1", "ln2", "lnf", "q_norm", "kv_norm"):
            out[name] = jnp.ones(shape, dtype)
        else:
            std = cfg.get("router_init_std", 0.02) if name == "router" else cfg.get("init_std", 0.02)
            out[name] = (std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def _key(seed):
    if isinstance(seed, int):
        seed = np.uint32(seed % (2 ** 32))
    return jax.random.PRNGKey(seed)


def init_layer(seed, cfg, layer, dtype):
    """One layer's leaves, drawn from ``fold_in(seed, layer)`` (one key folded
    per leaf name): normal(0.02) for every matrix (``router_init_std`` for the
    router), ones for norm scales.  Traceable; ``layer`` is static."""
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
    "ln1": ("input_norm", "scale"), "w_dq": ("attn", "q_a_proj", "kernel"),
    "q_norm": ("attn", "q_a_norm", "scale"), "w_uq": ("attn", "q_b_proj", "kernel"),
    "w_dkv": ("attn", "kv_a_proj", "kernel"), "kv_norm": ("attn", "kv_a_norm", "scale"),
    "w_ukv": ("attn", "kv_b_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "ln2": ("post_attn_norm", "scale"),
}
DENSE_PATHS = {"w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
               "w_down": ("mlp", "down_proj", "kernel")}
EXPERT_PATHS = {
    "router": ("moe_mlp", "router", "kernel"),
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
    y = cfg.get("rope_scaling")
    yarn = None if not y else {
        "factor": y["factor"], "original_max_position": y["original_max_position_embeddings"],
        "beta_fast": y["beta_fast"], "beta_slow": y["beta_slow"], "mscale": y.get("mscale", 1.0),
        "mscale_all_dim": y.get("mscale_all_dim", 0.0)}
    return {
        "vocab_size": m["vocab"], "hidden_size": m["d"], "intermediate_size": m["dense_width"],
        "num_layers": m["layers"], "num_heads": m["heads"], "num_kv_heads": m["heads"],
        "max_seq_len": cfg["max_position_embeddings"], "rope_theta": cfg["rope_theta"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "latent_attention": {"q_rank": m["q_rank"], "kv_rank": m["kv_rank"], "nope_dim": m["nope"],
                             "rope_dim": m["rope"], "v_dim": m["v"], "yarn": yarn},
        "experts": {"num_routed": m["routed"], "held": [m["lo"], m["hi"]], "top_k": m["top_k"],
                    "width": m["expert_width"], "n_group": cfg.get("n_group", 1),
                    "topk_group": cfg.get("topk_group", 1), "scaling": cfg.get("routed_scaling_factor", 1.0),
                    "norm_topk": bool(cfg.get("norm_topk_prob", False)), "shared_width": m["shared_width"],
                    "dense_layers": m["dense_layers"]},
    }


def to_program_tree(params, cfg):
    """The leaves of :func:`init_params` under the program's names
    (``layers_<i>/attn/q_a_proj/kernel`` and so on); nothing is transposed."""
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
    return jnp.einsum(spec, r(a), r(b), precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _swiglu(x, w_gate, w_up, w_down, precision):
    gate = _mm("td,di->ti", x, w_gate, precision)
    up = _mm("td,di->ti", x, w_up, precision)
    return _mm("ti,id->td", jax.nn.silu(gate) * up, w_down, precision)


# ------------------------------------------------------------------------ yarn
def _yarn_m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` rotary frequencies: ``base^(-2j/d)``, divided
    by ``factor`` where the ramp between the corrections of ``beta_fast`` and
    ``beta_slow`` is 1, blended between."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    y = cfg.get("rope_scaling")
    if not y:
        return inv.astype(np.float32)
    length = y["original_max_position_embeddings"]

    def corr(beta):
        return d * math.log(length / (2 * math.pi * beta)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    return ((inv / y["factor"]) * ramp + inv * (1.0 - ramp)).astype(np.float32)


def rope_amplitude(cfg):
    """What cos and sin are multiplied by: ``m(mscale) / m(mscale_all_dim)``."""
    y = cfg.get("rope_scaling")
    if not y:
        return 1.0
    return _yarn_m(y["factor"], y.get("mscale", 1.0)) / _yarn_m(y["factor"], y.get("mscale_all_dim", 0.0))


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    y = cfg.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= _yarn_m(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, positions, cfg):
    """``x [T, ..., d]`` rotated by pairs ``(2j, 2j+1)`` at ``positions [T]``."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[-1],)
    amp = rope_amplitude(cfg)
    cos, sin = (jnp.cos(angles) * amp).reshape(shape), (jnp.sin(angles) * amp).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------- blocks
def attention(x, p, cfg, precision="float32"):
    """MLA over one row ``x [T, d]`` at positions ``0 .. T-1``, decompressed."""
    m = dims(cfg)
    t, heads = x.shape[0], m["heads"]
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    c_q = _rms_norm(_mm("td,dr->tr", h, p["w_dq"], precision), p["q_norm"], eps)
    q = _mm("tr,re->te", c_q, p["w_uq"], precision).reshape(t, heads, m["nope"] + m["rope"])
    kv_a = _mm("td,de->te", h, p["w_dkv"], precision)
    c_kv = _rms_norm(kv_a[:, :m["kv_rank"]], p["kv_norm"], eps)
    kv = _mm("tc,ce->te", c_kv, p["w_ukv"], precision).reshape(t, heads, m["nope"] + m["v"])
    k_nope, v = kv[..., :m["nope"]], kv[..., m["nope"]:]
    pos = jnp.arange(t)
    q_nope, q_pe = q[..., :m["nope"]], _rope(q[..., m["nope"]:], pos, cfg)
    k_pe = _rope(kv_a[:, m["kv_rank"]:], pos, cfg)                 # one rope key a token, all heads
    scale = softmax_scale(cfg)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one(args):
        qn, qp, qpos = args
        scores = (_mm("qhc,khc->hqk", qn, k_nope, precision) + _mm("qhr,kr->hqk", qp, k_pe, precision)) * scale
        probs = jax.nn.softmax(jnp.where(pos[None, None, :] <= qpos[None, :, None], scores, -jnp.inf), axis=-1)
        return _mm("hqk,khc->qhc", probs, v, precision)

    split = lambda a: a.reshape((t // block, block) + a.shape[1:])
    ctx = jax.lax.map(one, (split(q_nope), split(q_pe), split(pos))).reshape(t, heads * m["v"])
    return _mm("te,ed->td", ctx, p["wo"], precision)


def route(scores, cfg):
    """``(experts [T, k], gates [T, k])`` from the router's float32 softmax
    ``scores [T, n_routed_experts]``: group-limited greedy top-k, gates scaled
    and not renormalised unless ``norm_topk_prob``."""
    t, n = scores.shape
    groups, keep, k = cfg.get("n_group", 1), cfg.get("topk_group", 1), cfg["num_experts_per_tok"]
    masked = scores
    if groups > 1:
        best = jnp.max(scores.reshape(t, groups, n // groups), axis=-1)
        _, kept = jax.lax.top_k(best, keep)
        allowed = jnp.any(jax.nn.one_hot(kept, groups, dtype=bool), axis=1)           # [T, groups]
        masked = jnp.where(jnp.repeat(allowed, n // groups, axis=1), scores, 0.0)
    gates, experts = jax.lax.top_k(masked, k)
    if cfg.get("norm_topk_prob") and k > 1:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    else:
        gates = gates * cfg.get("routed_scaling_factor", 1.0)
    return experts, gates


def expert_layer(h, p, cfg, precision="float32"):
    """``sum_{e chosen, lo <= e < hi} g_e E_e(h) + S(h)`` and the choices."""
    m = dims(cfg)
    scores = jax.nn.softmax(_mm("td,de->te", h, p["router"], precision), axis=-1)
    experts, gates = route(scores, cfg)

    def one(acc, held):
        e, w_gate, w_up, w_down = held
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)                # [T], 0 where not chosen
        return acc + weight[:, None] * _swiglu(h, w_gate, w_up, w_down, precision), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(m["lo"], m["hi"]), p["e_gate"], p["e_up"],
                                                      p["e_down"]))
    return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], precision), experts, gates


def layer_forward(x, p, cfg, layer, precision="float32"):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x = x + attention(x, p, cfg, precision)
    h = _rms_norm(x, p["ln2"], cfg["rms_norm_eps"])
    if is_expert_layer(cfg, layer):
        return x + expert_layer(h, p, cfg, precision)[0]
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)


def head_logits(x, top, cfg, precision="float32"):
    top = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), top)
    return _mm("td,dv->tv", _rms_norm(x, top["lnf"], cfg["rms_norm_eps"]), top["head"], precision)


def forward(params, ids, cfg, precision="float32"):
    """Logits ``[T, vocab]`` (float32) of one row of token ids ``[T]``, the
    whole stack at once (tiny sizes)."""
    x = params["top"]["embed"].astype(jnp.float32)[ids]
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


@functools.partial(jax.jit, static_argnames=("cfg_key", "expert", "precision"), donate_argnums=(0,))
def _layer_jit(x, p, cfg_key, expert, precision):
    cfg = json.loads(cfg_key)
    # ``layer`` only selects the kind of MLP: one program for every expert layer
    return layer_forward(x, p, cfg, cfg["first_k_dense_replace"] if expert else -1, precision)


def forward_by_layer(seed, rows, cfg, dtype="float32", precisions=("float32",)):
    """Final hidden states of every row (all of one length) in every precision,
    one layer drawn and run at a time: ``({precision: [x [T, d]]}, top)``.  The
    weights are :func:`init_layer`'s in ``dtype``, read in float32."""
    key = json_key(cfg)
    seed = np.uint32(seed % (2 ** 32))
    top = _init_top_jit(seed, key, dtype)
    embed = top["embed"].astype(jnp.float32)
    xs = {prec: [embed[jnp.asarray(row)] for row in rows] for prec in precisions}
    for layer in range(cfg["num_hidden_layers"]):
        p = _init_layer_jit(seed, key, layer, dtype)
        for prec in precisions:
            xs[prec] = [_layer_jit(x, p, key, is_expert_layer(cfg, layer), prec) for x in xs[prec]]
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


def attention_flops_key(cfg):
    """FLOPs of one query against one key in every layer, as the decompressed
    form needs them (scores over ``nope + rope``, the weighted sum over ``v``),
    whichever form ran: the absorbed form does 3.4 x that and is not credited
    for it."""
    m = dims(cfg)
    return 2 * m["heads"] * (m["nope"] + m["rope"] + m["v"]) * m["layers"]


def forward_flops_token(cfg, context, with_head):
    """Forward FLOPs of one token that attends to ``context`` keys (itself
    included)."""
    w = matmul_params_token(cfg)
    return 2 * w["blocks"] + attention_flops_key(cfg) * context + (2 * w["head"] if with_head else 0)


def forward_flops_span(cfg, start, stop, heads):
    """Forward FLOPs of the tokens at positions ``start <= p < stop`` of one
    sequence, ``heads`` of which need their logits."""
    w = matmul_params_token(cfg)
    n = stop - start
    keys = (start + 1 + stop) * n // 2            # sum of (p + 1)
    return 2 * w["blocks"] * n + attention_flops_key(cfg) * keys + 2 * w["head"] * heads


def cache_bytes_token(cfg, bytes_per_value=2):
    """The latent and the rope key of one token in every layer."""
    m = dims(cfg)
    return m["layers"] * (m["kv_rank"] + m["rope"]) * bytes_per_value


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


def decode_least_bytes(cfg, contexts, num_slots, experts_hit, bytes_per_value=2):
    """Least HBM bytes to emit one token for each entry of ``contexts``: its
    cache once, its share of one read of the non-expert weights by a full
    batch of ``num_slots`` lanes, and one read of each routed expert that got
    a token (``experts_hit``: summed over the steps and layers, the program's
    own counter)."""
    share = dense_weight_bytes(cfg, bytes_per_value) / num_slots
    return (sum(c * cache_bytes_token(cfg, bytes_per_value) + share for c in contexts)
            + experts_hit * expert_bytes(cfg, bytes_per_value))

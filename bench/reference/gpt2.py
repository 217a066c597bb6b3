"""Plain reference of the published GPT-2 block, in ``jax.numpy`` and float32.

Imports nothing of ``accelerate_tpu`` and takes nothing the program made: the
weights come from :func:`init_params` (the benchmark's own seeded draw, which
the harness also hands to the program), the inputs from the harness.

The model (Radford et al. 2019, and ``modeling_gpt2.py``): token + learned
position embedding; ``n_layer`` pre-norm blocks ``x += attn(ln_1(x))``,
``x += mlp(ln_2(x))`` with centred layer norm (eps 1e-5, scale and bias),
causal softmax attention over heads of 64 scaled by 1/8, ``gelu_new`` (tanh)
MLP of width 4 x, every projection with a bias; final layer norm; logits
through the transposed token embedding.  Layers are stacked on a leading axis
and scanned, each under ``jax.checkpoint``, so one row of 1024 tokens fits
beside float32 weights; recomputation changes no value.

``precision`` selects the arithmetic of every matrix multiplication:

* ``"float32"`` - float32 operands at ``Precision.HIGHEST`` (the reference);
* ``"bfloat16"`` - operands rounded to bfloat16, float32 accumulation;
* ``"fp8"`` - operands rounded to float8_e4m3fn under a per-tensor scale
  (amax / 448), float32 accumulation; in the backward pass the cotangent of
  each operand is rounded to float8_e5m2 (the hybrid recipe of fp8 training,
  which is also the program's own ``fp8_format="HYBRID"``).  This is the
  *control*: the nearest precision below bfloat16, the step that would tempt
  a later PR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
                "ln2_g", "ln2_b", "w_up", "b_up", "w_down", "b_down")
HEAD_DIM = 64


def shapes(cfg):
    """Leaf name -> shape, layers stacked on axis 0.  ``cfg`` is the
    ``published`` dict of a configuration file."""
    d, n_layer = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * d
    per = {"ln1_g": (d,), "ln1_b": (d,), "wq": (d, d), "bq": (d,), "wk": (d, d), "bk": (d,),
           "wv": (d, d), "bv": (d,), "wo": (d, d), "bo": (d,), "ln2_g": (d,), "ln2_b": (d,),
           "w_up": (d, inner), "b_up": (inner,), "w_down": (inner, d), "b_down": (d,)}
    out = {"wte": (cfg["vocab_size"], d), "wpe": (cfg["n_positions"], d), "lnf_g": (d,), "lnf_b": (d,)}
    out.update({k: (n_layer,) + v for k, v in per.items()})
    return out


def parameter_count(cfg):
    return int(sum(np.prod(s) for s in shapes(cfg).values()))


def init_params(seed, cfg, dtype):
    """Every leaf drawn on the device from ``seed`` (one key folded per leaf
    name): normal(0.02) for tables, matrices and biases, ones for norm scales.
    Traceable: call it inside one ``jax.jit``, with ``seed`` (taken modulo
    2**32) as a uint32 argument so that one program serves every seed."""
    if isinstance(seed, int):
        seed = np.uint32(seed % (2 ** 32))
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (0.02 * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


# ----------------------------------------------------------------- arithmetic
def _scaled_round(x, dtype, largest):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _round_fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


# the hybrid fp8 recipe: e4m3 operands forward, e5m2 cotangents backward
_round_fp8.defvjp(lambda x: (_round_fp8(x), None),
                  lambda _, g: (_scaled_round(g, jnp.float8_e5m2, 57344.0),))


@jax.custom_vjp
def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_round_bf16.defvjp(lambda x: (_round_bf16(x), None), lambda _, g: (g,))

_ROUND = {"float32": lambda x: x, "bfloat16": _round_bf16, "fp8": _round_fp8}


def _mm(spec, a, b, precision):
    r = _ROUND[precision]
    return jnp.einsum(spec, r(a), r(b), precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, eps, precision):
    t, d = x.shape
    heads = d // HEAD_DIM
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    q = (_mm("td,de->te", h, p["wq"], precision) + p["bq"]).reshape(t, heads, HEAD_DIM)
    k = (_mm("td,de->te", h, p["wk"], precision) + p["bk"]).reshape(t, heads, HEAD_DIM)
    v = (_mm("td,de->te", h, p["wv"], precision) + p["bv"]).reshape(t, heads, HEAD_DIM)
    scores = _mm("qhc,khc->hqk", q, k, precision) / np.sqrt(HEAD_DIM)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    ctx = _mm("hqk,khc->qhc", probs, v, precision).reshape(t, d)
    x = x + _mm("td,de->te", ctx, p["wo"], precision) + p["bo"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    h = _gelu_new(_mm("td,di->ti", h, p["w_up"], precision) + p["b_up"])
    return x + _mm("ti,id->td", h, p["w_down"], precision) + p["b_down"]


def forward(params, ids, cfg, precision="float32"):
    """Logits ``[T, vocab]`` (float32) of one row of token ids ``[T]``."""
    eps = cfg["layer_norm_epsilon"]
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = p["wte"][ids] + p["wpe"][: ids.shape[0]]
    layers = {k: p[k] for k in LAYER_LEAVES}

    @jax.checkpoint
    def body(x, layer):
        return _block(x, layer, eps, precision), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _layer_norm(x, p["lnf_g"], p["lnf_b"], eps)
    return _mm("td,vd->tv", x, p["wte"], precision)


def row_loss(params, ids, cfg, precision="float32"):
    """Sum of next-token negative log-likelihoods of one row, and its count."""
    logits = forward(params, ids, cfg, precision)[:-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked), ids.shape[0] - 1


# -------------------------------------------------------------------- training
def batch_loss_and_grad(params, rows, cfg, precision="float32"):
    """Mean next-token loss of a batch ``[B, T]`` and its gradient, one row at
    a time (a scan over rows) so that the activations of one row are all that
    is live."""

    def one(carry, ids):
        def f(p):
            s, n = row_loss(p, ids, cfg, precision)
            return s / n
        loss, grad = jax.value_and_grad(f)(params)
        total, acc = carry
        return (total + loss, jax.tree_util.tree_map(jnp.add, acc, grad)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (total, acc), _ = jax.lax.scan(one, (jnp.float32(0.0), zero), rows)
    b = rows.shape[0]
    return total / b, jax.tree_util.tree_map(lambda g: g / b, acc)


def leaf_norms(tree):
    """Leaf name -> norms: a scalar for a top leaf, ``[n_layer]`` for a stacked one."""
    return {k: (jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axis=tuple(range(1, v.ndim))))
                if k in LAYER_LEAVES else jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "steps"))
def _train_jit(seed_params, batches, hyper, cfg_key, precision, steps):
    cfg = dict(cfg_key)
    lr, b1, b2, eps, wd, clip = (hyper[k] for k in ("lr", "b1", "b2", "eps", "weight_decay", "max_grad_norm"))
    params = seed_params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t in range(1, steps + 1):
        loss, grad = batch_loss_and_grad(params, batches[t - 1], cfg, precision)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grad.values()))
        factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
        grad = jax.tree_util.tree_map(lambda g: g * factor, grad)
        if first_grad is None:
            first_grad = leaf_norms(grad)
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grad)
        nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grad)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p),
            params, mu, nu)
        losses.append(loss)
    delta = leaf_norms({k: params[k] - seed_params[k] for k in params})
    return jnp.stack(losses), first_grad, delta


def train_steps(seed, cfg, batches, hyper, precision="float32"):
    """The first ``len(batches)`` adamw steps from ``init_params(seed)``:
    ``(losses, norms of the first clipped gradient, norms of the parameters'
    change)``, all as numpy, leaves by the names of :func:`shapes`.

    The update is optax's ``adamw``: bias-corrected moments, decoupled weight
    decay on every leaf, the gradient first scaled by
    ``min(1, max_grad_norm / (norm + 1e-6))``."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    params = jax.jit(lambda s: init_params(s, cfg, jnp.float32))(np.uint32(seed % (2 ** 32)))
    hyper = {k: jnp.float32(v) for k, v in hyper.items()}
    out = _train_jit(params, jnp.asarray(batches, jnp.int32), hyper, cfg_key, precision, len(batches))
    return jax.tree_util.tree_map(np.asarray, out)


# --------------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _gaps_jit(params, ids, n_prompt, n_total, cfg_key, precision):
    """For one padded row: at each served position the gap between the
    reference's best logit and the logit of the token that follows, and the
    reference's best token there."""
    logits = forward(params, ids, dict(cfg_key), precision)
    best = jnp.max(logits, axis=-1)
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    picked = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    pos = jnp.arange(ids.shape[0])
    served = (pos >= n_prompt - 1) & (pos < n_total - 1)
    return jnp.where(served, best - picked, 0.0), jnp.argmax(logits, axis=-1), served, logits


def served_token_gaps(params, prompt, served, cfg, width, precision="float32", lower=None):
    """Teacher-forced pass over ``prompt + served`` (padded to ``width``).

    Returns ``(gaps, lower_gaps)``: for each served token how far its float32
    reference logit lies below the reference's best at that position; and,
    where ``lower`` names a precision, the same gap for the token that the
    lower precision puts first at each position (the control)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    ids = np.zeros((width,), np.int32)
    n_prompt, n_total = len(prompt), len(prompt) + len(served)
    ids[:n_prompt] = prompt
    ids[n_prompt:n_total] = served
    gaps, _, mask, logits = _gaps_jit(params, jnp.asarray(ids), n_prompt, n_total, cfg_key, precision)
    mask = np.asarray(mask)
    out = np.asarray(gaps)[mask]
    if lower is None:
        return out, None
    _, low_best, _, _ = _gaps_jit(params, jnp.asarray(ids), n_prompt, n_total, cfg_key, lower)
    picked = jnp.take_along_axis(logits, low_best[:, None], axis=-1)[:, 0]
    low = np.asarray(jnp.max(logits, axis=-1) - picked)[mask]
    return out, low

"""The one-pass retention step (``accelerate_tpu/ops/retention.py``) in interpret
mode on the CPU, at the published head: width 128, ``D`` 8,320, 2 key/value
heads of 5 query heads, 2 stacked layers, 3 lanes of which one is frozen, the
state seeded by three steps from zero (a state drawn at random has a
normaliser ``phi(q) . z`` of any sign; a state that steps made has the sum of
squares it is).

The oracle is ``models.retention.retention_step``.  Tolerances: both forms sum
8,320 float32 products whose terms are hundreds of times the result (``num``
reads ~1e3 from terms that cancel), in different orders; each is 3e-5 away from
the same step in float64 at ``y`` ~ 3, so ``y`` is held to ``rtol`` 1e-5 +
``atol`` 1e-4.  An operand rounded through bfloat16 reads 0.1 away
(``test_a_bfloat16_operand_fails_the_tolerance`` guards the precision).
What interpret mode cannot show (tiling, fast memory, the aliasing of the
whole state) is ``tests/test_tpu_compile.py``'s.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))

from reference import brumby as ref  # noqa: E402

from accelerate_tpu.models import retention  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.retention import StateCache, gated, log_gate, normalise, phi, retention_step  # noqa: E402
from accelerate_tpu.models.retention import retention_step_stored, state_width  # noqa: E402
from accelerate_tpu.models.transformer import Transformer, TransformerConfig  # noqa: E402
from accelerate_tpu.ops import retention_step_onepass  # noqa: E402
from accelerate_tpu.ops.retention import onepass_applies  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.telemetry import MetricsRegistry  # noqa: E402

LAYERS, LANES, KV_HEADS, GROUPS, HEAD = 2, 3, 2, 5, 128
WIDTH = state_width(HEAD, 2)
EPS = 1e-6 * HEAD
LIVE = jnp.array([1, 0, 1], jnp.int32)              # lane 1 is frozen
RTOL, ATOL = 1e-5, 1e-4


def _draw(seed):
    """One token's ``(q̂, k̂, v, log g)`` for every lane."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (LANES, KV_HEADS, GROUPS, HEAD)), jax.random.normal(k[1], (LANES, KV_HEADS, HEAD)),
            jax.random.normal(k[2], (LANES, KV_HEADS, HEAD)), log_gate(jax.random.normal(k[3], (LANES, KV_HEADS))))


@pytest.fixture(scope="module")
def seeded():
    """A stacked state that three steps from zero left in every layer and lane."""
    s = jnp.zeros((LAYERS, LANES, KV_HEADS, WIDTH, HEAD))
    z = jnp.zeros((LAYERS, LANES, KV_HEADS, WIDTH))
    for layer in range(LAYERS):
        sl, zl = s[layer], z[layer]
        for t in range(3):
            _, sl, zl = retention_step(*_draw(100 + 10 * layer + t), sl, zl, jnp.ones((LANES,), bool), 2, EPS)
        s, z = s.at[layer].set(sl), z.at[layer].set(zl)
    assert float(jnp.abs(s).max()) > 1                                           # seeded non-zero
    return StateCache(s=s, z=z, index=jnp.zeros((LANES,), jnp.int32), live=LIVE)


def _stored(cache, layer, seed=7):
    return jax.jit(lambda *a: retention_step_stored(*a, layer, 2, EPS, interpret=True))(*_draw(seed), cache)


@pytest.mark.parametrize("layer", range(LAYERS))
def test_one_step_equals_retention_step(seeded, layer):
    y, cache = _stored(seeded, layer)
    want_y, want_s, want_z = retention_step(*_draw(7), seeded.s[layer], seeded.z[layer], LIVE > 0, 2, EPS)
    np.testing.assert_allclose(y, want_y, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(cache.s[layer], want_s, rtol=RTOL, atol=1e-5)
    np.testing.assert_allclose(cache.z[layer], want_z, rtol=RTOL, atol=1e-5)


@pytest.mark.parametrize("layer", range(LAYERS))
def test_the_other_layer_and_the_frozen_lane_come_back_bit_equal(seeded, layer):
    _, cache = _stored(seeded, layer)
    other = 1 - layer
    assert np.array_equal(cache.s[other], seeded.s[other]) and np.array_equal(cache.z[other], seeded.z[other])
    assert np.array_equal(cache.s[layer, 1], seeded.s[layer, 1]) and np.array_equal(cache.z[layer, 1], seeded.z[layer, 1])
    assert not np.array_equal(cache.s[layer, 0], seeded.s[layer, 0])                 # a live lane did move


def test_four_steps_in_a_scan_equal_four_calls_of_retention_step(seeded):
    """The decode window's shape: the stacked state carried through a scan, each
    step rewriting layer 1 in place."""
    tokens = [_draw(20 + t) for t in range(4)]
    stacked = tuple(jnp.stack(parts) for parts in zip(*tokens))

    def body(cache, token):
        y, cache = retention_step_stored(*token, cache, 1, 2, EPS, interpret=True)
        return cache, y

    cache, ys = jax.jit(lambda c, xs: jax.lax.scan(body, c, xs))(seeded, stacked)
    s, z = seeded.s[1], seeded.z[1]
    for t, token in enumerate(tokens):
        want, s, z = retention_step(*token, s, z, LIVE > 0, 2, EPS)
        np.testing.assert_allclose(ys[t], want, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(cache.s[1], s, rtol=RTOL, atol=1e-5)
    np.testing.assert_allclose(cache.z[1], z, rtol=RTOL, atol=1e-5)
    assert np.array_equal(cache.s[0], seeded.s[0])


@pytest.mark.parametrize("rounded", ["phi_q", "state"])
def test_a_bfloat16_operand_fails_the_tolerance(seeded, rounded):
    """``phi(q) . phi(k)`` is 8,320 signed terms that cancel down to ``(q . k)^2``:
    8 bits of ``phi(q)`` or of the state leave an error of the size of the
    terms.  A kernel that fed the MXU bfloat16 operands would read this."""
    q, k, v, log_g = _draw(7)
    through = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    gate, pk = gated(phi(k), log_g, LIVE > 0)
    pq = through(phi(q)) if rounded == "phi_q" else phi(q)
    s = through(seeded.s) if rounded == "state" else seeded.s
    num, den, _, _ = retention_step_onepass(pq, pk, v, gate, s, seeded.z, 1, interpret=True)
    want, _, _ = retention_step(q, k, v, log_g, seeded.s[1], seeded.z[1], LIVE > 0, 2, EPS)
    gap = np.abs(np.asarray(normalise(num, den, EPS)) - np.asarray(want))
    assert not np.all(gap <= ATOL + RTOL * np.abs(want)) and gap.max() > 100 * ATOL, gap.max()


@pytest.mark.parametrize("head,degree,dtype,interpret,kernel", [
    (128, 2, jnp.float32, True, True),
    (128, 2, jnp.float32, None, False),          # a CPU takes the XLA form unasked
    (16, 2, jnp.float32, True, False),           # the tiny models of tests/test_brumby.py
    (128, 1, jnp.float32, True, False),          # phi the identity: a state of [128, 128]
    (128, 2, jnp.bfloat16, True, False),
    (256, 2, jnp.float32, True, False),          # a (lane, head) of 33.8 MB does not fit fast memory four times
], ids=["published", "cpu_unasked", "narrow_head", "degree_1", "bfloat16_state", "wide_head"])
def test_the_step_picks_its_form_by_shape_and_device(head, degree, dtype, interpret, kernel):
    s = jax.ShapeDtypeStruct((2, 1, 1, state_width(head, degree), head), dtype)
    assert onepass_applies(s, degree, interpret) is kernel


def _tiny(head, degree, layers=2):
    published = {"hidden_size": 64, "num_hidden_layers": layers, "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": head, "intermediate_size": 128, "vocab_size": 97, "max_position_embeddings": 256,
                 "rope_theta": 1e6, "rms_norm_eps": 1e-6, "power_degree": degree, "normaliser_eps": 1e-6,
                 "init_std": 0.1}
    config = TransformerConfig(**ref.program_fields(published), dtype=jnp.float32, param_dtype=jnp.float32)
    ref_params = ref.init_params(5, published, jnp.float32)
    return published, Transformer(config), ref.to_program_tree(ref_params, published), ref_params


@pytest.mark.parametrize("head,degree", [(16, 2), (16, 1), (128, 1)], ids=["narrow_head", "narrow_degree_1", "degree_1"])
def test_the_xla_form_serves_what_the_kernel_does_not_take(monkeypatch, head, degree):
    """``generate``'s cached steps on shapes the kernel refuses: the XLA form
    runs (the kernel is never reached) and the greedy tokens are the
    reference's."""
    def never(*a, **k):
        raise AssertionError("the one-pass kernel was called")

    monkeypatch.setattr(retention, "retention_step_onepass", never)
    published, model, params, ref_params = _tiny(head, degree)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(40), (11,), 0, 97), np.int32)
    seq, cache = generate(model, params, prompt[None], max_new_tokens=5)
    logits = ref.forward(ref_params, jnp.asarray(seq[0, :-1]), published)
    assert [int(t) for t in seq[0, 11:]] == [int(t) for t in jnp.argmax(logits[10:], -1)]


def test_a_model_stepped_by_the_kernel_gives_the_logits_of_the_xla_form(monkeypatch):
    """The whole layer round the kernel (projections, head norm, rope, the gate,
    the frozen rows, the normaliser) at a head of 128: ``generate`` with every
    cached step through the interpreted kernel against the same model on the
    XLA form."""
    published, model, params, ref_params = _tiny(128, 2, layers=1)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(41), (6,), 0, 97), np.int32)
    want_seq, want = generate(model, params, prompt[None], max_new_tokens=4)

    calls = []

    def asked(s, degree, interpret=None):
        calls.append(s.shape)
        return onepass_applies(s, degree, True)

    monkeypatch.setattr(retention, "onepass_applies", asked)
    monkeypatch.setattr(retention, "retention_step_onepass",
                        lambda *a, interpret=None: retention_step_onepass(*a, interpret=True))
    # ``generate`` keeps its jitted loop by the model's fields: another position limit is another trace
    patched = Transformer(dataclasses.replace(model.config, max_seq_len=192))
    seq, got = generate(patched, params, prompt[None], max_new_tokens=4)
    assert calls and all(shape == (1, 1, 2, WIDTH, HEAD) for shape in calls)
    assert np.array_equal(seq, want_seq)
    np.testing.assert_allclose(got.s, want.s, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got.z, want.z, rtol=1e-4, atol=1e-5)


def test_the_engine_says_which_form_its_window_runs():
    """``serve/state_step_onepass``: 0 for a tiny model on the CPU (the XLA
    form), set where ``serve/state_bytes`` is."""
    _, model, params, _ = _tiny(16, 2)
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=64, prefill_buckets=(16,), decode_window=4,
                           registry=registry)
    assert registry.gauge("serve/state_bytes").value == engine.kv_pool_bytes()
    assert registry.gauge("serve/state_step_onepass").value == 0 and engine.kv.step_onepass is False

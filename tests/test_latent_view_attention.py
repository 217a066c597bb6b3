"""The prefill chunk's latent flash kernel (``accelerate_tpu/ops/latent_view_attention.py``)
in interpret mode on the CPU, against ``attend_decompressed``, the XLA loop it replaces.

Tolerances: both forms multiply bfloat16 operands, round the decompressed keys
and values to bfloat16, score in float32 and cast the probabilities to
bfloat16 for ``P V``; the kernel normalises once at the end and rounds its
output once, the loop divides its float32 sum.  Queries, latents and rope keys
are unit normals and ``W_UKV`` is scaled by ``kv_rank^-1/2``, so the
decompressed keys and values are of size ~1 and the outputs, averages of
values, at most that: a bfloat16 step is up to 2^-8 = 0.004 and the
probabilities' rounding adds as much, ``ATOL`` 0.02 (``tests/test_view_attention.py``'s).
A wrong mask moves a row that sees few keys by its whole size
(``test_a_wrong_mask_fails_the_tolerance``).  What interpret mode cannot show
(tiling, fast memory, the dead blocks never fetched) is
``tests/test_tpu_compile.py``'s and the chip's.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import latent_attention as mla
from accelerate_tpu.models.transformer import (
    KVCache,
    LatentAttentionSpec,
    Transformer,
    TransformerConfig,
)
from accelerate_tpu.ops import latent_view_attention as lva
from accelerate_tpu.ops.latent_view_attention import latent_flash_applies, latent_view_attention
from accelerate_tpu.ops.view_attention import KEY_BLOCK, xla_form
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry

ATOL = 0.02
BF16 = jnp.bfloat16
#: DeepSeek-V2's widths: kv_rank, nope, rope, v
PUBLISHED = (512, 128, 64, 128)
SCALE = (128 + 64) ** -0.5


def _draw(rows, heads, m, seed=0, batch=1, widths=PUBLISHED):
    kv_rank, nope, rope, v = widths
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda key, shape, scale=1.0: (jax.random.normal(key, shape, jnp.float32) * scale).astype(BF16)
    return (normal(keys[0], (batch, rows, heads, nope)), normal(keys[1], (batch, rows, heads, rope)),
            normal(keys[2], (batch, m, kv_rank)), normal(keys[3], (batch, m, rope)),
            normal(keys[4], (kv_rank, heads * (nope + v)), kv_rank ** -0.5))


def _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions, live_only=True):
    """``attend_decompressed`` on the same bfloat16 values, as ``[B, S, H * v]``."""
    b, s, h, nope = q_nope.shape
    w = w_ukv.reshape(w_ukv.shape[0], h, -1)
    out = mla.attend_decompressed(q_nope, q_pe, latent, k_pe, w[..., :nope], w[..., nope:], positions, SCALE,
                                  live_only=live_only)
    return np.asarray(out.astype(jnp.float32)).reshape(b, s, -1)


def _gap(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max())


#: where the chunk's keys end: inside the first block, several blocks on, and mid-block
DEPTHS = {"one_block": 0, "several_blocks": 2 * KEY_BLOCK, "mid_block": 2 * KEY_BLOCK + 300}


@pytest.mark.parametrize("depth", sorted(DEPTHS))
@pytest.mark.parametrize("rows", [128, 512, 200])
def test_kernel_matches_the_decompressed_loop(rows, depth):
    """{a 128-chunk, a 512-chunk, 200 rows that fill no bucket} x {live keys in
    one block, in three whole blocks, ending mid-block}, at DeepSeek-V2's
    widths and four heads, over a view of 4,096."""
    base = DEPTHS[depth] + (KEY_BLOCK - rows if depth == "several_blocks" else 0)
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(rows, 4, 4096, seed=rows + base)
    positions = base + jnp.arange(rows)[None]
    got = latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions, SCALE, interpret=True)
    assert got.dtype == BF16 and got.shape == (1, rows, 4 * 128)
    assert _gap(got, _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)) <= ATOL


def test_two_lanes_at_different_depths():
    """Each lane has its own count of live blocks (one, and five)."""
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(128, 2, 8192, seed=11, batch=2)
    positions = jnp.asarray([[300], [4500]]) + jnp.arange(128)[None]
    got = latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions, SCALE, interpret=True)
    assert _gap(got, _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)) <= ATOL


@pytest.mark.parametrize("widths", [(128, 128, 64, 128), (256, 256, 64, 128)], ids=["rank128", "nope256"])
def test_other_widths_of_whole_lanes(widths):
    """A narrower latent and wider no-rope heads than DeepSeek-V2's."""
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(128, 2, 2048, seed=5, widths=widths)
    positions = 1500 + jnp.arange(128)[None]
    got = latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions, SCALE, interpret=True)
    assert got.shape == (1, 128, 2 * widths[3])
    assert _gap(got, _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)) <= ATOL


def test_a_layer_of_a_stacked_view():
    """``layer`` picks a layer of ``[L, B, M, width]`` inside the kernel: the
    result is that layer's alone."""
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(128, 2, 2048, seed=6)
    other = _draw(128, 2, 2048, seed=7)
    positions = 900 + jnp.arange(128)[None]
    stacked = [jnp.stack([o, a]) for o, a in ((other[2], latent), (other[3], k_pe))]
    got = latent_view_attention(q_nope, q_pe, *stacked, w_ukv, positions, SCALE, layer=1, interpret=True)
    assert _gap(got, _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)) <= ATOL


def test_dead_key_blocks_are_never_visited():
    """NaN in every key block past the last live one: the kernel never fetches
    them, and the loop over every block (``live_only=False``) turns them into
    NaN, so the poison would show where a block was read."""
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(128, 2, 4096, seed=9)
    positions = 1100 + jnp.arange(128)[None]
    want = _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)
    dead = (jnp.arange(4096) >= 2 * KEY_BLOCK)[None, :, None]
    latent, k_pe = (jnp.where(dead, jnp.nan, a.astype(jnp.float32)).astype(BF16) for a in (latent, k_pe))
    got = np.asarray(latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions, SCALE, interpret=True),
                     np.float32)
    assert np.isfinite(got).all() and _gap(got, want) <= ATOL
    assert not np.isfinite(_loop(q_nope, q_pe, latent, k_pe, w_ukv, positions, live_only=False)).any()


def test_a_wrong_mask_fails_the_tolerance():
    """Queries that see eight keys too many move a chunk's first rows (which
    average over a handful of keys) by tens of tolerances."""
    q_nope, q_pe, latent, k_pe, w_ukv = _draw(128, 2, 2048, seed=3)
    positions = jnp.arange(128)[None]
    wrong = latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions + 8, SCALE, interpret=True)
    assert _gap(wrong, _loop(q_nope, q_pe, latent, k_pe, w_ukv, positions)) > 10 * ATOL


# ------------------------------------------------- what LatentAttention picks
def _toy_config(**kw):
    """A latent stack at widths the kernel takes: two heads of nope 128, rope
    64 and v 128 over a 128-wide latent, bfloat16, no experts."""
    return TransformerConfig.tiny(
        hidden_size=256, num_heads=2, num_kv_heads=2, intermediate_size=256, num_layers=2, vocab_size=97,
        max_seq_len=2048, latent_attention=LatentAttentionSpec(q_rank=64, kv_rank=128, nope_dim=128, rope_dim=64,
                                                               v_dim=128),
        dtype=BF16, param_dtype=BF16, **kw)


@pytest.fixture(scope="module")
def toy():
    model = Transformer(_toy_config())
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _compiles(monkeypatch, calls):
    """A platform that compiles the kernel, with the kernel itself run
    interpreted (this is still a CPU) and every call of it recorded."""
    def recorded(q_nope, q_pe, latent, k_pe, w_ukv, positions, scale, **kw):
        calls.append((q_nope.shape, latent.shape, kw))
        return latent_view_attention(q_nope, q_pe, latent, k_pe, w_ukv, positions, scale, interpret=True, **kw)

    monkeypatch.setattr(lva, "_platform_compiles", lambda: True)
    monkeypatch.setattr(mla, "latent_view_attention", recorded)


def test_a_cached_chunk_takes_the_kernel_on_a_platform_that_compiles_it(toy, monkeypatch):
    """A 128-chunk after a first one, through the stacked cache: each layer
    calls the kernel with the whole stacked view and its own index, and the
    logits are the loop's within the model's bfloat16 noise."""
    model, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, 97)
    run = lambda c, tokens: model.apply({"params": params}, tokens, cache=c)
    empty = lambda: KVCache.create(model.config, 1, 2048)
    _, filled = run(empty(), ids[:, :128])
    want, _ = run(filled, ids[:, 128:])
    calls = []
    _compiles(monkeypatch, calls)
    _, filled = run(empty(), ids[:, :128])
    got, _ = run(filled, ids[:, 128:])
    assert [(q[1], lat, kw["layer"]) for q, lat, kw in calls] == [(128, (2, 1, 2048, 128), layer)
                                                                  for layer in (0, 1)] * 2
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    assert _gap(got, np.asarray(want, np.float32)) <= 0.05 * scale


REFUSED = {
    "a_decode_window": dict(rows=4),
    "a_verify_window_under_a_chunk": dict(rows=64),
    "no_cache": dict(cache=False),
    "float32": dict(dtype=jnp.float32),
    "a_view_of_1024": dict(max_len=1024),
    "under_xla_form": dict(context=xla_form),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_loop_is_what_lowers_for_everything_else(toy, monkeypatch, name):
    """One case a refusal, on a platform that compiles the kernel: the kernel is
    never reached, and the program lowered is letter for letter the one lowered
    where no platform compiles it."""
    case = dict(dict(rows=128, cache=True, dtype=BF16, max_len=2048, context=None), **REFUSED[name])
    model, params = toy
    if case["dtype"] != BF16:
        model = Transformer(dataclasses.replace(model.config, dtype=case["dtype"], param_dtype=case["dtype"]))
        params = jax.tree_util.tree_map(lambda a: a.astype(case["dtype"]), params)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, case["rows"]), 0, 97)
    cache = KVCache.create(model.config, 1, case["max_len"]) if case["cache"] else None
    apply = jax.jit(lambda p, i, c: model.apply({"params": p}, i, cache=c))
    before = apply.lower(params, ids, cache).as_text()

    def never(*a, **kw):
        raise AssertionError("the latent flash kernel was called")

    monkeypatch.setattr(lva, "_platform_compiles", lambda: True)
    monkeypatch.setattr(mla, "latent_view_attention", never)
    with (case["context"] or contextlib.nullcontext)():
        after = apply.lower(params, ids, cache).as_text()
        out = apply(params, ids, cache)
    logits = out if cache is None else out[0]
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    if case["context"] is None:
        assert after == before


def test_the_shape_rule_refuses_what_the_kernel_does_not_take():
    """Heads in no whole group, head widths or a latent of no whole lanes."""
    spec = lambda shape: jax.ShapeDtypeStruct(shape, BF16)
    ok = (spec((1, 128, 4, 128)), spec((1, 128, 4, 64)), spec((1, 2048, 512)), spec((512, 4 * 256)))
    assert latent_flash_applies(*ok, interpret=True)
    assert not latent_flash_applies(*ok)                                   # the CPU rig keeps the loop
    assert not latent_flash_applies(spec((1, 128, 3, 128)), spec((1, 128, 3, 64)), ok[2], spec((512, 3 * 256)),
                                    interpret=True)
    assert not latent_flash_applies(spec((1, 128, 4, 64)), ok[1], ok[2], spec((512, 4 * 192)), interpret=True)
    assert not latent_flash_applies(*ok[:2], spec((1, 2048, 96)), spec((96, 4 * 256)), interpret=True)
    with pytest.raises(ValueError, match="whole lanes"):
        latent_view_attention(*(jnp.zeros(s.shape, BF16) for s in (ok[0], ok[1], spec((1, 2048 + 64, 512)),
                                                                   spec((1, 2048 + 64, 64)), ok[3])),
                              jnp.zeros((1, 128), jnp.int32), SCALE, interpret=True)


# ------------------------------------------------------------ the engine's word
def _engine(model, params, registry):
    return ServingEngine(model, params, num_slots=2, max_len=2048, page_size=128, prefill_buckets=(128,),
                         decode_window=4, prefix_cache_mb=0, registry=registry)


def test_a_latent_engine_says_which_form_its_chunks_run_and_what_share_of_the_view_was_live(toy, monkeypatch):
    """``serve/chunk_attention_kernel``: 0 on the CPU rig, 1 where the platform
    compiles the latent kernel and the chunk's shapes pass; the latent engine's
    ``chunk_key_blocks_live`` / ``chunk_key_blocks_view`` count, a dispatched
    chunk, the key blocks of its ``max_len``-wide latent view up to its last row
    and the view's, whichever form runs."""
    model, params = toy
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 0 and engine.chunk_attention_kernel is False
    calls = []
    _compiles(monkeypatch, calls)
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 1 and engine.chunk_attention_kernel is True
    request = engine.submit([int(t) for t in np.arange(700) % 97], max_new_tokens=4)
    engine.run()
    assert len(request.tokens) == 4
    chunks = -(-700 // 128)                                                     # six chunks of 128 rows
    assert engine.stats["prefill_chunks"] == chunks
    assert engine.stats["chunk_key_blocks_live"] == sum(-(-(128 * (i + 1)) // KEY_BLOCK) for i in range(chunks)) == 6
    assert engine.stats["chunk_key_blocks_view"] == chunks * (2048 // KEY_BLOCK)
    assert registry.counter("serve/chunk_key_blocks_live_total").value == 6
    # both layers' chunks ran in the kernel, traced once a layer for the one bucket; no decode window did
    assert [kw["layer"] for *_, kw in calls] == [0, 1] and all(q[1] == 128 for q, *_ in calls)
    # a model the kernel does not take: the gauge says so, the counters still count
    f32 = Transformer(dataclasses.replace(model.config, dtype=jnp.float32, param_dtype=jnp.float32))
    registry = MetricsRegistry()
    engine = _engine(f32, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params), registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 0
    assert engine.stats["chunk_key_blocks_view"] == 0

"""The decode window's page copy (``accelerate_tpu/ops/view_gather.py``) in
interpret mode on the CPU, against ``serving/pool.py`` ``_gather_view``'s zero
fill and page-wide updates, which the CPU rig runs.

A copy has no tolerance: the kernel's view is the update form's bit for bit,
dead slots and the null page's garbage included.  What interpret mode cannot
show (the pool taken in the layout the chip holds it in, no copy of the pool or
of the view round the kernel, fast memory) is ``tests/test_tpu_compile.py``'s
and the chip's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.ops import view_gather as vg
from accelerate_tpu.ops.view_attention import xla_form
from accelerate_tpu.ops.view_gather import STEP_BYTES, _plan, gather_pages, view_gather_applies
from accelerate_tpu.serving import ServingEngine, pool
from accelerate_tpu.serving.paging import NULL_PAGE
from accelerate_tpu.telemetry import MetricsRegistry

BF16 = jnp.bfloat16
PAGE = 128
#: the cells' rows a page: Trinity's 8 heads of 128, Mellum2's 4 of 128, GPT-2-XL's 25 of 64
ROWS = {"1024": (8, 128), "512": (4, 128), "1600": (25, 64)}
#: (layers, pages in the pool, lanes, slots a lane)
TABLES = {
    "one_lane": (2, 9, 1, 5),
    "many_lanes_dead_slots": (3, 13, 3, 4),
    "ring": (1, 10, 2, 3),
    "null_page_garbage": (2, 9, 2, 4),
}


def _pool(layers, num_pages, h, d, seed=0):
    pages = jax.random.normal(jax.random.PRNGKey(seed), (layers, num_pages, h, PAGE, d), jnp.float32)
    return pages.astype(BF16)


def _tables(case, lanes, slots, num_pages):
    rng = np.random.default_rng(1)
    ids = rng.permutation(np.arange(1, num_pages))[:lanes * slots].reshape(lanes, slots)
    ids = np.resize(ids, (lanes, slots)).astype(np.int32)
    if case == "many_lanes_dead_slots":
        ids[0, 2:] = NULL_PAGE                         # a lane two pages long
        ids[2, :] = NULL_PAGE                          # a vacant lane
    if case == "ring":
        ids[1] = np.roll(ids[1], 1)                    # a ring that has wrapped: its newest page first
        ids[0, -1] = NULL_PAGE                         # a ring not yet full
    if case == "null_page_garbage":
        ids[:, -1] = NULL_PAGE
    return jnp.asarray(ids)


def _update_form(pages, tables):
    """The view the CPU rig builds: ``_gather_view`` where the kernel does not apply."""
    assert not view_gather_applies(pages)
    return pool._gather_view(pages, tables, True)


@pytest.mark.parametrize("case", sorted(TABLES))
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_the_page_copy_is_the_update_form_bit_for_bit(rows, case):
    """The stacked view and each layer's view alone, against the update form:
    live pages where the tables name them, the null page's contents in a dead
    slot (a frozen lane's garbage among them), a lane's ring in its table's
    order."""
    h, d = ROWS[rows]
    layers, num_pages, lanes, slots = TABLES[case]
    pages = _pool(layers, num_pages, h, d)
    if case == "null_page_garbage":
        # a frozen lane writes its pad token's rows into the null page
        pages = pages.at[:, NULL_PAGE].set(jnp.asarray(7.0, BF16))
    tables = _tables(case, lanes, slots, num_pages)
    want = _update_form(pages, tables)
    got = gather_pages(pages, tables, interpret=True)
    assert got.shape == (layers, lanes, h * d, slots * PAGE) and got.dtype == BF16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for layer in range(layers):
        one = gather_pages(pages, tables, layer=layer, interpret=True)
        np.testing.assert_array_equal(np.asarray(one, np.float32), np.asarray(want[layer], np.float32))
    if case == "null_page_garbage":
        assert float(got[0, 0, 0, -1]) == 7.0                           # copied, not zeroed


def test_ids_past_the_pool_are_clamped_as_the_update_form_clamps():
    pages = _pool(1, 5, 4, 128)
    tables = jnp.asarray([[1, 9, 4]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(gather_pages(pages, tables, interpret=True), np.float32),
                                  np.asarray(_update_form(pages, tables), np.float32))


@pytest.mark.parametrize("layers,slots,block,want", [
    (1, 256, 256 * 1024, (1, 8)),          # Trinity's full layer: a page of one layer is 256 KB
    (4, 37, 256 * 1024, (4, 1)),           # its ring: 37 slots, no divisor but itself
    (2, 64, 128 * 1024, (2, 8)),           # Mellum2's two full layers
    (48, 8, 400 * 1024, (4, 1)),           # GPT-2-XL: 1600 rows of 128 positions a layer
])
def test_the_plan_fills_a_step_up_to_its_bytes(layers, slots, block, want):
    per_block, per_step = _plan(layers, slots, block)
    assert (per_block, per_step) == want
    assert layers % per_block == 0 and slots % per_step == 0
    assert per_block * per_step * block <= STEP_BYTES


#: what the kernel refuses, each on a platform that would compile it
REFUSED = {
    "float32": dict(dtype=jnp.float32),
    "pages_of_64": dict(page=64),
    "rows_not_whole_tiles": dict(d=72),
    "under_xla_form": dict(context=True),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_selection_rule(monkeypatch, name):
    """The CPU rig keeps the update form; a platform that compiles the kernel
    takes it for a bfloat16 pool of whole-lane pages, and not for a float32
    one, pages of 64, rows that are no whole tiles, or under ``xla_form`` (a
    pool sharded over key/value heads)."""
    case = dict(dict(dtype=BF16, page=PAGE, d=128, context=False), **REFUSED[name])
    shape = jax.ShapeDtypeStruct((2, 9, 4, case["page"], case["d"]), case["dtype"])
    ok = jax.ShapeDtypeStruct((2, 9, 4, PAGE, 128), BF16)
    assert not view_gather_applies(ok)                                  # the CPU rig
    assert view_gather_applies(ok, interpret=True)
    monkeypatch.setattr(vg, "_platform_compiles", lambda: True)
    assert view_gather_applies(ok)
    if case["context"]:
        with xla_form():
            assert not view_gather_applies(ok)
    else:
        assert not view_gather_applies(shape)


def test_the_latent_view_and_the_cpu_rig_never_reach_the_kernel(monkeypatch):
    """Latent attention's position-major view is the compiler's gather whatever
    the platform; the flat view on the CPU rig lowers as written."""
    pages = _pool(2, 9, 1, 128)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    before = jax.jit(lambda p, t: pool._gather_view(p, t, True)).lower(pages, tables).as_text()
    assert "view_gather" not in before

    def never(*a, **kw):
        raise AssertionError("the page copy was called")

    monkeypatch.setattr(pool, "gather_pages", never)
    assert jax.jit(lambda p, t: pool._gather_view(p, t, True)).lower(pages, tables).as_text() == before
    monkeypatch.setattr(vg, "_platform_compiles", lambda: True)
    latent = pool._gather_view(pages, tables, False)
    assert latent.shape == (2, 2, 3 * PAGE, 1, 128)


# ------------------------------------------------------------- through the engine
def _compiles(monkeypatch, calls):
    """A platform that compiles the kernel, with the kernel itself run
    interpreted (this is still a CPU) and every call of it recorded."""
    def recorded(pages, tables, **kw):
        calls.append((tables.shape[0], kw.get("layer")))
        return gather_pages(pages, tables, interpret=True, **kw)

    monkeypatch.setattr(vg, "_platform_compiles", lambda: True)
    monkeypatch.setattr(pool, "gather_pages", recorded)


def _model(kind):
    """Toys at widths the kernel takes, bfloat16: a stack of a window and a full
    layer with heads of 128 (the mixed pool), and a GPT-2 block with heads of
    64 (the one-rule pool, ``page`` minor on the chip)."""
    if kind == "mixed":
        config = TransformerConfig.tiny(
            hidden_size=256, num_heads=2, num_kv_heads=1, head_dim=128, intermediate_size=256, num_layers=2,
            vocab_size=97, max_seq_len=2048, sliding_window=1792, layer_types=("window", "full"),
            rope_full_layers=False, dtype=BF16, param_dtype=BF16)
    else:
        config = TransformerConfig.gpt2(num_layers=2, hidden_size=128, num_heads=2, num_kv_heads=2,
                                        intermediate_size=256, vocab_size=97, max_seq_len=2048,
                                        dtype=BF16, param_dtype=BF16)
    model = Transformer(config)
    return model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


PROMPT = [int(t) for t in (np.arange(700) * 7) % 97]
NEW_TOKENS = 8


def _serve(model, params):
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=2048, page_size=PAGE, prefill_buckets=(128,),
                           decode_window=4, prefix_cache_mb=0, registry=registry)
    request = engine.submit(PROMPT, max_new_tokens=NEW_TOKENS)
    engine.run()
    return engine, registry, request.tokens


@pytest.mark.parametrize("kind", ["mixed", "paged"])
def test_a_served_prompt_gives_the_same_tokens_under_both_forms(monkeypatch, kind):
    """One prompt through a small engine, its decode windows' views built by
    the updates and then by the kernel: the same tokens.  The mixed window
    gathers one view a layer (a call a layer of each of its four arrays), the
    paged window the stacked view (a call for K and one for V)."""
    model, params = _model(kind)
    engine, registry, want = _serve(model, params)
    assert engine.view_gather_kernel is False and registry.gauge("serve/view_gather_kernel").value == 0
    calls = []
    _compiles(monkeypatch, calls)
    engine, registry, got = _serve(model, params)
    assert engine.view_gather_kernel is True and registry.gauge("serve/view_gather_kernel").value == 1
    assert len(want) == NEW_TOKENS and got == want
    if kind == "mixed":
        assert calls == [(2, 0)] * 4                     # traced once: K and V of each kind, one layer each
    else:
        # the window's K and V and the one-lane chunk's, stacked
        assert sorted(calls, key=str) == [(1, None)] * 2 + [(2, None)] * 2


def test_the_counters_are_held_to_the_prompts_pages(monkeypatch):
    """``view_slots`` counts every (lane, slot) block of every flat view a
    decode window fills (K's and V's, both kinds), ``view_slots_live`` those
    copied from a live page: here the prompt's pages (700 positions and the
    tokens decoded after them lie in six pages of 128) in the full table and in
    the ring, K and V, a window; whichever form runs."""
    model, params = _model("mixed")
    for compiles in (False, True):
        if compiles:
            _compiles(monkeypatch, [])
        engine, registry, tokens = _serve(model, params)
        windows = engine.stats["decode_steps"] // 4
        kv = engine.kv
        assert windows >= NEW_TOKENS // 4
        assert (len(PROMPT) + NEW_TOKENS + 4) <= 6 * PAGE
        assert engine.stats["view_slots_live"] == windows * 2 * (6 + 6)
        assert engine.stats["view_slots"] == windows * 2 * (kv.tables.size + kv.ring_tables.size)
        assert registry.counter("serve/view_slots_live_total").value == engine.stats["view_slots_live"]


def test_engines_whose_window_reads_no_flat_view_count_nothing():
    """A retention model reads a state: the gauge reads 0 and the counters are
    not there."""
    from accelerate_tpu.models.retention import RetentionSpec

    config = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64,
                                    retention=RetentionSpec(chunk=8), qk_norm=True)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=64, prefill_buckets=(8,), decode_window=4,
                           prefix_cache_mb=None, registry=registry)
    assert registry.gauge("serve/view_gather_kernel").value == 0 and engine.view_gather_kernel is False
    assert "view_slots" not in engine.stats


def test_under_a_tensor_parallel_mesh_the_window_keeps_the_updates(monkeypatch):
    """A pool sharded over key/value heads: the engine's windows are traced
    under ``xla_form``, the gauge reads 0 and the kernel is never called, on a
    platform that would compile it; the counters still count."""
    from accelerate_tpu.parallel.mesh import build_mesh

    config = TransformerConfig.tiny(hidden_size=256, num_heads=2, num_kv_heads=2, head_dim=128, intermediate_size=256,
                                    num_layers=2, vocab_size=97, max_seq_len=2048, dtype=BF16, param_dtype=BF16)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def never(*a, **kw):
        raise AssertionError("the page copy was called")

    monkeypatch.setattr(vg, "_platform_compiles", lambda: True)
    monkeypatch.setattr(pool, "gather_pages", never)
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=2048, page_size=PAGE, prefill_buckets=(128,),
                           decode_window=4, prefix_cache_mb=None, registry=registry, mesh=build_mesh({"tp": 2}))
    assert engine.tp_degree == 2 and engine.view_gather_kernel is False
    assert registry.gauge("serve/view_gather_kernel").value == 0
    request = engine.submit([int(t) for t in np.arange(200) % 97], max_new_tokens=4)
    engine.run()
    assert len(request.tokens) == 4 and engine.stats["view_slots"] > engine.stats["view_slots_live"] > 0

"""The prefill chunk's flash kernel over a cache view (``accelerate_tpu/ops/view_attention.py``)
in interpret mode on the CPU, against ``cached_attention``'s masked einsum.

Tolerances: the kernel multiplies bfloat16 operands into float32 scores, casts
the probabilities to bfloat16 for ``P V`` and rounds once to bfloat16; the
reference here is the einsum on the same values in float32.  Outputs are
averages of unit normals (size ~1 where few keys are live), so a bfloat16 step
is up to 2^-8 = 0.004 and the probabilities' rounding adds as much: ``ATOL``
0.02.  A wrong mask moves a row that sees few keys by its whole size
(``test_a_wrong_mask_fails_the_tolerance``).  What interpret mode cannot show (tiling, fast memory,
the dead blocks never fetched) is ``tests/test_tpu_compile.py``'s and the
chip's.
"""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import transformer
from accelerate_tpu.models.transformer import (
    KVCache,
    MixedKVCache,
    Transformer,
    TransformerConfig,
    cached_attention,
)
from accelerate_tpu.ops import view_attention as va
from accelerate_tpu.ops.view_attention import (
    DECODE_BLOCK,
    KEY_BLOCK,
    live_key_blocks,
    view_decode_applies,
    view_decode_attention,
    view_flash_applies,
    view_flash_attention,
    xla_form,
)
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry

ATOL = 0.02
BF16 = jnp.bfloat16
D = 128
RING = 37 * 128                      # the long-document cell's ring: no multiple of a key block
WINDOW = 4096

#: mask -> (view width, window, ring, the chunk's first position for "start", "middle", "last_block")
MASKS = {
    "full": (2048, None, False, {"start": 0, "middle": 700, "last_block": 2048}),
    "band": (4096, 1024, False, {"start": 0, "middle": 1500, "last_block": 4096}),
    "ring": (RING, WINDOW, True, {"start": 0, "middle": 2500, "last_block": 9000}),      # 9000: wrapped twice
}


def _draw(rows, n_kv, rep, m, seed=0, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (batch, rows, n_kv * rep, D), jnp.float32).astype(BF16)
    k = jax.random.normal(keys[1], (batch, n_kv * D, m), jnp.float32).astype(BF16)
    v = jax.random.normal(keys[2], (batch, n_kv * D, m), jnp.float32).astype(BF16)
    return q, k, v


def _einsum(q, k, v, positions, **kw):
    f32 = lambda a: a.astype(jnp.float32)
    return np.asarray(cached_attention(f32(q), f32(k), f32(v), positions, **kw))


def _gap(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max())


@pytest.mark.parametrize("where", ["start", "middle", "last_block"])
@pytest.mark.parametrize("rep", [1, 6])
@pytest.mark.parametrize("rows", [128, 512])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_kernel_matches_the_masked_einsum(mask, rows, rep, where):
    """{full, band, ring} x {a 128-chunk, a 512-chunk} x {one query head a
    key/value head, six} x {the chunk at position 0, mid-view, in the view's
    last key block (the ring: after wrapping round twice)}; the view a power of
    two wide, and the ring's 37 x 128."""
    m, window, ring, bases = MASKS[mask]
    base = bases[where] - (rows if where == "last_block" and not ring else 0)
    q, k, v = _draw(rows, 1, rep, m, seed=rows + rep)
    positions = base + jnp.arange(rows)[None]
    want = _einsum(q, k, v, positions, window=window, ring=ring)
    got = view_flash_attention(q, k, v, positions, window=window, ring=ring, interpret=True)
    assert got.dtype == BF16 and got.shape == q.shape
    assert _gap(got, want) <= ATOL


@pytest.mark.parametrize("block", [128, 256, 512])
def test_other_key_blocks_and_two_lanes_at_different_depths(block):
    """Each lane has its own count of live blocks; key blocks of 128 tile the
    ring exactly, 256 and 512 leave it a masked tail."""
    q, k, v = _draw(128, 2, 2, RING, seed=block, batch=2)
    positions = jnp.asarray([[300], [6000]]) + jnp.arange(128)[None]
    want = _einsum(q, k, v, positions, window=WINDOW, ring=True)
    got = view_flash_attention(q, k, v, positions, window=WINDOW, ring=True, interpret=True, block=block)
    assert _gap(got, want) <= ATOL
    q, k, v = _draw(128, 2, 2, 2048, seed=block + 1, batch=2)
    positions = jnp.asarray([[0], [1900]]) + jnp.arange(128)[None]
    got = view_flash_attention(q, k, v, positions, interpret=True, block=block)
    assert _gap(got, _einsum(q, k, v, positions)) <= ATOL


@pytest.mark.parametrize("rows", [200, 1024, 640])
def test_rows_that_are_no_whole_row_block(rows):
    """A prefill that is no bucket: rows padded to whole lanes (the padding
    repeats the last position and is cut off), and a long one in row blocks,
    each with its own live key blocks."""
    q, k, v = _draw(rows, 1, 2, 2048, seed=rows)
    positions = 300 + jnp.arange(rows)[None]
    got = view_flash_attention(q, k, v, positions, interpret=True)
    assert got.shape == q.shape and _gap(got, _einsum(q, k, v, positions)) <= ATOL


@pytest.mark.parametrize("mask", ["full", "band"])
def test_dead_key_blocks_are_never_visited(mask):
    """NaN in every key block that can hold no visible key (past the last live
    one; under a band, before the first): the einsum turns them into NaN (0 x
    NaN), the kernel never fetches them."""
    m, window = (4096, None) if mask == "full" else (4096, 512)
    q, k, v = _draw(128, 2, 2, m, seed=7)
    base = 700 if mask == "full" else 2100
    positions = base + jnp.arange(128)[None]
    first = 0 if window is None else (base - window + 1) // KEY_BLOCK * KEY_BLOCK
    last = -(-(base + 128) // KEY_BLOCK) * KEY_BLOCK
    dead = (jnp.arange(m) < first) | (jnp.arange(m) >= last)
    assert int(dead.sum()) >= 2 * KEY_BLOCK
    want = _einsum(q, k, v, positions, window=window)
    poisoned = [jnp.where(dead, jnp.nan, a.astype(jnp.float32)).astype(BF16) for a in (k, v)]
    got = np.asarray(view_flash_attention(q, *poisoned, positions, window=window, interpret=True), np.float32)
    assert np.isfinite(got).all() and _gap(got, want) <= ATOL
    assert not np.isfinite(_einsum(q, *poisoned, positions, window=window)).any()


def test_a_wrong_mask_fails_the_tolerance():
    """The tolerance sees a wrong mask: queries that see eight keys too many
    move a chunk's first rows (which average over a handful of keys) by tens of
    tolerances."""
    q, k, v = _draw(128, 1, 2, 2048, seed=3)
    positions = jnp.arange(128)[None]
    wrong = view_flash_attention(q, k, v, positions + 8, interpret=True)
    assert _gap(wrong, _einsum(q, k, v, positions)) > 10 * ATOL


# ------------------------------------------------- what cached_attention picks
def _compiles(monkeypatch, calls, decode_calls=None):
    """A platform that compiles the kernels, with the kernels themselves run
    interpreted (this is still a CPU) and every call of the chunk's kernel
    recorded in ``calls``, of the decode step's in ``decode_calls``."""
    def recorded(kernel, into):
        def call(q, k, v, positions, **kw):
            into.append((q.shape, k.shape, kw))
            return kernel(q, k, v, positions, interpret=True, **kw)
        return call

    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(transformer, "view_flash_attention", recorded(view_flash_attention, calls))
    monkeypatch.setattr(transformer, "view_decode_attention",
                        recorded(view_decode_attention, [] if decode_calls is None else decode_calls))


def test_cached_attention_takes_the_kernel_for_a_chunk_on_a_platform_that_compiles_it(monkeypatch):
    q, k, v = _draw(128, 2, 3, 2048)
    positions = 500 + jnp.arange(128)[None]
    assert not view_flash_applies(q, k)                       # the CPU rig keeps the einsum
    want = _einsum(q, k, v, positions)
    calls = []
    _compiles(monkeypatch, calls)
    assert view_flash_applies(q, k)
    assert _gap(cached_attention(q, k, v, positions), want) <= ATOL
    assert calls == [((1, 128, 6, D), (1, 2 * D, 2048), dict(window=None, ring=False))]


REFUSED = {
    "a_decode_window": dict(rows=4),
    "a_verify_window_under_a_chunk": dict(rows=64),
    "heads_64_wide": dict(d=64),
    "float32": dict(dtype=jnp.float32),
    "a_view_of_1024": dict(m=1024),
    "a_view_of_no_whole_lanes": dict(m=2048 + 64),
    "tree_mask": dict(rows=128, tree=True),
    "alibi": dict(alibi=True),
    "under_xla_form": dict(context=xla_form),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_einsum_is_what_lowers_for_everything_else(monkeypatch, name):
    """One case a refusal of the shape test, on a platform that compiles the
    kernel: the kernel is never reached, and the program lowered is letter for
    letter the one lowered where no platform compiles it."""
    case = dict(dict(rows=128, d=D, dtype=BF16, m=2048, tree=False, alibi=False, context=None), **REFUSED[name])
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, case["rows"], 4, case["d"]), jnp.float32).astype(case["dtype"])
    k, v = (jax.random.normal(key, (1, 2 * case["d"], case["m"]), jnp.float32).astype(case["dtype"]) for key in keys[1:])
    positions = 300 + jnp.arange(case["rows"])[None]
    tree = np.tril(np.ones((case["rows"],) * 2, bool)) if case["tree"] else None
    attend = functools.partial(cached_attention, alibi=case["alibi"], tree_mask=tree)
    before = jax.jit(attend).lower(q, k, v, positions).as_text()

    def never(*a, **kw):
        raise AssertionError("the flash kernel was called")

    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(transformer, "view_flash_attention", never)
    with (case["context"] or contextlib.nullcontext)():
        after = jax.jit(attend).lower(q, k, v, positions).as_text()
        out = attend(q, k, v, positions)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    if case["context"] is None:
        assert after == before


# ---------------------------------------------------- a toy stack of two kinds
@pytest.fixture(scope="module")
def toy():
    """Window and full layers in one stack at widths the kernel takes: heads of
    128, bfloat16, a window of 1,792 so that the ring is 2,048 columns."""
    config = TransformerConfig.tiny(
        hidden_size=256, num_heads=2, num_kv_heads=1, head_dim=128, intermediate_size=256, num_layers=2,
        vocab_size=97, max_seq_len=2048, sliding_window=1792, layer_types=("window", "full"),
        rope_full_layers=False, dtype=BF16, param_dtype=BF16)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_a_chunk_of_a_two_kinds_stack_through_the_kernel(toy, monkeypatch):
    """Both kinds of layer of a ``MixedKVCache`` prefill take the kernel (the
    window layer with its ring mask), and the logits are the einsum's within
    the model's bfloat16 noise."""
    model, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, 97)
    rows = model.config.num_kv_heads * D
    empty = lambda: jnp.zeros((1, 1, rows, 2048), BF16)                          # one layer of each kind, one lane
    cache = lambda: MixedKVCache(k=empty(), v=empty(), k_ring=empty(), v_ring=empty(),
                                 index=jnp.zeros((), jnp.int32), page=128)
    run = lambda c, tokens: model.apply({"params": params}, tokens, cache=c)
    _, filled = run(cache(), ids[:, :128])
    want, _ = run(filled, ids[:, 128:])
    calls = []
    _compiles(monkeypatch, calls)
    _, filled = run(cache(), ids[:, :128])
    got, _ = run(filled, ids[:, 128:])
    assert [(kw["ring"], kw["window"], k[2]) for _, k, kw in calls] == [(True, 1792, 2048), (False, None, 2048)] * 2
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    assert _gap(got, np.asarray(want, np.float32)) <= 0.05 * scale


def test_generate_style_prefill_on_a_contiguous_cache_takes_the_band(toy, monkeypatch):
    """``KVCache`` keeps ``max_len`` columns for the window layer too: the band
    mask, not the ring's."""
    model, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 128), 0, 97)
    calls = []
    _compiles(monkeypatch, calls)
    logits, _ = model.apply({"params": params}, ids, cache=KVCache.create(model.config, 1, 2048))
    assert [(kw["ring"], kw["window"]) for *_, kw in calls] == [(False, 1792), (False, None)]
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def _engine(model, params, registry, **kw):
    return ServingEngine(model, params, num_slots=2, max_len=2048, page_size=128, prefill_buckets=(128,),
                         decode_window=4, prefix_cache_mb=0, registry=registry, **kw)


def test_the_engine_says_which_form_its_chunks_run_and_what_share_of_the_view_was_live(toy, monkeypatch):
    """``serve/chunk_attention_kernel``: 0 on the CPU rig, 1 where the platform
    compiles the kernel and the chunk's shapes pass; ``chunk_key_blocks_live`` /
    ``chunk_key_blocks_view`` count, a dispatched chunk, the key blocks up to its
    last row and the view's."""
    model, params = toy
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 0 and engine.chunk_attention_kernel is False
    calls = []
    _compiles(monkeypatch, calls)
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 1 and engine.chunk_attention_kernel is True
    prompt = [int(t) for t in np.arange(700) % 97]
    request = engine.submit(prompt, max_new_tokens=4)
    engine.run()
    assert len(request.tokens) == 4
    chunks = -(-700 // 128)                                                     # six chunks of 128 rows
    assert engine.stats["prefill_chunks"] == chunks
    assert engine.stats["chunk_key_blocks_live"] == sum(-(-(128 * (i + 1)) // KEY_BLOCK) for i in range(chunks)) == 6
    assert engine.stats["chunk_key_blocks_view"] == chunks * (2048 // KEY_BLOCK)
    assert registry.counter("serve/chunk_key_blocks_live_total").value == 6
    # every chunk ran both layers in the kernel; no decode window did
    assert len(calls) == 2 and all(q[1] == 128 for q, *_ in calls)              # traced once a layer, one bucket
    # widths the kernel does not take: the gauge says so, the counters still count
    narrow = Transformer(dataclasses.replace(model.config, dtype=jnp.float32))
    registry = MetricsRegistry()
    engine = _engine(narrow, params, registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 0
    assert engine.stats["chunk_key_blocks_view"] == 0


def test_a_model_without_a_gathered_view_counts_no_key_blocks():
    """A retention model's chunk reads a state, not a view: the gauge reads 0
    and the counters are not there."""
    from accelerate_tpu.models.retention import RetentionSpec

    config = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64,
                                    retention=RetentionSpec(chunk=8), qk_norm=True)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=64, prefill_buckets=(8,), decode_window=4,
                           prefix_cache_mb=None, registry=registry)
    assert registry.gauge("serve/chunk_attention_kernel").value == 0
    assert "chunk_key_blocks_live" not in engine.stats


def test_under_a_tensor_parallel_mesh_the_chunk_keeps_the_einsum(monkeypatch):
    """Views sharded over key/value heads: a ``pallas_call`` has no partitioning
    rule, so the engine's chunk programs are traced under ``xla_form`` and the
    gauge reads 0, on a platform that would compile the kernel."""
    from accelerate_tpu.parallel.mesh import build_mesh

    config = TransformerConfig.tiny(hidden_size=256, num_heads=2, num_kv_heads=2, head_dim=128, intermediate_size=256,
                                    num_layers=2, vocab_size=97, max_seq_len=2048, dtype=BF16, param_dtype=BF16)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def never(*a, **kw):
        raise AssertionError("the flash kernel was called")

    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(transformer, "view_flash_attention", never)
    registry = MetricsRegistry()
    one_chip = ServingEngine(model, params, num_slots=2, max_len=2048, page_size=128, prefill_buckets=(128,),
                             decode_window=4, prefix_cache_mb=None, registry=registry)
    assert one_chip.chunk_attention_kernel is True
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=2048, page_size=128, prefill_buckets=(128,),
                           decode_window=4, prefix_cache_mb=None, registry=registry, mesh=build_mesh({"tp": 2}))
    assert engine.tp_degree == 2 and engine.chunk_attention_kernel is False
    assert registry.gauge("serve/chunk_attention_kernel").value == 0
    request = engine.submit([int(t) for t in np.arange(200) % 97], max_new_tokens=4)
    engine.run()
    assert len(request.tokens) == 4 and engine.stats["chunk_key_blocks_live"] == 2


# --------------------------------------------------------- the decode step's kernel
#: mask -> (view width, window, ring, four lanes' positions): a lane that is not
#: decoding (0), mid-view, in or next to the view's last key block (the ring: its
#: tail of 128 columns past nine blocks of 512), at the view's end
DECODE_MASKS = {
    "full": (4096, None, False, [0, 700, 3100, 4095]),
    "band": (4096, 1024, False, [0, 1500, 3100, 4095]),
    "ring_unwrapped": (RING, WINDOW, True, [0, 2500, 4600, 4735]),
    "ring_wrapped": (RING, WINDOW, True, [4736, 6000, 9100, 3 * RING + 4700]),
}


def _decode_draw(lanes, n_kv, rep, m, seed):
    q, k, v = _draw(1, n_kv, rep, m, seed=seed, batch=lanes)
    return q, k, v


@pytest.mark.parametrize("rep", [6, 8])
@pytest.mark.parametrize("mask", sorted(DECODE_MASKS))
def test_decode_kernel_matches_the_masked_einsum(mask, rep):
    """{full, band, a ring before and after it wraps} x {Trinity's six query
    heads a key/value head, Mellum2's eight}, four lanes at different depths
    each; the ring's 37 x 128 columns leave a tail block of 128 past nine key
    blocks of 512, read as the block ending at the view's end."""
    m, window, ring, lanes = DECODE_MASKS[mask]
    q, k, v = _decode_draw(len(lanes), 2, rep, m, seed=rep)
    positions = jnp.asarray(lanes)[:, None]
    want = _einsum(q, k, v, positions, window=window, ring=ring)
    got = view_decode_attention(q, k, v, positions, window=window, ring=ring, interpret=True)
    assert got.dtype == BF16 and got.shape == q.shape
    assert _gap(got, want) <= ATOL


@pytest.mark.parametrize("mask", sorted(DECODE_MASKS))
def test_decode_kernel_keeps_each_lanes_sums_apart(mask):
    """Nine lanes whose live blocks differ from one lane to the next (one
    block, every block, one again): the kernel holds one lane's running max,
    sum and accumulator at a time, and no lane's sums leak into the next's."""
    m, window, ring, lanes = DECODE_MASKS[mask]
    depths = [0, lanes[-1], 1, lanes[1], 0, lanes[2], lanes[-1], 0, lanes[1] + 513]
    q, k, v = _decode_draw(len(depths), 1, 6, m, seed=3)
    positions = jnp.asarray(depths)[:, None]
    want = _einsum(q, k, v, positions, window=window, ring=ring)
    got = view_decode_attention(q, k, v, positions, window=window, ring=ring, interpret=True)
    assert _gap(got, want) <= ATOL


#: heads -> (query heads, key/value heads, view width): Trinity's full view and Mellum2's
DECODE_HEADS = {"trinity": (48, 8, 32768), "mellum2": (32, 4, 8192)}


@pytest.mark.parametrize("heads", sorted(DECODE_HEADS))
def test_decode_kernel_is_refused_where_its_lanes_outgrow_fast_memory(heads):
    """The kernel keeps every lane's query and output in fast memory: the
    rule takes lanes up to what ``_DECODE_FAST_BYTES`` holds and refuses one
    more, and refuses a list of live pairs longer than scalar memory was
    measured to hold, so larger batches keep the einsum."""
    n_q, n_kv, m = DECODE_HEADS[heads]
    spec = lambda lanes, m=m: (jax.ShapeDtypeStruct((lanes, 1, n_q, D), BF16),
                               jax.ShapeDtypeStruct((lanes, n_kv * D, m), BF16))
    fits = lambda lanes: va._decode_fast_bytes(lanes, n_kv, n_q // n_kv, D, 1024, 2) <= va._DECODE_FAST_BYTES
    most = max(lanes for lanes in range(1, 4096) if fits(lanes))
    assert view_decode_applies(*spec(most, 1024), interpret=True)
    assert not view_decode_applies(*spec(most + 1, 1024), interpret=True)
    assert most >= 256                                        # vLLM's default batch of sequences
    longest = 512 * DECODE_BLOCK                              # 512 key blocks a lane
    lanes = va._DECODE_MAX_PAIRS // 512
    assert lanes < most
    assert view_decode_applies(*spec(lanes, longest), interpret=True)
    assert not view_decode_applies(*spec(lanes + 1, longest), interpret=True)


@pytest.mark.parametrize("mask", ["full", "band", "ring_wrapped"])
def test_decode_dead_key_blocks_are_never_fetched(mask):
    """NaN in every key block that holds no key a lane's query sees (past its
    position; before its band; a ring's block wholly behind the band): the einsum
    turns them into NaN (0 x NaN), the kernel never fetches them.  The ring here
    is 3,072 columns under a band of 2,048, and each lane's newest column ends
    a block, so its 1,024 columns behind the band are two whole blocks."""
    m, window, ring, lanes = {"full": (4096, None, False, [0, 700, 2100, 3000]),
                              "band": (4096, 1024, False, [0, 1500, 2100, 4095]),
                              "ring_wrapped": (3072, 2048, True, [4095, 8191, 12287, 7167])}[mask]
    q, k, v = _decode_draw(len(lanes), 2, 6, m, seed=11)
    positions = jnp.asarray(lanes)[:, None]
    live = np.repeat(live_key_blocks(np.asarray(lanes), m, DECODE_BLOCK, window, ring, xp=np), DECODE_BLOCK, axis=1)
    assert (~live).sum() >= 4 * KEY_BLOCK
    want = _einsum(q, k, v, positions, window=window, ring=ring)
    dead = jnp.asarray(~live)[:, None, :]
    poisoned = [jnp.where(dead, jnp.nan, a.astype(jnp.float32)).astype(BF16) for a in (k, v)]
    got = np.asarray(view_decode_attention(q, *poisoned, positions, window=window, ring=ring, interpret=True),
                     np.float32)
    assert np.isfinite(got).all() and _gap(got, want) <= ATOL
    assert not np.isfinite(_einsum(q, *poisoned, positions, window=window, ring=ring)).any()


def test_decode_live_key_blocks_on_the_host_and_on_the_device_agree():
    """The engine counts on the host with numpy what the kernel plans on the
    device: the same blocks, for views written from 0 and rings."""
    last = np.asarray([0, 1, 1023, 1024, 4095, 4735, 4736, 6000, 9100, 20000])
    for m, window, ring in ((4096, None, False), (4096, 1024, False), (RING, WINDOW, True), (1664, 1024, True)):
        clipped = last if ring else np.minimum(last, m - 1)
        host = live_key_blocks(clipped, m, 512, window, ring, xp=np)
        device = np.asarray(live_key_blocks(jnp.asarray(clipped), m, 512, window, ring))
        assert (host == device).all() and host.any(axis=1).all()


def test_cached_attention_takes_the_decode_kernel_for_one_row_on_a_platform_that_compiles_it(monkeypatch):
    q, k, v = _decode_draw(3, 2, 3, 2048, seed=5)
    positions = jnp.asarray([[0], [900], [2047]])
    assert not view_decode_applies(q, k)                       # the CPU rig keeps the einsum
    want = _einsum(q, k, v, positions)
    calls, decode_calls = [], []
    _compiles(monkeypatch, calls, decode_calls)
    assert view_decode_applies(q, k) and not view_flash_applies(q, k)
    assert _gap(cached_attention(q, k, v, positions), want) <= ATOL
    assert calls == [] and decode_calls == [((3, 1, 6, D), (3, 2 * D, 2048), dict(window=None, ring=False))]


DECODE_REFUSED = {
    "a_verify_window": dict(rows=4),
    "heads_64_wide": dict(d=64),
    "float32": dict(dtype=jnp.float32),
    "a_view_of_768": dict(m=768),
    "tree_mask": dict(tree=True),
    "alibi": dict(alibi=True),
    "under_xla_form": dict(context=xla_form),
}


@pytest.mark.parametrize("name", sorted(DECODE_REFUSED))
def test_a_decode_step_keeps_the_einsum_where_the_kernel_does_not_apply(monkeypatch, name):
    """Verify rows, 64-wide heads, float32, a tree mask, a view under 1,024 columns, alibi
    and ``xla_form``, on a platform that compiles the kernels: neither kernel is
    reached, and the program lowered is letter for letter the one lowered where
    no platform compiles them."""
    case = dict(dict(rows=1, d=D, dtype=BF16, m=4096, tree=False, alibi=False, context=None),
                **DECODE_REFUSED[name])
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (2, case["rows"], 4, case["d"]), jnp.float32).astype(case["dtype"])
    k, v = (jax.random.normal(key, (2, 2 * case["d"], case["m"]), jnp.float32).astype(case["dtype"])
            for key in keys[1:])
    positions = jnp.asarray([[300], [900]]) + jnp.arange(case["rows"])[None]
    tree = np.tril(np.ones((case["rows"],) * 2, bool)) if case["tree"] else None
    attend = functools.partial(cached_attention, alibi=case["alibi"], tree_mask=tree)
    before = jax.jit(attend).lower(q, k, v, positions).as_text()

    def never(*a, **kw):
        raise AssertionError("a kernel was called")

    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(transformer, "view_flash_attention", never)
    monkeypatch.setattr(transformer, "view_decode_attention", never)
    with (case["context"] or contextlib.nullcontext)():
        after = jax.jit(attend).lower(q, k, v, positions).as_text()
        out = attend(q, k, v, positions)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    if case["context"] is None:
        assert after == before


def test_generate_style_decode_on_a_contiguous_cache_takes_the_decode_kernel(toy, monkeypatch):
    """``KVCache`` (``generate``'s) with 128-wide bfloat16 heads: a decode step
    of both layers goes to the decode kernel by the same rule (the window layer
    with its band over ``max_len`` columns), and the logits are the einsum's
    within the model's bfloat16 noise."""
    model, params = toy
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 130), 0, 97)

    def run():
        _, cache = model.apply({"params": params}, ids[:, :128], cache=KVCache.create(model.config, 2, 2048))
        logits, cache = model.apply({"params": params}, ids[:, 128:129], cache=cache)
        return model.apply({"params": params}, ids[:, 129:130], cache=cache)[0]

    want = run()
    calls, decode_calls = [], []
    _compiles(monkeypatch, calls, decode_calls)
    got = run()
    assert [(kw["ring"], kw["window"]) for *_, kw in decode_calls] == [(False, 1792), (False, None)] * 2
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    assert _gap(got, np.asarray(want, np.float32)) <= 0.05 * scale


def test_the_engine_says_which_form_its_windows_run_and_what_share_of_the_view_was_live(toy, monkeypatch):
    """``serve/decode_attention_kernel``: 0 on the CPU rig, 1 where the platform
    compiles the kernel and the window's shapes pass; ``decode_key_blocks_live``
    / ``decode_key_blocks_view`` count, a dispatched window, the key blocks of
    every lane's ``max_len``-wide view up to the window's last position and the
    view's, whichever form runs; a served prompt's tokens are the einsum's."""
    model, params = toy
    prompt = [int(t) for t in np.arange(1020) % 97]
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/decode_attention_kernel").value == 0 and engine.decode_attention_kernel is False
    want = engine.submit(prompt, max_new_tokens=9)
    engine.run()
    calls, decode_calls = [], []
    _compiles(monkeypatch, calls, decode_calls)
    registry = MetricsRegistry()
    engine = _engine(model, params, registry)
    assert registry.gauge("serve/decode_attention_kernel").value == 1 and engine.decode_attention_kernel is True
    request = engine.submit(prompt, max_new_tokens=9)
    engine.run()
    assert request.tokens == want.tokens and len(request.tokens) == 9
    # both layers of the window's steps in the decode kernel (traced once a layer)
    assert sorted((kw["ring"], k[2]) for _, k, kw in decode_calls) == [(False, 2048), (True, 2048)]
    windows = engine.stats["decode_steps"] // 4
    assert windows == 3
    # the lane's view: the windows' last positions 1,022 (two blocks of 512),
    # 1,026 and 1,030 (three); the idle lane's one block each window; four
    # blocks a lane's view
    assert DECODE_BLOCK == 512
    assert engine.stats["decode_key_blocks_live"] == (2 + 1) + (3 + 1) + (3 + 1)
    assert engine.stats["decode_key_blocks_view"] == windows * 2 * (2048 // DECODE_BLOCK)
    assert registry.counter("serve/decode_key_blocks_live_total").value == 11


def test_a_model_without_a_gathered_window_view_counts_no_decode_key_blocks():
    """A retention model's window reads a state, not a view: the gauge reads 0
    and the counters are not there."""
    from accelerate_tpu.models.retention import RetentionSpec

    config = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64,
                                    retention=RetentionSpec(chunk=8), qk_norm=True)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=64, prefill_buckets=(8,), decode_window=4,
                           prefix_cache_mb=None, registry=registry)
    engine.submit(list(range(1, 12)), max_new_tokens=6)
    engine.run()
    assert registry.gauge("serve/decode_attention_kernel").value == 0
    assert "decode_key_blocks_live" not in engine.stats and engine.stats["decode_steps"] > 0


def test_under_a_tensor_parallel_mesh_the_decode_window_keeps_the_einsum(monkeypatch):
    """Views sharded over key/value heads: the engine's decode window is
    traced under ``xla_form`` and the gauge reads 0, on a platform that would
    compile the kernel; the counters still count."""
    from accelerate_tpu.parallel.mesh import build_mesh

    config = TransformerConfig.tiny(hidden_size=256, num_heads=2, num_kv_heads=2, head_dim=128, intermediate_size=256,
                                    num_layers=2, vocab_size=97, max_seq_len=2048, dtype=BF16, param_dtype=BF16)
    model = Transformer(config)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]

    def never(*a, **kw):
        raise AssertionError("a kernel was called")

    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(transformer, "view_flash_attention", never)
    monkeypatch.setattr(transformer, "view_decode_attention", never)
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, num_slots=2, max_len=2048, page_size=128, prefill_buckets=(128,),
                           decode_window=4, prefix_cache_mb=None, registry=registry, mesh=build_mesh({"tp": 2}))
    assert engine.decode_attention_kernel is False and registry.gauge("serve/decode_attention_kernel").value == 0
    request = engine.submit([int(t) for t in np.arange(200) % 97], max_new_tokens=6)
    engine.run()
    assert len(request.tokens) == 6 and engine.stats["decode_key_blocks_view"] == (
        2 * engine.stats["decode_steps"] // 4 * (2048 // DECODE_BLOCK))

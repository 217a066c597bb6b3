"""The main path's Pallas kernels, compiled for a TPU v5e that is described, not attached.

The CPU rig runs every kernel under ``interpret=True``, which checks results
and nothing about what the TPU compiler accepts: both paged kernels passed
every interpret-mode test while Mosaic refused their block shapes.  The TPU
compiler is installed here and compiles for a described ``v5e:2x2`` device, so
these tests hand it the kernels at the widths ``chip_smoke.py`` runs (25x64
and 12x64 heads, page 16, a 128-wide prefill chunk) with ``interpret=False``
and require ``tpu_custom_call`` in the compiled program.  Nothing runs; a
compile that passes is not a chip run.

The same facility holds the serving engine's paged programs to what the
incremental forward promises: the stacked KV cache goes through the layers
whole and each layer writes its new rows in place.  A slice -> update -> stack
round trip per layer compiles (it did, until PR 26) to four passes over the
whole cache on every decode step, which no CPU test can see: the values are
the same.  So the compiled decode window and prefill chunk are read here, at
gpt2-xl's widths and a few layers, and may hold nothing of the whole cache's
shape under the model's name but the in-place write itself.  The gathered
arm's sandwich round the model is held the same way: the pool is written by
pages, in its own layout, and never copied (``test_pages_are_written_back_whole``).

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), with the persistent compile cache off around it — such a
compile can be written to the cache but not read back without a chip.
"""

import collections
import functools
import re
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.ops.grouped_matmul import grouped_matmul
from accelerate_tpu.ops.latent_view_attention import latent_view_attention
from accelerate_tpu.ops.paged_attention import (
    KV_FORMATS,
    paged_attention,
    paged_flash_prefill,
)
from accelerate_tpu.ops.retention import retention_step_onepass
from accelerate_tpu.ops.view_attention import view_flash_attention

NUM_PAGES, PAGE, PAGES_PER_LANE, LANES = 512, 16, 64, 4
HEADS = [(25, 25, 64), (12, 12, 64)]          # (q heads, kv heads, head dim)
PAGE_DTYPES = [None, "int8", "fp8"]           # None = native bf16 pages


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or its library is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_operands(one_chip, n, s, hq, hkv, d, page_dtype):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    storage = jnp.bfloat16 if page_dtype is None else KV_FORMATS[page_dtype][0]
    pool = spec((NUM_PAGES, hkv, PAGE, d), storage)
    operands = [
        spec((n, s, hq, d), jnp.bfloat16), pool, pool,
        spec((n, PAGES_PER_LANE), jnp.int32), spec((n,), jnp.int32),
    ]
    if page_dtype is not None:
        operands += [spec((NUM_PAGES, hkv), jnp.float32)] * 2
    return operands


def _binary_tree_mask(s):
    """Ancestor-or-self mask of a complete binary tree over ``s`` nodes."""
    mask = np.eye(s, dtype=bool)
    for node in range(1, s):
        parent = (node - 1) // 2
        mask[node] |= mask[parent]
    return mask


@pytest.mark.parametrize("page_dtype", PAGE_DTYPES)
@pytest.mark.parametrize("hq,hkv,d", HEADS)
@pytest.mark.parametrize(
    "s,tree", [(1, False), (4, False), (7, True)],
    ids=["decode", "verify", "tree_verify"],
)
def test_paged_decode_kernel_compiles(one_chip, hq, hkv, d, page_dtype, s, tree):
    tree_mask = _binary_tree_mask(s) if tree else None

    def fn(q, pk, pv, tables, lengths, *scales):
        return paged_attention(q, pk, pv, tables, lengths, *scales,
                               interpret=False, tree_mask=tree_mask)

    text = _compiled_text(fn, *_paged_operands(one_chip, LANES, s, hq, hkv, d, page_dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_dtype", PAGE_DTYPES)
@pytest.mark.parametrize("hq,hkv,d", HEADS)
def test_paged_prefill_kernel_compiles(one_chip, hq, hkv, d, page_dtype):
    def fn(q, pk, pv, tables, lengths, *scales):
        return paged_flash_prefill(q, pk, pv, tables, lengths, *scales, interpret=False)

    text = _compiled_text(fn, *_paged_operands(one_chip, 1, 128, hq, hkv, d, page_dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,seq,heads,d", [(8, 128, 12, 64), (1, 2048, 25, 64)])
def test_flash_attention_fwd_bwd_compiles(one_chip, batch, seq, heads, d):
    qkv = jax.ShapeDtypeStruct((batch, seq, heads, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    # forward, dq and dk/dv are three kernels
    assert text.count("tpu_custom_call") >= 3


# ------------------------------------------------- the cache write, compiled
# gpt2-xl's widths (25 heads of 64, 1600 wide) at the serve cell's pool: 4
# lanes of 8 pages of 128 and the null page, window 4
XL = dict(vocab_size=50257, hidden_size=1600, intermediate_size=6400, num_layers=2,
          num_heads=25, num_kv_heads=25, max_seq_len=1024, norm_type="layernorm",
          use_bias=True, positional="learned", mlp_variant="gelu",
          tie_word_embeddings=True, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
XL_LANES, XL_PAGES_PER_LANE, XL_PAGE, XL_WINDOW, XL_CHUNK = 4, 8, 128, 4, 128
#: opcodes that hand a buffer on without writing it
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "bitcast"}
#: the in-place writes: what ``_write_columns`` / ``_write_rows`` / ``paged_insert`` lower to
_WRITES = {"scatter", "dynamic-update-slice"}

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?[\w.\-]+\s+=\s+(\w+\[[\d,]*\])\S*\s+([\w\-]+)\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


class _Ins(NamedTuple):
    """One HLO instruction: ``shape`` and ``opcode`` are None where the result
    is a tuple (a while, a call), which still has computations it ``called``."""
    is_root: bool
    shape: Optional[str]
    opcode: Optional[str]
    op_name: str
    called: list
    line: str


def _parse_hlo(text):
    """``{computation: [_Ins]}`` and the entry computation's name."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            if line.startswith("ENTRY"):
                entry = head.group(1)
            continue
        ins, called = _INSTRUCTION.match(line), _CALLED.findall(line)
        if cur is None or not (ins or called):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        is_root, shape, opcode = ins.groups() if ins else (None, None, None)
        cur.append(_Ins(bool(is_root), shape, opcode,
                        op_name.group(1) if op_name else "", called, line[:300]))
    return comps, entry


def _reachable(comps, roots):
    seen, todo = [], list(roots)
    while todo:
        name = todo.pop()
        if name not in seen and name in comps:
            seen.append(name)
            todo.extend(c for ins in comps[name] for c in ins.called)
    return seen


def _check_cache_plumbing(text, whole_shape, n_writes, scope):
    """No ``Transformer/squeeze`` or ``Transformer/concatenate`` (a layer cut
    out of the stack, the stack put together again) in ``scope`` and what it
    calls, and nothing in ``scope`` that outputs an array of the whole cache's
    shape except the write: a scatter or dynamic-update-slice, alone or as the
    root of a fusion.  ``scope`` is "while": the decode scan's body; or
    "model": what the model's forward gave its name to in the entry
    computation (the gather and the write-back round it are ``pool.py``'s)."""
    comps, entry = _parse_hlo(text)
    if scope == "while":
        top = [body for body in set(re.findall(r"body=%?([\w.\-]+)", text))
               if any(ins.shape == whole_shape for ins in comps[body])]
        assert top, "no while body carries the cache"
    else:
        top = [entry]
    writes = 0
    for comp in _reachable(comps, top):
        for ins in comps[comp]:
            assert not ins.op_name.endswith(
                ("Transformer/squeeze", "Transformer/concatenate")), ins.line
            if (comp not in top or ins.shape != whole_shape
                    or ins.opcode in _PASS_THROUGH
                    or (scope == "model" and "/Transformer/" not in ins.op_name)):
                continue
            if ins.opcode == "fusion":
                root = next(i for i in comps[ins.called[0]] if i.is_root)
                assert root.opcode in _WRITES, ins.line
            else:
                assert ins.opcode in _WRITES, ins.line
            writes += 1
    # K and V, once a layer (the scan body is compiled once, run ``window`` times)
    assert writes == n_writes, f"{writes} whole-cache writes, expected {n_writes}"


def _deepseek_fields():
    """DeepSeek-V2's widths (``bench/configs/deepseek-v2.json``) cut to two
    layers: the dense one and one expert layer holding 4 experts."""
    import json
    from pathlib import Path

    fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "deepseek-v2.json").read_text())["transformer"]
    fields.update(num_layers=2, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    fields["experts"] = dict(fields["experts"], held=[0, 4])
    return fields


#: the serve cells' pools: fields, lanes, pages a lane, K's and V's rows ``[H, D]``
_POOLS = {
    "xl": (lambda: XL, XL_LANES, XL_PAGES_PER_LANE, ((25, 64), (25, 64))),
    "deepseek": (_deepseek_fields, 16, 64, ((1, 512), (1, 64))),
}
SPECULATE_K = 3


class _Program(NamedTuple):
    compiled: object
    pool_shapes: tuple       # K's and V's, as the HLO prints them
    view_shapes: tuple


@pytest.fixture(scope="module")
def paged_program(one_chip):
    """``(config, program, direct) -> _Program``, each compiled once a module."""
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import pool

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @functools.cache
    def build(config, program, direct):
        fields, n, p, rows = _POOLS[config]
        model = Transformer(TransformerConfig(**fields()))
        L, page = model.config.num_layers, XL_PAGE
        params = jax.tree_util.tree_map(
            lambda a: spec(a.shape, a.dtype),
            jax.eval_shape(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        )
        num_pages = n * p + 1
        pages = [spec((L, num_pages, h, page, d), jnp.bfloat16) for h, d in rows]
        scales = [spec((L, num_pages, h), jnp.float32) for h, _ in rows] if direct else []
        i32, f32 = (lambda *s: spec(s, jnp.int32)), (lambda *s: spec(s, jnp.float32))
        flag = lambda *s: spec(s, jnp.bool_)
        lanes = (flag(n), i32(n), flag(n), f32(n), i32(n), f32(n), i32(n),
                 spec((n, 2), jnp.uint32))
        if program == "prefill":
            fn = pool.make_paged_prefill_chunk(model, XL_CHUNK, page, direct=direct)
            args = (params, i32(1, XL_CHUNK), *pages, *scales, i32(p), i32())
            n = 1
        elif program == "decode":
            fn = pool.make_paged_decode_window(model, XL_WINDOW, direct=direct)
            args = (params, *pages, *scales, i32(n, p), i32(n), i32(n), *lanes)
        else:
            fn = pool.make_paged_verify_window(model, SPECULATE_K, direct=direct)
            args = (params, *pages, *scales, i32(n, p), i32(n), i32(n, SPECULATE_K + 1), *lanes)
        flat = model.config.latent_attention is None      # per-head rows: [L, N, H*D, M]
        # the flat view's gather asks the platform which form to take, and this
        # process sees a CPU: say "a TPU" while the program is traced
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr("accelerate_tpu.ops.view_gather._platform_compiles", lambda: True)
            lowered = fn.lower(*args)
        return _Program(
            lowered.compile(),
            tuple(f"bf16[{L},{num_pages},{h},{page},{d}]" for h, d in rows),
            tuple(f"bf16[{L},{n},{h * d},{p * page}]" if flat
                  else f"bf16[{L},{n},{p * page},{h},{d}]" for h, d in rows),
        )

    return build


@pytest.mark.parametrize(
    "program,direct",
    [("decode", False), ("decode", True), ("prefill", False), ("prefill", True)],
    ids=["decode-gathered-lane_index", "decode-direct-lane_index",
         "prefill-gathered-scalar_index", "prefill-direct-lane_index"],
)
def test_cache_is_written_in_place(paged_program, program, direct):
    """The gathered arm writes the view ``[L, N, H * D, M]`` (per-lane
    index in the decode scan, the scalar chunk base in prefill), the direct arm
    the page pool ``[L, NP, H, page, D]`` through the block tables (always per
    lane), both with the XLA read.  The view's per-lane write is one
    ``dynamic_update_slice`` a lane (``_write_columns``), everything else one
    write a layer and array."""
    built = paged_program("xl", program, direct)
    per_layer = XL_LANES if (program, direct) == ("decode", False) else 1
    _check_cache_plumbing(
        built.compiled.as_text(), (built.pool_shapes if direct else built.view_shapes)[0],
        n_writes=2 * XL["num_layers"] * per_layer,
        scope="while" if program == "decode" else "model",
    )


#: what brings a 2-layer pool into the faster memory space ``S(1)`` and back,
#: which a 48-layer pool (0.65 GB) cannot have: not the write-back's doing
_STAGING = {"copy-start", "copy-done", "slice-start", "slice-done"}
#: view-sized outputs of the sandwich, K's and V's, that are not an in-place
#: write of one page into the view nor the page copy that writes the view
#: (``ops/view_gather.py``).  GPT-2-XL's flat view: none (the zero fill the
#: update form put the pages into went with it; no layout pass: the pool's pages
#: are the view's column blocks as they lie).  DeepSeek-V2's position-major view: the
#: gather and its one layout pass; its rope key (rows of one head of 64, half a
#: lane tile; a ninth of the latent's bytes) is carried with the positions
#: minor, so the compiler re-tiles it on its way into the scan and again on its
#: way to the page gather: five small passes in the decode window, four in the
#: verify.
_VIEW_PASSES = {"xl": (0, 0), "deepseek": (2, 5)}
#: in-place writes of the pool: one scatter an array; the flat view's pages go
#: back one ``dynamic_update_slice`` a (lane, touched page): two a lane
_POOL_WRITES = {"xl": 2 * XL_LANES * 2, "deepseek": 2}


def _squeezed(shape):
    """``bf16[2,1025,1,128,512]`` as the compiler prints it once it has
    dropped the unit axes, and the number of elements."""
    dtype, dims = shape.rstrip("]").split("[")
    dims = [int(d) for d in dims.split(",") if d]
    return f"{dtype}[{','.join(str(d) for d in dims if d != 1)}]", int(np.prod(dims))


@pytest.mark.parametrize("program", ["decode", "verify"])
@pytest.mark.parametrize("config", list(_POOLS))
def test_pages_are_written_back_whole(paged_program, config, program):
    """The gathered windows' write-back (``pool._store_span_pages``), compiled:
    nothing transposes the view to cut rows out of it (``vmap()/transpose``);
    in the entry computation an array of the pool's shape comes only out of the
    in-place write (a fusion whose root is a scatter or dynamic-update-slice;
    ``_POOL_WRITES`` of them), so no ``copy`` takes the pool into the layout a
    row store wants and back; and outside the model the view is passed over no
    more often than ``_VIEW_PASSES`` says."""
    built = paged_program(config, program, False)
    comps, entry = _parse_hlo(built.compiled.as_text())
    pools = [_squeezed(s)[0] for s in built.pool_shapes]
    views = [_squeezed(s)[1] for s in built.view_shapes]
    limits, passes, writes = collections.Counter(), collections.Counter(), 0
    for size, limit in zip(views, _VIEW_PASSES[config]):
        limits[size] += limit                 # gpt2-xl's K and V are of one size
    for name, instructions in comps.items():
        for ins in instructions:
            assert not ins.op_name.endswith("vmap()/transpose"), ins.line
            if (name != entry or ins.shape is None or ins.opcode in _PASS_THROUGH | _STAGING
                    or "ConcatBitcast" in ins.line):
                continue
            shape, size = _squeezed(ins.shape)
            root = ins if ins.opcode != "fusion" else next(
                i for i in comps[ins.called[0]] if i.is_root)
            if shape in pools:
                assert root.opcode in _WRITES, ins.line
                writes += 1
            elif (shape.startswith("bf16") and size in views and "/Transformer/" not in ins.op_name
                  and "params" not in ins.line         # a weight can be of the view's size
                  and root.opcode != "dynamic-update-slice"      # a page put into the view
                  and "view_gather" not in ins.line):            # the page copy writing it
                passes[size] += 1
    assert writes == _POOL_WRITES[config], f"{writes} writes of the pool"
    assert all(passes[size] <= limits[size] for size in limits), (passes, limits)


_LAYOUT = re.compile(r"= (bf16)\[([\d,]+)\]\{([\d,]+):T\(8,128\)\(2,1\)")


def _padded_elements(dims, minor_to_major):
    """Elements a bfloat16 array occupies under the tiling ``T(8,128)(2,1)``:
    the minor dimension in lanes of 128, the next in 8 sublanes of 2."""
    dims = list(dims)
    dims[minor_to_major[0]] = -(-dims[minor_to_major[0]] // 128) * 128
    if len(minor_to_major) > 1:
        dims[minor_to_major[1]] = -(-dims[minor_to_major[1]] // 16) * 16
    return int(np.prod(dims))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gathered_view_pads_nothing(paged_program, program):
    """GPT-2-XL's gathered window and chunk, compiled: no bfloat16 array of the
    view's element count, anywhere in the program, lies in a layout that pads
    it (``[.., M, 25, 64]`` with ``(25, 64)`` tiled occupied 2.56 x: 25 heads
    to 32 sublanes, 64 values to 128 lanes); the view the scan carries is
    ``[L, N, 1600, 1024]`` as written; no ``copy`` or transpose of the whole
    view stands between the gather and the model; and the window's temporaries
    are the two views plus what the model needs, not two padded copies more
    (0.176 GB here, 0.253 GB with the padded view; at 48 layers 2.30 against
    4.39 GB: PERF.md)."""
    built = paged_program("xl", program, False)
    text = built.compiled.as_text()
    view = built.view_shapes[0]
    elements = _squeezed(view)[1]
    seen = 0
    for dtype, dims, layout in _LAYOUT.findall(text):
        dims = [int(d) for d in dims.split(",")]
        if int(np.prod(dims)) != elements:
            continue
        seen += 1
        padded = _padded_elements(dims, [int(i) for i in layout.split(",")])
        assert padded <= 1.05 * elements, f"{dtype}{dims}{{{layout}}} occupies {padded / elements:.2f} x"
    assert seen, "no array of the view's size in the program"
    assert f"{view}{{3,2,1,0:" in text, "the view is not carried as written"
    comps, entry = _parse_hlo(text)
    for ins in comps[entry]:
        if ins.shape is None or _squeezed(ins.shape)[1] != elements or "params" in ins.line:
            continue
        root = ins if ins.opcode != "fusion" else next(
            i for i in comps[ins.called[0]] if i.is_root)
        assert root.opcode not in ("copy", "transpose"), ins.line
    if program == "decode":
        memory = built.compiled.memory_analysis()
        unpadded = 2 * elements * 2                   # K's and V's views, bfloat16
        assert memory.temp_size_in_bytes <= 0.15e9 + unpadded, memory


def test_latent_decode_window_fits_at_published_widths(paged_program):
    """DeepSeek-V2's widths at two layers, 16 lanes of 8192: the gathered
    decode window compiles for the chip, the held experts' products are the
    compiler's own grouped matmul (``ragged-dot``), and the latent view
    ``[L, N, M, 1, 512]`` is not padded out on its unit axis (16 x the view
    would be 5 GB of temporaries where 0.71 GB is measured, 1.06 GB before
    the write-back went by pages)."""
    compiled = paged_program("deepseek", "decode", False).compiled
    assert "ragged-dot" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.79e9, memory
    # the pool itself is handed over unpadded: 576 values a token and layer
    _, lanes, pages_per_lane, _ = _POOLS["deepseek"]
    assert memory.alias_size_in_bytes == 2 * (lanes * pages_per_lane + 1) * XL_PAGE * (512 + 64) * 2


# ------------------------------------------------------------- the state pool
#: Brumby-14B's widths (``bench/configs/brumby-14b.json``) at two layers, the
#: serve cell's 8 lanes, window and larger chunk
STATE_LAYERS, STATE_LANES, STATE_WINDOW, STATE_CHUNK = 2, 8, 4, 512


@pytest.fixture(scope="module")
def state_program(one_chip):
    """``program -> (compiled, shape of S as the program sees it)``: the decode
    window over the whole state pool, or a prefill chunk over one lane of it."""
    import json
    from pathlib import Path

    from accelerate_tpu.models.retention import state_shapes
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import pool

    fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "brumby-14b.json").read_text())["transformer"]
    fields.update(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n = STATE_LANES
    i32, f32 = (lambda *s: spec(s, jnp.int32)), (lambda *s: spec(s, jnp.float32))
    flag = lambda *s: spec(s, jnp.bool_)

    @functools.cache
    def build(program, layers=STATE_LAYERS):
        model = Transformer(TransformerConfig(**dict(fields, num_layers=layers)))
        params = jax.tree_util.tree_map(
            lambda a: spec(a.shape, a.dtype),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
        s_shape, z_shape = state_shapes(model.config, n)
        state = (spec(s_shape, jnp.float32), spec(z_shape, jnp.float32))
        if program == "decode":
            fn = pool.make_state_decode_window(model, STATE_WINDOW)
            args = (params, *state, i32(n), i32(n), flag(n), i32(n), flag(n), f32(n), i32(n), f32(n), i32(n),
                    spec((n, 2), jnp.uint32))
            seen = s_shape
        else:
            fn = pool.make_state_prefill_chunk(model)
            args = (params, i32(1, STATE_CHUNK), *state, i32(), i32(), i32())
            seen = s_shape[:1] + (1,) + s_shape[2:]
        # the step asks the platform which form to take, and this process sees a
        # CPU: say "a TPU" while the window is traced, as the chip would
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr("accelerate_tpu.ops.retention._platform_compiles", lambda: True)
            compiled = fn.lower(*args).compile()
        return compiled, "f32[" + ",".join(map(str, seen)) + "]"

    return build


_STEP_KERNEL = re.compile(r"^\s+%retention_step_onepass[\w.]* = .*custom_call_target=\"tpu_custom_call\".*$", re.M)


def _check_state_kernel(text, whole_shape, layers):
    """The decode window with the one-pass step: its loop body holds one
    ``retention_step_onepass`` kernel a layer, each handing the whole ``S`` and
    ``z`` back through the operands they came in by; nothing else in the
    program makes an array of the state's shape (no ``dynamic-update-slice``,
    no copy), and no fusion takes the state as an operand."""
    kernels = _STEP_KERNEL.findall(text)
    assert len(kernels) == layers, f"{len(kernels)} one-pass kernels, expected {layers}"
    for line in kernels:
        assert "/while/body/" in line and "output_to_operand_aliasing={{2}: (4, {}), {3}: (5, {})}" in line, line[:400]
        assert whole_shape + "{" in line.split("custom-call(")[0], line[:400]          # the whole state comes back
    comps, _ = _parse_hlo(text)
    fused = {ins.called[0] for instructions in comps.values() for ins in instructions if ins.opcode == "fusion"}
    for name, instructions in comps.items():
        for ins in instructions:
            if ins.shape != whole_shape:
                continue
            assert name not in fused, f"a fusion reads or writes the state: {name}: {ins.line}"
            assert ins.opcode in _PASS_THROUGH, ins.line


@pytest.mark.parametrize("program,scope,temp_gb", [("decode", "while", 0.20), ("prefill", "model", 0.75)])
def test_retention_state_is_updated_in_place(state_program, program, scope, temp_gb):
    """The retention decode window and 512-chunk at the published widths, two
    layers, 8 lanes (a state pool of 0.55 GB): each layer rewrites its slice of
    the stacked state in place, once (the window: one aliased one-pass kernel a
    layer in the loop's body, ``_check_state_kernel``; the chunk: one
    ``dynamic-update-slice`` a layer; no layer cut out, nothing stacked back),
    the donated pool comes out as the result, and the program holds no
    temporary of the state's size: 0.16 GB for the window, 0.67 GB for the
    chunk (``phi`` of 512 rows of 40 heads in sub-chunks of 128; one lane's
    state is 0.07 GB)."""
    compiled, seen = state_program(program)
    if program == "decode":
        _check_state_kernel(compiled.as_text(), seen, STATE_LAYERS)
    else:
        _check_cache_plumbing(compiled.as_text(), seen, n_writes=STATE_LAYERS, scope=scope)
    memory = compiled.memory_analysis()
    state_bytes = 4 * STATE_LAYERS * STATE_LANES * 8 * 8320 * (128 + 1)
    assert memory.alias_size_in_bytes == state_bytes, memory
    assert memory.temp_size_in_bytes < temp_gb * 1e9, memory
    if program == "decode":
        assert memory.temp_size_in_bytes < state_bytes / STATE_LAYERS, memory     # not one layer's state


def test_retention_decode_window_at_the_cells_ten_layers_fits_and_is_not_rematerialised(state_program):
    """The window the serve cell runs: ten layers, 8 lanes, 12.47 GB of weights
    and state handed in.  It fits the chip with 0.76 GB of temporaries, and the
    compiler rematerialises nothing: while the read-out read the OLD state (the
    update fused into it a second time) the ten-layer window, and only it, came
    out with ``add_dynamic-update-slice_fusion.20.remat``: layer 0's in-place
    update run twice a step on one buffer, every served token wrong on the
    chip and nothing to see at two layers or on the CPU (PERF.md, PR 33).
    The XLA form of ``retention_step_stored`` stores first and reads behind a
    barrier; the one-pass kernel this window runs leaves XLA no update to
    duplicate: ten kernels, and no fusion that touches the state at all (a
    copy of ``phi(q)`` into the kernel's layout was rematerialised until the
    kernel took whole sublanes of query heads)."""
    compiled, seen = state_program("decode", 10)
    assert not re.findall(r"%[\w.\-]+\.remat[\w.]*", compiled.as_text())       # an instruction run twice
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * 10 * STATE_LANES * 8 * 8320 * (128 + 1), memory
    assert memory.temp_size_in_bytes < 0.85e9, memory
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9, memory
    _check_state_kernel(compiled.as_text(), seen, 10)


def test_retention_step_kernel_compiles_at_the_published_shapes(one_chip):
    """The kernel alone, as the serve cell calls it: 8 lanes, 8 key/value heads
    of 5 query heads, the ten-layer float32 state ``[10, 8, 8, 8320, 128]``
    donated.  Mosaic takes the whole-(lane, head) tile (4.26 MB, four buffers)
    under the kernel's fast-memory limit, the state and ``z`` alias through,
    and nothing of the state's size is left over."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lanes, heads, groups, rows, width = 8, 8, 5, 8320, 128

    def fn(pq, pk, v, gate, s, z):
        return retention_step_onepass(pq, pk, v, gate, s, z, 3, interpret=False)

    compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(
        f32(lanes, heads, groups, rows), f32(lanes, heads, rows), f32(lanes, heads, width), f32(lanes, heads),
        f32(10, lanes, heads, rows, width), f32(10, lanes, heads, rows)).compile()
    assert len(_STEP_KERNEL.findall(compiled.as_text())) == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * 10 * lanes * heads * rows * (width + 1), memory
    assert memory.temp_size_in_bytes < 32e6, memory          # phi(q) padded to whole sublanes: 17 MB


# ----------------------------------------------------------- the two-rule pool
@pytest.mark.parametrize("program,temp_gb", [("decode", 3.4), ("chunk512", 1.9)])
def test_two_rule_pool_programs_fit_at_the_cells_sizes(one_chip, cell_window, program, temp_gb):
    """``bench/configs/trinity-large.json`` whole (five layers, 8.64 GB of
    weights) with the serve cell's pool: 8 lanes of 32,768 positions, pages of
    128, a ring of 37 pages a lane for the four window layers and whole tables
    for the full one.  Both pools alias through (1.697 GB: 1.07 + 0.62, where
    one rule for all five layers would be 5.37), the window layers' view is
    the ring's width and not ``max_len``, and arguments and temporaries fit the
    chip together (the decode window: 10.34 + 2.01 GB; 3.33 with the zero fill
    and page-wide updates).  The chunk's views come from the compiler's gather,
    the window's from the page copy kernel, one view a layer, as the chip
    traces it (``serving/pool.py`` ``_gather_columns`` and ``_gather_layers``
    say why); the window compiles in a fifth of a minute here where its 4,688
    updates took three."""
    import json
    from pathlib import Path

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import pool

    fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "trinity-large.json").read_text())["transformer"]
    fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    lanes, page, table, ring = 8, 128, 32768 // 128, -(-(4096 + 512) // 128) + 1
    full = [spec((1, lanes * table + 1, 8, page, 128), jnp.bfloat16)] * 2
    rings = [spec((4, lanes * ring + 1, 8, page, 128), jnp.bfloat16)] * 2
    i32 = lambda *s: spec(s, jnp.int32)
    if program == "decode":
        compiled = cell_window("trinity").compiled                # the same window, compiled once a module
    else:
        compiled = pool.make_mixed_prefill_chunk(model, 512, page).lower(
            params, i32(1, 512), *full, *rings, i32(table), i32(ring), i32(), i32()).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert memory.alias_size_in_bytes == 2 * 2 * (lanes * table + 1 + 4 * (lanes * ring + 1)) * 8 * page * 128
    assert memory.temp_size_in_bytes < temp_gb * 1e9, memory
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9, memory
    assert "ragged-dot" in text
    assert text.count("dynamic-update-slice(") < 400
    n = lanes if program == "decode" else 1
    lead = "1," if program == "decode" else "4,"                  # the window's views come a layer each
    assert f"bf16[{lead}{n},1024,{ring * page}]" in text and f",{n},1024,32768]" in text
    assert f"bf16[4,{n},1024,32768]" not in text


# ------------------------------------------------- the held experts' products
#: both routed cells' held experts ``[held, in, out]`` with a 512-chunk's and a decode window's sorted rows
GROUPED = [((40, 5120, 1536), 3072), ((40, 5120, 1536), 96), ((40, 1536, 5120), 3072), ((40, 1536, 5120), 96),
           ((32, 3072, 3072), 2048), ((32, 3072, 3072), 32)]


@pytest.mark.parametrize("kernel,rows", GROUPED, ids=[f"{'x'.join(map(str, k))}-{m}rows" for k, m in GROUPED])
def test_grouped_matmul_compiles_at_the_published_shapes(one_chip, kernel, rows):
    """``ops/grouped_matmul.py`` at DeepSeek-V2's and Trinity's experts: a whole
    expert a block (15.7 and 18.9 MB, double-buffered under the raised limit),
    row tiles of 128 for a chunk and the whole window's rows for a window."""
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compiled_text(lambda r, w, g: grouped_matmul(r, w, g, interpret=False),
                          spec((rows, kernel[1]), jnp.bfloat16), spec(kernel, jnp.bfloat16), spec(kernel[:1], jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "grouped_matmul" in text


@pytest.mark.parametrize("program", ["chunk512", "decode"])
def test_deepseek_programs_run_their_experts_in_the_kernel_and_copy_no_weights(one_chip, monkeypatch, program):
    """``bench/configs/deepseek-v2.json`` at three layers (the dense one and two
    expert layers of all 40 held experts) with the serve cell's pool, traced as
    a TPU traces it (``held_experts_grouped``): no ``ragged-dot`` is left, every
    live expert layer holds three kernels (the chunk returns no logits, so its
    last layer's experts are dead code), and nothing in the compiled program
    outputs an array of a held-experts weight's shape: the kernel reads the
    stacked leaves where they lie, through no copy, transpose or other layout."""
    import json
    from pathlib import Path

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.ops import grouped_matmul as gm
    from accelerate_tpu.parallel.moe import held_experts_grouped
    from accelerate_tpu.serving import pool

    monkeypatch.setattr(gm, "_platform_compiles", lambda: True)
    fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "deepseek-v2.json").read_text())["transformer"]
    fields.update(num_layers=3, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = Transformer(TransformerConfig(**fields))
    assert held_experts_grouped(model.config)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    lanes, per_lane, page, window = 16, 64, 128, 4
    pages = [spec((3, lanes * per_lane + 1, 1, page, d), jnp.bfloat16) for d in (512, 64)]
    i32, f32 = (lambda *s: spec(s, jnp.int32)), (lambda *s: spec(s, jnp.float32))
    flag = lambda *s: spec(s, jnp.bool_)
    if program == "decode":
        vectors = (flag(lanes), i32(lanes), flag(lanes), f32(lanes), i32(lanes), f32(lanes), i32(lanes),
                   spec((lanes, 2), jnp.uint32))
        compiled = pool.make_paged_decode_window(model, window).lower(
            params, *pages, i32(lanes, per_lane), i32(lanes), i32(lanes), *vectors).compile()
        live_layers = 2
    else:
        compiled = pool.make_paged_prefill_chunk(model, 512, page).lower(
            params, i32(1, 512), *pages, i32(per_lane), i32(), i32()).compile()
        live_layers = 1
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == 3 * live_layers
    assert len(set(re.findall(r"%(grouped_matmul[.\d]*) = ", text))) == 3 * live_layers
    held = {"bf16[40,5120,1536]", "bf16[40,1536,5120]"}
    comps, _ = _parse_hlo(text)
    made = [ins.line for body in comps.values() for ins in body
            if ins.shape in held and ins.opcode not in _PASS_THROUGH]
    assert not made, made


# ------------------------------------------- the chunk's attention over a view
#: (rows, view, window, ring): the long-document cell's two buckets against its full view and its ring of 37
#: pages (a masked tail: 4,736 is no multiple of a key block); a banded full view (``generate``'s cache of a
#: window layer); a prefill of four row blocks; rows that are no whole row block
VIEWS = [(512, 32768, None, False), (128, 32768, None, False), (512, 4736, 4096, True), (128, 4736, 4096, True),
         (512, 32768, 4096, False), (2048, 8192, None, False), (200, 4096, None, False)]


@pytest.mark.parametrize("rows,view,window,ring", VIEWS,
                         ids=[f"{r}rows-{m}{'-ring' if ring else '-band' if w else ''}" for r, m, w, ring in VIEWS])
def test_view_flash_attention_compiles_at_the_published_shapes(one_chip, rows, view, window, ring):
    """``ops/view_attention.py`` at Trinity's 48 query and 8 key/value heads of
    128: one kernel, the view handed over as it lies: nothing in the compiled
    program outputs an array of the view's shape but the compiler's own
    prefetch of a small view (the ring's 9.7 MB) into fast memory."""
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, p: view_flash_attention(q, k, v, p, window=window, ring=ring, interpret=False),
        spec((1, rows, 48, 128), jnp.bfloat16), spec((1, 1024, view), jnp.bfloat16),
        spec((1, 1024, view), jnp.bfloat16), spec((1, rows), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "view_flash_attention" in text
    comps, _ = _parse_hlo(text)
    made = [ins.line for body in comps.values() for ins in body
            if ins.shape == f"bf16[1,1024,{view}]" and ins.opcode not in _PASS_THROUGH | {"copy-start", "copy-done"}]
    assert not made, made


def test_trinity_chunk_attends_in_the_kernel_and_forms_no_scores_over_the_view(one_chip, monkeypatch):
    """``bench/configs/trinity-large.json`` whole with the serve cell's pool,
    its 512-chunk traced as a TPU traces it: five kernels (four window layers
    on the ring, the full layer on the whole view), no float32 array over the
    view's 32,768 or the ring's 4,736 columns left, and the temporaries no
    larger than with the einsum (1.77 GB: sandbox compile, PR 35)."""
    import json
    from pathlib import Path

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.ops import grouped_matmul as gm
    from accelerate_tpu.ops import latent_view_attention as lva
    from accelerate_tpu.ops import view_attention as va
    from accelerate_tpu.serving import pool

    monkeypatch.setattr(gm, "_platform_compiles", lambda: True)
    monkeypatch.setattr(va, "_platform_compiles", lambda: True)
    monkeypatch.setattr(lva, "_platform_compiles", lambda: True)
    fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "trinity-large.json").read_text())["transformer"]
    fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    lanes, page, table, ring = 8, 128, 32768 // 128, -(-(4096 + 512) // 128) + 1
    full = [spec((1, lanes * table + 1, 8, page, 128), jnp.bfloat16)] * 2
    rings = [spec((4, lanes * ring + 1, 8, page, 128), jnp.bfloat16)] * 2
    i32 = lambda *s: spec(s, jnp.int32)
    compiled = pool.make_mixed_prefill_chunk(model, 512, page).lower(
        params, i32(1, 512), *full, *rings, i32(table), i32(ring), i32(), i32()).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert len(set(re.findall(r"%(view_flash_attention[.\d]*) = ", text))) == 5
    assert "latent_view_attention" not in text
    assert not re.search(r"f32\[[\d,]*,(32768|4736)\]", text)
    assert memory.temp_size_in_bytes < 1.8e9, memory


# ------------------------------------------- the chunk's latent attention over its view
LATENT_ROWS = [512, 128, 200]


@pytest.mark.parametrize("rows", LATENT_ROWS, ids=[f"{r}rows" for r in LATENT_ROWS])
def test_latent_view_attention_compiles_at_the_published_shapes(one_chip, rows):
    """``ops/latent_view_attention.py`` at DeepSeek-V2's 128 heads, ``kv_rank``
    512, nope 128, rope 64 and v 128 over the cell's 8,192-wide view, for its
    two buckets and rows that are no bucket: one kernel."""
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compiled_text(
        lambda qn, qp, lat, pe, w, p: latent_view_attention(qn, qp, lat, pe, w, p, 0.1, interpret=False),
        spec((1, rows, 128, 128)), spec((1, rows, 128, 64)), spec((1, 8192, 512)), spec((1, 8192, 64)),
        spec((512, 128 * 256)), spec((1, rows), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_view_attention" in text


def test_deepseek_chunk_attends_in_the_kernel_and_copies_no_latent_view(one_chip, monkeypatch):
    """``_deepseek_fields`` (published widths, two layers) with the serve cell's
    pool, its 512-chunk traced as a TPU traces it: one kernel a layer; no
    float32 ``[.., 128, 512, 1024]`` scores (the XLA loop's, a key block and
    layer); the views handed over as the cache holds them, a layer picked
    inside the kernel: nothing outside a fusion outputs a layer's latent or
    rope-key view (the loop's ``squeeze`` of the stacked view) or the stacked
    view but the in-place writes, bitcasts and the compiler's moves into fast
    memory; and the temporaries under the XLA loop's (0.34 GB, compiled for the
    same described chip)."""
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.ops import latent_view_attention as lva
    from accelerate_tpu.serving import pool

    monkeypatch.setattr(lva, "_platform_compiles", lambda: True)
    model = Transformer(TransformerConfig(**_deepseek_fields()))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    L, lanes, per_lane, page = model.config.num_layers, 16, 64, 128
    pages = [spec((L, lanes * per_lane + 1, 1, page, d), jnp.bfloat16) for d in (512, 64)]
    i32 = lambda *s: spec(s, jnp.int32)
    compiled = pool.make_paged_prefill_chunk(model, 512, page).lower(
        params, i32(1, 512), *pages, i32(per_lane), i32(), i32()).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert len(set(re.findall(r"%(latent_view_attention[.\d]*) = ", text))) == L
    assert not re.search(r"f32\[[\d,]*128,512,1024\]", text)
    views = {f"bf16[{stack}1,{per_lane * page},{unit}{d}]" for stack in ("", f"{L},") for unit in ("", "1,")
             for d in (512, 64)}
    comps, _ = _parse_hlo(text)
    fused = {ins.called[0] for body in comps.values() for ins in body if ins.opcode == "fusion"}
    made = [ins.line for name, body in comps.items() if name not in fused for ins in body
            if ins.shape in views and ins.opcode not in _PASS_THROUGH | _WRITES | {"copy-start", "copy-done"}]
    assert not made, made
    assert memory.temp_size_in_bytes < 0.34e9, memory


# --------------------------------------------- the decode window's views, built by the page copy
#: the cells whose decode window gathers flat views: configuration, lanes, positions a lane, the
#: window layers' ring of pages (None: one rule for every layer), and the temporaries of the window
#: that filled its views by a zero fill and page-wide updates (compiled for the described chip)
VIEW_CELLS = {
    "trinity": ("trinity-large", 8, 32768, 37, 3.33),
    "mellum2": ("mellum2-12b", 32, 8192, 13, 3.83),
    "gpt2-xl": ("gpt2-xl", 4, 1024, None, 2.30),
}
#: what copies or fills a whole view: none of it may make one outside the model's writes
_VIEW_MADE = {"pad", "copy", "transpose", "broadcast", "slice", "concatenate", "gather", "dynamic-update-slice"}


class _Window(NamedTuple):
    compiled: object
    pool_shapes: tuple       # every page array the window is handed, as the HLO prints them
    view_shapes: list        # every view the kernel returns, K's and V's


@pytest.fixture(scope="module")
def cell_window(one_chip):
    """``cell -> _Window``: the cell's decode window at full depth with its
    pool, its views and their attention traced as a TPU traces them (the page
    copy, the decode kernel where the view's shapes take it; the experts'
    products stay ``ragged-dot``), each compiled once a module."""
    import json
    from pathlib import Path

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import pool

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32, f32 = (lambda *s: spec(s, jnp.int32)), (lambda *s: spec(s, jnp.float32))
    flag = lambda *s: spec(s, jnp.bool_)

    @functools.cache
    def build(cell):
        name, lanes, max_len, ring, _ = VIEW_CELLS[cell]
        fields = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                             / f"{name}.json").read_text())["transformer"]
        fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
        model = Transformer(TransformerConfig(**fields))
        config, page, slots = model.config, XL_PAGE, max_len // XL_PAGE
        rows = (config.num_kv_heads, page, config.resolved_head_dim)
        params = jax.tree_util.tree_map(
            lambda a: spec(a.shape, a.dtype),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
        lane_vectors = (flag(lanes), i32(lanes), flag(lanes), f32(lanes), i32(lanes), f32(lanes), i32(lanes),
                        spec((lanes, 2), jnp.uint32))
        width = config.num_kv_heads * config.resolved_head_dim
        if ring is None:
            arrays = [(config.num_layers, lanes * slots + 1)] * 2
            views = [f"bf16[{config.num_layers},{lanes},{width},{max_len}]"] * 2
            fn, tables = pool.make_paged_decode_window(model, XL_WINDOW), (i32(lanes, slots),)
        else:
            full = config.layer_types.count("full")
            arrays = [(full, lanes * slots + 1)] * 2 + [(config.num_layers - full, lanes * ring + 1)] * 2
            views = ([f"bf16[1,{lanes},{width},{max_len}]"] * 2 * full
                     + [f"bf16[1,{lanes},{width},{ring * page}]"] * 2 * (config.num_layers - full))
            fn, tables = pool.make_mixed_decode_window(model, XL_WINDOW), (i32(lanes, slots), i32(lanes, ring))
        pages = [spec((layers, num_pages, *rows), jnp.bfloat16) for layers, num_pages in arrays]
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr("accelerate_tpu.ops.view_gather._platform_compiles", lambda: True)
            patch.setattr("accelerate_tpu.ops.view_attention._platform_compiles", lambda: True)
            lowered = fn.lower(params, *pages, *tables, i32(lanes), i32(lanes), *lane_vectors)
        return _Window(lowered.compile(), tuple("bf16[" + ",".join(map(str, (*a, *rows))) + "]" for a in arrays),
                       views)

    return build


@pytest.mark.parametrize("cell", sorted(VIEW_CELLS))
def test_the_cells_decode_windows_fill_their_views_in_the_page_copy(cell_window, cell):
    """Trinity's mixed window (8 lanes of 32,768, a ring of 37 pages),
    Mellum2's (32 lanes of 8,192, a ring of 13) and GPT-2-XL's (4 lanes of
    1,024, 48 layers), compiled for the chip: every view comes out of a
    ``view_gather`` kernel (one a layer in the mixed windows, so the scan reads
    a layer's view whole: Mellum2's stacked two-layer view was sliced for the
    attention four times a step, 268 MB each); no zero fill, pad, copy,
    transpose, slice or update of a view-sized array is left but the model's
    own column writes and the compiler's prefetch of a layer into fast memory;
    the pool is written by pages and never copied; and the temporaries are no
    higher than the update form's."""
    built = cell_window(cell)
    text = built.compiled.as_text()
    kernels = re.findall(r"%view_gather[.\d]* = (bf16\[[\d,]+\])", text)
    assert sorted(kernels) == sorted(built.view_shapes), kernels
    comps, _ = _parse_hlo(text)
    fused = {ins.called[0] for body in comps.values() for ins in body if ins.opcode == "fusion"}
    sizes = {_squeezed(v)[1] for v in built.view_shapes}
    sizes |= {size // int(v.split("[")[1].split(",")[0]) for v, size in
              ((v, _squeezed(v)[1]) for v in built.view_shapes)}              # a layer of a stacked view
    for name, body in comps.items():
        if name in fused:
            continue
        for ins in body:
            if ins.shape is None or ins.opcode in _PASS_THROUGH:
                continue
            root = ins if ins.opcode != "fusion" else next(i for i in comps[ins.called[0]] if i.is_root)
            if ins.shape in built.pool_shapes:
                assert root.opcode in _WRITES or ins.opcode in _STAGING, ins.line      # no copy of the pool
            elif ins.shape.startswith("bf16") and _squeezed(ins.shape)[1] in sizes and "params" not in ins.line:
                if "/Transformer/" not in ins.op_name:
                    assert root.opcode not in _VIEW_MADE, ins.line
                else:
                    # the model writes its columns in place and reads a layer's view whole: a
                    # slice of one into HBM is a copy at every step (into fast memory, ``S(1)``,
                    # it is the compiler's prefetch of what the attention reads)
                    in_fast_memory = "S(1)}" in ins.line[:ins.line.index(f" {ins.opcode}(")]
                    assert root.opcode != "slice" or in_fast_memory, ins.line
    memory = built.compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= VIEW_CELLS[cell][-1] * 1e9, memory
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9, memory


#: the mixed cells' decode windows: how many layers attend in the decode kernel (every kind of view
#: at least ``view_attention._MIN_DECODE_VIEW`` wide), and the full view's width
DECODE_KERNELS = {"trinity": (5, 32768), "mellum2": (8, 8192)}
_NAMED = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+\S+\s+([\w\-]+)\(")


@pytest.mark.parametrize("cell", sorted(DECODE_KERNELS))
def test_the_cells_decode_windows_attend_in_the_decode_kernel(cell_window, cell):
    """Trinity's and Mellum2's mixed windows at their published widths, compiled
    for the chip: a decode step's attention over a view the rule takes is one
    ``view_decode_attention`` call a layer (Trinity's four rings of 4,736 and its
    full view; Mellum2's six rings of 1,664 and two full views);
    no float32 array over the full view's columns (the einsum's scores) is
    left; and each call is handed the K and V views as the layer's in-place
    column writes leave them, no copy of a view round the kernel (a carried view
    copied at every step cost the windows 13 ms before the page copy)."""
    kernels, width = DECODE_KERNELS[cell]
    text = cell_window(cell).compiled.as_text()
    named = {m.group(1): (m.group(2), line) for line in text.splitlines() for m in [_NAMED.match(line)] if m}
    calls = [line for op, line in named.values() if op == "custom-call" and "view_decode_attention" in line]
    assert len({line.split(" = ")[0] for line in calls}) == kernels
    assert not re.search(rf"f32\[[\d,]*,{width}\]", text)
    comps, _ = _parse_hlo(text)
    for line in calls:
        *_, k_view, v_view = re.search(r"custom-call\(([^)]*)\)", line).group(1).replace("%", "").split(", ")
        for view in (k_view, v_view):
            opcode, source = named[view]
            if opcode == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", source).group(1)
                opcode = next(i for i in comps[called] if i.is_root).opcode
            assert opcode in _WRITES | _PASS_THROUGH, source[:300]


#: heads -> (query heads, key/value heads): Trinity's and Mellum2's
DECODE_HEADS = {"trinity": (48, 8), "mellum2": (32, 4)}


@pytest.mark.parametrize("heads", sorted(DECODE_HEADS))
def test_view_decode_attention_compiles_at_the_most_lanes_its_rule_takes(one_chip, heads):
    """The decode kernel keeps every lane's query and output in fast memory:
    at the most lanes ``view_decode_applies`` takes for these heads (a view of
    1,024 columns, so the views fit the chip's memory) it compiles for the chip,
    and one lane more is refused, so larger batches keep the einsum."""
    from accelerate_tpu.ops import view_attention as va

    n_q, n_kv = DECODE_HEADS[heads]
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    shapes = lambda lanes: (spec((lanes, 1, n_q, 128)), spec((lanes, n_kv * 128, 1024)))
    most = max(lanes for lanes in range(1, 4096) if va.view_decode_applies(*shapes(lanes), interpret=True))
    assert most >= 256 and not va.view_decode_applies(*shapes(most + 1), interpret=True)
    q, k = shapes(most)
    text = _compiled_text(lambda q, k, v, p: va.view_decode_attention(q, k, v, p, interpret=False),
                          q, k, k, spec((most, 1), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "view_decode_attention" in text

"""The main path's Pallas kernels, compiled for a TPU v5e that is described, not attached.

The CPU rig runs every kernel under ``interpret=True``, which checks results
and nothing about what the TPU compiler accepts: both paged kernels passed
every interpret-mode test while Mosaic refused their block shapes.  The TPU
compiler is installed here and compiles for a described ``v5e:2x2`` device, so
these tests hand it the kernels at the widths ``chip_smoke.py`` runs (25x64
and 12x64 heads, page 16, a 128-wide prefill chunk) with ``interpret=False``
and require ``tpu_custom_call`` in the compiled program.  Nothing runs; a
compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), with the persistent compile cache off around it — such a
compile can be written to the cache but not read back without a chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.ops.paged_attention import (
    KV_FORMATS,
    paged_attention,
    paged_flash_prefill,
)

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

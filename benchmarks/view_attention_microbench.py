"""The chunk's view attention alone, on the chip: ``ops/view_attention.py`` beside
``cached_attention``'s masked einsum at the long-document cell's shapes (48 query
and 8 key/value heads of 128, a full view of 32,768 columns and a ring of 37 x
128), at 2 k / 8 k / 24 k live keys and on the ring before and after it wraps;
then ``ops/latent_view_attention.py`` beside ``attend_decompressed``'s blocked
loop at DeepSeek-V2's widths (128 heads, ``kv_rank`` 512, nope 128, rope 64, v
128, a view of 8,192) at one to eight live key blocks of 1,024.

    python3 benchmarks/view_attention_microbench.py [out.jsonl] [--blocks 256,512,1024] [--latent-only]
    python3 benchmarks/view_attention_microbench.py [out.jsonl] --decode-only

``--decode-only`` times a decode step instead (``view_decode_attention`` beside
the masked einsum): Trinity's full view ``[8, 1024, 32768]`` and ring of 4,736,
Mellum2's ``[32, 512, 8192]`` and ring of 1,664, and views of other widths at
Mellum2's heads, each with the lanes at the cell's depths (a seeded draw of its
prompt lengths and live share: ``decode_window_split.py``'s) and with every lane
at the view's end; ``LOOP`` steps in one program, so the time is the device's
and not the host's dispatch, and the share is of 819 GB/s for the bytes of the
keys and values a query sees.

One JSON line a measurement: milliseconds a call (host clock over ``CALLS`` calls
of one jitted function, the last waited for), the FLOPs the live keys need
(``4 x Hq x S x keys x D``; the latent case adds the decompression, ``2 x H x
keys x kv_rank x (nope + v)``, and scores over ``nope + rope``: masked pairs
inside the last blocks counted, dead blocks not) as a share of 197 TFLOP/s, and
the largest difference from the XLA form's output.  ``docs/kernels/view_attention.md`` quotes it.  Exits non-zero
off a TPU: a time from anything else is no measurement.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.models.latent_attention import attend_decompressed  # noqa: E402
from accelerate_tpu.models.transformer import cached_attention  # noqa: E402
from accelerate_tpu.ops.latent_view_attention import latent_view_attention  # noqa: E402
from accelerate_tpu.ops.view_attention import (  # noqa: E402
    KEY_BLOCK,
    view_decode_attention,
    view_flash_attention,
    xla_form,
)

HQ, HKV, D, FULL, RING, WINDOW = 48, 8, 128, 32768, 37 * 128, 4096
#: DeepSeek-V2's latent attention: heads, kv_rank, nope, rope, v, the view
MLA_H, KV_RANK, NOPE, ROPE, V, MLA_VIEW = 128, 512, 128, 64, 128, 8192
PEAK, CALLS = 197e12, 20
BF16 = jnp.bfloat16


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS * 1e3, out


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"needs a TPU, found {device.platform}")
    out_path = next((a for a in sys.argv[1:] if not a.startswith("--")), None)
    blocks = [KEY_BLOCK]
    if "--blocks" in sys.argv:
        blocks = [int(b) for b in sys.argv[sys.argv.index("--blocks") + 1].split(",")]

    def emit(**record):
        record["device"] = device.device_kind
        print(json.dumps(record), flush=True)
        if out_path:
            with open(out_path, "a") as fh:
                fh.write(json.dumps(record) + "\n")

    draw = lambda key, shape: jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32).astype(BF16))(key)
    if "--decode-only" in sys.argv:
        return decode(draw, emit)
    if "--latent-only" not in sys.argv:
        views(blocks, draw, emit)
    latent(draw, emit)


def views(blocks, draw, emit):
    keys = jax.random.split(jax.random.PRNGKey(38), 3)
    cases = [(rows, FULL, base, None, False) for rows in (512, 128) for base in (2048 - rows, 8192 - rows, 24576 - rows)]
    cases += [(rows, RING, base, WINDOW, True) for rows in (512, 128) for base in (1024, 16384)]
    for rows, m, base, window, ring in cases:
        q = draw(keys[0], (1, rows, HQ, D))
        live = min(base + rows, m)
        # a view as the gather leaves it: zeros where nothing was written
        fill = lambda key: draw(key, (1, HKV * D, m)) * (jnp.arange(m) < live).astype(BF16)
        k, v = fill(keys[1]), fill(keys[2])
        positions = base + jnp.arange(rows, dtype=jnp.int32)[None]
        seen = min(live, window + rows) if ring else live
        flops = 4 * HQ * rows * seen * D
        case = dict(rows=rows, view=m, base=base, ring=ring, live_keys=seen)

        def einsum(q, k, v, positions):
            with xla_form():
                return cached_attention(q, k, v, positions, window=window, ring=ring)

        ms, want = timed(jax.jit(einsum), q, k, v, positions)
        emit(**case, form="xla", ms=ms, peak_pct=100 * flops / (ms * 1e-3) / PEAK)
        for block in blocks:
            kernel = jax.jit(lambda q, k, v, p, block=block: view_flash_attention(
                q, k, v, p, window=window, ring=ring, block=block))
            try:
                ms, got = timed(kernel, q, k, v, positions)
            except Exception as e:  # a block the compiler refuses is a finding, not a crash
                emit(**case, form="kernel", block=block, error=repr(e)[:300])
                continue
            gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
            emit(**case, form="kernel", block=block, ms=ms, peak_pct=100 * flops / (ms * 1e-3) / PEAK,
                 max_abs_gap=gap, finite=bool(np.isfinite(np.asarray(got, np.float32)).all()))


def latent(draw, emit):
    """DeepSeek-V2's 512- and 128-chunks at one to eight live key blocks of its
    8,192-wide view: the kernel against the XLA loop it replaces."""
    keys = jax.random.split(jax.random.PRNGKey(44), 5)
    w_ukv = draw(keys[4], (KV_RANK, MLA_H * (NOPE + V))) * KV_RANK ** -0.5
    scale = (NOPE + ROPE) ** -0.5

    def xla(q_nope, q_pe, lat, k_pe, w_ukv, positions):
        w = w_ukv.reshape(KV_RANK, MLA_H, NOPE + V)
        return attend_decompressed(q_nope, q_pe, lat, k_pe, w[..., :NOPE], w[..., NOPE:], positions, scale)

    kernel = jax.jit(lambda *a: latent_view_attention(*a, scale))
    for rows in (512, 128):
        q_nope, q_pe = draw(keys[0], (1, rows, MLA_H, NOPE)), draw(keys[1], (1, rows, MLA_H, ROPE))
        for live_blocks in (1, 2, 3, 5, 8):
            base = live_blocks * KEY_BLOCK - rows
            # a view as the gather leaves it: zeros where nothing was written
            written = (jnp.arange(MLA_VIEW) < base + rows).astype(BF16)[None, :, None]
            lat, k_pe = draw(keys[2], (1, MLA_VIEW, KV_RANK)) * written, draw(keys[3], (1, MLA_VIEW, ROPE)) * written
            positions = base + jnp.arange(rows, dtype=jnp.int32)[None]
            seen = live_blocks * KEY_BLOCK
            flops = MLA_H * (2 * seen * KV_RANK * (NOPE + V) + 2 * rows * seen * (NOPE + ROPE + V))
            case = dict(case="latent", rows=rows, view=MLA_VIEW, base=base, live_keys=seen)
            ms, want = timed(jax.jit(xla), q_nope, q_pe, lat, k_pe, w_ukv, positions)
            emit(**case, form="xla", ms=ms, peak_pct=100 * flops / (ms * 1e-3) / PEAK)
            ms, got = timed(kernel, q_nope, q_pe, lat, k_pe, w_ukv, positions)
            gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.reshape(got.shape).astype(jnp.float32))))
            emit(**case, form="kernel", ms=ms, peak_pct=100 * flops / (ms * 1e-3) / PEAK,
                 max_abs_gap=gap, finite=bool(np.isfinite(np.asarray(got, np.float32)).all()))


#: decode cases: name, lanes, query heads, key/value heads, view, window, ring, config for the lanes' depths
DECODE = [("trinity-full", 8, 48, 8, 32768, None, False, "trinity-large"),
          ("trinity-ring", 8, 48, 8, 37 * 128, 4096, True, "trinity-large"),
          ("mellum2-full", 32, 32, 4, 8192, None, False, "mellum2-12b"),
          ("mellum2-ring", 32, 32, 4, 13 * 128, 1024, True, "mellum2-12b"),
          ("mellum2-heads-1024", 32, 32, 4, 1024, None, False, "mellum2-12b"),
          ("mellum2-heads-2048", 32, 32, 4, 2048, None, False, "mellum2-12b"),
          ("mellum2-heads-4096", 32, 32, 4, 4096, None, False, "mellum2-12b")]
LOOP, HBM = 16, 819e9


def decode(draw, emit):
    """One decode step's attention, ``LOOP`` times in one program (the query
    moved a little each time, so nothing is hoisted), the view the same."""
    from decode_window_split import lane_positions

    keys = jax.random.split(jax.random.PRNGKey(45), 3)

    def looped(attend):
        def run(q, k, v, positions):
            def body(i, acc):
                out = attend((q * (1 + i.astype(jnp.float32) * 1e-3)).astype(BF16), k, v, positions)
                return acc + out.astype(jnp.float32)
            return jax.lax.fori_loop(0, LOOP, body, jnp.zeros(q.shape, jnp.float32)) / LOOP
        return jax.jit(run)

    for name, lanes, hq, hkv, m, window, ring, config in DECODE:
        k, v = draw(keys[1], (lanes, hkv * D, m)), draw(keys[2], (lanes, hkv * D, m))
        q = draw(keys[0], (lanes, 1, hq, D))
        drawn = np.minimum(lane_positions(config, 45), m - 1 if not ring else 10 ** 6)
        for depth, last in (("cell", drawn), ("end", np.full(lanes, 3 * m + 17 if ring else m - 1))):
            positions = jnp.asarray(last, jnp.int32)[:, None]
            seen = np.minimum(last + 1, window) if window else last + 1
            need = 2 * hkv * D * 2 * int(seen.sum())
            case = dict(case=name, lanes=lanes, view=m, depth=depth, seen_keys=int(seen.sum()))
            einsum = looped(lambda q, k, v, p: cached_attention(q, k, v, p, window=window, ring=ring))
            with xla_form():
                ms, want = timed(einsum, q, k, v, positions)
            emit(**case, form="xla", ms=ms / LOOP, hbm_pct=100 * need / (ms / LOOP * 1e-3) / HBM)
            kernel = looped(lambda q, k, v, p: view_decode_attention(q, k, v, p, window=window, ring=ring))
            ms, got = timed(kernel, q, k, v, positions)
            gap = float(jnp.max(jnp.abs(got - want)))
            emit(**case, form="kernel", ms=ms / LOOP, hbm_pct=100 * need / (ms / LOOP * 1e-3) / HBM,
                 max_abs_gap=gap, finite=bool(np.isfinite(np.asarray(got)).all()))


if __name__ == "__main__":
    main()

"""The decode window's page copy alone, on the chip: ``ops/view_gather.py`` beside
the compiler's own gather (``serving/pool.py`` ``_gather_columns``, bit-equal to
the update form) and, where its program is small enough to compile quickly, the
zero fill and page-wide updates themselves, at each flat-view array of the three
cells that build one: Trinity's full layer and ring, Mellum2's full layers and
ring, GPT-2-XL's 48 layers.

    python3 benchmarks/view_gather_microbench.py [out.jsonl] [--live 0.25]

A lane's table holds live pages in its first ``live`` share of slots (the
rings all of them) and the null page after.  One JSON line a measurement:
milliseconds a call (host clock over ``CALLS`` calls of one jitted function, the
last waited for), the bytes the view needs (written once, its live pages read
once) over that time, and whether the view is the reference's bit for bit.
``docs/kernels/view_gather.md`` quotes it.  Exits non-zero off a TPU: a time
from anything else is no measurement.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.ops.view_attention import xla_form  # noqa: E402
from accelerate_tpu.ops.view_gather import gather_pages  # noqa: E402
from accelerate_tpu.serving.pool import _gather_columns, _gather_view  # noqa: E402

#: (array, layers, heads, head width, lanes, slots a lane, a ring)
ARRAYS = [
    ("trinity.full", 1, 8, 128, 8, 256, False),
    ("trinity.ring", 4, 8, 128, 8, 37, True),
    ("mellum2.full", 2, 4, 128, 32, 64, False),
    ("mellum2.ring", 6, 4, 128, 32, 13, True),
    ("gpt2-xl", 48, 25, 64, 4, 8, False),
]
PAGE, CALLS, HBM = 128, 20, 819e9
#: the update form is timed only where its program has at most this many updates
MAX_UPDATES = 600


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
    out_path = next((a for a in sys.argv[1:] if not a.startswith("--") and not a[0].isdigit()), None)
    live = float(sys.argv[sys.argv.index("--live") + 1]) if "--live" in sys.argv else 0.25

    def emit(**record):
        record["device"] = device.device_kind
        print(json.dumps(record), flush=True)
        if out_path:
            with open(out_path, "a") as fh:
                fh.write(json.dumps(record) + "\n")

    for name, layers, heads, d, lanes, slots, ring in ARRAYS:
        num_pages = lanes * slots + 1
        pages = jax.random.normal(jax.random.PRNGKey(0), (layers, num_pages, heads, PAGE, d),
                                  jnp.float32).astype(jnp.bfloat16)
        held = slots if ring else max(1, int(slots * live))
        ids = 1 + np.arange(lanes * slots, dtype=np.int32).reshape(lanes, slots)
        ids[:, held:] = 0
        tables = jnp.asarray(ids)
        page_bytes = layers * heads * d * PAGE * 2
        need = lanes * slots * page_bytes + lanes * held * page_bytes
        reference = jax.jit(_gather_columns)
        ref_ms, want = timed(reference, pages, tables)
        forms = {
            "kernel": jax.jit(lambda p, t: gather_pages(p, t)),
            "kernel_a_layer": jax.jit(lambda p, t: tuple(gather_pages(p, t, layer=i) for i in range(layers))),
        }
        if lanes * slots <= MAX_UPDATES:
            def updates(p, t):
                with xla_form():
                    return _gather_view(p, t, True)
            forms["updates"] = jax.jit(updates)
        emit(array=name, form="compiler_gather", ms=ref_ms, gb_per_s=need / ref_ms / 1e6, bytes=need)
        for form, fn in forms.items():
            ms, got = timed(fn, pages, tables)
            got = jnp.stack(got) if isinstance(got, tuple) else got
            emit(array=name, form=form, ms=ms, gb_per_s=need / ms / 1e6, bytes=need,
                 hbm_share=need / HBM / (ms / 1e3), equal=bool(jnp.array_equal(got, want)))
            del got
        del pages, want


if __name__ == "__main__":
    main()

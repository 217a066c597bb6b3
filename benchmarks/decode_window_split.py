"""A mixed-stack cell's decode window on the chip, its device time split by scope.

Builds the decode window of ``bench/configs/<config>.json`` at the size of its
cell in ``bench/workloads`` (``serving/pool.py`` ``make_mixed_decode_window``:
every layer of the file, the cell's lanes, ``max_len`` and ring of pages, four
steps) with random weights and pools, puts the lanes at the depths given, and
runs it in the forms asked for (``--forms``).  For each form: the window's device time (profiler
trace of ``CALLS`` windows) split by the HLO ``op_name`` scope of each operation
(``attn/full``, ``attn/window``, ``moe/...``; the compiled program's metadata,
which the trace's events lack), the largest operations, and the host clock over
``CALLS`` windows.

    python3 benchmarks/decode_window_split.py trinity-large [out.jsonl] [--forms kernel,einsum]
        [--lanes 6000,9000,14000,0,0,0,0,0] [--seed 7]

A form is ``kernel`` (what the program picks), ``full`` (the decode kernel for
the ``max_len``-wide views alone, the rings in the einsum) or ``einsum`` (every
decode step's attention in ``cached_attention``'s masked einsum).

``--lanes`` gives each lane's position (0: a lane that is not decoding, as the
engine leaves it); without it each lane is drawn from the cell's traffic, a
prompt and a share of its answer in, decoding at the share of lanes live in the
ledger's newest line for the cell (``lanes_live_pct``).  One JSON line a form.
Exits non-zero off a TPU.
"""

import json
import re
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "bench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from accelerate_tpu.models import transformer  # noqa: E402
from accelerate_tpu.models.transformer import Transformer, TransformerConfig  # noqa: E402
from accelerate_tpu.serving import pool  # noqa: E402

WINDOW, CALLS = 4, 8
SCOPES = ("attn/full", "attn/window", "attn/gate", "moe/experts", "moe/route", "moe/shared")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* ")


def cell(name):
    """The mixed-stack cell of ``bench/workloads`` on config ``name``: its engine
    block, its traffic, and the share of its lanes decoding in the ledger's
    newest line for it."""
    workload = next(spec for path in sorted((ROOT / "bench" / "workloads").glob(f"{name}.*.json"))
                    for spec in [json.loads(path.read_text())] if spec["kind"] == "serve_mixed")
    live = [line["per_layer"]["lanes_live_pct"][-1] for line in map(json.loads, open(ROOT / "PERF_LEDGER.jsonl"))
            if line.get("config") == name and "lanes_live_pct" in line.get("per_layer", {})]
    return workload["engine"], workload["traffic"], live[-1] / 100


def lane_positions(name, seed):
    engine, traffic, live = cell(name)
    lanes, max_len = engine["num_slots"], engine["max_len"]
    rng = np.random.default_rng(seed)
    draw = lambda spec: np.clip(rng.lognormal(np.log(spec["median"]), spec["sigma"], lanes),
                                spec["min"], spec["max"]).astype(int)
    prompts, answers = draw(traffic["prompt_tokens"]), draw(traffic["output_tokens"])
    decoding = rng.random(lanes) < live
    # a decoding lane is its prompt and a share of its answer in
    depths = prompts + (rng.random(lanes) * answers).astype(int)
    return np.where(decoding, np.minimum(depths, max_len - WINDOW), 0)


def scope_of(op_name):
    return next((s for s in SCOPES if s in op_name), "other")


def build(name, seed):
    fields = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())["transformer"]
    fields["dtype"] = fields["param_dtype"] = jnp.bfloat16
    model = Transformer(TransformerConfig(**fields))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, leaf) in zip(keys, leaves):
            if "norm" in jax.tree_util.keystr(path) or "scale" in jax.tree_util.keystr(path):
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                out.append((0.02 * jax.random.normal(k, leaf.shape, jnp.float32)).astype(leaf.dtype))
        return out

    params = jax.tree_util.tree_unflatten(tree, draw(jax.random.PRNGKey(seed)))
    return model, params


def pools(model, engine):
    config = model.config
    lanes, max_len, page = engine["num_slots"], engine["max_len"], engine["page_size"]
    # the engine's ring: a chunk's pages and the band behind its first query
    ring = -(-(config.sliding_window + max(engine["prefill_buckets"])) // page) + 1
    slots = max_len // page
    rows = (config.num_kv_heads, page, config.resolved_head_dim)
    full = config.layer_types.count("full")
    arrays = [(full, lanes * slots + 1)] * 2 + [(config.num_layers - full, lanes * ring + 1)] * 2
    fill = jax.jit(lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16),
                   static_argnums=1)
    pages = [fill(jax.random.PRNGKey(i), (layers, n, *rows)) for i, (layers, n) in enumerate(arrays)]
    # every lane's slots hold pages of their own (page 0 is the null page)
    tables = 1 + np.arange(lanes * slots, dtype=np.int32).reshape(lanes, slots)
    ring_tables = 1 + np.arange(lanes * ring, dtype=np.int32).reshape(lanes, ring)
    return pages, jnp.asarray(tables), jnp.asarray(ring_tables)


def lane_vectors(positions):
    lanes = len(positions)
    active = jnp.asarray(positions > 0)
    return (jnp.asarray(positions, jnp.int32), jnp.ones((lanes,), jnp.int32), active,
            jnp.full((lanes,), -1, jnp.int32), jnp.zeros((lanes,), bool), jnp.ones((lanes,), jnp.float32),
            jnp.zeros((lanes,), jnp.int32), jnp.ones((lanes,), jnp.float32), jnp.zeros((lanes,), jnp.int32),
            jax.random.split(jax.random.PRNGKey(1), lanes))


def measure(model, params, pages, tables, ring_tables, positions, form):
    from lib import xplane

    applies = transformer.view_decode_applies
    full = tables.shape[1] * pages[0].shape[3]
    if form == "einsum":
        transformer.view_decode_applies = lambda *a, **k: False
    elif form == "full":
        transformer.view_decode_applies = lambda q, k, **kw: k.shape[2] >= full and applies(q, k, **kw)
    try:
        fn = pool.make_mixed_decode_window(model, WINDOW)
        lane_args = lane_vectors(positions)
        started = time.time()
        lowered = fn.lower(params, *pages, tables, ring_tables, *lane_args)
        compiled = lowered.compile()
        compile_s = time.time() - started
    finally:
        transformer.view_decode_applies = applies
    text = compiled.as_text()
    op_names = {m.group(1): m.group(2) for m in map(_OP_NAME.match, text.splitlines()) if m}
    kernels = sorted(set(re.findall(r"%(view_decode_attention[.\d]*) = ", text)))

    def run(pages, n):
        for _ in range(n):
            *pages, toks, _, _, _ = compiled(params, *pages, tables, ring_tables, *lane_args)
        jax.block_until_ready(toks)
        return pages

    pages = run(pages, 2)
    started = time.perf_counter()
    pages = run(pages, CALLS)
    host_ms = (time.perf_counter() - started) / CALLS * 1e3
    trace_dir = tempfile.mkdtemp(prefix="decode_split_")
    jax.profiler.start_trace(trace_dir)
    pages = run(pages, CALLS)
    jax.profiler.stop_trace()
    trace = xplane.read(xplane.newest_xplane(trace_dir))
    device = trace["devices"][0]
    windows = sum(1 for name, _, _ in device["modules"] if "mixed_decode_window" in name) or CALLS
    by_scope, by_op = {}, {}
    for name, _, dur in device["ops"]:
        if _CONTAINER.match(name):
            continue
        short = xplane.short_op_name(name)
        scope = scope_of(op_names.get(short.split(" ")[0], ""))
        by_scope[scope] = by_scope.get(scope, 0.0) + dur
        key = f"{short} [{scope}]"
        by_op[key] = by_op.get(key, 0.0) + dur
    per = lambda ns: round(ns / windows / 1e6, 4)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:14]
    return pages, dict(form=form, windows=windows, compile_s=round(compile_s, 1), host_ms=round(host_ms, 4),
                       device_ms=per(sum(by_scope.values())),
                       scopes_ms={k: per(v) for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])},
                       top_ms=[[k, per(v)] for k, v in top], decode_kernels=len(kernels))


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"needs a TPU, found {device.platform}")
    args = [a for a in sys.argv[1:]]
    name = args.pop(0)
    option = lambda flag, default: args[args.index(flag) + 1] if flag in args else default
    seed = int(option("--seed", 7))
    forms = option("--forms", "kernel,einsum").split(",")
    out_path = next((a for i, a in enumerate(args) if not a.startswith("--")
                     and (i == 0 or not args[i - 1].startswith("--"))), None)
    engine = cell(name)[0]
    given = option("--lanes", None)
    positions = (np.asarray([int(p) for p in given.split(",")]) if given else lane_positions(name, seed))
    assert len(positions) == engine["num_slots"], (len(positions), engine["num_slots"])
    model, params = build(name, seed)
    pages, tables, ring_tables = pools(model, engine)
    for form in forms:
        pages, record = measure(model, params, pages, tables, ring_tables, positions, form)
        record.update(config=name, positions=[int(p) for p in positions], device=device.device_kind)
        print(json.dumps(record), flush=True)
        if out_path:
            with open(out_path, "a") as fh:
                fh.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()

"""Driver of a ``kind: train`` cell.

The program under test is ``Accelerator(mixed_precision=...)`` with
``prepare(SimpleDataLoader)``, ``create_train_state`` and
``compile_train_step(lm_loss_fn(model))``.  Set-up builds that one object,
drives it from the seed through its first three steps (recording what
``correct`` compares) and hands the same step and state to the window.
"""

from __future__ import annotations

import importlib
import json
import shutil
import time

import numpy as np

from lib import common, weights
from lib.tracing import start_trace, stop_trace, traced_metrics

CHECKED_STEPS = 3


class Rows:
    """Map-style dataset over a ``[rows, seq]`` array of token ids."""

    def __init__(self, rows):
        self.rows = rows

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return {"input_ids": self.rows[i]}


def make_rows(seed, n_rows, seq_len, vocab):
    """Seeded token rows, all different (uniform over the vocabulary)."""
    return np.random.default_rng(seed).integers(0, vocab, (n_rows, seq_len)).astype(np.int32)


def sized(cell, config, rehearse):
    """The cell's job and the configuration as run: the files' own, or the
    cell's tiny ``rehearse`` sizes off the chip."""
    job, published, fields = dict(cell["job"]), dict(config["published"]), dict(config["transformer"])
    if rehearse:
        tiny = cell["rehearse"]
        job.update(tiny.get("job", {}))
        published.update(tiny["published"])
        fields.update(tiny["transformer"])
    return job, published, fields


def build_program(seed, job, published, fields, reference):
    """The program under test: ``(accelerator, step, state, loader, rows)``."""
    import jax
    import jax.numpy as jnp
    import optax

    import accelerate_tpu as at
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig, lm_loss_fn
    from accelerate_tpu.state import AcceleratorState, GradientState

    GradientState._reset_state()
    AcceleratorState._reset_state(reset_partial_state=True)
    acc = at.Accelerator(mixed_precision=job["mixed_precision"])
    kw = dict(fields)
    kw["dtype"], kw["param_dtype"] = getattr(jnp, kw["dtype"]), getattr(jnp, kw["param_dtype"])
    kw["max_seq_len"] = max(kw["max_seq_len"], job["seq_len"])
    model = Transformer(TransformerConfig(**kw))
    params = weights.make_program_params(reference, seed, published, kw["param_dtype"])
    rows = make_rows(seed, job["rows"], job["seq_len"], published["vocab_size"])
    loader = acc.prepare(at.SimpleDataLoader(Rows(rows), batch_size=job["batch_size"], drop_last=True))
    opt = job["adamw"]
    tx = optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                     weight_decay=opt["weight_decay"])
    state = acc.create_train_state(params=params, tx=tx, seed=seed % (2 ** 31))
    step = acc.compile_train_step(lm_loss_fn(model), max_grad_norm=opt["max_grad_norm"])
    return acc, step, state, loader, rows


def batches_forever(loader):
    while True:
        yield from loader


def first_moment(opt_state):
    """Adam's first moment, wherever the optimizer's state keeps it."""
    import jax

    found = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one adam state, found {len(found)}")
    return found[0].mu


def first_steps(step, state, feed, seeded_params, job, n_layer):
    """Drives the step through its first three batches; returns the state and
    the program's readings: each loss, the leaf norms of the first gradient as
    the optimizer got it (adam's first moment after one step over 1 - b1), and
    the leaf norms of the parameters' change after the three.  The donated
    state loses the seeded parameters, so ``seeded_params()`` draws them anew
    once the three steps are done (a copy held through them would not fit)."""
    import jax

    losses, grad_norms = [], None
    for i in range(CHECKED_STEPS):
        state, metrics = step(state, next(feed))
        losses.append(float(metrics["loss"]))
        if i == 0:
            mu = weights.program_leaf_norms(first_moment(state.opt_state), n_layer)
            grad_norms = {k: v / (1.0 - job["adamw"]["b1"]) for k, v in mu.items()}
    change = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b))(
        state.params, seeded_params())
    delta_norms = weights.program_leaf_norms(change, n_layer)
    return state, {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}


def flatten(norms):
    """``{leaf: scalar | [layers]}`` -> ``{"leaf" | "leaf[i]": float}``."""
    out = {}
    for name, value in norms.items():
        value = np.asarray(value)
        if value.ndim == 0:
            out[name] = float(value)
        else:
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(value)})
    return out


def worst_leaf_gap(got, want, keep=None):
    """The largest, over leaves, gap between the two norms of a leaf (not the
    norm of a difference), against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns ``(gap, leaf)``."""
    got, want = flatten(got), flatten(want)
    median = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for leaf, ref in want.items():
        if keep is not None and leaf not in keep:
            continue
        gap = abs(got[leaf] - ref) / max(ref, median, 1e-30)
        if not gap <= worst:                      # also catches nan
            worst, where = gap, leaf
    return worst, where


def moving_leaves(ref_grad_norms):
    """Leaves the change is compared on: those whose reference gradient is at
    least a thousandth of the median leaf's.  The others (a key's bias under
    softmax) move under adam by round-off alone."""
    flat = flatten(ref_grad_norms)
    floor = 1e-3 * float(np.median(list(flat.values())))
    return {leaf for leaf, norm in flat.items() if norm >= floor}


def compare(program, ref, limits):
    """The numbers ``correct`` rests on, each beside its limit."""
    ref_losses, ref_grad, ref_delta = ref
    loss_gap = float(np.max(np.abs(np.asarray(program["losses"]) - np.asarray(ref_losses))))
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"], ref_grad)
    delta_gap, delta_leaf = worst_leaf_gap(program["delta_norms"], ref_delta, moving_leaves(ref_grad))
    readings = {
        "loss_gap": {"value": loss_gap},
        "grad_norm_gap": {"value": grad_gap, "leaf": grad_leaf},
        "delta_norm_gap": {"value": delta_gap, "leaf": delta_leaf},
    }
    # a number the cell gives no limit for is read and logged, not compared
    # (PERF.md says which and why: it has no upper reading)
    return {name: dict(item, limit=limits[name]) for name, item in readings.items() if name in limits}, \
        {name: item["value"] for name, item in readings.items() if name not in limits}


def reference_readings(reference, seed, published, rows, job, precision="float32", half=False):
    """The reference's (or, at a lower ``precision``, the control's) first
    three steps on the rows the harness fed.  ``half`` plants the fault of a
    batch whose second half is left out, the mean taken over the rest."""
    b = job["batch_size"]
    batches = rows[: CHECKED_STEPS * b].reshape(CHECKED_STEPS, b, -1)
    if half:
        batches = batches[:, : b // 2]
    hyper = {k: job["adamw"][k] for k in ("lr", "b1", "b2", "eps", "weight_decay", "max_grad_norm")}
    return reference.train_steps(seed, published, batches, hyper, precision)


def run(args, manifest, entry, cell, config, started):
    cache_dir = common.setup_cache(entry["name"])
    import jax

    devices = common.require_chips(entry["chips"], args.rehearse)
    clock = common.CompileClock()
    job, published, fields = sized(cell, config, args.rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    n_layer = published["n_layer"]
    common.log(event="start", cell=entry["name"], seed=args.seed, device=devices[0].device_kind,
               platform=devices[0].platform, chips=len(devices), cache_dir=cache_dir, jax=jax.__version__)

    acc, step, state, loader, rows = build_program(args.seed, job, published, fields, reference)
    feed = batches_forever(loader)
    param_dtype = jax.tree_util.tree_leaves(state.params)[0].dtype
    state, program = first_steps(
        step, state, feed,
        lambda: weights.make_program_params(reference, args.seed, published, param_dtype), job, n_layer)
    for _ in range(job.get("warm_steps", 2)):
        state, metrics = step(state, next(feed))
    float(metrics["loss"])
    warm = clock.snapshot()
    setup_s = time.time() - started
    common.log(event="setup", setup_s=setup_s, **warm)

    tokens_per_step = job["batch_size"] * job["seq_len"]
    trace_dir = common.BENCH / ".trace" / f"{entry['name']}-{args.seed}"
    tracing = bool(args.trace)
    step_ms, dispatch_ms, n_steps, in_flight = [], [], 0, []
    marker = None
    trace_from = float("inf")
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    if tracing:
        # the traced slice is the end of the window, so that stopping the
        # profiler (seconds, with this many events) falls outside it
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_from = deadline - min(float(cell["trace_seconds"]), args.seconds)
    while True:
        if tracing and marker is None and time.perf_counter() >= trace_from:
            marker = start_trace(trace_dir)
        t_step = time.perf_counter()
        state, metrics = step(state, next(feed))
        n_steps += 1
        dispatch_ms.append(1e3 * (time.perf_counter() - t_step))
        if tracing:
            # every step waited for, so that the host's clock times one step
            metrics["loss"].block_until_ready()
            step_ms.append(1e3 * (time.perf_counter() - t_step))
        else:
            # the host stays at most two steps ahead of the device
            in_flight.append(metrics["loss"])
            if len(in_flight) > 2:
                in_flight.pop(0).block_until_ready()
        if time.perf_counter() >= deadline:
            break
    metrics["loss"].block_until_ready()
    elapsed = time.perf_counter() - t0
    if marker is not None:
        stop_trace(marker)
    last_loss = float(metrics["loss"])
    in_window = clock.snapshot()
    compiles_in_window = in_window["backend_compiles"] - warm["backend_compiles"]
    tokens = n_steps * tokens_per_step
    device = common.device_block(devices)
    common.log(event="window", steps=n_steps, tokens=tokens, elapsed_s=elapsed, last_loss=last_loss,
               compiles_in_window=compiles_in_window,
               cache_hits_in_window=in_window["cache_hits"] - warm["cache_hits"],
               compiled_after_warm_up=clock.names[warm["backend_compiles"]:],
               dispatch_ms_p50=common.percentile(dispatch_ms, 50), dispatch_ms_max=max(dispatch_ms))

    del state, step, loader, feed, acc, metrics, in_flight
    common.free_program()
    t_ref = time.perf_counter()
    ref = reference_readings(reference, args.seed, published, rows, job)
    limits = cell["rehearse"]["limits"] if args.rehearse else cell["limits"]
    compared, not_compared = compare(program, ref, limits)
    compared["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    compared["last_loss_finite"] = {"value": 0.0 if np.isfinite(last_loss) else 1.0, "limit": 0}
    common.log(event="reference", seconds=time.perf_counter() - t_ref, not_compared=not_compared,
               program_losses=program["losses"],
               reference_losses=[float(x) for x in ref[0]])

    window = {"tokens": tokens, "elapsed_s": elapsed, "steps": n_steps, "step_ms": step_ms,
              "seq_len": job["seq_len"], "chips": len(devices)}
    breakdown = None
    if tracing:
        metrics_out, breakdown, summary = traced_metrics(
            manifest, entry, cell, published, window, devices, trace_dir, args)
        if summary is not None:
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
    else:
        metrics_out = common.end_to_end(manifest, entry["name"],
                                        {"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s})
    correct = common.judge(compared)
    return common.emit(correct, n_steps, 0, metrics_out, device, compared, breakdown, args.rehearse)


def readings(seeds, control_seeds, manifest, entry, cell, config, rehearse):
    """For ``limits.py``: what the limits are set from, in one process.  For
    each seed the program's readings against the reference (the lower
    readings); for each control seed also the control's (the reference at the
    precision below the cell's) and the planted fault's (half of each batch
    left out of the reference), each against the reference."""
    common.setup_cache(entry["name"])
    devices = common.require_chips(entry["chips"], rehearse)
    job, published, fields = sized(cell, config, rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    n_layer = published["n_layer"]
    loose = {"loss_gap": float("inf"), "grad_norm_gap": float("inf"), "delta_norm_gap": float("inf")}
    import jax.numpy as jnp

    for seed in seeds:
        t0 = time.perf_counter()
        acc, step, state, loader, rows = build_program(seed, job, published, fields, reference)
        state, program = first_steps(
            step, state, batches_forever(loader),
            lambda: weights.make_program_params(reference, seed, published, jnp.float32), job, n_layer)
        del state, step, loader, acc
        common.free_program()
        t1 = time.perf_counter()
        ref = reference_readings(reference, seed, published, rows, job)
        out = {"seed": seed, "program": compare(program, ref, loose)[0], "program_losses": program["losses"],
               "reference_losses": [float(x) for x in ref[0]], "program_s": t1 - t0,
               "reference_s": time.perf_counter() - t1}
        if seed in control_seeds:
            for name, kw in (("control_" + cell["control_precision"], {"precision": cell["control_precision"]}),
                             ("fault_half_batch", {"half": True})):
                losses, grad, delta = reference_readings(reference, seed, published, rows, job, **kw)
                as_program = {"losses": losses, "grad_norms": grad, "delta_norms": delta}
                out[name] = compare(as_program, ref, loose)[0]
        for key, item in out.items():
            if isinstance(item, dict):
                out[key] = {k: (v["value"], v.get("leaf")) for k, v in item.items()}
        print(json.dumps(out), flush=True)

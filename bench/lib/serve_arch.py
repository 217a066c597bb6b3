#!/usr/bin/env python3
"""Driver of a ``kind: serve_arch`` cell: the ``serve`` driver's service, load
and clients (imported from ``lib/serve.py`` unchanged) for an architecture that
brings its own reference module.

**How a new architecture joins a serve cell.**  ``lib/serve.py`` asks
``lib/weights.py`` and ``lib/counts.py`` for names and counts, and those are
GPT-2's.  A cell of another architecture says ``"kind": "serve_arch"`` in its
file under ``bench/workloads/`` (``bench/run.py`` and ``bench/limits.py``
dispatch on the kind) and its configuration names a module under
``bench/reference/`` that brings five functions beside its plain forward pass:

* ``init_params(seed, published, dtype)`` - the seeded weights (traceable);
* ``to_program_tree(params, published)`` - the same leaves under the program's
  parameter names;
* ``served_token_gaps(seed, samples, published, dtype, lower)`` - the served
  tokens teacher-forced through the float32 reference, which draws its own
  weights (a layer at a time where the stack does not fit), and through the
  ``lower`` precision for the control;
* ``forward_flops_token`` and ``forward_flops_span`` - the work the algorithm
  requires, for ``mfu.serve``;
* ``decode_least_bytes`` - the least bytes of a decode step, for the
  architecture's own ``decode_hbm_roofline.*`` reader.

The configuration file's ``transformer`` block is the program's
``TransformerConfig`` as run (nested groups as objects).  Beyond the ``serve``
driver's counters this one snapshots the engine's ``moe_*`` counters, over the
window and at the traced slice's ends.  ``correct`` is the ``serve`` driver's
(``check_requests`` finished requests, the longest among them, teacher-forced
through the reference; no failed request, every request ``max_tokens`` long, no
compile in the window) but for the statistic of the gap: the *mean* over the
checked tokens (``served_logit_gap_mean``), not the widest.  One routing choice
flipped by rounding between two near-equal scores swaps an expert and moves
that token's logits as far as a fault would, so the widest gap of a sound
bfloat16 run is an extreme of rare events (1.1-2.3 on the chip against the
fp8 control's 3.6-4.1); the mean reads 0.016-0.026 against 0.47-0.49 and
0.6-2.1 for the planted faults.  The widest is logged beside it.

``python3 bench/lib/serve_arch.py --workload <cell> ...`` is the one-process
rate sweep that finds the cell's knee (``bench/sweep.py`` imports the ``serve``
driver's builder); ``readings`` is what ``bench/limits.py`` calls, and with
``BENCH_ARCH_FAULTS=all`` (or a list of names) in the environment it also reads
each planted fault of :data:`FAULTS`.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    _BENCH = Path(__file__).resolve().parents[1]
    for _p in (str(_BENCH.parent), str(_BENCH)):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import numpy as np

from lib import common, traffic
from lib.serve import Call, check_sample, client_metrics, drive, engine_counters, sized, warm_up  # noqa: F401
from lib.tracing import start_trace, stop_trace, traced_metrics

MOE_COUNTERS = ("moe_pairs_total", "moe_pairs_here", "moe_experts_hit")


# -------------------------------------------------------------------- service
def build_service(seed, engine_kw, published, fields, reference):
    """``(engine, router, frontdoor, server)``: one replica of the configuration
    on this chip behind the router and the HTTP front door, weights from the
    reference module's seeded draw under the program's names."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine
    from accelerate_tpu.serving.api import ApiServer, FrontDoor

    kw = dict(fields)
    kw["dtype"], kw["param_dtype"] = getattr(jnp, kw["dtype"]), getattr(jnp, kw["param_dtype"])
    model = Transformer(TransformerConfig(**kw))
    # the seed is an argument, not a constant of the program: one compiled
    # program (and one entry of the persistent cache) serves every seed
    draw = jax.jit(lambda s: reference.to_program_tree(
        reference.init_params(s, published, kw["param_dtype"]), published))
    params = draw(np.uint32(seed % (2 ** 32)))
    engine_kw = dict(engine_kw)
    if engine_kw.get("prefill_buckets") is not None:
        engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
    engine = ServingEngine(model, params, rng_seed=seed % (2 ** 31), **engine_kw)
    router = ReplicaRouter([engine], policy="affinity")
    frontdoor = FrontDoor(router, model_name="bench").start()
    server = ApiServer(frontdoor, host="127.0.0.1", port=0, request_timeout_s=600.0)
    return engine, router, frontdoor, server


def arch_counters(engine):
    out = engine_counters(engine)
    out.update({k: engine.stats.get(k, 0) for k in MOE_COUNTERS})
    return out


def work_in(calls, lo, hi, published, reference):
    """Client-side count of the work whose tokens arrived in ``[lo, hi)``, as
    ``lib/serve.py`` ``work_in`` counts it, by the reference module's counts."""
    contexts, prompt_tokens, flops = [], 0, 0.0
    for c in calls:
        n_prompt = len(c.request["prompt"])
        for i, a in enumerate(c.arrivals):
            if not lo <= a < hi:
                continue
            if i == 0:
                prompt_tokens += n_prompt
                flops += reference.forward_flops_span(published, 0, n_prompt, 1)
            else:
                context = n_prompt + i
                contexts.append(context)
                flops += reference.forward_flops_token(published, context, True)
    return {"decode_contexts": contexts, "output_tokens": len(contexts) + sum(
        1 for c in calls if c.arrivals and lo <= c.arrivals[0] < hi),
            "prompt_tokens": prompt_tokens, "forward_flops": flops}


# -------------------------------------------------------------------- correct
def served_gaps(reference, seed, published, sample, param_dtype, lower=None):
    """Over the sample: the widest and the mean gap by which a served token's
    reference logit lies below the reference's best, the same two for the
    control where ``lower`` names its precision, and the tokens read."""
    samples = [(np.asarray(c.request["prompt"], np.int32), np.asarray(c.tokens, np.int32)) for c in sample]
    read = reference.served_token_gaps(seed, samples, published, param_dtype, lower)
    gaps = np.concatenate([g for g, _ in read])
    out = {"gap": float(gaps.max()), "gap_mean": float(gaps.mean()), "tokens": int(gaps.size)}
    if lower:
        low = np.concatenate([w for _, w in read])
        out.update(control_gap=float(low.max()), control_gap_mean=float(low.mean()))
    return out


# --------------------------------------------------------------------- faults
@contextlib.contextmanager
def _patched(module, name, replacement):
    original = getattr(module, name)
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def _fault_gates_renormalised(original):
    def route(scores, spec):
        experts, gates = original(scores, spec)
        return experts, gates / gates.sum(-1, keepdims=True)
    return route


def _fault_token_dropped(original):
    def route(scores, spec):
        import jax.numpy as jnp

        experts, gates = original(scores, spec)
        # every other row loses its routed experts, as a full buffer drops it
        return experts, jnp.where((jnp.arange(gates.shape[0]) % 2 == 1)[:, None], 0.0, gates)
    return route


def _fault_scale_without_yarn(original):
    def softmax_scale(cfg):
        la = cfg.latent_attention
        return (la.nope_dim + la.rope_dim) ** -0.5
    return softmax_scale


def _fault_rope_on_latent(original):
    def write_rows(buf, new, index, layer=None):
        import jax.numpy as jnp

        from accelerate_tpu.models import latent_attention as mla

        cfg = write_rows.cfg
        la = cfg.latent_attention
        if new.shape[-1] == la.kv_rank:                # the latent: rope its leading values too
            positions = jnp.reshape(index, (-1, 1)) + jnp.arange(new.shape[1])[None, :]
            positions = jnp.broadcast_to(positions, new.shape[:2])
            roped = mla.rope_pairs(new[..., :la.rope_dim], positions, cfg)
            new = jnp.concatenate([roped, new[..., la.rope_dim:]], axis=-1)
        return original(buf, new, index, layer)
    return write_rows


@contextlib.contextmanager
def planted(name, fields):
    """The program with one piece of the new mathematics wrong, for the run
    inside the ``with``; yields the ``transformer`` fields to build it from."""
    from accelerate_tpu.models import latent_attention as mla
    from accelerate_tpu.parallel import moe

    fields = json.loads(json.dumps(fields))
    if name == "gates_renormalised":
        ctx = _patched(moe, "route_top_k", _fault_gates_renormalised)
    elif name == "token_dropped":
        ctx = _patched(moe, "route_top_k", _fault_token_dropped)
    elif name == "yarn_scale_left_out":
        ctx = _patched(mla, "softmax_scale", _fault_scale_without_yarn)
    elif name == "rope_on_latent":
        from accelerate_tpu.models.transformer import TransformerConfig

        replacement = _fault_rope_on_latent(mla._write_rows)
        replacement.cfg = TransformerConfig(**{k: v for k, v in fields.items() if "dtype" not in k})
        ctx = _patched(mla, "_write_rows", lambda _: replacement)
    elif name == "shared_expert_left_out":
        fields["experts"]["shared_width"] = 0          # its weights are handed over and never read
        ctx = contextlib.nullcontext()
    else:
        raise KeyError(name)
    with ctx:
        yield fields


FAULTS = ("gates_renormalised", "shared_expert_left_out", "token_dropped", "rope_on_latent",
          "yarn_scale_left_out")


# ------------------------------------------------------------------- readings
def readings(seeds, control_seeds, manifest, entry, cell, config, rehearse, seconds=25.0):
    """For ``limits.py``: runs of the cell at its own load with a short window,
    in one process; on the control seeds also the control's readings, and,
    where ``BENCH_ARCH_FAULTS`` is set, on the first of them a run with each
    planted fault (``FAULTS``, or the names the variable lists)."""
    import argparse

    asked = os.environ.get("BENCH_ARCH_FAULTS", "")
    faults = () if not asked else FAULTS if asked == "all" else tuple(asked.split(","))
    first_control = min(control_seeds) if control_seeds else None
    for seed in seeds:
        args = argparse.Namespace(workload=entry["name"], seed=seed, seconds=seconds, trace=0,
                                  rehearse=rehearse, keep_trace=False)
        control = cell["control_precision"] if seed in control_seeds else None
        run(args, manifest, entry, cell, config, time.time(), control=control)
        for fault in faults if seed == first_control else ():
            common.log(event="fault", name=fault, seed=seed)
            run(args, manifest, entry, cell, config, time.time(), fault=fault)


# ------------------------------------------------------------------------ run
def run(args, manifest, entry, cell, config, started, control=None, fault=None):
    cache_dir = common.setup_cache(entry["name"])
    import jax

    devices = common.require_chips(entry["chips"], args.rehearse)
    clock = common.CompileClock()
    engine_kw, mix, published, fields = sized(cell, config, args.rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    common.log(event="start", cell=entry["name"], seed=args.seed, device=devices[0].device_kind,
               platform=devices[0].platform, chips=len(devices), cache_dir=cache_dir, jax=jax.__version__,
               fault=fault)

    with contextlib.ExitStack() as stack:
        if fault is not None:
            fields = stack.enter_context(planted(fault, fields))
        engine, router, frontdoor, server = build_service(args.seed, engine_kw, published, fields, reference)
        tracing = bool(args.trace)
        try:
            warm_up(server, engine, engine_kw, mix, published["vocab_size"], args.seed)
            requests = traffic.schedule(mix, args.seed, args.seconds, published["vocab_size"])
            warm = clock.snapshot()
            setup_s = time.time() - started
            common.log(event="setup", setup_s=setup_s, requests=len(requests), kv_pool_bytes=engine.kv_pool_bytes(),
                       page_size=engine.page_size, **warm)

            trace_dir = common.BENCH / ".trace" / f"{entry['name']}-{args.seed}"
            slice_times, slice_counters, trace_plan, marker = {}, {}, None, []
            if tracing:
                shutil.rmtree(trace_dir, ignore_errors=True)

                def begin():
                    marker.append(start_trace(trace_dir))
                    slice_times["lo"] = time.perf_counter()
                    slice_counters["lo"] = arch_counters(engine)

                def end():
                    slice_counters["hi"] = arch_counters(engine)
                    slice_times["hi"] = time.perf_counter()
                    stop_trace(marker[0])

                # the traced slice is the end of the window, as in the serve driver
                length = min(float(cell["trace_seconds"]), args.seconds)
                trace_plan = (args.seconds - length, length, begin, end)
            before = arch_counters(engine)
            at_close = {}
            calls, t0, lateness, abandoned = drive(server, requests, args.seconds,
                                                   float(cell.get("drain_seconds", 60.0)), trace_plan,
                                                   on_close=lambda: at_close.update(clock.snapshot()))
            after_counters = arch_counters(engine)
        finally:
            server.stop()
            frontdoor.stop()
    in_window, after_drain = at_close, clock.snapshot()
    compiles_in_window = in_window["backend_compiles"] - warm["backend_compiles"]
    device = common.device_block(devices)
    failed = [c for c in calls if not c.abandoned and (c.status != 200 or not c.done)]
    short = [c for c in calls if c.done and len(c.tokens) != c.request["max_tokens"]]
    seen = client_metrics(calls, t0, args.seconds)
    common.log(event="window", sent=len(calls), succeeded=sum(c.done for c in calls), failed=len(failed),
               abandoned=abandoned, generator_late_ms_p50=common.percentile([1e3 * x for x in lateness], 50),
               generator_late_ms_max=1e3 * max(lateness, default=0.0),
               tokens_in_window=seen["tokens_in_window"], compiles_in_window=compiles_in_window,
               cache_hits_in_window=in_window["cache_hits"] - warm["cache_hits"],
               compiles_in_drain=after_drain["backend_compiles"] - in_window["backend_compiles"],
               compiled_after_warm_up=clock.names[warm["backend_compiles"]:],
               first_errors=[(c.status, c.error) for c in failed[:3]], engine_before=before,
               engine_after=after_counters)

    sample = check_sample(calls, args.seed, int(cell["check_requests"]))
    param_dtype = fields["param_dtype"]
    del engine, router, frontdoor, server
    common.free_program()
    t_ref = time.perf_counter()
    read = {"gap": None, "gap_mean": None, "tokens": 0}
    if sample:
        read = served_gaps(reference, args.seed, published, sample, param_dtype, control)
    limits = cell["rehearse"]["limits"] if args.rehearse else cell["limits"]
    compared = {
        "served_logit_gap_mean": {"value": read["gap_mean"], "limit": limits["served_logit_gap_mean"],
                                  "tokens": read["tokens"], "widest": read["gap"]},
        "requests_failed": {"value": len(failed), "limit": 0},
        "wrong_token_counts": {"value": len(short), "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
    controls = {}
    if control is not None:
        controls = {"control_logit_gap": {"value": read["control_gap"], "limit": float("inf")},
                    "control_logit_gap_mean": {"value": read["control_gap_mean"], "limit": float("inf")}}
    common.log(event="reference", seconds=time.perf_counter() - t_ref, requests=len(sample), tokens=read["tokens"],
               served_logit_gap_widest=read["gap"])

    breakdown = None
    if tracing:
        lo, hi = slice_times.get("lo"), slice_times.get("hi")
        sliced = lo is not None and hi is not None and "hi" in slice_counters
        window = {"elapsed_s": args.seconds, "tokens": seen["tokens_in_window"], "chips": len(devices),
                  "work": work_in(calls, t0, t0 + args.seconds, published, reference),
                  "slice_work": work_in(calls, lo, hi, published, reference) if sliced else None,
                  "slice_s": (hi - lo) if sliced else None,
                  "counters": {k: after_counters[k] - before[k] for k in before},
                  "slice_counters": ({k: slice_counters["hi"][k] - slice_counters["lo"][k] for k in before}
                                     if sliced else None),
                  "num_slots": engine_kw["num_slots"], "reference": config["reference"]["module"]}
        metrics_out, breakdown, summary = traced_metrics(
            manifest, entry, cell, published, window, devices, trace_dir, args)
        if summary is not None:
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
    else:
        values = {
            "serve_tokens_per_s": seen["tokens_in_window"] / args.seconds,
            "gap_ms_p95": common.percentile(seen["gaps_ms"], 95),
            "ttft_ms_p90": common.percentile(seen["ttft_ms"], 90),
            "setup_s": setup_s,
        }
        metrics_out = common.end_to_end(manifest, entry["name"], values)
    common.log(event="seen", ttft_ms_p50=common.percentile(seen["ttft_ms"], 50),
               ttft_ms_p90=common.percentile(seen["ttft_ms"], 90),
               gap_ms_p50=common.percentile(seen["gaps_ms"], 50),
               unfinished_at_close=sum(1 for c in calls if not c.arrivals or c.arrivals[-1] > t0 + args.seconds),
               serve_tokens_per_s=seen["tokens_in_window"] / args.seconds,
               moe={k: after_counters[k] - before[k] for k in MOE_COUNTERS})
    correct = common.judge(compared)
    return common.emit(correct, len(calls), len(failed), metrics_out, device, dict(compared, **controls),
                       breakdown, args.rehearse)


# ---------------------------------------------------------------------- sweep
def _in_flight(calls, t):
    return sum(1 for c in calls if c.due <= t and (not c.arrivals or not c.done or c.arrivals[-1] > t))


def sweep(argv=None):
    """``bench/sweep.py`` with this driver's builder: one set-up, then the
    cell's mix at rates rising by ``factor``, one JSON line a rate."""
    import argparse

    parser = argparse.ArgumentParser(description="one-process rate sweep of a serve_arch cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--start", type=float, default=0.5)
    parser.add_argument("--factor", type=float, default=1.25)
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--stop-backlog", type=int, default=24)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    manifest, entry, cell, config = common.load_cell(args.workload)
    common.setup_cache(entry["name"])
    devices = common.require_chips(entry["chips"], args.rehearse)
    engine_kw, mix, published, fields = sized(cell, config, args.rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    t_setup = time.perf_counter()
    engine, router, frontdoor, server = build_service(args.seed, engine_kw, published, fields, reference)
    try:
        warm_up(server, engine, engine_kw, mix, published["vocab_size"], args.seed)
        common.log(event="setup", seconds=time.perf_counter() - t_setup, device=devices[0].device_kind,
                   memory_peak_bytes=common.memory_peak_bytes(devices))
        rate = args.start
        for step in range(args.steps):
            requests = traffic.schedule(dict(mix, rate_per_s=rate, initial_burst=0), args.seed + step,
                                        args.seconds, published["vocab_size"])
            before = arch_counters(engine)
            calls, t0, lateness, abandoned = drive(server, requests, args.seconds, 90.0)
            after = arch_counters(engine)
            seen = client_metrics(calls, t0, args.seconds)
            row = {
                "rate_per_s": rate, "sent": len(calls), "failed": sum(c.status != 200 for c in calls),
                "abandoned": abandoned,
                "offered_tokens_per_s": sum(r["max_tokens"] for r in requests) / args.seconds,
                "tokens_per_s": seen["tokens_in_window"] / args.seconds,
                "in_flight_half": _in_flight(calls, t0 + args.seconds / 2),
                "in_flight_close": _in_flight(calls, t0 + args.seconds),
                "ttft_ms_p50": common.percentile(seen["ttft_ms"], 50),
                "ttft_ms_p90": common.percentile(seen["ttft_ms"], 90),
                "gap_ms_p50": common.percentile(seen["gaps_ms"], 50),
                "gap_ms_p95": common.percentile(seen["gaps_ms"], 95),
                "generator_late_ms_max": 1e3 * max(lateness, default=0.0),
                "memory_peak_bytes": common.memory_peak_bytes(devices),
                "moe": {k: after[k] - before[k] for k in MOE_COUNTERS},
            }
            print(json.dumps(row), flush=True)
            if row["in_flight_close"] > args.stop_backlog:
                break
            rate *= args.factor
    finally:
        server.stop()
        frontdoor.stop()
    return 0


if __name__ == "__main__":
    sys.exit(sweep())

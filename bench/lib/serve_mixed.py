#!/usr/bin/env python3
"""Driver of a ``kind: serve_mixed`` cell: a served model with window and full
attention layers in one stack (``TransformerConfig.layer_types``), whose pool
keeps a ring of pages a lane for the one kind and whole tables for the other,
and with routed experts.

The service, the load, the clients, the sample the reference reads and the
sweep are ``lib/serve_arch.py``'s (imported unchanged: the reference module
named by the configuration brings the functions its docstring lists).  What
this driver brings:

* the engine's counters of the two-rule pool beside the ``moe_*`` ones
  (``kv_pages_taken``: pages the allocators of both kinds handed out;
  ``kv_pages_released_window``: window-layer pages handed back because they fell
  behind every position a later query can see; ``kv_rows_live`` /
  ``kv_rows_live_window``: the keys a live lane's decode step could see in all
  layers and in the window layers, summed over lane-steps, counted on the
  device), over the window and at the traced slice's ends, and the share of the
  window's engine steps that carried a prefill chunk (``chunk_step_share``,
  logged: where it nears a fifth, ``gap_ms_p95`` falls on one kind of step or
  the other by the seed);
* ``correct`` as the other routed cell has it (``check_requests`` finished
  requests, the longest among them, teacher-forced through the float32
  reference; no failed request, every request ``max_tokens`` long, no compile
  in the window), the statistic the *mean* gap of a served token below the
  reference's best (``served_logit_gap_mean``): one routing choice flipped by
  rounding between two near-equal scores moves a token as far as a fault
  would.  The widest is logged beside it;
* the planted faults of the new mathematics, :data:`FAULTS`, each the program
  with one piece wrong: ``window_left_out`` (a window layer sees every column
  of its ring, not the last ``sliding_window``), ``rope_on_full_layers``,
  ``attention_gate_left_out``, ``post_norms_left_out`` (no norm on a branch's
  output), ``softmax_router``, ``bias_in_the_gates`` (the selection bias enters
  the gates too), ``route_scale_left_out``, ``embed_scale_left_out`` and
  ``ring_one_page_short`` (a page of a window layer released while a query can
  still see it).  ``BENCH_MIXED_FAULTS=all`` (or a list of names) in the
  environment makes ``bench/limits.py`` read each on the first control seed.

``python3 bench/lib/serve_mixed.py --workload <cell> ...`` is the one-process
rate sweep (``serve_arch.sweep``).
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

from lib import common, traffic
from lib.serve import check_sample, client_metrics, drive, engine_counters, sized, warm_up
from lib.serve_arch import MOE_COUNTERS, _patched, build_service, served_gaps, sweep, work_in
from lib.tracing import start_trace, stop_trace, traced_metrics

KV_COUNTERS = ("kv_pages_taken", "kv_pages_released_window", "kv_rows_live", "kv_rows_live_window")
MIXED_COUNTERS = MOE_COUNTERS + KV_COUNTERS + ("prefill_chunks",)


def mixed_counters(engine):
    out = engine_counters(engine)
    out.update({k: engine.stats.get(k, 0) for k in MIXED_COUNTERS})
    return out


# --------------------------------------------------------------------- faults
def _fault_window_left_out(original):
    def cached_attention(q, k, v, q_positions, window=None, **kw):
        if kw.get("ring"):
            window = k.shape[-1]                      # every column the ring still holds
        return original(q, k, v, q_positions, window=window, **kw)
    return cached_attention


def _fault_bias_in_the_gates(original):
    def route(scores, spec, bias=None):
        return original(scores if bias is None else scores + bias, spec)
    return route


def _fault_ring_one_page_short(original):
    def ring_advance(self, slot, query, last):
        window = self.window
        self.window = window - self.page_size         # what is kept stops a page short of the window
        try:
            return original(self, slot, query, last)
        finally:
            self.window = window
    return ring_advance


@contextlib.contextmanager
def planted(name, fields):
    """The program with one piece of the new mathematics wrong, for the run
    inside the ``with``; yields the ``transformer`` fields to build it from."""
    from accelerate_tpu.models import transformer
    from accelerate_tpu.parallel import moe
    from accelerate_tpu.serving import paging

    fields = json.loads(json.dumps(fields))
    ctx = contextlib.nullcontext()
    if name == "window_left_out":
        ctx = _patched(transformer, "cached_attention", _fault_window_left_out)
    elif name == "rope_on_full_layers":
        fields["rope_full_layers"] = True
    elif name == "attention_gate_left_out":
        fields["attention_gate"] = False              # its weights are handed over and never read
    elif name == "post_norms_left_out":
        fields["sandwich_norm"] = False               # the two scales a layer likewise
    elif name == "softmax_router":
        fields["experts"]["score_func"] = "softmax"
    elif name == "bias_in_the_gates":
        ctx = _patched(moe, "route_top_k", _fault_bias_in_the_gates)
    elif name == "route_scale_left_out":
        fields["experts"]["scaling"] = 1.0
    elif name == "embed_scale_left_out":
        fields["embed_scale"] = False
    elif name == "ring_one_page_short":
        ctx = _patched(paging.MixedKVPool, "ring_advance", _fault_ring_one_page_short)
    else:
        raise KeyError(name)
    with ctx:
        yield fields


FAULTS = ("window_left_out", "rope_on_full_layers", "attention_gate_left_out", "post_norms_left_out",
          "softmax_router", "bias_in_the_gates", "route_scale_left_out", "embed_scale_left_out",
          "ring_one_page_short")


# ------------------------------------------------------------------- readings
def readings(seeds, control_seeds, manifest, entry, cell, config, rehearse, seconds=25.0):
    """For ``limits.py``: runs of the cell at its own load with a short window,
    in one process; on the control seeds also the control's readings, and,
    where ``BENCH_MIXED_FAULTS`` is set, on the first of them a run with each
    planted fault (``FAULTS``, or the names the variable lists)."""
    import argparse

    asked = os.environ.get("BENCH_MIXED_FAULTS", "")
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
                       page_size=engine.page_size, ring_pages=getattr(engine.kv, "ring_pages", None), **warm)

            trace_dir = common.BENCH / ".trace" / f"{entry['name']}-{args.seed}"
            slice_times, slice_counters, trace_plan, marker = {}, {}, None, []
            if tracing:
                shutil.rmtree(trace_dir, ignore_errors=True)

                def begin():
                    marker.append(start_trace(trace_dir))
                    slice_times["lo"] = time.perf_counter()
                    slice_counters["lo"] = mixed_counters(engine)

                def end():
                    slice_counters["hi"] = mixed_counters(engine)
                    slice_times["hi"] = time.perf_counter()
                    stop_trace(marker[0])

                # the traced slice is the end of the window, as in the serve driver
                length = min(float(cell["trace_seconds"]), args.seconds)
                trace_plan = (args.seconds - length, length, begin, end)
            before = mixed_counters(engine)
            at_close = {}

            def on_close():
                at_close.update(clock.snapshot())
                at_close["counters"] = mixed_counters(engine)

            calls, t0, lateness, abandoned = drive(server, requests, args.seconds,
                                                   float(cell.get("drain_seconds", 60.0)), trace_plan,
                                                   on_close=on_close)
            after_counters = mixed_counters(engine)
        finally:
            server.stop()
            frontdoor.stop()
    in_window, after_drain = at_close, clock.snapshot()
    compiles_in_window = in_window["backend_compiles"] - warm["backend_compiles"]
    device = common.device_block(devices)
    failed = [c for c in calls if not c.abandoned and (c.status != 200 or not c.done)]
    short = [c for c in calls if c.done and len(c.tokens) != c.request["max_tokens"]]
    seen = client_metrics(calls, t0, args.seconds)
    # of the engine steps of the window (a decode window each, prefill chunks
    # before it in some), the chunks a step
    closed = in_window["counters"]
    windows = (closed["decode_steps"] - before["decode_steps"]) / engine_kw["decode_window"]
    chunk_step_share = (closed["prefill_chunks"] - before["prefill_chunks"]) / windows if windows else None
    common.log(event="window", sent=len(calls), succeeded=sum(c.done for c in calls), failed=len(failed),
               abandoned=abandoned, generator_late_ms_p50=common.percentile([1e3 * x for x in lateness], 50),
               generator_late_ms_max=1e3 * max(lateness, default=0.0),
               tokens_in_window=seen["tokens_in_window"], compiles_in_window=compiles_in_window,
               cache_hits_in_window=in_window["cache_hits"] - warm["cache_hits"],
               compiles_in_drain=after_drain["backend_compiles"] - in_window["backend_compiles"],
               compiled_after_warm_up=clock.names[warm["backend_compiles"]:],
               first_errors=[(c.status, c.error) for c in failed[:3]], engine_before=before,
               engine_after=after_counters, windows=windows, chunk_step_share=chunk_step_share)

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
               longest_context=max((len(c.request["prompt"]) + len(c.tokens) for c in sample), default=0),
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
               gap_ms_p95=common.percentile(seen["gaps_ms"], 95),
               unfinished_at_close=sum(1 for c in calls if not c.arrivals or c.arrivals[-1] > t0 + args.seconds),
               serve_tokens_per_s=seen["tokens_in_window"] / args.seconds,
               pool={k: after_counters[k] - before[k] for k in MIXED_COUNTERS})
    correct = common.judge(compared)
    return common.emit(correct, len(calls), len(failed), metrics_out, device, dict(compared, **controls),
                       breakdown, args.rehearse)


if __name__ == "__main__":
    sys.exit(sweep())

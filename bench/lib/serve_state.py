#!/usr/bin/env python3
"""Driver of a ``kind: serve_state`` cell: a served model whose cache is a
recurrent state a lane (``TransformerConfig.retention``), not rows a token.

The service, the load, the clients, the sample the reference reads and the
sweep are ``lib/serve_arch.py``'s (imported unchanged: the reference module
named by the configuration brings the functions its docstring lists).  What
this driver brings:

* the engine's ``state_*`` counters (``state_lane_steps``: lane-steps whose
  state a decode window read and rewrote; ``state_live_lane_steps``: those that
  emitted a token; ``state_installs``: lanes zeroed for a new request), over
  the window and at the traced slice's ends, and the share of the window's
  engine steps that carried a prefill chunk (``chunk_step_share``, logged: where
  it nears a fifth, ``gap_ms_p95`` falls on one kind of step or the other by the
  seed);
* ``correct`` as the other serve cells have it (``check_requests`` finished
  requests, the longest among them, teacher-forced through the float32
  reference; no failed request, every request ``max_tokens`` long, no compile
  in the window), the statistic the *widest* gap of a served token below the
  reference's best, as for GPT-2: a dense model has no routing choice for
  rounding to flip.  The mean is logged beside it;
* the planted faults of the new mathematics, :data:`FAULTS`, each the program
  with one piece wrong: ``gate_left_out`` (``g = 1``: nothing is forgotten),
  ``normaliser_left_out`` (the weighted sum is not divided by the sum of the
  weights), ``degree_1`` (the kernel's power), ``head_norm_left_out`` (q and k
  not rms-normed a head), ``state_kept_across_requests`` (install does not
  zero the lane: the next request reads its predecessor's state) and
  ``state_in_bfloat16`` (the state stored, and so accumulated, in bfloat16).
  ``BENCH_STATE_FAULTS=all`` (or a list of names) in the environment makes
  ``bench/limits.py`` read each on the first control seed.
  On the chip at the published init the first four and the last read 2.3 to
  12 against the program's 0.22 to 0.43; ``state_kept_across_requests`` reads
  as the program does (0.31): with normal(0.02) gate weights and no bias ``log
  g`` averages -0.9 a token, a predecessor's state has decayed by ``e^-29``
  over the shortest prompt, and no served token can show it.  It is planted
  and read all the same (``bench/tests/test_state_faults.py`` reads it over
  the limit with prompts of one to four tokens).

``python3 bench/lib/serve_state.py --workload <cell> ...`` is the one-process
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
from lib.serve_arch import _patched, build_service, served_gaps, sweep, work_in
from lib.tracing import start_trace, stop_trace, traced_metrics

STATE_COUNTERS = ("state_lane_steps", "state_live_lane_steps", "state_installs", "prefill_chunks")


def state_counters(engine):
    out = engine_counters(engine)
    out.update({k: engine.stats.get(k, 0) for k in STATE_COUNTERS})
    return out


# --------------------------------------------------------------------- faults
def _fault_gate_left_out(original):
    def log_gate(a):
        return 0.0 * original(a)
    return log_gate


def _fault_normaliser_left_out(original):
    def normalise(num, den, eps):
        return num
    return normalise


def _fault_state_kept(original):
    def make_state_install(shardings=None):
        import jax

        return jax.jit(lambda s, z, slot: (s, z), donate_argnums=(0, 1))
    return make_state_install


@contextlib.contextmanager
def planted(name, fields):
    """The program with one piece of the new mathematics wrong, for the run
    inside the ``with``; yields the ``transformer`` fields to build it from."""
    from accelerate_tpu.models import retention
    from accelerate_tpu.serving import engine

    fields = json.loads(json.dumps(fields))
    ctx = contextlib.nullcontext()
    if name == "gate_left_out":
        ctx = _patched(retention, "log_gate", _fault_gate_left_out)
    elif name == "normaliser_left_out":
        ctx = _patched(retention, "normalise", _fault_normaliser_left_out)
    elif name == "degree_1":
        fields["retention"]["degree"] = 1
    elif name == "head_norm_left_out":
        fields["qk_norm"] = False                     # the two weights are handed over and never read
    elif name == "state_kept_across_requests":
        ctx = _patched(engine, "make_state_install", _fault_state_kept)
    elif name == "state_in_bfloat16":
        fields["retention"]["state_dtype"] = "bfloat16"
    else:
        raise KeyError(name)
    with ctx:
        yield fields


FAULTS = ("gate_left_out", "normaliser_left_out", "degree_1", "head_norm_left_out",
          "state_kept_across_requests", "state_in_bfloat16")


# ------------------------------------------------------------------- readings
def readings(seeds, control_seeds, manifest, entry, cell, config, rehearse, seconds=25.0):
    """For ``limits.py``: runs of the cell at its own load with a short window,
    in one process; on the control seeds also the control's reading, and, where
    ``BENCH_STATE_FAULTS`` is set, on the first of them a run with each planted
    fault (``FAULTS``, or the names the variable lists)."""
    import argparse

    asked = os.environ.get("BENCH_STATE_FAULTS", "")
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
            common.log(event="setup", setup_s=setup_s, requests=len(requests), state_pool_bytes=engine.kv_pool_bytes(),
                       **warm)

            trace_dir = common.BENCH / ".trace" / f"{entry['name']}-{args.seed}"
            slice_times, slice_counters, trace_plan, marker = {}, {}, None, []
            if tracing:
                shutil.rmtree(trace_dir, ignore_errors=True)

                def begin():
                    marker.append(start_trace(trace_dir))
                    slice_times["lo"] = time.perf_counter()
                    slice_counters["lo"] = state_counters(engine)

                def end():
                    slice_counters["hi"] = state_counters(engine)
                    slice_times["hi"] = time.perf_counter()
                    stop_trace(marker[0])

                # the traced slice is the end of the window, as in the serve driver; the
                # cell's file makes it long enough to hold admissions on every seed (a
                # slice with none gives the prefill metrics nothing to read)
                length = min(float(cell["trace_seconds"]), args.seconds)
                trace_plan = (args.seconds - length, length, begin, end)
            before = state_counters(engine)
            at_close = {}

            def on_close():
                at_close.update(clock.snapshot())
                at_close["counters"] = state_counters(engine)

            calls, t0, lateness, abandoned = drive(server, requests, args.seconds,
                                                   float(cell.get("drain_seconds", 60.0)), trace_plan,
                                                   on_close=on_close)
            after_counters = state_counters(engine)
        finally:
            server.stop()
            frontdoor.stop()
    in_window, after_drain = at_close, clock.snapshot()
    compiles_in_window = in_window["backend_compiles"] - warm["backend_compiles"]
    device = common.device_block(devices)
    failed = [c for c in calls if not c.abandoned and (c.status != 200 or not c.done)]
    short = [c for c in calls if c.done and len(c.tokens) != c.request["max_tokens"]]
    seen = client_metrics(calls, t0, args.seconds)
    # of the engine steps of the window (a decode window each, a prefill chunk
    # before it in some), the share that carried a chunk
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
        "served_logit_gap": {"value": read["gap"], "limit": limits["served_logit_gap"],
                             "tokens": read["tokens"], "mean": read["gap_mean"]},
        "requests_failed": {"value": len(failed), "limit": 0},
        "wrong_token_counts": {"value": len(short), "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
    controls = {}
    if control is not None:
        controls = {"control_logit_gap": {"value": read["control_gap"], "limit": float("inf")},
                    "control_logit_gap_mean": {"value": read["control_gap_mean"], "limit": float("inf")}}
    common.log(event="reference", seconds=time.perf_counter() - t_ref, requests=len(sample), tokens=read["tokens"],
               served_logit_gap_mean=read["gap_mean"])

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
               state={k: after_counters[k] - before[k] for k in STATE_COUNTERS})
    correct = common.judge(compared)
    return common.emit(correct, len(calls), len(failed), metrics_out, device, dict(compared, **controls),
                       breakdown, args.rehearse)


if __name__ == "__main__":
    sys.exit(sweep())

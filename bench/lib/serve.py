"""Driver of a ``kind: serve`` cell.

The program under test is the HTTP front door (``ApiServer`` / ``FrontDoor``)
over one ``ServingEngine`` replica behind ``ReplicaRouter``, assembled as
``accelerate_tpu.serve.build_service`` assembles it but from the cell's
configuration file, in this process on threads (one process per chip).  Load is
open loop: each request is sent at its due time on a thread of its own over
``POST /v1/completions`` (streamed, greedy), whatever the server's state.
"""

from __future__ import annotations

import http.client
import importlib
import json
import shutil
import threading
import time

import numpy as np

from lib import common, counts, traffic
from lib.tracing import start_trace, stop_trace, traced_metrics


# --------------------------------------------------------------------- client
class Call(threading.Thread):
    """One streamed completion; records when each token frame arrived."""

    def __init__(self, host, port, request, due, timeout):
        super().__init__(daemon=True)
        self.host, self.port, self.request, self.due, self.timeout = host, port, request, due, timeout
        self.status = None
        self.error = None
        self.tokens = []
        self.arrivals = []
        self.done = False
        self.abandoned = False
        self._conn = None

    def run(self):
        body = json.dumps({"prompt": [int(t) for t in self.request["prompt"]],
                           "max_tokens": self.request["max_tokens"], "temperature": 0, "stream": True})
        try:
            self._conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout)
            self._conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
            resp = self._conn.getresponse()
            self.status = resp.status
            if resp.status != 200:
                self.error = resp.read()[:300].decode("utf-8", "replace")
                return
            for raw in iter(resp.readline, b""):
                line = raw.strip()
                if line == b"data: [DONE]":
                    self.done = True
                    return
                if line.startswith(b"data: "):
                    now = time.perf_counter()
                    for token in json.loads(line[6:])["choices"][0].get("token_ids") or []:
                        self.tokens.append(int(token))
                        self.arrivals.append(now)
            self.error = "stream ended without [DONE]"
        except (OSError, http.client.HTTPException, ValueError) as exc:
            self.error = self.error or repr(exc)
        finally:
            if self._conn is not None:
                self._conn.close()

    def abandon(self):
        """Closes the socket of a call that has not ended by the drain limit; the
        server frees its lane.  Such a call is late, not failed."""
        self.abandoned = True
        if self._conn is not None and self._conn.sock is not None:
            try:
                self._conn.sock.shutdown(2)
            except OSError:
                pass


# -------------------------------------------------------------------- service
def sized(cell, config, rehearse):
    engine, mix = dict(cell["engine"]), dict(cell["traffic"])
    published, fields = dict(config["published"]), dict(config["transformer"])
    if rehearse:
        tiny = cell["rehearse"]
        engine.update(tiny.get("engine", {}))
        mix.update(tiny.get("traffic", {}))
        published.update(tiny["published"])
        fields.update(tiny["transformer"])
    return engine, mix, published, fields


def build_service(seed, engine_kw, published, fields, reference):
    """``(engine, router, frontdoor, server)``: one replica of the configuration
    on this chip behind the router and the HTTP front door, weights from the
    benchmark's seeded draw."""
    import jax.numpy as jnp

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine
    from accelerate_tpu.serving.api import ApiServer, FrontDoor
    from lib import weights

    kw = dict(fields)
    kw["dtype"], kw["param_dtype"] = getattr(jnp, kw["dtype"]), getattr(jnp, kw["param_dtype"])
    model = Transformer(TransformerConfig(**kw))
    params = weights.make_program_params(reference, seed, published, kw["param_dtype"])
    engine_kw = dict(engine_kw)
    if engine_kw.get("prefill_buckets") is not None:
        engine_kw["prefill_buckets"] = tuple(engine_kw["prefill_buckets"])
    engine = ServingEngine(model, params, rng_seed=seed % (2 ** 31), **engine_kw)
    router = ReplicaRouter([engine], policy="affinity")
    frontdoor = FrontDoor(router, model_name="bench").start()
    server = ApiServer(frontdoor, host="127.0.0.1", port=0, request_timeout_s=600.0)
    return engine, router, frontdoor, server


def warm_up(server, engine, engine_kw, mix, vocab, seed):
    """Every program the window will use, by three streamed requests that each
    decode through a few windows: a prompt that crosses every prefill bucket;
    one of exactly the smallest bucket, whose only chunk the prefix cache takes
    and shares with the lane, so that the lane's first decode write copies the
    page (``copy_page``: prompts of such lengths come up in the mix); and a
    short one."""
    rng = np.random.default_rng(seed + 1)
    longest = min(int(mix["prompt_tokens"].get("max", mix["prompt_tokens"].get("value", 0))),
                  engine_kw["max_len"] - 3 * engine_kw["decode_window"] - 8)
    for n in (longest, min(engine.buckets), 24):
        call = Call(server.host, server.port,
                    {"prompt": rng.integers(0, vocab, (n,)).astype(np.int32),
                     "max_tokens": 2 * engine_kw["decode_window"] + 1}, time.perf_counter(), 1100.0)
        call.start()
        call.join()
        if not call.done:
            raise common.NoResult(f"warm-up request failed: status {call.status} {call.error}")


def engine_counters(engine):
    hist = engine._queue_wait_hist
    return {"decode_steps": engine.stats["decode_steps"],
            "occupied_lane_steps": engine.stats["occupied_lane_steps"],
            "queue_wait_sum_s": hist.sum, "queue_wait_count": hist.count,
            "requests_completed": engine.stats["requests_completed"],
            "preempted": engine.stats.get("preempted", 0)}


# --------------------------------------------------------------------- window
def drive(server, requests, seconds, drain_s, trace=None, on_close=None):
    """Sends each request at its due time; returns the calls, the window's
    start and how late the generator ran.  ``trace`` is ``(start_after_s,
    seconds, begin, end)``: ``begin`` runs on this thread at its time and
    ``end`` at the window's close, after ``on_close``."""
    events = [(r["due_s"], "request", r) for r in requests]
    if trace is not None:
        events.append((trace[0], "trace_begin", None))
    events.sort(key=lambda e: e[0])
    calls, lateness = [], []
    t0 = time.perf_counter()
    for due, kind, payload in events:
        if due >= seconds and kind == "request":
            continue
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if kind == "request":
            call = Call(server.host, server.port, payload, t0 + due, 600.0)
            call.start()
            lateness.append(time.perf_counter() - (t0 + due))
            calls.append(call)
        else:
            trace[2]()
    wait = t0 + seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    closed = time.perf_counter()
    if on_close is not None:
        on_close()
    if trace is not None:
        trace[3]()
    for call in calls:
        call.join(max(0.0, closed + drain_s - time.perf_counter()))
    abandoned = [c for c in calls if c.is_alive()]
    for call in abandoned:
        call.abandon()
    for call in abandoned:
        call.join(10.0)
    return calls, t0, lateness, len(abandoned)


def client_metrics(calls, t0, seconds):
    """The end-to-end numbers, from what the clients saw."""
    t1 = t0 + seconds
    tokens_in_window = sum(1 for c in calls for a in c.arrivals if t0 <= a < t1)
    gaps = [1e3 * (b - a) for c in calls for a, b in zip(c.arrivals, c.arrivals[1:]) if t0 <= b < t1]
    ttft = [1e3 * (c.arrivals[0] - c.due) for c in calls if c.arrivals]
    missing = sum(1 for c in calls if not c.arrivals)
    if ttft and missing:
        ttft += [max(ttft)] * missing              # a request with no token counts as the largest
    return {"tokens_in_window": tokens_in_window, "gaps_ms": gaps, "ttft_ms": ttft}


def work_in(calls, lo, hi, published):
    """Client-side count of the work whose tokens arrived in ``[lo, hi)``:
    output tokens with the keys each attended to, prompt tokens of the requests
    whose first token arrived there, and the forward FLOPs both required."""
    contexts, prompt_tokens, flops = [], 0, 0.0
    for c in calls:
        n_prompt = len(c.request["prompt"])
        for i, a in enumerate(c.arrivals):
            if not lo <= a < hi:
                continue
            if i == 0:
                prompt_tokens += n_prompt
                flops += counts.forward_flops_span(published, 0, n_prompt, 1)
            else:
                context = n_prompt + i
                contexts.append(context)
                flops += counts.forward_flops_token(published, context, True)
    return {"decode_contexts": contexts, "output_tokens": len(contexts) + sum(
        1 for c in calls if c.arrivals and lo <= c.arrivals[0] < hi),
            "prompt_tokens": prompt_tokens, "forward_flops": flops}


# -------------------------------------------------------------------- correct
def check_sample(calls, seed, n_sample):
    """The finished requests the reference reads: a seeded sample, the longest in it."""
    finished = [c for c in calls if c.done and c.tokens]
    if not finished:
        return []
    longest = max(finished, key=lambda c: len(c.request["prompt"]) + len(c.tokens))
    rest = [c for c in finished if c is not longest]
    rng = np.random.default_rng(seed + 2)
    picked = [rest[i] for i in rng.permutation(len(rest))[: max(0, n_sample - 1)]]
    return [longest] + picked


def served_gaps(reference, seed, published, sample, width, param_dtype, lower=None):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over the sample; with ``lower``, also the control's."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(lambda s: reference.init_params(s, published, getattr(jnp, param_dtype)))(
        np.uint32(seed % (2 ** 32)))
    worst, worst_low, n_tokens = 0.0, 0.0, 0
    for call in sample:
        gaps, low = reference.served_token_gaps(params, call.request["prompt"], call.tokens,
                                                published, width, "float32", lower)
        n_tokens += len(gaps)
        worst = max(worst, float(np.max(gaps)))
        if low is not None:
            worst_low = max(worst_low, float(np.max(low)))
    return worst, (worst_low if lower else None), n_tokens


def readings(seeds, control_seeds, manifest, entry, cell, config, rehearse, seconds=25.0):
    """For ``limits.py``: runs of the cell at its own load with a short window,
    in one process; on the control seeds also the control's reading (the gap,
    under the float32 reference, of the token the lower precision puts first
    at each served position of the same prompts and tokens)."""
    import argparse

    for seed in seeds:
        args = argparse.Namespace(workload=entry["name"], seed=seed, seconds=seconds, trace=0,
                                  rehearse=rehearse, keep_trace=False)
        control = cell["control_precision"] if seed in control_seeds else None
        run(args, manifest, entry, cell, config, time.time(), control=control)


# ------------------------------------------------------------------------ run
def run(args, manifest, entry, cell, config, started, control=None):
    cache_dir = common.setup_cache(entry["name"])
    import jax

    devices = common.require_chips(entry["chips"], args.rehearse)
    clock = common.CompileClock()
    engine_kw, mix, published, fields = sized(cell, config, args.rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    common.log(event="start", cell=entry["name"], seed=args.seed, device=devices[0].device_kind,
               platform=devices[0].platform, chips=len(devices), cache_dir=cache_dir, jax=jax.__version__)

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
        slice_times, trace_plan, marker = {}, None, []
        if tracing:
            shutil.rmtree(trace_dir, ignore_errors=True)

            def begin():
                marker.append(start_trace(trace_dir))
                slice_times["lo"] = time.perf_counter()

            def end():
                slice_times["hi"] = time.perf_counter()
                stop_trace(marker[0])

            # the traced slice is the end of the window: stopping the profiler
            # (seconds, with this many events) then falls into the drain and
            # holds no request back
            length = min(float(cell["trace_seconds"]), args.seconds)
            trace_plan = (args.seconds - length, length, begin, end)
        before = engine_counters(engine)
        at_close = {}
        calls, t0, lateness, abandoned = drive(server, requests, args.seconds,
                                               float(cell.get("drain_seconds", 60.0)), trace_plan,
                                               on_close=lambda: at_close.update(clock.snapshot()))
        after_counters = engine_counters(engine)
    finally:
        server.stop()
        frontdoor.stop()
    # the window ends at its close: what the drain compiles (abandoning a call
    # takes the engine's cancel path) is logged apart
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
    gap, control_gap, n_checked = (None, None, 0)
    if sample:
        gap, control_gap, n_checked = served_gaps(reference, args.seed, published, sample,
                                                  engine_kw["max_len"], param_dtype, control)
    limits = cell["rehearse"]["limits"] if args.rehearse else cell["limits"]
    compared = {
        "served_logit_gap": {"value": gap, "limit": limits["served_logit_gap"], "tokens": n_checked},
        "requests_failed": {"value": len(failed), "limit": 0},
        "wrong_token_counts": {"value": len(short), "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
    if control is not None:
        compared["control_logit_gap"] = {"value": control_gap, "limit": float("inf")}
    common.log(event="reference", seconds=time.perf_counter() - t_ref, requests=len(sample), tokens=n_checked)

    breakdown = None
    if tracing:
        lo, hi = slice_times.get("lo"), slice_times.get("hi")
        window = {"elapsed_s": args.seconds, "tokens": seen["tokens_in_window"], "chips": len(devices),
                  "work": work_in(calls, t0, t0 + args.seconds, published),
                  "slice_work": work_in(calls, lo, hi, published) if lo and hi else None,
                  "slice_s": (hi - lo) if lo and hi else None,
                  "counters": {k: after_counters[k] - before[k] for k in before},
                  "num_slots": engine_kw["num_slots"]}
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
               unfinished_at_close=sum(1 for c in calls if not c.arrivals or c.arrivals[-1] > t0 + args.seconds))
    correct = common.judge({k: v for k, v in compared.items() if k != "control_logit_gap"})
    return common.emit(correct, len(calls), len(failed), metrics_out, device, compared, breakdown, args.rehearse)

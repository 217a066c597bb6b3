"""Per-request latency waterfalls (ISSUE 17): attribution you can trust.

Contracts under test: under ``async_depth=1`` the tiled phases of every
completed trace sum to the observed TTFT and total latency within tolerance
(phases close at drain, so the pipeline is attributed, not hidden); the
``reqtrace.set_enabled(False)`` kill switch produces zero traces and zero
overhead surface; a preempted-and-replayed request keeps ONE trace that
records the preemption; killing a replica mid-generation carries the trace
to the survivor — the waterfall gains a ``failover`` phase, lists both
replica ids, and the greedy tokens stay identical; the waterfall is
addressable over live HTTP at ``GET /debug/requests/<X-Request-Id>``
(Chrome-trace export included); tracer event retention is a deque (dropped
oldest-first, counted); flight events carry the emitting replica id; and
``engine.stats`` doubles as a callable returning the trace rollup.

Tiny float32 models throughout, same as ``test_serving_async.py`` — TTFT
attribution needs real engine steps, not mocks, but only a handful of them.
"""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.serving import ReplicaRouter, ServingEngine
from accelerate_tpu.serving.api import ApiServer, FrontDoor
from accelerate_tpu.telemetry import (
    MetricsRegistry, get_flight_recorder, get_reqtrace,
)
from accelerate_tpu.telemetry import reqtrace as reqtrace_mod
from accelerate_tpu.telemetry.server import TelemetryEndpoints
from accelerate_tpu.telemetry.tracer import Tracer

NEW_TOKENS = 6
# CPU-host scheduling jitter floor: 5% of TTFT or 20ms, whichever is larger
_FLOOR_S = 0.02


def _tiny_model(seed=0, **kw):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2,
                    registry=MetricsRegistry())
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


def _prompts(seed, lengths, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]


def _ttft_ok(wf):
    return abs(wf["ttft_attributed_s"] - wf["ttft_s"]) <= max(
        0.05 * wf["ttft_s"], _FLOOR_S)


def _settle(predicate, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------------ waterfall correctness

class TestWaterfall:
    def test_phase_sums_attribute_ttft_and_total(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        prompts = _prompts(0, (5, 9, 3), model.config.vocab_size)
        reqs = eng.serve(prompts,
                         GenerationConfig(max_new_tokens=NEW_TOKENS, do_sample=False))
        for req in reqs:
            tr = req.trace
            assert tr is not None and tr.finished
            wf = tr.waterfall()
            assert wf["status"] == "done"
            assert wf["tokens"] == len(req.tokens)
            assert wf["prompt_len"] == len(req.prompt)
            # queue_wait + prefill + decode up to the first token == TTFT
            assert wf["ttft_s"] > 0 and _ttft_ok(wf), wf
            # tiled phases cover submit → finish (overlays excluded)
            tiled = sum(p["dur_s"] for p in wf["phase_list"]
                        if not p.get("overlay"))
            assert abs(tiled - wf["total_s"]) <= max(0.05 * wf["total_s"],
                                                     _FLOOR_S)
            names = [p["phase"] for p in wf["phase_list"]]
            assert names[0] == "queue_wait"
            assert "prefill" in names and "decode" in names
            for p in wf["phase_list"]:
                if p["phase"] == "prefill":
                    assert p["source"] in ("fresh", "cached", "promoted")
                    assert p["tokens"] >= 1
        # the JSON bodies the debug endpoint emits must actually serialize
        json.dumps(reqs[0].trace.waterfall())
        chrome = reqs[0].trace.chrome_trace()
        assert any(e["ph"] == "X" for e in chrome["traceEvents"])
        json.dumps(chrome)

    def test_derived_histograms_and_index(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        prompts = _prompts(1, (8, 5), model.config.vocab_size)
        reqs = eng.serve(prompts,
                         GenerationConfig(max_new_tokens=NEW_TOKENS, do_sample=False))
        snap = reg.snapshot()
        assert snap["serve/queue_wait_s"]["count"] == len(reqs)
        assert snap["serve/prefill_compute_s"]["count"] >= len(reqs)
        # one observation per drained window per live lane, weighted by tokens
        assert snap["serve/decode_s_per_token"]["count"] >= NEW_TOKENS * len(reqs)
        idx = get_reqtrace().index()
        assert idx["enabled"]
        assert idx["counts"]["started"] == len(reqs)
        assert idx["counts"]["completed"] == len(reqs)
        assert idx["counts"]["active"] == 0
        assert len(idx["recent"]) == len(reqs)
        assert idx["slowest_ttft"] and idx["slowest_total"]
        # addressable by bare rid and by engine-qualified rid
        tr = get_reqtrace().lookup(str(reqs[0].rid))
        assert tr is reqs[0].trace
        assert get_reqtrace().lookup(f"{eng.engine_id}:{reqs[0].rid}") is tr

    def test_stats_callable_returns_request_rollup(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        eng = _engine(model, params)
        prompts = _prompts(2, (6,), model.config.vocab_size)
        eng.serve(prompts, GenerationConfig(max_new_tokens=4, do_sample=False))
        # plain dict consumers (benches zero it, routers sum it) still work
        assert eng.stats["requests_completed"] == 1
        rollup = eng.stats()
        assert rollup["requests_completed"] == 1
        req_summary = rollup["requests"]
        assert req_summary["active"] == 0
        assert req_summary["completed"] >= 1
        assert req_summary["recent_ttft_p50_s"] > 0


# ------------------------------------------------------------- kill switch

class TestKillSwitch:
    def test_disabled_tracing_yields_no_traces(self):
        get_reqtrace().reset()
        reqtrace_mod.set_enabled(False)
        try:
            model, params = _tiny_model()
            eng = _engine(model, params)
            reqs = eng.serve(_prompts(3, (6,), model.config.vocab_size),
                             GenerationConfig(max_new_tokens=4, do_sample=False))
            assert reqs[0].trace is None
            idx = get_reqtrace().index()
            assert not idx["enabled"]
            assert idx["counts"]["started"] == 0
            # stats() still answers, with an empty rollup
            assert eng.stats()["requests"]["completed"] == 0
        finally:
            reqtrace_mod.set_enabled(None)
        assert reqtrace_mod.tracing_enabled()


# ------------------------------------------------- preemption + replay

class TestPreemptionSingleTrace:
    def test_preempted_request_keeps_one_trace_with_annotations(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        prompts = _prompts(14, (12, 16, 9, 14), model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=28, do_sample=False,
                               eos_token_id=None)
        eng = _engine(model, params, prefix_cache_mb=None,
                      num_pages=17)  # Pmax = 16 + null: forces preemption
        reqs = eng.serve([p.copy() for p in prompts], gen)
        assert eng.stats["preemptions"] >= 1
        started = get_reqtrace().traces_started
        assert started == len(reqs)  # replay reuses the trace, never reopens
        preempted = [r for r in reqs
                     if any(e["event"] == "preempt" for e in r.trace.events)]
        assert preempted, "no trace recorded the preemption"
        for req in preempted:
            events = [e["event"] for e in req.trace.events]
            assert "requeue" in events
            wf = req.trace.waterfall()
            assert wf["status"] == "done"
            # the replayed prefill chunks land in the SAME waterfall
            assert _ttft_ok(wf), wf


# ----------------------------------------------------- tracer event deque

class TestTracerDeque:
    def test_fifo_drop_keeps_newest_and_counts(self):
        tr = Tracer(enabled=True, max_events=4)
        for i in range(6):
            with tr.span(f"s{i}"):
                pass
        events = tr.events
        assert len(events) == 4
        assert tr.dropped_events == 2
        # oldest dropped, export order preserved
        assert [e["name"] for e in events] == ["s2", "s3", "s4", "s5"]
        assert tr.chrome_trace()["otherData"]["dropped_events"] == 2


# ------------------------------------------------- replica-tagged events

class TestTaggedFlightEvents:
    def test_engine_events_carry_replica_id(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        eng = _engine(model, params)
        eng.serve(_prompts(4, (6,), model.config.vocab_size),
                  GenerationConfig(max_new_tokens=4, do_sample=False))
        tail = get_flight_recorder().tail()
        mine = [e for e in tail if e.get("engine") == eng.engine_id]
        assert mine, f"no events tagged for {eng.engine_id}"
        kinds = {e["kind"] for e in mine}
        assert "serve/submit" in kinds and "serve/finish" in kinds

    def test_tagged_recorder_explicit_fields_win(self):
        rec = get_flight_recorder().tagged(engine="eX")
        rec.record("serve/step", engine="eY", step=1)
        last = get_flight_recorder().tail(1)[0]
        assert last["engine"] == "eY"


# ------------------------------------------------------- debug endpoints

class TestDebugEndpoints:
    def _endpoints(self):
        return TelemetryEndpoints(registry=MetricsRegistry())

    def test_index_and_waterfall_routes(self):
        get_reqtrace().reset()
        model, params = _tiny_model()
        eng = _engine(model, params)
        reqs = eng.serve(_prompts(5, (6,), model.config.vocab_size),
                         GenerationConfig(max_new_tokens=4, do_sample=False))
        ep = self._endpoints()
        status, ctype, body = ep.handle("/debug/requests")
        assert status == 200 and ctype == "application/json"
        idx = json.loads(body)
        assert idx["counts"]["completed"] == len(reqs)
        status, _, body = ep.handle(f"/debug/requests/{reqs[0].rid}")
        assert status == 200
        wf = json.loads(body)
        assert wf["status"] == "done" and wf["phase_list"]
        status, _, body = ep.handle(f"/debug/requests/{reqs[0].rid}",
                                    "format=chrome")
        assert status == 200
        assert json.loads(body)["traceEvents"]

    def test_unknown_id_is_json_404(self):
        ep = self._endpoints()
        status, ctype, body = ep.handle("/debug/requests/no-such-request")
        assert status == 404 and ctype == "application/json"
        assert json.loads(body)["error"] == "unknown request id"


# --------------------------------------- live HTTP + forced mid-gen failover

class Service:
    """Two paged replicas behind router + front door + HTTP server, with
    in-process greedy references computed before the driver took over."""

    ENGINE_KW = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                     decode_window=2, max_queue=4, prefix_cache_mb=0)

    def __init__(self):
        self.cfg = TransformerConfig.tiny(
            dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64
        )
        self.model = Transformer(self.cfg)
        self.params = self.model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        self.registry = MetricsRegistry()

        def build():
            return ServingEngine(
                self.model, self.params, registry=self.registry,
                page_size=4, num_pages=65, **self.ENGINE_KW,
            )

        self.e1, self.e2 = build(), build()
        rng = np.random.default_rng(7)
        self.prompts = [
            rng.integers(1, self.cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in (4, 5, 7, 8)
        ]
        gen = GenerationConfig(max_new_tokens=NEW_TOKENS)
        reqs = self.e1.serve(self.prompts, gen)
        self.expected = [[int(t) for t in q.tokens] for q in reqs]
        get_reqtrace().reset()  # references above are not part of the test

        self.router = ReplicaRouter([self.e1, self.e2], registry=self.registry,
                                    breaker_base_s=0.05)
        self.frontdoor = FrontDoor(self.router, model_name="test-model").start()
        self.server = ApiServer(self.frontdoor, registry=self.registry)
        self.host, self.port = self.server.host, self.server.port

    def get(self, path):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=60.0)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    def completion(self, prompt):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=60.0)
        try:
            body = {"prompt": [int(t) for t in prompt],
                    "max_tokens": NEW_TOKENS, "temperature": 0}
            conn.request("POST", "/v1/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            headers = dict(resp.getheaders())
            return resp.status, headers, json.loads(resp.read())
        finally:
            conn.close()

    def stat(self, key):
        parked = [b["engine"] for b in self.router._breaker.values()]
        return sum(e.stats[key] for e in list(self.router.engines) + parked)

    def stop(self):
        self.server.stop()
        self.frontdoor.stop()


@pytest.fixture(scope="class")
def svc():
    service = Service()
    yield service
    service.stop()


class TestLiveHttpWaterfalls:
    def test_waterfall_by_request_id_over_http(self, svc):
        status, headers, body = svc.completion(svc.prompts[0])
        assert status == 200
        assert body["choices"][0]["token_ids"] == svc.expected[0]
        rid = headers["X-Request-Id"]
        assert rid == body["id"]
        status, wf = svc.get(f"/debug/requests/{rid}")
        assert status == 200
        assert wf["status"] == "done"
        assert wf["tokens"] == NEW_TOKENS
        assert _ttft_ok(wf), wf
        # chrome export over the same route
        status, chrome = svc.get(f"/debug/requests/{rid}?format=chrome")
        assert status == 200 and chrome["traceEvents"]
        status, idx = svc.get("/debug/requests")
        assert status == 200 and idx["counts"]["completed"] >= 1

    def test_failover_carries_trace_to_survivor(self, svc):
        n = 6
        results = [None] * n
        submitted_before = svc.stat("requests_submitted")

        def fire(k):
            results[k] = svc.completion(svc.prompts[k % len(svc.prompts)])

        threads = [threading.Thread(target=fire, args=(k,)) for k in range(n)]
        for t in threads:
            t.start()
        assert _settle(
            lambda: svc.stat("requests_submitted") - submitted_before >= n,
            timeout=30.0,
        ), "not every request was admitted"
        assert _settle(lambda: svc.e2.has_work, timeout=30.0), \
            "victim replica never received work"
        svc.e2.kill("chaos: simulated device loss")
        for t in threads:
            t.join()
        failed_over = []
        for status, headers, body in results:
            assert status == 200, body
            assert body["choices"][0]["token_ids"] in svc.expected
            wf_status, wf = svc.get(f"/debug/requests/{headers['X-Request-Id']}")
            assert wf_status == 200, "completed trace fell out of retention"
            assert wf["status"] == "done"
            assert _ttft_ok(wf), wf
            if wf["failover"]:
                failed_over.append(wf)
        assert failed_over, "no surviving request recorded a failover"
        for wf in failed_over:
            assert len(wf["replicas"]) == 2
            phases = [p["phase"] for p in wf["phase_list"]]
            assert "failover" in phases
            # the survivor's replayed prefill continues the SAME waterfall
            events = [e["event"] for e in wf["events"]]
            assert "export_inflight" in events
        # flagged retention: failover survivors stay in the index
        _, idx = svc.get("/debug/requests")
        assert any(s["failover"] for s in idx["flagged"])
        assert _settle(lambda: self._idle(svc))

    @staticmethod
    def _idle(svc):
        return all(not e.has_work for e in svc.router.engines)

"""Chaos-tested fault tolerance (ISSUE 13), over the wire where it counts.

Contracts under test: the fault injector is deterministic per ``(seed,
point)`` and off by default; killing a replica mid-generation loses zero
requests — the router ejects it, survivors adopt its in-flight lanes as
prompt + generated-so-far, and greedy outputs stay token-identical; the
ejected replica re-admits through the half-open circuit breaker; an
unmeetable ``deadline_s`` is refused at admission (429) while a blown one
mid-decode cancels and answers 504; an injected page-pool exhaustion rides
the preemption ladder without losing tokens; a wedged driver ticket maps to
503 + Retry-After; a torn hot-swap upload leaves the old weights serving.

Tier-1 on purpose: one module-scoped tiny float32 service with TWO replicas,
4-8 token prompts, a handful of decode windows per request.  Token-exactness
needs float32 argmax margins, same as ``test_api_server.py``.
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
from accelerate_tpu.serving import ReplicaRouter, ServingEngine, faults
from accelerate_tpu.serving.api import ApiServer, FrontDoor
from accelerate_tpu.serving.faults import FaultInjected, FaultInjector, FaultPlan
from accelerate_tpu.telemetry import MetricsRegistry

NEW_TOKENS = 6
ENGINE_KW = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                 decode_window=2, max_queue=4, prefix_cache_mb=0)


# ------------------------------------------------------------ injector unit

def test_fault_plan_parse_roundtrip():
    plan = FaultPlan.parse("seed=7,decode_dispatch=0.02,replica_kill@40,slow_ms=25")
    assert plan.seed == 7
    assert plan.probs == {"decode_dispatch": 0.02}
    assert plan.at == {"replica_kill": 40}
    assert plan.slow_ms == 25.0
    # empty entries tolerated; defaults hold
    assert FaultPlan.parse("fetch_slow=0.5,").probs == {"fetch_slow": 0.5}


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultPlan.parse("decode_dispatchh=0.5")
    with pytest.raises(ValueError, match="probability"):
        FaultPlan(probs={"fetch_fail": 1.5})
    with pytest.raises(ValueError, match="both"):
        FaultPlan(probs={"replica_kill": 0.1}, at={"replica_kill": 3})
    with pytest.raises(ValueError, match="1-based"):
        FaultPlan(at={"replica_kill": 0})
    with pytest.raises(ValueError, match="bad fault plan entry"):
        FaultPlan.parse("decode_dispatch")


def test_injector_deterministic_per_seed_and_point():
    plan = FaultPlan(seed=7, probs={"decode_dispatch": 0.3, "fetch_slow": 0.2})
    a = FaultInjector(plan, registry=MetricsRegistry())
    b = FaultInjector(plan, registry=MetricsRegistry())
    # interleave b's points differently: per-point streams must not care
    seq_a = [a.fire("decode_dispatch") for _ in range(200)]
    for _ in range(57):
        b.fire("fetch_slow")
    seq_b = [b.fire("decode_dispatch") for _ in range(200)]
    assert seq_a == seq_b
    assert sum(seq_a) == a.fired("decode_dispatch") > 0
    other = FaultInjector(FaultPlan(seed=8, probs={"decode_dispatch": 0.3}),
                          registry=MetricsRegistry())
    assert seq_a != [other.fire("decode_dispatch") for _ in range(200)]


def test_injector_one_shot_fires_exactly_once():
    reg = MetricsRegistry()
    inj = FaultInjector(FaultPlan(at={"replica_kill": 40}), registry=reg)
    seq = [inj.fire("replica_kill") for _ in range(100)]
    assert seq.index(True) == 39 and sum(1 for hit in seq if hit is True) == 1
    assert inj.checks("replica_kill") == 100
    assert inj.fired("replica_kill") == 1
    assert reg.snapshot()["serve/faults_injected_total"] == 1
    # a point absent from the plan never fires and costs no rng state
    assert not any(inj.fire("fetch_fail") for _ in range(50))


def test_faults_off_by_default_and_clear():
    faults.install("seed=1,decode_dispatch=0.5")
    assert faults.ACTIVE is not None
    faults.clear()
    assert faults.ACTIVE is None


# ----------------------------------------------------------------- service

class Service:
    """TWO identical replicas behind router + front door + HTTP server, a
    fast circuit breaker, and in-process greedy references computed BEFORE
    the driver took over."""

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
                page_size=4, num_pages=65, **ENGINE_KW,
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

        self.router = ReplicaRouter([self.e1, self.e2], registry=self.registry,
                                    breaker_base_s=0.05)
        self.frontdoor = FrontDoor(self.router, model_name="test-model").start()
        self.server = ApiServer(self.frontdoor, registry=self.registry)
        self.host, self.port = self.server.host, self.server.port

    def post(self, path, payload, timeout=60.0):
        conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
        try:
            conn.request("POST", path, json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), json.loads(resp.read())
        finally:
            conn.close()

    def completion(self, prompt, **kw):
        body = {"prompt": [int(t) for t in prompt],
                "max_tokens": NEW_TOKENS, "temperature": 0}
        body.update(kw)
        return self.post("/v1/completions", body)

    def engines(self):
        """Live replicas plus any parked behind the breaker (stats live on
        the engine, which survives ejection)."""
        parked = [b["engine"] for b in self.router._breaker.values()]
        return list(self.router.engines) + parked

    def stat(self, key):
        return sum(e.stats[key] for e in self.engines())

    def idle(self):
        return all(not e.has_work for e in self.router.engines)

    def stop(self):
        self.server.stop()
        self.frontdoor.stop()


@pytest.fixture(scope="module")
def svc():
    service = Service()
    yield service
    service.stop()


def _settle(predicate, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------- replica kill + breaker

def test_replica_kill_mid_decode_loses_nothing(svc):
    n = 6
    results = [None] * n
    submitted_before = svc.stat("requests_submitted")

    def fire(k):
        results[k] = svc.completion(svc.prompts[k % len(svc.prompts)])

    threads = [threading.Thread(target=fire, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    # every submit must be ACCEPTED before the kill: the kill runs on this
    # thread, not through the driver ticket queue, so on a loaded host it can
    # otherwise land between submits — and a straggler then finds the lone
    # survivor holding the victim's replayed lanes with a full queue (429).
    # Any 3/3..6/0 split of 6 accepted requests fits the survivor's
    # 2 slots + 4 queue after replay, so waiting makes the test deterministic.
    assert _settle(
        lambda: svc.stat("requests_submitted") - submitted_before >= n,
        timeout=30.0,
    ), "not every request was admitted"
    # the victim must genuinely own work when it dies, or the test shows
    # nothing: least-loaded routing spreads 6 requests across 2 replicas
    assert _settle(lambda: svc.e2.has_work, timeout=30.0), \
        "victim replica never received work"
    svc.e2.kill("chaos: simulated device loss")
    for t in threads:
        t.join()
    # zero failed requests, greedy token identity preserved through replay
    for status, _, body in results:
        assert status == 200, body
        assert body["choices"][0]["token_ids"] in svc.expected
    health = svc.frontdoor.health()
    assert health["ejections"] >= 1
    assert svc.stat("requests_replayed") >= 1
    assert svc.registry.snapshot()["serve/replica_ejections_total"] >= 1
    # half-open breaker: after the cooldown the driver probes, revives, and
    # re-admits the dead replica under a fresh stable id
    assert _settle(lambda: svc.frontdoor.health()["replicas"] == 2), \
        f"breaker never re-admitted the killed replica: {svc.frontdoor.health()}"
    # the revived pool still serves token-exact
    status, _, body = svc.completion(svc.prompts[0])
    assert status == 200 and body["choices"][0]["token_ids"] == svc.expected[0]
    assert _settle(svc.idle)


# ------------------------------------------------------- deadline shedding

def test_unmeetable_deadline_refused_429(svc):
    assert _settle(svc.idle)
    shed_before = svc.stat("deadline_shed")
    gen = GenerationConfig(max_new_tokens=24)

    def flood():
        # on the driver thread: pin a pessimistic service-time estimate and
        # fill both queues in one atomic ticket, so the deadline submit that
        # follows sees a waiting line no 10ms budget can clear
        for e in svc.router.engines:
            e._service_ema = 50.0
        for k in range(8):
            svc.router.submit(svc.prompts[k % len(svc.prompts)], config=gen)

    svc.frontdoor._call(flood)
    status, headers, body = svc.completion(svc.prompts[0], deadline_s=0.01)
    assert status == 429, body
    assert "Retry-After" in headers and int(headers["Retry-After"]) >= 1
    assert body["error"]["code"] == "engine_overloaded"
    assert "deadline" in body["error"]["message"]
    # the router's failover ladder consults BOTH replicas; each refusal is a
    # shed, so the count rises by 1 per admittable replica
    assert svc.stat("deadline_shed") >= shed_before + 1
    assert _settle(svc.idle)  # the flood itself completes untouched
    for e in svc.router.engines:
        e._service_ema = 0.0


def test_blown_deadline_cancels_running_lane_504(svc):
    assert _settle(svc.idle)
    free_before = [e.kv.allocator.free_count for e in svc.router.engines]
    shed_before = svc.stat("deadline_shed")
    status, _, body = svc.completion(
        svc.prompts[0], deadline_s=0.005, max_tokens=48,
    )
    assert status == 504, body
    assert body["error"]["code"] == "deadline_exceeded"
    assert body["error"]["type"] == "timeout_error"
    assert svc.stat("deadline_shed") == shed_before + 1
    assert _settle(svc.idle)
    # and the shed lane leaked no KV pages
    free_after = [e.kv.allocator.free_count for e in svc.router.engines]
    assert free_after == free_before


# ------------------------------------------------ injected infrastructure

def test_page_exhaustion_fault_preempts_without_losing_tokens(svc):
    assert _settle(svc.idle)
    pre_before = svc.stat("preemptions")
    faults.install("seed=3,page_exhaustion@2", registry=svc.registry)
    try:
        n = 4
        results = [None] * n
        threads = [
            threading.Thread(
                target=lambda k=k: results.__setitem__(
                    k, svc.completion(svc.prompts[k % len(svc.prompts)])
                )
            )
            for k in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        faults.clear()
    for status, _, body in results:
        assert status == 200, body
        assert body["choices"][0]["token_ids"] in svc.expected
    assert svc.stat("preemptions") >= pre_before + 1
    assert svc.registry.snapshot()["serve/faults_injected_total"] >= 1
    assert _settle(svc.idle)


def test_sse_handler_disconnect_cancels_lane_and_frees_pages(svc):
    assert _settle(svc.idle)
    free_before = [e.kv.allocator.free_count for e in svc.router.engines]
    cancelled_before = svc.stat("cancelled")
    faults.install("handler_disconnect@1", registry=svc.registry)
    try:
        conn = http.client.HTTPConnection(svc.host, svc.port, timeout=60.0)
        try:
            conn.request("POST", "/v1/completions", json.dumps({
                "prompt": [int(t) for t in svc.prompts[1]],
                "max_tokens": 40, "temperature": 0, "stream": True,
            }), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()  # server breaks the stream mid-flight; drain to EOF
        finally:
            conn.close()
        assert _settle(lambda: svc.stat("cancelled") > cancelled_before), \
            "injected disconnect never cancelled the lane"
    finally:
        faults.clear()
    assert _settle(
        lambda: svc.idle()
        and [e.kv.allocator.free_count for e in svc.router.engines] == free_before
    ), (
        f"cancelled lane leaked KV pages "
        f"({[e.kv.allocator.free_count for e in svc.router.engines]} free, "
        f"expected {free_before})"
    )


def test_hot_swap_upload_fault_keeps_old_weights_serving(svc):
    assert _settle(svc.idle)
    versions_before = svc.frontdoor.model_versions()
    params2 = jax.tree_util.tree_map(lambda x: x * 1.01, svc.params)
    faults.install("hot_swap_upload=1.0", registry=svc.registry)
    try:
        with pytest.raises(FaultInjected):
            svc.frontdoor.hot_swap(params2, version="torn")
    finally:
        faults.clear()
    # the torn upload changed nothing: same versions, admission resumed,
    # greedy outputs still match the original weights
    assert svc.frontdoor.model_versions() == versions_before
    assert "torn" not in svc.frontdoor.model_versions()
    status, _, body = svc.completion(svc.prompts[2])
    assert status == 200 and body["choices"][0]["token_ids"] == svc.expected[2]
    assert _settle(svc.idle)


# --------------------------------------------------------- edge mappings

def test_driver_ticket_timeout_maps_to_503_retry_after(svc, monkeypatch):
    def wedged(call, model_version=None):
        raise TimeoutError("driver did not service the request within 0.0s")

    monkeypatch.setattr(svc.frontdoor, "submit", wedged)
    status, headers, body = svc.completion(svc.prompts[0])
    assert status == 503, body
    assert body["error"]["code"] == "driver_busy"
    assert "Retry-After" in headers and int(headers["Retry-After"]) >= 1


def test_retry_after_values_are_jittered():
    from accelerate_tpu.serving.api.server import _retry_after

    values = {int(_retry_after(20.0)) for _ in range(64)}
    assert len(values) > 1, "Retry-After must jitter, or synchronized clients stampede"
    assert all(15 <= v <= 26 for v in values), values
    assert int(_retry_after(0.05)) >= 1  # floor: never advertise 0

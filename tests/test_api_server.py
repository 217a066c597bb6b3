"""The OpenAI front door, over the wire: a real ``ApiServer`` on an
ephemeral port, driven with stdlib ``http.client`` only.

Contracts under test (ISSUE 12): over-the-wire greedy completions are
token-identical to in-process ``engine.serve``; SSE streams frame each token
before completion and terminate with ``data: [DONE]``; a queue flood answers
429 (with ``Retry-After``) and nothing worse; a client that disconnects
mid-stream gets its lane cancelled and its KV pages freed; draining a
replica finishes its in-flight lanes before detach; a weight hot-swap under
live traffic fails zero requests.

Tier-1 on purpose (NOT in conftest ``SLOW_MODULES``): one module-scoped
tiny float32 service, 4-8 token prompts, and every request a handful of
decode windows.  Token-exactness needs float32 argmax margins, same as
``test_serving.py``.
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
from accelerate_tpu.telemetry import MetricsRegistry, get_tracer

NEW_TOKENS = 6
ENGINE_KW = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                 decode_window=2, max_queue=4, prefix_cache_mb=0)


class Service:
    """One engine behind router + front door + HTTP server, plus the
    in-process greedy references computed BEFORE the driver took over."""

    def __init__(self):
        self.cfg = TransformerConfig.tiny(
            dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64
        )
        self.model = Transformer(self.cfg)
        self.params = self.model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        self.registry = MetricsRegistry()
        self.engine = ServingEngine(
            self.model, self.params, registry=self.registry,
            page_size=4, num_pages=65, **ENGINE_KW,
        )
        rng = np.random.default_rng(7)
        self.prompts = [
            rng.integers(1, self.cfg.vocab_size, (int(n),)).astype(np.int32)
            for n in (4, 5, 7, 8)
        ]
        gen = GenerationConfig(max_new_tokens=NEW_TOKENS)
        reqs = self.engine.serve(self.prompts, gen)
        self.expected = [[int(t) for t in q.tokens] for q in reqs]

        self.router = ReplicaRouter([self.engine])
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

    def stop(self):
        self.server.stop()
        self.frontdoor.stop()


@pytest.fixture(scope="module")
def svc():
    service = Service()
    yield service
    service.stop()


def test_cli_builds_page_pool_replicas_and_has_no_paged_flag():
    from accelerate_tpu import serve

    with pytest.raises(SystemExit):
        serve.parse_args(["--preset", "tiny", "--paged"])
    router, frontdoor, server = serve.build_service(serve.parse_args(
        ["--preset", "tiny", "--max-len", "64", "--replicas", "2", "--port", "0"]))
    try:
        assert len(router.engines) == 2
        assert all(e.kv.allocator.free_count == e.num_pages - 1
                   for e in router.engines)
    finally:
        server.stop()
        frontdoor.stop()


def _settle(predicate, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def test_over_the_wire_matches_in_process_submit(svc):
    for prompt, expected in zip(svc.prompts, svc.expected):
        status, _, body = svc.completion(prompt)
        assert status == 200, body
        choice = body["choices"][0]
        assert choice["token_ids"] == expected
        assert choice["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == NEW_TOKENS
    # the chat dialect rides the same engine path (empty template: content
    # ids ARE the prompt) and must produce the same greedy tokens
    status, _, body = svc.post("/v1/chat/completions", {
        "messages": [{"role": "user",
                      "content": [int(t) for t in svc.prompts[0]]}],
        "max_tokens": NEW_TOKENS, "temperature": 0,
    })
    assert status == 200, body
    assert body["choices"][0]["token_ids"] == svc.expected[0]
    assert body["object"] == "chat.completion"


def test_sse_streams_frame_tokens_before_done(svc):
    conn = http.client.HTTPConnection(svc.host, svc.port, timeout=60.0)
    try:
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": [int(t) for t in svc.prompts[0]],
            "max_tokens": NEW_TOKENS, "temperature": 0, "stream": True,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/event-stream")
        frames = []
        for raw in iter(resp.readline, b""):
            line = raw.strip()
            if line.startswith(b"data: "):
                frames.append(line[len(b"data: "):])
            if frames and frames[-1] == b"[DONE]":
                break
    finally:
        conn.close()
    assert frames[-1] == b"[DONE]"
    chunks = [json.loads(f) for f in frames[:-1]]
    # one chunk per token, then the summary chunk carrying finish_reason —
    # the first token arrived as its own frame BEFORE the completion did
    token_chunks = [c for c in chunks if c["choices"][0]["token_ids"]]
    streamed = [t for c in token_chunks for t in c["choices"][0]["token_ids"]]
    assert streamed == svc.expected[0]
    assert all(c["object"] == "text_completion" for c in chunks)
    assert chunks[0]["choices"][0]["token_ids"], "first frame must carry a token"
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert chunks[-1]["choices"][0]["token_ids"] == []


def _stream(svc, prompt):
    """One streamed completion; ``(request id header, token frames)``."""
    conn = http.client.HTTPConnection(svc.host, svc.port, timeout=60.0)
    try:
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": [int(t) for t in prompt],
            "max_tokens": NEW_TOKENS, "temperature": 0, "stream": True,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        frames = []
        for raw in iter(resp.readline, b""):
            line = raw.strip()
            if line == b"data: [DONE]":
                break
            if line.startswith(b"data: "):
                frames.append(json.loads(line[len(b"data: "):]))
        return resp.getheader("X-Request-Id"), [f for f in frames if f["choices"][0]["token_ids"]]
    finally:
        conn.close()


def test_driver_spans_nest_and_every_streamed_frame_is_recorded(svc):
    """The driver thread's spans nest door -> router -> engine step -> admit /
    dispatch / drain -> emit, on one thread; a handler thread records one
    ``http/stream_write`` interval per token frame under the request's id."""
    tracer = get_tracer()
    last = max((e["id"] for e in tracer.events), default=0)
    request_id, token_frames = _stream(svc, svc.prompts[1])
    assert len(token_frames) == NEW_TOKENS
    # the trailing steps (pipeline flush, reap) close after the last frame
    assert _settle(lambda: not svc.router.has_work and not svc.frontdoor._outstanding)
    events = [e for e in tracer.events if e["id"] > last]
    by_id = {e["id"]: e for e in events}
    named = lambda name: [e for e in events if e["name"] == name]

    def inside(child, parent):
        return (parent["ts"] <= child["ts"]
                and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1.0)

    steps, router_steps = named("serve/step"), named("router/step")
    assert steps and len(router_steps) == len(steps)      # one replica: one each
    for step in steps:
        router_step = by_id[step["parent"]]
        assert router_step["name"] == "router/step" and inside(step, router_step)
        assert router_step["args"]["replicas"] == 1 and router_step["parent"] is None
        assert {"queue", "occupied"} <= set(step["args"])
        parts = [e for e in events if e["parent"] == step["id"]]
        assert {e["name"] for e in parts} <= {"serve/admit", "serve/dispatch", "serve/drain"}
        assert all(inside(e, step) for e in parts)
        assert sum(e["dur"] for e in parts) <= step["dur"]
    assert len(named("serve/admit")) == len(steps)
    assert sum(e["args"]["prefill_tokens"] for e in named("serve/admit")) == len(svc.prompts[1])
    # the windows and the blocking fetch stay children of dispatch and drain
    assert all(by_id[e["parent"]]["name"] == "serve/dispatch" for e in named("serve/decode_window"))
    assert all(by_id[e["parent"]]["name"] == "serve/drain" for e in named("serve/readback"))
    # one emit per drain, and together they landed the request's tokens
    emits, drains = named("serve/emit"), named("serve/drain")
    assert len(emits) == len(drains) >= NEW_TOKENS // ENGINE_KW["decode_window"]
    assert all(by_id[e["parent"]]["name"] == "serve/drain" for e in emits)
    assert sum(e["args"]["tokens"] for e in emits) == NEW_TOKENS
    assert all(e["args"]["lanes"] == (1 if e["args"]["tokens"] else 0) for e in emits)
    # the front door: the submit ticket, and a reap on every stepped iteration
    assert sum(e["args"]["tickets"] for e in named("door/tickets")) >= 1
    assert len(named("door/reap")) == len(router_steps)
    assert sum(e["args"]["finished"] for e in named("door/reap")) == 1
    # every span above ran on the one driver thread; the frames did not
    driver = {e["tid"] for e in events if e["name"] != "http/stream_write"}
    assert len(driver) == 1
    writes = named("http/stream_write")
    assert len(writes) == len(token_frames)
    assert {f"cmpl-{e['args']['req']}" for e in writes} == {request_id}
    assert all(e["parent"] is None and e["dur"] > 0 and e["tid"] not in driver for e in writes)
    # the emit stamp travels with the token: a write starts inside an emit
    for write in writes:
        assert any(e["ts"] <= write["ts"] <= e["ts"] + e["dur"] for e in emits)


def test_an_idle_server_opens_no_driver_span(svc):
    assert _settle(lambda: not svc.router.has_work)
    tracer = get_tracer()
    last = max((e["id"] for e in tracer.events), default=0)
    time.sleep(0.2)                      # some two hundred idle iterations
    assert [e["name"] for e in tracer.events if e["id"] > last] == []


def test_driver_thread_has_an_os_name_of_its_own(svc):
    """A device trace names a host thread's line by its OS name, ``python``
    for every Python thread: the driver's spans need a line of their own."""
    comm = f"/proc/self/task/{svc.frontdoor._thread.native_id}/comm"
    try:
        with open(comm) as f:
            name = f.read().strip()
    except OSError:
        pytest.skip("no /proc/self/task here")
    assert name == "atpu-driver"


def test_queue_flood_answers_429_with_retry_after(svc):
    n = 16  # far past num_slots=2 + max_queue=4
    results = [None] * n

    def fire(k):
        results[k] = svc.completion(svc.prompts[k % len(svc.prompts)])

    threads = [threading.Thread(target=fire, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    statuses = [s for s, _, _ in results]
    assert set(statuses) <= {200, 429}, statuses
    assert statuses.count(429) >= 1, "flood never hit admission backpressure"
    for status, headers, body in results:
        if status == 429:
            assert "Retry-After" in headers
            assert body["error"]["code"] == "engine_overloaded"
        else:  # admitted requests stay token-exact under load
            assert body["choices"][0]["token_ids"] in svc.expected
    assert svc.registry.snapshot()["serve/http_429_total"] >= 1


def test_client_disconnect_cancels_and_frees_pages(svc):
    allocator = svc.engine.kv.allocator
    assert _settle(lambda: not svc.engine.has_work)
    free_before = allocator.free_count
    cancelled_before = svc.engine.stats["cancelled"]
    conn = http.client.HTTPConnection(svc.host, svc.port, timeout=60.0)
    conn.request("POST", "/v1/completions", json.dumps({
        "prompt": [int(t) for t in svc.prompts[1]],
        "max_tokens": 40, "temperature": 0, "stream": True,
    }), {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200  # SSE headers are out; generation is admitted
    # vanish before the first frame: BOTH the response and the connection
    # must close or the OS socket stays half-open (the HTTPResponse holds
    # its own file object) and the server's writes never break
    resp.close()
    conn.close()
    assert _settle(lambda: svc.engine.stats["cancelled"] > cancelled_before), \
        "disconnect never reached engine.cancel"
    assert _settle(lambda: not svc.engine.has_work
                   and allocator.free_count == free_before), \
        f"cancelled lane leaked KV pages ({allocator.free_count} free, " \
        f"expected {free_before})"


def test_drain_replica_completes_in_flight_lanes(svc):
    second = ServingEngine(
        svc.model, svc.params, registry=MetricsRegistry(),
        page_size=4, num_pages=65, **ENGINE_KW,
    )
    rid2 = svc.frontdoor.add_replica(second)
    assert svc.frontdoor.health()["replicas"] == 2
    n = 6  # both replicas get lanes (least-loaded spillover)
    results = [None] * n

    def fire(k):
        results[k] = svc.completion(svc.prompts[k % len(svc.prompts)])

    threads = [threading.Thread(target=fire, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    time.sleep(0.02)  # let lanes start
    svc.frontdoor.drain_replica(rid2)
    for t in threads:
        t.join()
    # every request admitted anywhere — including lanes on the draining
    # replica — completed, token-exact
    for status, _, body in results:
        assert status == 200, body
        assert body["choices"][0]["token_ids"] in svc.expected
    # once idle the drained replica detaches from the router entirely
    assert _settle(lambda: svc.frontdoor.health()["replicas"] == 1)
    assert second.drained


def test_hot_swap_serves_zero_failed_requests(svc):
    params2 = jax.tree_util.tree_map(lambda x: x * 1.01, svc.params)
    results = []
    lock = threading.Lock()
    stop = threading.Event()

    def hammer(widx):
        k = 0
        while not stop.is_set():
            out = svc.completion(svc.prompts[(widx + k) % len(svc.prompts)])
            k += 1
            with lock:
                results.append(out)

    workers = [threading.Thread(target=hammer, args=(w,)) for w in range(2)]
    for t in workers:
        t.start()
    time.sleep(0.05)  # requests genuinely in flight across the swap
    swapped = svc.frontdoor.hot_swap(params2, version="v1")
    time.sleep(0.05)
    stop.set()
    for t in workers:
        t.join()
    assert swapped == len(svc.router.engines)
    assert results, "no traffic crossed the swap"
    for status, _, body in results:
        assert status == 200, body
        assert len(body["choices"][0]["token_ids"]) == NEW_TOKENS
    assert svc.engine.weights_version == "v1"
    assert svc.frontdoor.model_versions() == {"v1": len(svc.router.engines)}
    assert svc.registry.snapshot()["serve/hot_swaps_total"] == 1
    # /v1/models now advertises the new version behind the same model id
    conn = http.client.HTTPConnection(svc.host, svc.port, timeout=30.0)
    try:
        conn.request("GET", "/v1/models")
        resp = conn.getresponse()
        body = json.loads(resp.read())
    finally:
        conn.close()
    ids = {m["id"] for m in body["data"]}
    assert "test-model" in ids and "test-model@v1" in ids

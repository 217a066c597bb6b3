"""A request's path under one id, and an engine step that says what it carried
(ISSUE 37).

On the CPU, a tiny engine of each pool kind (paged KV, a retention model's
recurrent state, window + full layers on a pool of two rules) behind a real
``FrontDoor``: every finished request has one ``req/queue``, one
``req/prefill`` and one ``req/decode`` on the process tracer, all with the
front door's id, which its ``serve/prefill_chunk`` spans carry too;
``req/queue`` + ``req/prefill`` is the request's ``serve/ttft_s`` observation;
what ``serve/step`` and the window spans say they carried sums to the engine's
own counters; ``door/idle`` is one event an idle period; and telemetry changes
no token.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.serving import ReplicaRouter, ServingEngine
from accelerate_tpu.serving.api import FrontDoor
from accelerate_tpu.serving.api.protocol import CompletionCall
from accelerate_tpu.telemetry import MetricsRegistry, get_reqtrace, get_tracer, set_enabled
from accelerate_tpu.telemetry import reqtrace as reqtrace_mod
from accelerate_tpu.telemetry.watchdog import RecompileWatchdog

NEW_TOKENS = 7
LENGTHS = (5, 19, 9, 33, 3)
WINDOWS = ("serve/decode_window", "serve/verify_window", "serve/tree_verify_window")

CONFIGS = {
    "kv": dict(),
    "state": dict(qk_norm=True, rope_theta=1e6,
                  retention=dict(degree=2, gate_heads=2, state_dtype="float32", eps=1e-6, chunk=8)),
    "mixed": dict(layer_types=("window", "window", "full"), num_layers=3, sliding_window=16),
}
ENGINES = {
    "kv": dict(page_size=4),
    "state": dict(),
    "mixed": dict(page_size=4, prefix_cache_mb=0),
}


@functools.lru_cache(maxsize=None)
def _tiny(kind):
    """``(pool kind, model, params)``: one tiny float32 model a pool kind."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, vocab_size=97,
                                 **CONFIGS[kind])
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return kind, model, params


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def tiny(request):
    return _tiny(request.param)


def _engine(tiny, **kw):
    kind, model, params = tiny
    kw = dict(dict(num_slots=2, max_len=128, prefill_buckets=(4, 16), decode_window=2,
                   registry=MetricsRegistry(), **ENGINES[kind]), **kw)
    return ServingEngine(model, params, **kw)


def _prompts(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 97, (n,)).astype(np.int32) for n in lengths]


def _serve_through_door(engine, prompts, settle_s=0.05):
    """Sends ``prompts`` through a ``FrontDoor`` over ``engine`` and waits for
    them; returns ``[(request, stream)]``.  The door idles ``settle_s`` before
    the first submit and after the last completion."""
    door = FrontDoor(ReplicaRouter([engine]), idle_sleep_s=0.001).start()
    try:
        time.sleep(settle_s)
        sent = [door.submit(CompletionCall(prompt=[int(t) for t in p], max_tokens=NEW_TOKENS,
                                           temperature=0.0)) for p in prompts]
        for _, stream in sent:
            assert stream.wait_done(120.0)
        time.sleep(settle_s)
    finally:
        door.stop()
    return sent


@pytest.fixture(scope="module")
def run(tiny):
    """One run of five requests through the door on a fresh tracer: the
    engine, what was sent, the tracer's events and the counters' movement."""
    get_reqtrace().reset()
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(tiny)
    before = dict(engine.stats)
    sent = _serve_through_door(engine, _prompts())
    moved = {k: engine.stats[k] - before[k] for k in before if isinstance(before[k], (int, float))}
    return engine, sent, tracer.events, moved


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_every_request_has_its_three_records_under_the_doors_id(run):
    _, sent, events, _ = run
    keys = [stream.rid for _, stream in sent]
    assert keys == sorted(set(keys)) and len(keys) == len(LENGTHS)
    for (req, stream), prompt in zip(sent, _prompts()):
        assert req.key == stream.rid == req.trace_id
        (queue,), (prefill,), (decode,) = (                 # exactly one of each
            [e for e in _named(events, n) if e["args"]["req"] == stream.rid]
            for n in ("req/queue", "req/prefill", "req/decode"))
        assert queue["parent"] is prefill["parent"] is decode["parent"] is None
        assert queue["args"]["prompt_tokens"] == prefill["args"]["prompt_tokens"] == len(prompt)
        assert decode["args"] == {"req": stream.rid, "tokens": NEW_TOKENS, "status": "done"}
        # the three tile the request's life: each begins where the last ended
        assert queue["ts"] + queue["dur"] == pytest.approx(prefill["ts"], abs=1.0)
        assert prefill["ts"] + prefill["dur"] == pytest.approx(decode["ts"], abs=1.0)


def test_queue_and_prefill_sum_to_the_requests_ttft(run):
    engine, sent, events, _ = run
    sums = []
    for req, stream in sent:
        parts = [e["dur"] for n in ("req/queue", "req/prefill") for e in _named(events, n)
                 if e["args"]["req"] == stream.rid]
        assert len(parts) == 2
        sums.append(sum(parts) / 1e6)
        assert sums[-1] == pytest.approx(req.trace.ttft_s, abs=1e-3)
    # the engine's own serve/ttft_s saw the same observations
    assert engine._ttft_hist.count == len(sent)
    assert engine._ttft_hist.sum == pytest.approx(sum(sums), abs=1e-3 * len(sent))


def test_chunk_spans_carry_the_requests_id(run):
    engine, sent, events, moved = run
    chunks = _named(events, "serve/prefill_chunk")
    assert len(chunks) == moved["prefill_chunks"]
    by_req = {}
    for e in chunks:
        by_req[e["args"]["req"]] = by_req.get(e["args"]["req"], 0) + e["args"]["valid"]
    # every fresh token of every prompt, under the id its req/* records carry
    assert by_req == {stream.rid: len(req.prompt) for req, stream in sent}
    counted = {e["args"]["req"]: e["args"]["chunks"] for e in _named(events, "req/prefill")}
    assert counted == {rid: sum(1 for e in chunks if e["args"]["req"] == rid) for rid in by_req}
    installs = _named(events, "serve/state_install")
    assert len(installs) == moved.get("state_installs", 0)
    assert sorted(e["args"]["req"] for e in installs) == sorted(by_req)[:len(installs)]


def test_steps_say_what_they_carried(run):
    _, _, events, moved = run
    steps = _named(events, "serve/step")
    total = lambda arg: sum(e["args"][arg] for e in steps)
    assert total("chunks") == moved["prefill_chunks"] > 0
    assert total("chunk_tokens") == moved["prefill_tokens"] == sum(LENGTHS)
    assert total("emitted") == moved["tokens_generated"] == NEW_TOKENS * len(LENGTHS)
    windows = [e for name in WINDOWS for e in _named(events, name)]
    assert sum(e["args"]["occupied"] * e["args"]["steps"] for e in windows) == moved["occupied_lane_steps"]
    assert total("window") == len(windows)
    assert sum(e["args"]["steps"] for e in windows) == moved["decode_steps"]
    # ``live`` is the lanes live at the step's one dispatch
    assert all(w["args"]["steps"] == 2 for w in windows)
    assert ([e["args"]["live"] for e in steps if e["args"]["window"]]
            == [w["args"]["occupied"] for w in sorted(windows, key=lambda w: w["ts"])])
    assert all(e["args"]["live"] == 0 for e in steps if not e["args"]["window"])


def test_ticket_wait_is_one_record_a_ticket(run):
    _, sent, events, _ = run
    waits = _named(events, "door/ticket_wait")
    assert len(waits) == len(sent)
    assert all(e["args"] == {"admin": False} and e["dur"] >= 0 for e in waits)
    assert sum(e["args"]["tickets"] for e in _named(events, "door/tickets")) == len(sent)


def test_idle_is_one_span_a_period_and_none_while_there_is_work(run):
    _, _, events, _ = run
    idles = _named(events, "door/idle")
    # the settle before the first submit and the one after the last completion
    assert 2 <= len(idles) <= 3, idles
    assert idles[0]["args"]["naps"] > 5 and idles[-1]["args"]["naps"] > 5
    assert idles[0]["dur"] >= 20e3 and idles[-1]["dur"] >= 20e3
    # none overlaps a step of the router: an idle period ends before work begins
    busy = [(e["ts"], e["ts"] + e["dur"]) for n in ("router/step", "door/tickets") for e in _named(events, n)]
    for idle in idles:
        lo, hi = idle["ts"], idle["ts"] + idle["dur"]
        assert not any(a < hi and b > lo for a, b in busy), idle


def test_an_idle_period_is_cut_where_a_capture_needs_it():
    """A span is mirrored into the device trace only if opened while the capture
    is on, and the profiler keeps it only if it closed before the capture ended:
    an idle period is cut where a capture begins or ends under it, and every
    few naps while one is on."""
    from accelerate_tpu.serving.api.frontdoor import _TRACED_IDLE_NAPS
    from accelerate_tpu.telemetry.tracer import set_device_trace_active

    tracer = get_tracer()
    tracer.reset()
    door = FrontDoor(ReplicaRouter([_engine(_tiny("kv"))]), idle_sleep_s=0.001).start()
    try:
        time.sleep(0.05)
        set_device_trace_active(True)
        time.sleep(0.15)
    finally:
        set_device_trace_active(False)
        time.sleep(0.05)
        door.stop()
    idles = _named(tracer.events, "door/idle")
    traced = [e["args"]["traced"] for e in idles]
    n = sum(traced)
    assert n >= 3 and traced == [False] + [True] * n + [False]
    assert idles[0]["args"]["naps"] > _TRACED_IDLE_NAPS                 # untraced: one span however long
    assert all(e["args"]["naps"] == _TRACED_IDLE_NAPS for e in idles[1:n])
    assert idles[n]["args"]["naps"] <= _TRACED_IDLE_NAPS


def test_speculative_windows_say_their_width():
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(_tiny("kv"), speculate_k=3)        # speculation runs on the paged KV pool alone
    before = engine.stats["occupied_lane_steps"]
    prompt = np.tile(np.arange(1, 9, dtype=np.int32), 4)            # repeats: the n-gram drafter finds matches
    engine.serve([prompt], GenerationConfig(max_new_tokens=12, do_sample=False))
    events = tracer.events
    verifies = _named(events, "serve/verify_window")
    assert verifies and all(e["args"]["steps"] == 4 for e in verifies)
    windows = [e for name in WINDOWS for e in _named(events, name)]
    assert (sum(e["args"]["occupied"] * e["args"]["steps"] for e in windows)
            == engine.stats["occupied_lane_steps"] - before)
    # straight to the engine, a request's records carry the engine's rid
    assert {e["args"]["req"] for e in events if e["name"].startswith("req/")} == {0}


def test_the_reqtrace_switch_turns_the_records_off(tiny):
    tracer = get_tracer()
    tracer.reset()
    reqtrace_mod.set_enabled(False)
    try:
        engine = _engine(tiny)
        sent = _serve_through_door(engine, _prompts(lengths=(6,)), settle_s=0.0)
    finally:
        reqtrace_mod.set_enabled(None)
    events = tracer.events
    assert not [e for e in events if e["name"].startswith("req/")]
    # the spans do not hang on the switch: the chunk still names its request
    (req, stream), = sent
    assert {e["args"]["req"] for e in _named(events, "serve/prefill_chunk")} == {stream.rid}


def test_tokens_are_the_same_with_telemetry_off(tiny, run):
    _, sent, _, _ = run
    tracer = get_tracer()
    set_enabled(False)
    tracer.enabled = False
    try:
        tracer.reset()
        quiet = _serve_through_door(_engine(tiny), _prompts(), settle_s=0.0)
        assert tracer.events == []
    finally:
        tracer.enabled = True
        set_enabled(True)
    assert [req.tokens for req, _ in quiet] == [req.tokens for req, _ in sent]
    assert all(req.trace is None for req, _ in quiet)


def test_a_first_call_is_recorded_with_its_program_and_time():
    tracer = get_tracer()
    tracer.reset()
    registry = MetricsRegistry()
    watched = RecompileWatchdog(jax.jit(lambda x: x + 1), name="unit/add", registry=registry)
    for shape in ((2,), (2,), (3,)):
        watched(jnp.zeros(shape))
    records = _named(tracer.events, "compile/unit/add")
    assert [e["args"]["n"] for e in records] == [1, 2]
    assert sum(e["dur"] for e in records) / 1e6 == pytest.approx(
        registry.get("compile/unit/add/first_call_s").value, abs=1e-6)


def test_the_loader_spans_its_fetch_and_its_placement():
    from accelerate_tpu import Accelerator, SimpleDataLoader

    class Rows:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return {"x": np.full((4,), i, np.float32)}

    tracer = get_tracer()
    loader = Accelerator().prepare(SimpleDataLoader(Rows(), batch_size=2, drop_last=True))
    tracer.reset()
    batches = list(loader)
    assert len(batches) == 4
    fetches, places = _named(tracer.events, "data/fetch"), _named(tracer.events, "data/place")
    assert len(places) == 4 and len(fetches) == 5           # the fifth fetch found the end
    assert all(e["tid"] == places[0]["tid"] for e in fetches + places)

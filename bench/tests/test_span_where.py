"""The two readers of what the engine dispatched and of a request's waits
(``span_where``, ``device_time_per_dispatched``) and ``registry_sum`` on
hand-written events and counters; each new metric's file against its entry in
``BENCHMARK.json``; and a CPU rehearsal of one serve cell that prints every new
metric a CPU run can feed (the two over device time need a device's trace)."""

import argparse
import json
import math
import time

import pytest

from lib import common, serve, train
from reducers import device_time_per_dispatched, registry_sum, span_where

SERVE = "gpt2-xl.serve-chat-surge"
TRAIN = "gpt2-medium.train-seq1024"
SERVE_CELLS = ["gpt2-xl.serve-chat-surge", "deepseek-v2.serve-doc-surge", "brumby-14b.serve-reason-surge",
               "trinity-large.serve-longdoc-surge"]
NEW = {
    "queue_wait_ms_p50": SERVE_CELLS, "prefill_wall_ms_p50": SERVE_CELLS, "ticket_wait_ms_p95": SERVE_CELLS,
    "chunk_step_share_pct": SERVE_CELLS, "engine_step_ms_p80": SERVE_CELLS,
    "decode_device_ms_per_lane_step": SERVE_CELLS, "decode_window_device_ms": SERVE_CELLS,
    "lanes_live_pct": ["deepseek-v2.serve-doc-surge", "trinity-large.serve-longdoc-surge"],
    "prefill_device_ms_per_ktoken_dispatched": SERVE_CELLS[1:],
    "feed_ms_per_step": [TRAIN], "programs_first_call_s": [TRAIN] + SERVE_CELLS,
}


def _event(name, ts_ms, dur_ms, **args):
    event = {"name": name, "id": 0, "parent": None, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3}
    if args:
        event["args"] = args
    return event


# a capture of [100, 200) ms.  Five steps: one that began before it, one idle
# (no window), three with a window (one of them emitted nothing), one after.
STEPS = [
    _event("serve/step", 90, 20, window=1, live=4, chunks=1, chunk_tokens=512, emitted=8),
    _event("serve/step", 100, 10, window=1, live=2, chunks=1, chunk_tokens=512, emitted=8),
    _event("serve/step", 120, 30, window=1, live=3, chunks=2, chunk_tokens=640, emitted=12),
    _event("serve/step", 150, 5, window=0, live=0, chunks=1, chunk_tokens=128, emitted=0),
    _event("serve/step", 160, 50, window=1, live=1, chunks=0, chunk_tokens=0, emitted=0),
    _event("serve/step", 200, 10, window=1, live=4, chunks=0, chunk_tokens=0, emitted=16),
]
# waits: one that ended before the capture, two that ended inside it (one began
# long before), one still open at its end
WAITS = [
    _event("req/queue", 10, 80, req=1), _event("req/queue", -5000, 5110, req=2),
    _event("req/queue", 150, 10, req=3), _event("req/queue", 190, 20, req=4),
]
WINDOWS = [
    _event("serve/decode_window", 95, 1, occupied=4, steps=4),
    _event("serve/decode_window", 101, 1, occupied=2, steps=4),
    _event("serve/decode_window", 121, 1, occupied=3, steps=4),
    _event("serve/verify_window", 161, 1, occupied=1, steps=3),
    _event("serve/decode_window", 170, 1, occupied=1),               # an older program: no width
]
CHUNKS = [_event("serve/prefill_chunk", 99, 1, bucket=512, valid=512, req=1),
          _event("serve/prefill_chunk", 110, 1, bucket=512, valid=500, req=2),
          _event("serve/prefill_chunk", 130, 1, bucket=128, valid=100, req=2)]
TAKEN = {"t0": 100e3, "t1": 200e3, "events": STEPS + WAITS + WINDOWS + CHUNKS}


def _stat(span, stat, inside="begin", value="dur", where=None, over=None, slots=1, scale=1e3):
    return span_where.stat_of(span_where.events_inside(TAKEN, span, inside, where), stat, value, over, slots, scale)


def test_an_event_is_taken_once_by_the_end_asked_for():
    began = span_where.events_inside(TAKEN, "serve/step", "begin")
    assert [e["ts"] / 1e3 for e in began] == [100, 120, 150, 160]
    ended = span_where.events_inside(TAKEN, "req/queue", "end")
    assert [e["args"]["req"] for e in ended] == [2, 3]
    # pooled names, as span_stat pools them
    both = span_where.events_inside(TAKEN, ["serve/decode_window", "serve/verify_window"], "begin")
    assert len(both) == 4


@pytest.mark.parametrize("kw, expected", [
    (dict(span="serve/step", stat="sum"), 95.0),
    (dict(span="serve/step", stat="mean"), 23.75),
    (dict(span="serve/step", stat="p50"), 20.0),
    (dict(span="serve/step", stat="p80", where={"window": [1, None]}), 42.0),
    (dict(span="serve/step", stat="p80", where={"window": [1, None], "emitted": [1, None]}), 26.0),
    (dict(span="serve/step", stat="p100", where={"live": [None, 2]}), 50.0),
    (dict(span="serve/step", stat="sum", value="chunks", over="window", scale=100.0), 400.0 / 3),
    (dict(span="serve/step", stat="sum", value="live", over="window", slots=4, scale=100.0), 50.0),
    (dict(span="serve/step", stat="mean", value="chunk_tokens", scale=1.0), 320.0),
    (dict(span="req/queue", stat="p50", inside="end"), 2560.0),
    (dict(span="req/queue", stat="p95", inside="begin"), 19.5),
])
def test_span_where_statistics(kw, expected):
    assert _stat(**kw) == pytest.approx(expected)


def test_span_where_reads_nothing_where_there_is_nothing():
    assert _stat("req/prefill", "p50", inside="end") is None                       # no such record
    assert _stat("serve/step", "p50", where={"tokens": [1, None]}) is None        # an arg no event has
    assert _stat("serve/decode_window", "sum", value="queue") is None
    assert _stat("serve/step", "sum", value="chunks", over="lanes") is None       # the divisor never moved
    with pytest.raises(ValueError, match="over goes with stat 'sum'"):
        _stat("serve/step", "p50", value="chunks", over="window")
    with pytest.raises(ValueError, match="pNN, mean or sum"):
        _stat("serve/step", "median")


def test_dispatched_work_is_the_product_of_the_args_of_what_began_inside():
    windows = span_where.events_inside(TAKEN, "serve/decode_window", "begin")
    assert device_time_per_dispatched.dispatched(windows, ["occupied", "steps"]) == 2 * 4 + 3 * 4
    chunks = span_where.events_inside(TAKEN, "serve/prefill_chunk", "begin")
    assert device_time_per_dispatched.dispatched(chunks, ["valid"]) == 600


def test_device_time_per_dispatched(monkeypatch):
    trace = {"modules": {"jit_paged_decode_window": {"seconds": 0.060, "count": 2},
                         "jit_paged_prefill_chunk_512": {"seconds": 0.030, "count": 1},
                         "jit_paged_prefill_chunk_128": {"seconds": 0.006, "count": 1}}}
    monkeypatch.setattr(device_time_per_dispatched, "capture", lambda: TAKEN)
    decode = dict(pattern="decode", span="serve/decode_window", product=["occupied", "steps"])
    assert device_time_per_dispatched.reduce({"trace": trace}, **decode) == pytest.approx(3.0)
    # no arg named: a count of the windows, the older program's among them
    a_window = dict(pattern="decode", span="serve/decode_window", product=[])
    assert device_time_per_dispatched.reduce({"trace": trace}, **a_window) == pytest.approx(20.0)
    prefill = dict(pattern="prefill", span="serve/prefill_chunk", product=["valid"], scale=1e6)
    assert device_time_per_dispatched.reduce({"trace": trace}, **prefill) == pytest.approx(60.0)
    assert device_time_per_dispatched.reduce({"trace": None}, **decode) is None
    assert device_time_per_dispatched.reduce({"trace": {"modules": {}}}, **decode) is None
    # a program whose windows do not say their width: nothing, not a wrong number
    old = dict(TAKEN, events=[dict(e, args={"occupied": 2}) for e in WINDOWS])
    monkeypatch.setattr(device_time_per_dispatched, "capture", lambda: old)
    assert device_time_per_dispatched.reduce({"trace": trace}, **decode) is None
    monkeypatch.setattr(device_time_per_dispatched, "capture", lambda: None)
    assert device_time_per_dispatched.reduce({"trace": trace}, **decode) is None


def test_no_capture_reads_as_nothing():
    from accelerate_tpu.telemetry import get_tracer

    get_tracer().reset()
    assert span_where.reduce({"window": {"num_slots": 4}}, span="serve/step", stat="p50") is None


def test_a_capture_hands_the_readers_what_began_and_what_ended_in_it():
    from accelerate_tpu.telemetry import get_tracer

    tracer = get_tracer()
    tracer.reset()
    with tracer.span("serve/step", queue=0) as args:                # before the capture: not read
        args.update(window=1, live=1, chunks=1, emitted=1)
    began = time.perf_counter()
    tracer.mark_capture(True)
    for live, chunks in ((2, 1), (4, 0)):
        with tracer.span("serve/step", queue=0) as args:
            args.update(window=1, live=live, chunks=chunks, emitted=4)
    tracer.record("req/queue", began - 10.0, time.perf_counter(), req=7)
    tracer.mark_capture(False)
    tracer.record("req/queue", began, time.perf_counter(), req=8)     # closed after it: not read
    ctx = {"window": {"num_slots": 4}}
    live = span_where.reduce(ctx, span="serve/step", stat="sum", value="live", over="window",
                             over_times_slots=True, scale=100.0)
    assert live == pytest.approx(75.0)
    share = span_where.reduce(ctx, span="serve/step", stat="sum", value="chunks", over="window", scale=100.0)
    assert share == pytest.approx(50.0)
    wait = span_where.reduce(ctx, span="req/queue", inside="end", stat="p50")
    assert 10_000.0 <= wait < 10_100.0
    tracer.reset()


def test_registry_sum_adds_the_counters_that_match():
    from accelerate_tpu.telemetry import get_registry

    pattern = "unit_test_registry_sum/.*/first_call_s"
    assert registry_sum.reduce({}, pattern=pattern) is None
    registry = get_registry()
    registry.counter("unit_test_registry_sum/a/first_call_s").inc(1.5)
    registry.counter("unit_test_registry_sum/b/first_call_s").inc(2.0)
    registry.counter("unit_test_registry_sum/b/first_call_s_more").inc(8.0)
    registry.gauge("unit_test_registry_sum/c/first_call_s").set(16.0)
    assert registry_sum.reduce({}, pattern=pattern) == pytest.approx(3.5)


def test_manifest_and_metric_files_agree():
    manifest = common.read_json(common.ROOT / "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    for name, workloads in NEW.items():
        spec, reduce = common.load_reducer(name)
        assert callable(reduce)
        for key in ("layer", "moves", "unit", "source"):
            assert spec[key] == entries[name][key], (name, key)
        assert entries[name]["workloads"] == workloads and set(workloads) <= cells
        moved = end_to_end[entries[name]["moves"]]
        assert all(cell in moved.get("workloads", cells) for cell in workloads)


def _rehearse(kind, name, seed):
    manifest, entry, cell, config = common.load_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=5.0, trace=1, rehearse=True,
                              keep_trace=False)
    return kind.run(args, manifest, entry, cell, config, time.time())


@pytest.mark.parametrize("kind, name, metrics", [
    (serve, SERVE, ["queue_wait_ms_p50", "prefill_wall_ms_p50", "ticket_wait_ms_p95", "chunk_step_share_pct",
                    "engine_step_ms_p80", "programs_first_call_s"]),
    (train, TRAIN, ["feed_ms_per_step", "programs_first_call_s"]),
])
def test_a_rehearsal_prints_the_new_metrics(kind, name, metrics):
    line = _rehearse(kind, name, seed=3_700_000_017)
    assert line["correct"] is True
    manifest = common.read_json(common.ROOT / "BENCHMARK.json")
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    for metric in metrics:
        value = line["metrics"][metric]["value"]
        assert math.isfinite(value) and value > 0.0, (metric, value)
        assert line["metrics"][metric]["unit"] == units[metric]
    json.dumps(line)

"""The reader of the program's tracer spans: each ``value``, ``stat``,
``minus`` and ``per_span`` on a hand-written event list, nothing without a
capture (or with a program whose tracer has none), and a CPU rehearsal of each
cell that prints the five metrics the reader feeds."""

import argparse
import json
import math
import time

import pytest

from lib import common, serve, train
from reducers import span_stat

TRAIN = "gpt2-medium.train-seq1024"
SERVE = "gpt2-xl.serve-chat-surge"


def _event(ident, name, ts_ms, dur_ms, parent=None, **args):
    event = {"name": name, "id": ident, "parent": parent, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3}
    if args:
        event["args"] = args
    return event


# two driver iterations: a step of 10 ms holding a 6 ms readback, then one of
# 20 ms holding a window of 2 ms and an 8 ms readback; 1 ms of tickets, a reap
EVENTS = [
    _event(1, "door/tickets", 0, 1.0, tickets=2),
    _event(2, "router/step", 1, 11.0, replicas=1),
    _event(3, "serve/step", 1.5, 10.0, parent=2, queue=4),
    _event(4, "serve/readback", 2, 6.0, parent=3),
    _event(5, "door/reap", 12, 0.5, finished=0),
    _event(6, "router/step", 13, 21.0, replicas=1),
    _event(7, "serve/step", 13.5, 20.0, parent=6, queue=8),
    _event(8, "serve/decode_window", 14, 2.0, parent=7),
    _event(9, "serve/readback", 17, 8.0, parent=7),
    _event(10, "door/reap", 34, 0.5, finished=1),
    _event(11, "http/stream_write", 20, 3.0, req=5),
    _event(12, "serve/decode_window", 30, 2.0, parent=7),
]
DRIVER = ["door/tickets", "router/step", "door/reap"]


@pytest.mark.parametrize("params, expected", [
    ({"span": "serve/step", "stat": "p50"}, 15.0),
    ({"span": "serve/step", "stat": "p95"}, 19.5),
    ({"span": "serve/step", "stat": "mean"}, 15.0),
    ({"span": "serve/step", "stat": "sum"}, 30.0),
    ({"span": "serve/step", "stat": "sum", "value": "self"}, 30.0 - 6.0 - 8.0 - 2.0 - 2.0),
    ({"span": "router/step", "stat": "p50", "value": "self"}, 1.0),
    ({"span": "serve/readback", "stat": "mean", "value": "self"}, 7.0),          # no children
    ({"span": "serve/step", "stat": "mean", "value": "queue", "scale": 1.0}, 6.0),
    ({"span": DRIVER, "stat": "sum"}, 34.0),
    ({"span": DRIVER, "stat": "sum", "minus": ["serve/readback"]}, 20.0),
    ({"span": DRIVER, "stat": "sum", "minus": ["serve/readback"], "per_span": "serve/decode_window"}, 10.0),
    ({"span": DRIVER, "stat": "sum", "minus": ["serve/step"], "per_span": "serve/decode_window"}, 2.0),
    ({"span": "http/stream_write", "stat": "p95"}, 3.0),
])
def test_each_value_and_stat_on_a_hand_written_list(params, expected):
    assert span_stat.stat_of(EVENTS, **params) == pytest.approx(expected)


@pytest.mark.parametrize("params", [
    {"span": "train/step", "stat": "p50"},                                   # no such span
    {"span": "serve/step", "stat": "mean", "value": "occupied"},             # no such arg
    {"span": DRIVER, "stat": "sum", "per_span": "serve/verify_window"},     # nothing to divide by
])
def test_nothing_to_read_gives_none(params):
    assert span_stat.stat_of(EVENTS, **params) is None


def test_minus_with_a_percentile_is_refused():
    with pytest.raises(ValueError):
        span_stat.stat_of(EVENTS, "serve/step", "p50", minus=["serve/readback"])


def test_none_without_a_capture_and_from_a_program_without_one(monkeypatch):
    from accelerate_tpu import telemetry

    tracer = telemetry.Tracer(enabled=True)
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    with tracer.span("train/step"):
        pass
    assert span_stat.reduce({}, "train/step", "p50") is None               # no capture yet
    tracer.mark_capture(True)
    with tracer.span("train/step"):
        time.sleep(0.002)
    tracer.mark_capture(False)
    assert span_stat.reduce({}, "train/step", "sum") >= 2.0                  # ms; the first span is outside

    class Older:                                                             # the parent's tracer: no capture()
        enabled = True

    monkeypatch.setattr(telemetry, "get_tracer", lambda: Older())
    assert span_stat.reduce({}, "train/step", "p50") is None


def _rehearse(kind, name, seed):
    manifest, entry, cell, config = common.load_cell(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=5.0, trace=1, rehearse=True,
                              keep_trace=False)
    return kind.run(args, manifest, entry, cell, config, time.time())


@pytest.mark.parametrize("kind, name, metrics", [
    (train, TRAIN, ["train_step_host_ms_p50", "train_step_self_ms_p50"]),
    (serve, SERVE, ["driver_host_ms_per_window", "router_door_ms_per_window", "stream_lag_ms_p95"]),
])
def test_a_rehearsal_of_each_cell_prints_the_new_metrics(kind, name, metrics):
    line = _rehearse(kind, name, seed=3_000_000_017)
    assert line["correct"] is True
    for metric in metrics:
        value = line["metrics"][metric]["value"]
        assert math.isfinite(value) and value > 0.0, (metric, value)
        assert line["metrics"][metric]["unit"] == "ms"
    json.dumps(line)


def test_manifest_and_metric_files_agree():
    manifest = common.read_json(common.ROOT / "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("train_step_host_ms_p50", "train_step_self_ms_p50", "driver_host_ms_per_window",
                 "router_door_ms_per_window", "stream_lag_ms_p95"):
        spec, reduce = common.load_reducer(name)
        assert reduce is span_stat.reduce
        for key in ("layer", "moves", "unit", "source"):
            assert spec[key] == entries[name][key]
        assert len(entries[name]["workloads"]) == 1

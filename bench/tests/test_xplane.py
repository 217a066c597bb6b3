"""The trace reducer: ``summarize`` on events recorded on the chip in PR 23
(``tests/data/train_trace_events.json.gz``: what ``read`` returned for a
0.3 s slice of ``gpt2-medium.train-seq1024`` on a TPU v5 lite, operation names
shortened), and ``read`` itself on a trace taken here on the CPU."""

import gzip
import json
from pathlib import Path

import pytest

from lib import xplane

DATA = Path(__file__).parent / "data" / "train_trace_events.json.gz"


def test_union_and_clip():
    assert xplane._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane._clip([("a", 0.0, 10.0), ("b", 20.0, 5.0)], (5.0, 22.0)) == [("a", 5.0, 10.0), ("b", 20.0, 22.0)]


def test_short_op_name():
    assert xplane.short_op_name(
        "%fusion.592 = bf16[50257,1024]{1,0:T(8,128)(2,1)} fusion(f32[4,1024]{1,0} %x)") == "fusion.592 bf16[50257,1024]"
    assert xplane.short_op_name("%fusion.1 = (bf16[1024]{0}, f32[4,1024]{1,0}) fusion(") == "fusion.1 bf16[1024]"


def test_gaps_named_by_innermost_program_span():
    host = {"python3": [("bench_window", 0.0, 1000.0), ("train/step", 100.0, 300.0), ("Execute", 150.0, 50.0)],
            "other": [("idle_thing", 600.0, 100.0)]}
    names = xplane._name_gaps(host, [(160.0, 180.0), (620.0, 640.0), (900.0, 950.0)])
    assert names == ["train/step", "idle_thing", "no span open"]


def test_summarize_synthetic():
    trace = {"window": (0.0, 1_000_000.0),
             "devices": [{"name": "/device:TPU:0",
                          "ops": [("a", 0.0, 400_000.0), ("b", 300_000.0, 200_000.0), ("a", 900_000.0, 200_000.0)],
                          "modules": [("jit_f(1)", 0.0, 500_000.0), ("jit_f(1)", 900_000.0, 200_000.0)]}],
             "host": {"python3": [("bench_window", 0.0, 1_000_000.0), ("train/step", 450_000.0, 500_000.0)]}}
    s = xplane.summarize(trace)
    assert s["window_s"] == pytest.approx(1e-3)
    assert s["busy_s"] == pytest.approx(0.6e-3)            # [0, 0.5) and [0.9, 1.0) ms
    assert s["modules"]["jit_f(1)"] == {"seconds": pytest.approx(0.6e-3), "count": 2}
    assert s["idle_gaps"] == [["train/step", pytest.approx(0.4e-3)]]
    assert s["device_ops"][0] == ["a", pytest.approx(0.5e-3)]


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_summarize_recorded_chip_trace():
    with gzip.open(DATA, "rt") as f:
        recorded = json.load(f)
    want = recorded.pop("summary_on_chip")
    recorded["window"] = tuple(recorded["window"])
    got = xplane.summarize(recorded)
    assert got["devices"] == want["devices"] == 1
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < got["busy_s"] < got["window_s"]
    (name, module), = got["modules"].items()
    assert name.startswith("jit__step(")
    assert module["seconds"] == pytest.approx(want["modules"][name]["seconds"], rel=1e-6)
    # the step's operations are all of the device's busy time, to a percent
    assert module["seconds"] == pytest.approx(got["busy_s"], rel=0.02)
    assert got["idle_gaps"][0][0] == "train/step"


def test_read_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train/step"):
                f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.newest_xplane(str(tmp_path))
    assert path is not None
    assert xplane.summarize(xplane.read(path)) is None          # no TPU plane: nothing to read
    trace = xplane.read(path, allow_host_ops=True)
    assert trace["window"] is not None
    spans = [n for events in trace["host"].values() for n, _, _ in events]
    assert spans.count("train/step") == 3
    s = xplane.summarize(trace)
    assert 0 < s["busy_s"] <= s["window_s"]

"""Flight recorder, XLA cost table, and the live debug server.

Covers the ISSUE acceptance surface: Prometheus exposition survives
non-finite values and escapes HELP text, empty histograms export valid JSON
through ``JSONTracker`` (``Infinity`` is not JSON), the flight ring is
bounded with an honest drop count, the stall detector trips exactly once
per stall with all-thread stacks in the dump and never false-positives on a
healthy run, ``/metrics`` + ``/healthz`` serve live state on an ephemeral
port (``/healthz`` flips 503 when heartbeats stop), ``train/step_mfu`` on
CPU is finite and in ``(0, 1]``, and ``ATPU_TELEMETRY=0`` /
``set_enabled(False)`` disables the recorder and the server too.
"""

import json
import math
import urllib.request

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import accelerate_tpu as at
from accelerate_tpu.telemetry import (
    CostTable,
    DebugServer,
    FlightRecorder,
    MetricsRegistry,
    StallDetector,
    detect_device_peaks,
    set_enabled,
    start_debug_server,
    stop_debug_server,
)
from accelerate_tpu.telemetry.metrics import _fmt


def fresh_accelerator(**kw):
    at.AcceleratorState._reset_state(reset_partial_state=True)
    at.GradientState._reset_state()
    return at.Accelerator(**kw)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# satellite: exposition robustness


class TestPrometheusExposition:
    def test_fmt_survives_non_finite(self):
        # int(v) raises OverflowError on inf and ValueError on nan — the old
        # formatter crashed the whole scrape on one poisoned gauge.
        assert _fmt(math.inf) == "+Inf"
        assert _fmt(-math.inf) == "-Inf"
        assert _fmt(math.nan) == "NaN"
        assert _fmt(3.0) == "3"
        assert _fmt(2.5) == "2.5"

    def test_scrape_survives_non_finite_gauge(self):
        reg = MetricsRegistry(namespace="atpu")
        reg.gauge("poisoned").set(float("-inf"))
        reg.gauge("nan_gauge").set(float("nan"))
        text = reg.prometheus_text()
        assert "atpu_poisoned -Inf" in text.splitlines()
        assert "atpu_nan_gauge NaN" in text.splitlines()

    def test_help_escaping(self):
        reg = MetricsRegistry(namespace="atpu")
        reg.counter("c", help="line one\nline two \\ backslash").inc()
        text = reg.prometheus_text()
        assert "# HELP atpu_c_total line one\\nline two \\\\ backslash" in text
        # the literal newline must NOT appear inside the HELP line
        for line in text.splitlines():
            if line.startswith("# HELP"):
                assert "line two" not in line or "\\n" in line

    def test_golden_round_trip(self):
        reg = MetricsRegistry(namespace="atpu")
        reg.counter("events", help="evt").inc(2)
        h = reg.histogram("lat_s", buckets=(0.5, 2.0))
        for v in (0.1, 1.0, 9.0):
            h.observe(v)
        lines = reg.prometheus_text().splitlines()
        assert "# TYPE atpu_events_total counter" in lines
        assert "atpu_events_total 2" in lines
        assert 'atpu_lat_s_bucket{le="0.5"} 1' in lines
        assert 'atpu_lat_s_bucket{le="2"} 2' in lines
        assert 'atpu_lat_s_bucket{le="+Inf"} 3' in lines
        assert "atpu_lat_s_count 3" in lines

    def test_empty_histogram_min_max_clamped(self):
        from accelerate_tpu.telemetry import Histogram

        h = Histogram("h", buckets=(1.0,))
        # internal extrema start at +/-inf; public accessors must clamp
        assert h.min == 0.0 and h.max == 0.0
        snap = h.snapshot()
        assert snap["min"] == 0.0 and snap["max"] == 0.0

    def test_empty_histogram_json_tracker_round_trip(self, tmp_path):
        # Infinity is not valid JSON — an empty histogram exported through
        # JSONTracker must still produce a strictly-parseable line.
        from accelerate_tpu.tracking import JSONTracker

        reg = MetricsRegistry()
        reg.histogram("train/step_time_s", buckets=(0.1, 1.0))  # never observed
        tracker = JSONTracker("run", logging_dir=str(tmp_path))
        reg.export_to_trackers([tracker], step=0)
        tracker.finish()
        line = (tmp_path / "run" / "metrics.jsonl").read_text().splitlines()[-1]

        def reject(const):  # parse_constant fires only on Infinity/NaN tokens
            raise AssertionError(f"non-JSON constant in export: {const}")

        record = json.loads(line, parse_constant=reject)
        assert record["train/step_time_s/count"] == 0


# ---------------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_ring_bound_and_drop_count(self):
        rec = FlightRecorder(capacity=4, clock=FakeClock(), registry=MetricsRegistry())
        for i in range(10):
            rec.record("e", i=i)
        assert len(rec) == 4
        assert rec.dropped == 6
        assert rec.events_total == 10
        assert [e["i"] for e in rec.tail()] == [6, 7, 8, 9]
        assert [e["i"] for e in rec.tail(2)] == [8, 9]

    def test_heartbeat_age(self):
        clock = FakeClock()
        rec = FlightRecorder(clock=clock, registry=MetricsRegistry())
        assert rec.heartbeat_age() is None  # before the first beat
        rec.heartbeat("train/step", step=0)
        clock.advance(3.5)
        assert rec.heartbeat_age() == pytest.approx(3.5)

    def test_dump_contains_stacks_ring_and_metrics(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(5)
        rec = FlightRecorder(clock=FakeClock(), registry=reg)
        rec.record("serve/submit", rid=1)
        rec.heartbeat("serve/step", step=3)
        dump = rec.dump("test")
        assert dump["reason"] == "test"
        assert [e["kind"] for e in dump["events"]] == ["serve/submit", "serve/step"]
        # every live thread's stack, including this one
        assert any("MainThread" in name for name in dump["stacks"])
        assert any("test_dump_contains" in f for frames in dump["stacks"].values() for f in frames)
        assert dump["metrics"]["c"] == 5
        json.dumps(dump)  # JSON-safe end to end

    def test_dump_json_safe_with_non_finite_fields(self):
        rec = FlightRecorder(clock=FakeClock(), registry=MetricsRegistry())
        rec.record("e", loss=float("inf"), arr=jnp.float32(2.0))
        text = json.dumps(rec.dump("x"))
        json.loads(text)  # no Infinity token leaked

    def test_disabled_recorder_is_noop(self):
        rec = FlightRecorder(clock=FakeClock(), registry=MetricsRegistry())
        set_enabled(False)
        try:
            rec.record("e")
            rec.heartbeat("h")
        finally:
            set_enabled(True)
        assert len(rec) == 0 and rec.events_total == 0
        assert rec.heartbeat_age() is None


class TestStallDetector:
    def _pair(self, timeout=10.0):
        clock = FakeClock()
        rec = FlightRecorder(clock=clock, registry=MetricsRegistry())
        det = StallDetector(rec, timeout_s=timeout, clock=clock)
        return clock, rec, det

    def test_no_false_positive_before_first_heartbeat(self):
        clock, rec, det = self._pair()
        clock.advance(1000.0)  # long first-step compile
        assert det.check() is False
        assert det.dumps == 0

    def test_no_false_positive_on_healthy_run(self):
        clock, rec, det = self._pair(timeout=10.0)
        for step in range(50):
            rec.heartbeat("train/step", step=step)
            clock.advance(1.0)
            assert det.check() is False
        assert det.dumps == 0

    def test_trips_once_then_rearms(self):
        clock, rec, det = self._pair(timeout=10.0)
        rec.heartbeat("train/step", step=0)
        clock.advance(11.0)
        assert det.check() is True  # stall
        assert det.check() is False  # same stall: no dump storm
        assert det.dumps == 1
        assert rec.registry.counter("flight/stalls_total").value == 1
        rec.heartbeat("train/step", step=1)  # progress resumes
        assert det.check() is False
        clock.advance(11.0)
        assert det.check() is True  # a NEW stall trips again
        assert det.dumps == 2

    def test_dump_has_stacks_and_ring_tail(self):
        clock, rec, det = self._pair(timeout=5.0)
        rec.record("serve/submit", rid=7)
        rec.heartbeat("serve/step", step=1)
        clock.advance(6.0)
        assert det.check() is True
        dump = det.last_dump
        assert "stall" in dump["reason"]
        assert [e["kind"] for e in dump["events"]] == ["serve/submit", "serve/step"]
        assert dump["stacks"]  # all-thread stacks present

    def test_artifact_written_to_flight_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ATPU_FLIGHT_DIR", str(tmp_path))
        clock, rec, det = self._pair(timeout=5.0)
        rec.heartbeat("train/step", step=0)
        clock.advance(6.0)
        assert det.check() is True
        files = list(tmp_path.glob("flight-*.json"))
        assert len(files) == 1
        artifact = json.loads(files[0].read_text())
        assert "stall" in artifact["reason"]
        assert artifact["events"][-1]["kind"] == "train/step"

    def test_disabled_detector_is_noop(self):
        clock, rec, det = self._pair(timeout=5.0)
        rec.heartbeat("train/step")
        clock.advance(100.0)
        set_enabled(False)
        try:
            assert det.check() is False
        finally:
            set_enabled(True)
        assert det.dumps == 0


# ---------------------------------------------------------------------------
# cost table


class TestCostTable:
    def test_capture_and_analyze_jitted(self):
        import jax

        reg = MetricsRegistry()
        table = CostTable(reg)
        fn = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((16, 32), jnp.float32)
        b = jnp.ones((32, 8), jnp.float32)
        fn(a, b)
        table.capture("mm", fn, (a, b))
        assert table.captured("mm")
        entry = table.analyze("mm")
        assert entry["flops"] and entry["flops"] > 0
        assert entry["hbm_peak_bytes"] and entry["hbm_peak_bytes"] > 0
        # published as gauges on the private registry
        assert reg.gauge("cost/mm/flops").value == entry["flops"]  # noqa: metric-docs
        # analyze is idempotent / cached
        assert table.analyze("mm") is not None
        assert table.flops("mm") == entry["flops"]
        assert table.max_hbm_peak_bytes() >= entry["hbm_peak_bytes"]

    def test_graceful_none_for_python_dispatch(self):
        table = CostTable(MetricsRegistry())

        def plain(x):  # no .lower — e.g. the accum-split python wrapper
            return x + 1

        table.capture("plain", plain, (jnp.ones((2,)),))
        entry = table.analyze("plain")
        assert entry["flops"] is None
        assert entry["error"]  # records why, instead of raising

    def test_capture_disabled_is_noop(self):
        import jax

        table = CostTable(MetricsRegistry())
        set_enabled(False)
        try:
            table.capture("mm", jax.jit(lambda x: x), (jnp.ones((2,)),))
        finally:
            set_enabled(True)
        assert not table.captured("mm")

    def test_device_peaks_cpu_standin_and_unknown_accelerator_raises(self):
        peaks = detect_device_peaks()
        assert peaks.flops_per_s > 0 and peaks.hbm_bytes_per_s > 0
        assert peaks.source == "fallback"  # the CPU rig's labelled stand-in

        class Dev:
            def __init__(self, platform, kind):
                self.platform, self.device_kind = platform, kind

        v5e = detect_device_peaks(Dev("tpu", "TPU v5 lite"))
        assert v5e.source == "spec" and v5e.flops_per_s == 197e12
        with pytest.raises(ValueError, match="HARDWARE_PEAKS"):
            detect_device_peaks(Dev("tpu", "TPU v9 imaginary"))


# ---------------------------------------------------------------------------
# debug server


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode(), resp.headers
    except urllib.error.HTTPError as err:  # 4xx/5xx still carry a body
        return err.code, err.read().decode(), err.headers


class TestDebugServer:
    def test_metrics_healthz_flight_stacks(self):
        clock = FakeClock()
        reg = MetricsRegistry(namespace="atpu")
        reg.counter("serve/requests", help="reqs").inc(3)
        rec = FlightRecorder(clock=clock, registry=reg)
        rec.heartbeat("serve/step", step=1)
        server = DebugServer(
            0, host="127.0.0.1", registry=reg, recorder=rec, unhealthy_after_s=30.0
        )
        try:
            status, body, headers = _get(server.url + "/metrics")
            assert status == 200
            assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
            assert "atpu_serve_requests_total 3" in body

            status, body, _ = _get(server.url + "/healthz")
            assert status == 200 and json.loads(body)["healthy"] is True

            # heartbeats stop -> unhealthy
            clock.advance(31.0)
            status, body, _ = _get(server.url + "/healthz")
            assert status == 503
            payload = json.loads(body)
            assert payload["healthy"] is False
            assert payload["heartbeat_age_s"] == pytest.approx(31.0)

            status, body, _ = _get(server.url + "/debug/flight?n=5")
            assert status == 200
            assert json.loads(body)["events"][-1]["kind"] == "serve/step"

            status, body, _ = _get(server.url + "/debug/stacks")
            assert status == 200 and "-- thread" in body

            status, _, _ = _get(server.url + "/nope")
            assert status == 404
        finally:
            server.stop()

    def test_collector_runs_before_scrape(self):
        reg = MetricsRegistry(namespace="atpu")
        server = DebugServer(0, host="127.0.0.1", registry=reg,
                             recorder=FlightRecorder(registry=reg))
        try:
            server.add_collector(lambda: reg.gauge("fresh").set(42))
            _, body, _ = _get(server.url + "/metrics")
            assert "atpu_fresh 42" in body
        finally:
            server.stop()

    def test_singleton_join_and_disable(self):
        stop_debug_server()
        try:
            reg = MetricsRegistry()
            first = start_debug_server(0, host="127.0.0.1", registry=reg)
            assert first is not None
            # a second surface asking for a port joins the running server
            assert start_debug_server(0, host="127.0.0.1") is first
        finally:
            stop_debug_server()
        set_enabled(False)
        try:
            assert start_debug_server(0, host="127.0.0.1") is None
        finally:
            set_enabled(True)

    def test_no_port_means_no_server(self, monkeypatch):
        monkeypatch.delenv("ATPU_METRICS_PORT", raising=False)
        stop_debug_server()
        assert start_debug_server(None) is None

    def test_env_port_resolution(self, monkeypatch):
        from accelerate_tpu.telemetry.server import resolve_metrics_port

        monkeypatch.setenv("ATPU_METRICS_PORT", "9105")
        assert resolve_metrics_port(None) == 9105
        assert resolve_metrics_port(0) == 0  # explicit wins, 0 included
        monkeypatch.setenv("ATPU_METRICS_PORT", "junk")
        assert resolve_metrics_port(None) is None


# ---------------------------------------------------------------------------
# end-to-end: train step MFU on CPU + a live scrape while training


def regression_loss(params, batch):
    pred = batch["x"] * params["a"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


class TestTrainIntegration:
    def _batch(self, n=8):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, 1)).astype(np.float32)
        return {"x": jnp.asarray(x), "y": jnp.asarray(2.0 * x + 3.0)}

    def test_step_mfu_finite_in_unit_interval(self):
        stop_debug_server()
        acc = fresh_accelerator(metrics_port=0)
        try:
            assert acc.debug_server is not None  # ephemeral port
            state = acc.create_train_state(
                params={"a": jnp.zeros((1,)), "b": jnp.zeros((1,))}, tx=optax.sgd(0.1)
            )
            step = acc.compile_train_step(regression_loss)
            batch = self._batch()
            state, _ = step(state, batch)        # captures the signature
            snap = acc.analyze_costs()           # lazy lower+compile+analyze
            assert snap["train_step/regression_loss"]["flops"] > 0
            state, _ = step(state, batch)        # first step with costs known
            mfu = acc.telemetry.gauge("train/step_mfu").value
            assert math.isfinite(mfu) and 0.0 < mfu <= 1.0
            assert acc.telemetry.gauge("train/model_flops").value > 0
            assert acc.telemetry.gauge("train/hbm_peak_bytes").value > 0

            # live scrape while the loop runs: /metrics must include the MFU
            # gauge (the collector re-runs analyze_costs, harmlessly cached)
            status, body, _ = _get(acc.debug_server.url + "/metrics")
            assert status == 200
            assert "atpu_train_step_mfu" in body
            # the train-step heartbeat keeps /healthz green
            status, body, _ = _get(acc.debug_server.url + "/healthz")
            assert status == 200
        finally:
            stop_debug_server()

    def test_flight_ring_sees_train_steps(self):
        stop_debug_server()
        acc = fresh_accelerator()
        state = acc.create_train_state(
            params={"a": jnp.zeros((1,)), "b": jnp.zeros((1,))}, tx=optax.sgd(0.1)
        )
        step = acc.compile_train_step(regression_loss)
        before = acc.flight_recorder.events_total
        state, _ = step(state, self._batch())
        kinds = [e["kind"] for e in acc.flight_recorder.tail()]
        assert acc.flight_recorder.events_total > before
        assert "train/step" in kinds

    def test_train_dispatch_span_lies_inside_train_step(self):
        """``train/step`` is the whole instrumented wrapper; ``train/dispatch``
        is the call into the compiled program alone (the watchdog's signature
        pass outside it), so the step's self time is the repo's own Python."""
        stop_debug_server()
        acc = fresh_accelerator()
        state = acc.create_train_state(
            params={"a": jnp.zeros((1,)), "b": jnp.zeros((1,))}, tx=optax.sgd(0.1)
        )
        step = acc.compile_train_step(regression_loss)
        last = max((e["id"] for e in acc.tracer.events), default=0)
        for _ in range(3):
            state, _ = step(state, self._batch())
        events = [e for e in acc.tracer.events if e["id"] > last]
        steps = [e for e in events if e["name"] == "train/step"]
        dispatches = [e for e in events if e["name"] == "train/dispatch"]
        assert len(steps) == len(dispatches) == 3
        for outer, inner in zip(steps, dispatches):
            assert inner["parent"] == outer["id"] and outer["parent"] is None
            assert outer["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        # an eval step's watchdog was given no span name and opens nothing:
        # its first call alone leaves a record, of the program's first-call time
        evaluate = acc.compile_eval_step(lambda params, batch, rng=None: params["a"] * batch["x"])
        for expected in (["compile/eval_step/<lambda>", "eval/step"], ["eval/step"]):
            last = max(e["id"] for e in acc.tracer.events)
            evaluate(state, self._batch())
            assert [e["name"] for e in acc.tracer.events if e["id"] > last] == expected

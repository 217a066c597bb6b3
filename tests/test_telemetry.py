"""Unified telemetry layer: metrics registry, span tracer, recompile watchdog.

Covers the ISSUE acceptance surface: histogram percentiles against numpy
quantiles (within bucket resolution), span nesting + Chrome-trace JSON
validity, the watchdog's budget warning on a forced shape-driven retrace,
Prometheus text exposition, the JSONTracker export round-trip, and the
``warning_once`` dedupe regression (lru_cache keyed on self / unhashable
kwargs).
"""

import json
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import telemetry
from accelerate_tpu.logging import MultiProcessAdapter, get_logger
from accelerate_tpu.telemetry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RecompileWatchdog,
    Tracer,
    exponential_buckets,
    get_registry,
    set_enabled,
    watch_recompiles,
)


class TestCountersAndGauges:
    def test_counter_inc_add(self):
        c = Counter("c")
        c.inc()
        c.add(2.5)
        assert c.value == 3.5
        c.reset()
        assert c.value == 0.0

    def test_gauge_defers_device_coercion(self):
        g = Gauge("g")
        g.set(jnp.float32(2.5))  # stored as-is; float() only at .value
        assert isinstance(g._value, jax.Array)
        assert g.value == 2.5

    def test_disable_switch_makes_observation_noop(self):
        c, g, h = Counter("c"), Gauge("g"), Histogram("h", buckets=(1.0,))
        set_enabled(False)
        try:
            c.inc()
            g.set(7)
            h.observe(0.5)
        finally:
            set_enabled(True)
        assert c.value == 0.0 and g.value == 0.0 and h.count == 0


class TestHistogram:
    def test_percentiles_within_bucket_resolution(self):
        # exhaustive-ish check: interpolated percentile must land within one
        # bucket of numpy's on a few distributions
        buckets = exponential_buckets(1e-4, 2.0, 24)
        rng = np.random.default_rng(0)
        for samples in (
            rng.lognormal(-5, 1.0, 4000),
            rng.uniform(1e-4, 0.5, 4000),
            rng.exponential(0.01, 4000),
        ):
            h = Histogram("h", buckets=buckets)
            for s in samples:
                h.observe(float(s))
            for q in (50, 90, 99):
                est = h.percentile(q)
                exact = float(np.quantile(samples, q / 100))
                # owning bucket's bounds bracket the true quantile: error is
                # bounded by one x2 bucket width
                idx = int(np.searchsorted(buckets, exact))
                lo = buckets[idx - 1] if idx > 0 else 0.0
                hi = buckets[idx] if idx < len(buckets) else float(samples.max())
                assert lo <= est <= hi * (1 + 1e-9), (q, est, exact, lo, hi)

    def test_min_max_clamp_and_snapshot(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["min"] == 0.5 and snap["max"] == 100.0
        assert h.percentile(0) == 0.5
        assert h.percentile(100) == 100.0
        assert 0.5 <= snap["p50"] <= 3.0

    def test_empty_snapshot(self):
        h = Histogram("h", buckets=(1.0,))
        assert h.snapshot() == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                                "max": 0.0, "p50": 0.0, "p90": 0.0, "p99": 0.0}

    def test_bulk_observe_equals_repeated_observe(self):
        """observe(v, n) is exactly n observe(v) calls in one update — the
        serving emit path's whole-window recording."""
        buckets = (0.5, 1.0, 2.0)
        bulk, loop = Histogram("b", buckets=buckets), Histogram("l", buckets=buckets)
        for v, n in ((0.3, 4), (1.5, 1), (9.0, 3)):
            bulk.observe(v, n)
            for _ in range(n):
                loop.observe(v)
        assert bulk.snapshot() == loop.snapshot()
        assert bulk.count == 8
        bulk.observe(0.1, 0)   # n < 1 records nothing
        bulk.observe(0.1, -2)
        assert bulk.count == 8


class TestRegistry:
    def test_get_or_create_idempotent_and_type_checked(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(TypeError):
            reg.gauge("a")

    def test_flat_snapshot_flattens_histograms(self):
        reg = MetricsRegistry()
        reg.counter("n").inc(3)
        reg.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
        flat = reg.flat_snapshot()
        assert flat["n"] == 3
        assert flat["lat/count"] == 1
        assert "lat/p99" in flat

    def test_prometheus_text_exposition(self):
        reg = MetricsRegistry(namespace="atpu")
        reg.counter("serve/tokens", help="tokens").inc(5)
        reg.gauge("queue_depth").set(2)
        h = reg.histogram("lat_s", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(3.0)
        text = reg.prometheus_text()
        lines = text.splitlines()
        assert "# TYPE atpu_serve_tokens_total counter" in lines
        assert "atpu_serve_tokens_total 5" in lines
        assert "atpu_queue_depth 2" in lines
        # cumulative le buckets + the implicit +Inf catching overflow
        assert 'atpu_lat_s_bucket{le="0.1"} 1' in lines
        assert 'atpu_lat_s_bucket{le="1"} 2' in lines
        assert 'atpu_lat_s_bucket{le="+Inf"} 3' in lines
        assert "atpu_lat_s_count 3" in lines
        assert text.endswith("\n")

    def test_json_tracker_round_trip(self, tmp_path):
        from accelerate_tpu.tracking import JSONTracker

        reg = MetricsRegistry()
        reg.counter("train/steps_total").inc(7)
        reg.gauge("train/loss").set(jnp.float32(1.25))  # deferred device value
        reg.histogram("train/step_time_s", buckets=(0.1, 1.0)).observe(0.2)
        tracker = JSONTracker("run", logging_dir=str(tmp_path))
        flat = reg.export_to_trackers([tracker], step=7)
        tracker.finish()
        lines = (tmp_path / "run" / "metrics.jsonl").read_text().splitlines()
        record = json.loads(lines[-1])
        assert record["_step"] == 7
        assert record["train/steps_total"] == 7
        assert record["train/loss"] == 1.25
        assert record["train/step_time_s/count"] == 1
        assert flat["train/loss"] == 1.25

    def test_reset_zeroes_but_keeps_handles(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc(4)
        reg.reset()
        assert c.value == 0.0
        assert reg.counter("c") is c


class TestTracer:
    def test_nesting_depth_and_chrome_trace_json(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            with tr.span("inner", bucket=8):
                pass
        events = tr.events
        assert [e["name"] for e in events] == ["inner", "outer"]  # close order
        inner, outer = events
        assert inner["args"]["depth"] == 1
        assert inner["args"]["bucket"] == 8
        assert inner["ph"] == outer["ph"] == "X"
        # inner is contained in outer on the timeline
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
        # round-trips as valid Chrome trace-event JSON
        doc = json.loads(json.dumps(tr.chrome_trace()))
        assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}
        assert doc["otherData"]["dropped_events"] == 0

    def test_aggregate_and_decorator(self):
        tr = Tracer(enabled=True)

        @tr.trace(name="work")
        def work(x):
            return x + 1

        assert work(1) == 2 and work(2) == 3
        agg = tr.aggregate()
        assert agg["work"]["count"] == 2
        assert agg["work"]["mean_s"] >= 0.0

    def test_event_cap_fifo(self):
        tr = Tracer(enabled=True, max_events=3)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert [e["name"] for e in tr.events] == ["s2", "s3", "s4"]
        assert tr.dropped_events == 2
        assert tr.aggregate()["s0"]["count"] == 1  # aggregate keeps counting

    def test_dump_writes_file(self, tmp_path):
        tr = Tracer(enabled=True)
        with tr.span("a"):
            pass
        path = tr.dump(str(tmp_path / "trace.json"))
        with open(path) as f:
            assert json.load(f)["traceEvents"][0]["name"] == "a"

    def test_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("a") as args:
            args["n"] = 1          # a caller's late count is taken and dropped
        tr.record("b", 0.0, 1.0, req=3)
        assert tr.events == [] and tr.aggregate() == {}

    def test_id_and_parent_link_nested_spans_per_thread(self):
        tr = Tracer(enabled=True)
        other = {}

        def elsewhere():
            with tr.span("other_outer"):
                with tr.span("other_inner"):
                    other["done"] = True

        with tr.span("outer"):
            with tr.span("first"):
                pass
            # a span open on another thread is no parent of this thread's
            worker = threading.Thread(target=elsewhere)
            worker.start()
            worker.join(10.0)
            with tr.span("second") as args:
                args["tokens"] = 5     # a count known only at the span's end
        assert other == {"done": True}
        by = {e["name"]: e for e in tr.events}
        assert len({e["id"] for e in tr.events}) == 5
        assert by["outer"]["parent"] is None and by["other_outer"]["parent"] is None
        assert by["first"]["parent"] == by["second"]["parent"] == by["outer"]["id"]
        assert by["other_inner"]["parent"] == by["other_outer"]["id"]
        assert by["second"]["args"] == {"tokens": 5, "depth": 1}
        # self time: a parent's duration less the events that name it parent
        children = sum(e["dur"] for e in tr.events if e["parent"] == by["outer"]["id"])
        assert 0.0 <= children <= by["outer"]["dur"]

    def test_record_takes_an_interval_stamped_elsewhere(self):
        tr = Tracer(enabled=True)
        t0 = time.perf_counter()
        with tr.span("open"):
            tr.record("http/stream_write", t0, t0 + 0.25, req=7)
        event = next(e for e in tr.events if e["name"] == "http/stream_write")
        assert event["dur"] == pytest.approx(0.25e6)
        assert event["args"] == {"req": 7}
        # not nested under whatever is open here: its start lies elsewhere
        assert event["parent"] is None
        assert tr.aggregate()["http/stream_write"]["count"] == 1

    def test_record_enters_no_trace_annotation_during_a_capture(self, monkeypatch):
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        tr = telemetry.get_tracer()
        telemetry.set_device_trace_active(True)
        try:
            with tr.span("test/mirrored"):
                pass
            now = time.perf_counter()
            tr.record("test/recorded", now - 0.001, now, req=1)
        finally:
            telemetry.set_device_trace_active(False)
        assert entered == ["test/mirrored"]
        names = [e["name"] for e in tr.capture()["events"]]
        assert "test/mirrored" in names and "test/recorded" in names

    def test_capture_returns_the_events_that_overlap_the_last_flips(self):
        tr = Tracer(enabled=True)
        with tr.span("before_any_capture"):
            pass
        assert tr.capture() is None            # nothing before a first flip
        tr.mark_capture(True)
        tr.mark_capture(False)                 # an earlier capture, superseded
        with tr.span("between"):
            pass
        with tr.span("straddles_start"):
            tr.mark_capture(True)
            with tr.span("inside"):
                pass
            during = tr.capture()              # still on: it ends now
        with tr.span("straddles_end"):
            tr.mark_capture(False)
        with tr.span("after"):
            pass
        taken = tr.capture()
        assert [e["name"] for e in during["events"]] == ["inside"]
        assert [e["name"] for e in taken["events"]] == ["inside", "straddles_start", "straddles_end"]
        assert taken["t0"] < taken["t1"]
        inside = taken["events"][0]
        assert taken["t0"] <= inside["ts"] and inside["ts"] + inside["dur"] <= taken["t1"]
        tr.reset()
        assert tr.capture() is None

    def test_device_trace_flips_stamp_the_default_tracer(self):
        tr = telemetry.get_tracer()
        telemetry.set_device_trace_active(True)
        with tr.span("test/in_capture"):
            pass
        telemetry.set_device_trace_active(False)
        with tr.span("test/after_capture"):
            pass
        names = [e["name"] for e in tr.capture()["events"]]
        assert "test/in_capture" in names and "test/after_capture" not in names


class TestRecompileWatchdog:
    def test_budget_warning_on_shape_driven_retrace(self, caplog):
        reg = MetricsRegistry()
        fn = jax.jit(lambda x: x * 2)
        wd = RecompileWatchdog(fn, name="step", budget=1, registry=reg)
        logger_name = "accelerate_tpu.telemetry.watchdog"
        with caplog.at_level(logging.WARNING, logger=logger_name):
            wd(jnp.ones((2, 4)))
            wd(jnp.ones((2, 4)))  # same signature: no new compile
            assert not any(r.levelno == logging.WARNING for r in caplog.records)
            wd(jnp.ones((2, 5)))  # forced retrace: second shape
        warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
        assert len(warnings) == 1
        msg = warnings[0].getMessage()
        assert "step" in msg and "budget" in msg and "(2, 5)" in msg
        assert wd.compile_count == 2
        assert reg.get("compile/step/count").value == 2
        assert reg.get("compile/step/first_call_s").value > 0
        # warning fires once, not per call
        with caplog.at_level(logging.WARNING, logger=logger_name):
            before = len(warnings)
            wd(jnp.ones((2, 6)))
        assert sum(r.levelno == logging.WARNING for r in caplog.records) == before

    def test_static_value_change_counts_as_signature(self):
        wd = RecompileWatchdog(lambda x, flag: x, name="f", registry=MetricsRegistry())
        wd(np.ones(3), flag=True)
        wd(np.ones(3), flag=False)
        assert wd.compile_count == 2

    def test_attribute_forwarding_preserves_jit_internals(self):
        fn = jax.jit(lambda x: x + 1)
        wd = RecompileWatchdog(fn, name="g", registry=MetricsRegistry())
        wd(jnp.zeros(2))
        # the serving pool's jit_cache_sizes path reads _cache_size through
        # the wrapper
        assert int(wd._cache_size()) == 1

    def test_decorator_form_and_report(self):
        reg = MetricsRegistry()

        @watch_recompiles(budget=4, registry=reg)
        def f(x):
            return x

        f(np.ones(2))
        rep = f.report()
        assert rep["count"] == 1 and rep["budget"] == 4 and not rep["over_budget"]


class TestWarningOnceRegression:
    def setup_method(self):
        MultiProcessAdapter._warned_once.clear()

    def test_unhashable_kwargs_do_not_raise(self, caplog):
        logger = get_logger("atpu.test.warnonce.a")
        with caplog.at_level(logging.WARNING, logger="atpu.test.warnonce.a"):
            # lru_cache version raised TypeError: unhashable type 'dict'
            logger.warning_once("msg %s", "x", extra={"unhashable": {}})
        assert sum(r.levelno == logging.WARNING for r in caplog.records) == 1

    def test_dedupes_across_adapter_instances(self, caplog):
        # lru_cache keyed on self: a fresh adapter per get_logger call
        # re-warned every time
        with caplog.at_level(logging.WARNING, logger="atpu.test.warnonce.b"):
            get_logger("atpu.test.warnonce.b").warning_once("dup message")
            get_logger("atpu.test.warnonce.b").warning_once("dup message")
        assert sum(r.levelno == logging.WARNING for r in caplog.records) == 1

    def test_distinct_messages_and_loggers_still_warn(self, caplog):
        with caplog.at_level(logging.WARNING):
            get_logger("atpu.test.warnonce.c").warning_once("m1")
            get_logger("atpu.test.warnonce.c").warning_once("m2")
            get_logger("atpu.test.warnonce.d").warning_once("m1")
        assert sum(r.levelno == logging.WARNING for r in caplog.records) == 3


class TestDefaultRegistryWiring:
    def test_process_registry_is_shared(self):
        assert get_registry() is get_registry()

    def test_accelerator_exposes_registry_and_tracer(self):
        import accelerate_tpu as at

        at.AcceleratorState._reset_state(reset_partial_state=True)
        at.GradientState._reset_state()
        acc = at.Accelerator()
        assert acc.telemetry is get_registry()
        with acc.tracer.span("t"):
            pass
        assert acc.tracer.aggregate()["t"]["count"] >= 1

"""Fleet-health layer (ISSUE 19): time-series, tenants, SLO burn rates.

Contracts under test: the ring store is bounded and its windowed
rate/quantile/good-fraction math is exact over a fake clock; an SLO
fast-burns only when BOTH windows cross the threshold; the fast-burn
diagnostics hook is rate-limited to one bundle per SLO per cooldown and
the bundle freezes the offending window; tenant attribution sums exactly
to the global counters across preemption+replay and across a failover
``export_inflight``/``adopt``; reading snapshots (``bucket_snapshot``,
store sampling, windowed queries) leaves the Prometheus exposition
byte-for-byte unchanged; and everything is inert under the
``ATPU_TELEMETRY=0`` kill switch (``set_enabled(False)`` is the
programmatic spelling the tests flip so the env stays untouched).

Tier-1 on purpose: the windowed math runs on fake clocks with hand-built
registries; the two engine tests reuse the tiny float32 single-replica
idiom of ``test_paging.py``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.api.server import _tenant_from_headers
from accelerate_tpu.telemetry import (
    MetricsRegistry,
    SloEngine,
    SloSpec,
    TimeSeriesStore,
    capture_bundle,
    get_slo_engine,
    install_slos,
    slo_tick,
    uninstall_slos,
)
from accelerate_tpu.telemetry import metrics as metrics_mod


class Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


# --------------------------------------------------------------- ring store

def test_ring_capacity_bounded_and_validated():
    clock = Clock()
    store = TimeSeriesStore(registry=MetricsRegistry(), capacity=4,
                            interval_s=0.0, clock=clock)
    for i in range(10):
        clock.t = float(i)
        store.sample()
    assert len(store) == 4
    assert [s["t"] for s in store.tail()] == [6.0, 7.0, 8.0, 9.0]
    assert [s["t"] for s in store.tail(2)] == [8.0, 9.0]
    with pytest.raises(ValueError, match="capacity"):
        TimeSeriesStore(registry=MetricsRegistry(), capacity=1)


def test_maybe_sample_gates_on_interval():
    clock = Clock()
    store = TimeSeriesStore(registry=MetricsRegistry(), capacity=8,
                            interval_s=5.0, clock=clock)
    assert store.maybe_sample() is True
    clock.t = 4.9
    assert store.maybe_sample() is False
    clock.t = 5.0
    assert store.maybe_sample() is True
    assert len(store) == 2


def test_windowed_rate_and_delta_hand_computed():
    reg = MetricsRegistry()
    c = reg.counter("serve/tok_total")
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=16, interval_s=0.0,
                            clock=clock)
    store.sample()                      # t=0,  c=0
    c.inc(100)
    clock.t = 10.0
    store.sample()                      # t=10, c=100
    c.inc(60)
    clock.t = 20.0
    store.sample()                      # t=20, c=160
    # tightest pair spanning 10s is (t=10, t=20)
    assert store.delta("serve/tok_total", 10.0) == 60
    assert store.rate("serve/tok_total", 10.0) == pytest.approx(6.0)
    # a window wider than the ring falls back to the oldest sample
    assert store.rate("serve/tok_total", 1000.0) == pytest.approx(8.0)
    assert store.span_s(1000.0) == pytest.approx(20.0)
    assert store.rate("serve/nope_total", 10.0) is None
    assert store.delta("serve/nope_total", 10.0) is None


def test_windowed_quantile_and_good_fraction():
    reg = MetricsRegistry()
    h = reg.histogram("serve/lat_s", buckets=(0.1, 1.0, 10.0))
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=16, interval_s=0.0,
                            clock=clock)
    h.observe(0.05)  # pre-window history must not leak into the window
    h.observe(50.0)
    store.sample()
    for _ in range(8):
        h.observe(0.05)
    for _ in range(2):
        h.observe(5.0)
    clock.t = 10.0
    store.sample()
    d = store.hist_delta("serve/lat_s", 10.0)
    assert d["count"] == 10 and sum(d["counts"]) == 10
    # 8/10 observations sit at or under the 0.1 bound
    assert store.good_fraction("serve/lat_s", 0.1, 10.0) == pytest.approx(0.8)
    # the median interpolates inside the owning (0, 0.1] bucket
    q50 = store.quantile("serve/lat_s", 50.0, 10.0)
    assert 0.0 < q50 <= 0.1
    q95 = store.quantile("serve/lat_s", 95.0, 10.0)
    assert 1.0 < q95 <= 10.0
    # +Inf-bucket observations are never good
    h.observe(100.0)
    clock.t = 11.0
    store.sample()
    gf = store.good_fraction("serve/lat_s", 1e6, 2.0)
    assert gf == pytest.approx(10.0 / 11.0)


def test_family_rollup_windowed_rates():
    reg = MetricsRegistry()
    a = reg.counter("serve/tok_tenant_acme_total")
    b = reg.counter("serve/tok_tenant_umbrella_total")
    reg.counter("serve/tok_total")  # prefix-adjacent, must not match
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=0.0,
                            clock=clock)
    store.sample()
    a.inc(30)
    b.inc(10)
    clock.t = 10.0
    store.sample()
    fam = store.family("serve/tok_tenant_", 10.0, suffix="_total")
    assert fam == {"acme": pytest.approx(3.0), "umbrella": pytest.approx(1.0)}
    assert store.family("serve/absent_", 10.0) == {}


# ------------------------------------------------------------- burn verdicts

def _burning_setup():
    """96 good observations over [0, 50], then bad ones near t=100: the
    fast (10s) window burns long before the slow (100s) window does."""
    reg = MetricsRegistry()
    h = reg.histogram("serve/lat_s", buckets=(0.1, 1.0))
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=32, interval_s=0.0,
                            clock=clock)
    spec = SloSpec(name="lat", kind="latency", objective=0.99,
                   hist="serve/lat_s", threshold_s=0.1)
    eng = SloEngine(store, specs=[spec], fast_window_s=10.0,
                    slow_window_s=100.0, burn_threshold=14.4,
                    cooldown_s=1e9, registry=reg, clock=clock)
    store.sample()
    for _ in range(96):
        h.observe(0.05)
    clock.t = 50.0
    store.sample()
    return reg, h, clock, store, eng


def test_fast_burn_requires_both_windows():
    reg, h, clock, store, eng = _burning_setup()
    # 10 bad observations: the fast window sees only them (burn 100) but
    # the slow window still holds 96 good ones (burn ~9.4 < 14.4)
    for _ in range(10):
        h.observe(5.0)
    clock.t = 100.0
    store.sample()
    v = eng.evaluate()["lat"]
    assert v["fast_burn"] == pytest.approx(100.0)
    assert v["slow_burn"] < 14.4
    assert v["fast_burning"] is False
    # 90 more bad: now both windows cross the threshold
    for _ in range(90):
        h.observe(5.0)
    clock.t = 105.0
    store.sample()
    v = eng.evaluate()["lat"]
    assert v["fast_burn"] == pytest.approx(100.0)
    assert v["slow_burn"] >= 14.4
    assert v["fast_burning"] is True
    # a window with no data never alerts
    empty = SloEngine(
        TimeSeriesStore(registry=MetricsRegistry(), clock=Clock()),
        specs=[SloSpec(name="lat", kind="latency", objective=0.99,
                       hist="serve/lat_s", threshold_s=0.1)],
        clock=Clock())
    assert empty.evaluate()["lat"]["fast_burn"] is None
    assert empty.evaluate()["lat"]["fast_burning"] is False


def test_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        SloSpec(name="x", kind="vibes")
    with pytest.raises(ValueError, match="objective"):
        SloSpec(name="x", kind="latency", objective=1.0,
                hist="h", threshold_s=1.0)
    with pytest.raises(ValueError, match="hist"):
        SloSpec(name="x", kind="latency")
    with pytest.raises(ValueError, match="total"):
        SloSpec(name="x", kind="availability")
    with pytest.raises(ValueError, match="floor"):
        SloSpec(name="x", kind="throughput")


def test_bundle_cooldown_rate_limits_capture():
    reg, h, clock, store, eng = _burning_setup()
    for _ in range(100):
        h.observe(5.0)
    clock.t = 100.0
    captured = []
    eng.on_fast_burn = lambda name, detail: (
        captured.append((name, detail["fast_burn"])) or f"p{len(captured)}")
    eng.cooldown_s = 50.0
    store.interval_s = 1.0
    assert eng.tick()["lat"]["fast_burning"] is True
    assert captured == [("lat", pytest.approx(100.0))]
    assert eng.bundles == ["p1"]
    # still burning inside the cooldown: ticks sample but capture nothing
    for dt in (2.0, 4.0, 6.0):
        clock.t = 100.0 + dt
        h.observe(5.0)
        assert eng.tick()["lat"]["fast_burning"] is True
    assert len(captured) == 1
    # past the cooldown (and still burning) the next tick captures again
    clock.t = 151.0
    h.observe(5.0)
    assert eng.tick()["lat"]["fast_burning"] is True
    assert len(captured) == 2
    assert eng.bundles == ["p1", "p2"]
    # a hook that raises must not take down the serving loop
    eng._last_bundle.clear()
    eng.on_fast_burn = lambda name, detail: 1 / 0
    clock.t = 153.0
    eng.tick()
    assert eng.bundles == ["p1", "p2"]


def test_capture_bundle_freezes_the_window(tmp_path):
    reg = MetricsRegistry()
    h = reg.histogram("serve/lat_s", buckets=(0.1, 1.0))
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=0.0,
                            clock=clock)
    store.sample()
    h.observe(5.0)
    clock.t = 1.0
    store.sample()
    path = capture_bundle("test-burn", store=store,
                          slo_detail={"slo": "lat", "fast_burn": 42.0},
                          registry=reg, directory=str(tmp_path))
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("slo-")
    with open(path) as fh:
        bundle = json.load(fh)
    assert bundle["kind"] == "slo_bundle"
    assert bundle["reason"] == "test-burn"
    assert bundle["slo"] == {"slo": "lat", "fast_burn": 42.0}
    assert "stacks" in bundle and "events" in bundle
    series = bundle["timeseries"]
    assert len(series) == 2
    assert (series[-1]["hists"]["serve/lat_s"]["count"]
            - series[0]["hists"]["serve/lat_s"]["count"]) == 1
    # no directory configured anywhere -> no artifact, no crash
    env_before = os.environ.pop("ATPU_FLIGHT_DIR", None)
    try:
        assert capture_bundle("nowhere", store=store, registry=reg) is None
    finally:
        if env_before is not None:
            os.environ["ATPU_FLIGHT_DIR"] = env_before


# ----------------------------------------------------------- global wiring

def test_install_slo_tick_uninstall():
    reg = MetricsRegistry()
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=1.0,
                            clock=clock)
    try:
        eng = install_slos(
            specs=[SloSpec(name="lat", kind="latency", objective=0.99,
                           hist="serve/lat_s", threshold_s=0.1)],
            store=store, registry=reg, clock=clock)
        assert get_slo_engine() is eng
        slo_tick()
        assert len(store) == 1
        slo_tick()  # interval not elapsed: no second sample
        assert len(store) == 1
        clock.t = 1.5
        slo_tick()
        assert len(store) == 2
        # the fast-window burn gauge materializes on tick
        assert "serve/slo_burn_rate_lat" in reg.snapshot()
    finally:
        uninstall_slos()
    assert get_slo_engine() is None
    slo_tick()  # a no-op branch, not an error
    assert len(store) == 2


def test_telemetry_kill_switch_disables_fleet_health():
    reg = MetricsRegistry()
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=0.0,
                            clock=clock)
    spec = SloSpec(name="lat", kind="latency", objective=0.99,
                   hist="serve/lat_s", threshold_s=0.1)
    eng = SloEngine(store, specs=[spec], registry=reg, clock=clock,
                    on_fast_burn=lambda *a: pytest.fail("captured while off"))
    metrics_mod.set_enabled(False)
    try:
        assert store.maybe_sample() is False and len(store) == 0
        assert eng.tick() == {}
        assert eng.any_fast_burning() is False
        assert capture_bundle("off", store=store, registry=reg,
                              directory="/nonexistent") is None
    finally:
        metrics_mod.set_enabled(True)
    assert store.maybe_sample() is True  # back on without re-creation


def test_debug_slo_route_and_opt_in_healthz():
    from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
    from accelerate_tpu.telemetry.server import TelemetryEndpoints

    reg = MetricsRegistry()
    # a recorder of the test's own: the process-wide one may carry a heartbeat
    # that an earlier test of this worker left and that is stale by now
    recorder = FlightRecorder(registry=reg)
    # uninstalled: the route answers, disabled; /healthz ignores SLOs
    uninstall_slos()
    eps = TelemetryEndpoints(registry=reg, recorder=recorder, slo_healthz=True)
    status, ctype, body = eps.handle("/debug/slo")
    assert status == 200 and ctype == "application/json"
    assert json.loads(body) == {"enabled": False, "slos": {}}
    healthy, hbody = eps.health()
    assert healthy and hbody["slo_fast_burning"] is False
    # install a burning SLO: the route reports it and /healthz flips 503,
    # but only for endpoints that opted in
    h = reg.histogram("serve/lat_s", buckets=(0.1, 1.0))
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=0.0,
                            clock=clock)
    try:
        install_slos(
            specs=[SloSpec(name="lat", kind="latency", objective=0.99,
                           hist="serve/lat_s", threshold_s=0.1)],
            store=store, registry=reg, clock=clock,
            fast_window_s=10.0, slow_window_s=10.0,
            on_fast_burn=lambda *a: None)
        store.sample()
        for _ in range(5):
            h.observe(5.0)
        clock.t = 5.0
        store.sample()
        status, _, body = eps.handle("/debug/slo")
        payload = json.loads(body)
        assert status == 200 and payload["enabled"] is True
        assert payload["slos"]["lat"]["fast_burning"] is True
        healthy, hbody = eps.health()
        assert healthy is False and hbody["slo_fast_burning"] is True
        default_eps = TelemetryEndpoints(registry=reg, recorder=recorder)  # opt-in is off
        healthy, hbody = default_eps.health()
        assert healthy is True and "slo_fast_burning" not in hbody
    finally:
        uninstall_slos()


# ------------------------------------------------- prometheus no-regression

def test_prometheus_exposition_unchanged_by_windowed_reads():
    reg = MetricsRegistry()
    c = reg.counter("serve/tok_total", help="tokens")
    g = reg.gauge("serve/depth", help="queue depth")
    h = reg.histogram("serve/lat_s", buckets=(0.1, 1.0), help="latency")
    c.inc(42)
    g.set(7)
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    before = reg.prometheus_text()
    clock = Clock()
    store = TimeSeriesStore(registry=reg, capacity=8, interval_s=0.0,
                            clock=clock)
    store.sample()
    h.bucket_snapshot()
    clock.t = 10.0
    store.sample()
    store.rate("serve/tok_total", 10.0)
    store.quantile("serve/lat_s", 99.0, 10.0)
    store.good_fraction("serve/lat_s", 0.1, 10.0)
    store.family("serve/tok_", 10.0, suffix="_total")
    store.tail()
    assert reg.prometheus_text() == before  # byte-for-byte


# ------------------------------------------------------- tenant attribution

def test_tenant_from_headers_resolution():
    assert _tenant_from_headers({"X-Tenant": "Acme_1"}) == "acme_1"
    assert _tenant_from_headers({"X-Tenant": " acme "}) == "acme"
    # the header wins over the API-key prefix
    assert _tenant_from_headers({"X-Tenant": "acme",
                                 "Authorization": "Bearer umbrella-k"}) == "acme"
    assert _tenant_from_headers({"Authorization": "Bearer Umbrella-s3cr3t"}) \
        == "umbrella"
    # malformed labels resolve to None (unattributed), never raise: the
    # tenant becomes a metric-name segment, so the charset is strict
    assert _tenant_from_headers({}) is None
    assert _tenant_from_headers({"X-Tenant": "a b"}) is None
    assert _tenant_from_headers({"X-Tenant": "a/b"}) is None
    assert _tenant_from_headers({"X-Tenant": "x" * 65}) is None
    assert _tenant_from_headers({"Authorization": "Bearer "}) is None
    assert _tenant_from_headers({"Authorization": "Basic acme-k"}) is None


def _tiny_model(seed=0):
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64
    )
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2, prefix_cache_mb=0)
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


def _tenant_sums_match(engine, registry, keys):
    """Every per-tenant family must sum EXACTLY to its global counter, and
    the numeric rollup must mirror the registry."""
    snap = registry.snapshot()
    rollup = engine.stats()["tenants"]
    for key in keys:
        fam_sum = 0
        for tenant, stats in rollup.items():
            fam = snap.get(f"serve/{key}_tenant_{tenant}_total", 0)
            assert fam == stats.get(key, 0), (key, tenant, fam, stats)
            fam_sum += fam
        assert fam_sum == snap[f"serve/{key}_total"], (key, fam_sum, snap)


def test_tenant_rollup_exact_across_preemption():
    model, params = _tiny_model()
    registry = MetricsRegistry()
    # Pmax=16 + null page: the pool is one lane's worth, forcing preemption
    eng = _engine(model, params, page_size=4, num_pages=17,
                  max_queue=8, registry=registry)
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, model.config.vocab_size, (n,)).astype(np.int32)
               for n in (12, 16, 9, 14)]
    gen = GenerationConfig(max_new_tokens=28, do_sample=False,
                           eos_token_id=None)
    tenants = ("acme", "umbrella", "acme", None)  # mixed + unattributed
    reqs = [eng.submit(p, config=gen, tenant=t)
            for p, t in zip(prompts, tenants)]
    eng.run()
    assert eng.stats["preemptions"] >= 1
    assert all(q.tenant == t for q, t in zip(reqs, tenants))
    rollup = eng.stats()["tenants"]
    assert set(rollup) == {"acme", "umbrella"}
    assert rollup["acme"]["requests_submitted"] == 2
    assert rollup["umbrella"]["requests_submitted"] == 1
    # a preempted-and-replayed lane keeps generating for its tenant: token
    # counts stay exact through the preemption ladder
    assert rollup["acme"]["tokens_generated"] == 2 * 28
    assert rollup["umbrella"]["tokens_generated"] == 28
    # any preemptions attributed to a tenant are a subset of the global count
    snap = registry.snapshot()
    assert (sum(v.get("preemptions", 0) for v in rollup.values())
            <= eng.stats["preemptions"])
    # the families sum to the globals once the untenanted request is
    # accounted: 3 of 4 requests carry a label
    for key, labelled in (("requests_submitted", 3), ("requests_completed", 3),
                          ("tokens_generated", 3 * 28)):
        fam_sum = sum(snap.get(f"serve/{key}_tenant_{t}_total", 0)
                      for t in ("acme", "umbrella"))
        assert fam_sum == labelled
        assert snap[f"serve/{key}_total"] >= labelled
    # every rollup cell mirrors its registry family counter exactly
    family_cells = {
        (t, k): snap.get(f"serve/{k}_tenant_{t}_total", 0)
        for t, v in rollup.items() for k in v
    }
    for (t, k), fam in family_cells.items():
        assert fam == rollup[t][k], (t, k, fam, rollup[t][k])
    # per-tenant TTFT histograms observed one TTFT per labelled request
    assert snap["serve/ttft_s_tenant_acme"]["count"] == 2
    assert snap["serve/ttft_s_tenant_umbrella"]["count"] == 1


def test_tenant_survives_export_adopt():
    model, params = _tiny_model()
    registry = MetricsRegistry()
    e1 = _engine(model, params, page_size=4, num_pages=33,
                 max_queue=8, registry=registry)
    e2 = _engine(model, params, page_size=4, num_pages=33,
                 max_queue=8, registry=registry)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, model.config.vocab_size, (8,)).astype(np.int32)
    gen = GenerationConfig(max_new_tokens=6, do_sample=False,
                           eos_token_id=None)
    expected = [int(t) for t in e2.serve([prompt.copy()], gen)[0].tokens]
    req = e1.submit(prompt.copy(), config=gen, tenant="acme")
    exported = e1.export_inflight()
    assert [q.tenant for q in exported] == ["acme"]
    adopted = e2.adopt(exported[0])
    assert adopted.tenant == "acme"  # the SAME label rides the failover
    e2.run()
    assert [int(t) for t in adopted.tokens] == expected
    del req
    # the adopting replica attributes the replay to the tenant, and the
    # family counters mirror the rollup exactly
    rollup = e2.stats()["tenants"]
    assert rollup["acme"]["requests_replayed"] == 1
    assert rollup["acme"]["requests_completed"] >= 1
    snap = registry.snapshot()
    assert snap["serve/requests_replayed_tenant_acme_total"] == 1
    _tenant_sums_match(e2, registry, ["requests_replayed"])

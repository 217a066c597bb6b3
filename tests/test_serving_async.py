"""Depth-1 pipelined serve loop: identity, lag semantics, telemetry.

``ServingEngine(async_depth=1)`` dispatches decode window N+1 before
materializing window N's tokens, overlapping host scheduling with device
compute.  The contract under test is that the pipeline is *invisible* in the
outputs — token-for-token identical to the synchronous loop (``async_depth=0``)
across every sampling and pool mode — while the lag semantics it introduces
(EOS and cancel take effect one masked window late, retired paged lanes park
their pages on the in-flight handle until it drains) stay internally
consistent: no leaked pages, no tokens emitted for retired lanes, no extra
compiled executables, and the stall-detector heartbeat still lands every step.

float32 like ``test_serving.py``: token-exactness needs full-precision argmax
margins, not bf16 ties.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig, generate
from accelerate_tpu.models.transformer import Transformer, TransformerConfig
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import MetricsRegistry, get_flight_recorder, get_tracer


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


def _expected(model, params, prompt, gen):
    seqs, _ = generate(model, params, jnp.asarray(prompt, jnp.int32)[None], gen)
    out = np.asarray(seqs[0])[len(prompt):]
    if gen.eos_token_id is not None:
        hits = np.nonzero(out == gen.eos_token_id)[0]
        if hits.size:
            out = out[: hits[0] + 1]
    return out.tolist()


class TestAsyncKnob:
    def test_depth_validated(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError, match="async_depth"):
            _engine(model, params, async_depth=2)

    def test_default_is_pipelined_and_drains_on_exit(self):
        model, params = _tiny_model()
        eng = _engine(model, params)
        assert eng.async_depth == 1
        prompts = _prompts(0, (8, 5), model.config.vocab_size)
        eng.serve(prompts, GenerationConfig(max_new_tokens=6, do_sample=False))
        # run() must not exit with a window still in flight
        assert eng._inflight is None
        assert not eng.has_work


class TestTokenIdentity:
    """async_depth=1 must reproduce async_depth=0 token for token, bitwise."""

    def _serve(self, model, params, gens, async_depth, lengths=(8, 12, 5), **kw):
        eng = _engine(model, params, async_depth=async_depth, **kw)
        prompts = _prompts(1, lengths, model.config.vocab_size)
        reqs = eng.serve(prompts, gens)
        return [list(r.tokens) for r in reqs], eng

    def _pair(self, model, params, gens, **kw):
        t1, e1 = self._serve(model, params, gens, 1, **kw)
        t0, e0 = self._serve(model, params, gens, 0, **kw)
        assert t1 == t0
        # the pipeline re-orders host work; it must never add device programs
        assert e1.compiled_executable_counts() == e0.compiled_executable_counts()
        return t1

    # the window gathers a view of each lane's pages, or reads them in place
    ARMS = pytest.mark.parametrize("arm", [
        {}, dict(decode_kernel="pallas"),
    ], ids=["gathered", "direct"])

    @ARMS
    def test_greedy(self, arm):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        self._pair(model, params, gen, **arm)

    @ARMS
    def test_sampled(self, arm):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=True,
                               temperature=0.8, top_k=8, top_p=0.95)
        self._pair(model, params, gen, rng_seed=7, **arm)

    def test_speculative(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        self._pair(model, params, gen, speculate_k=2)

    def test_int8_kv(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        self._pair(model, params, gen, kv_dtype="int8")

    def test_tp2(self):
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=12, do_sample=False)
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        self._pair(model, params, gen, mesh=mesh, num_slots=4)

    def test_eos_lag_is_invisible(self):
        """A lane hitting EOS (or max_new_tokens) mid-pipeline runs one extra
        masked window; the trailing tokens must be dropped, not emitted."""
        model, params = _tiny_model()
        gen = GenerationConfig(max_new_tokens=9, do_sample=False, eos_token_id=3)
        toks = self._pair(model, params, gen, lengths=(8, 12, 5, 7))
        for prompt, got in zip(
            _prompts(1, (8, 12, 5, 7), model.config.vocab_size), toks
        ):
            assert got == _expected(model, params, prompt, gen)


class TestCancelMidFlight:
    def test_cancel_running_mid_flight(self):
        """Cancel with a window in flight: the lane's pages are deferred on
        the in-flight handle (not freed NOW — the device is still writing
        them), then returned when it drains; no token of the cancelled
        request leaks and the surviving lane never notices."""
        model, params = _tiny_model()
        p1, p2 = _prompts(15, (12, 16), model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=16, do_sample=False, eos_token_id=None)
        expect2 = _expected(model, params, p2, gen)
        eng = _engine(model, params, prefix_cache_mb=None)
        r1 = eng.submit(p1, config=gen)
        r2 = eng.submit(p2, config=gen)
        while r1.state.value != "running":
            eng.step()
        assert eng._inflight is not None and eng._inflight.lane_live(0)
        free_before = eng.kv.allocator.free_count
        n_before = len(r1.tokens)
        assert eng.cancel(r1)
        assert r1.state.value == "cancelled"
        # pages deferred, not freed: the in-flight window still writes them
        assert eng.kv.allocator.free_count == free_before
        assert eng._inflight.deferred_pages
        eng.step()  # drains the in-flight window -> deferred pages return
        assert eng.kv.allocator.free_count > free_before
        assert len(r1.tokens) == n_before  # in-flight tokens dropped at drain
        eng.run()
        assert r2.tokens == expect2
        assert eng.stats["cancelled"] == 1
        assert eng.kv.allocator.used_count == 0

    def test_slot_reuse_after_lazy_free(self):
        """A lazily-freed slot is immediately readmissible: the next request
        installs over it while the stale window retires, and both streams
        stay token-exact."""
        model, params = _tiny_model()
        prompts = _prompts(21, (8, 5, 12, 6, 9), model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        eng = _engine(model, params, num_slots=2)
        reqs = eng.serve(prompts, gen)
        for prompt, req in zip(prompts, reqs):
            assert req.tokens == _expected(model, params, prompt, gen)


class TestPreemptionMidFlight:
    def test_preemption_token_exact_under_pipeline(self):
        """Page pressure with a window in flight: reclaim drains the pipeline
        to collect deferred pages before preempting, and replay stays
        token-exact against ``generate``."""
        model, params = _tiny_model()
        prompts = _prompts(14, (12, 16, 9, 14), model.config.vocab_size)
        gen = GenerationConfig(max_new_tokens=28, do_sample=False, eos_token_id=None)
        expect = [_expected(model, params, p, gen) for p in prompts]
        eng = _engine(model, params, prefix_cache_mb=None,
                      num_pages=17)  # Pmax = 16 + null: forces preemption
        reqs = eng.serve([p.copy() for p in prompts], gen)
        assert [r.tokens for r in reqs] == expect
        assert eng.stats["preemptions"] >= 1
        assert eng.kv.allocator.used_count == 0
        assert eng._inflight is None


class TestTelemetry:
    def test_overlap_gauges_and_readback_events(self):
        model, params = _tiny_model()
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg)
        prompts = _prompts(3, (8, 6), model.config.vocab_size)
        before = get_flight_recorder().events_total
        spans0 = get_tracer().aggregate()
        eng.serve(prompts, GenerationConfig(max_new_tokens=8, do_sample=False))
        assert reg.gauge("serve/host_overlap_ratio").value > 0.0
        # what the device waited for is read from spans, not from a gauge: the
        # blocking readbacks lie inside the steps that drained them
        spans = get_tracer().aggregate()
        grew = lambda name, key: spans[name][key] - spans0.get(name, {key: 0})[key]
        assert grew("serve/readback", "count") > 0
        assert grew("serve/step", "total_s") >= grew("serve/readback", "total_s")
        events = [e for e in get_flight_recorder().tail()
                  if e.get("kind") == "serve/readback"]
        assert events
        for e in events[-3:]:
            assert e["window"] in ("decode", "verify")
            assert e["wait_ms"] >= 0.0
            assert e["overlapped_ms"] >= 0.0
        assert get_flight_recorder().events_total > before

    def test_heartbeat_fires_every_step_no_false_stall(self):
        """The pipelined loop must keep the per-step progress heartbeat: a
        stall detector with a generous timeout never trips mid-serve."""
        from accelerate_tpu.telemetry import StallDetector

        model, params = _tiny_model()
        eng = _engine(model, params)
        recorder = get_flight_recorder()
        detector = StallDetector(recorder, timeout_s=120.0)
        prompts = _prompts(4, (8, 6, 10), model.config.vocab_size)
        for p in prompts:
            eng.submit(p, config=GenerationConfig(max_new_tokens=8, do_sample=False))
        steps = 0
        while eng.has_work:
            eng.step()
            steps += 1
            assert recorder.heartbeat_age() is not None
            assert not detector.check()
        assert steps == eng._step_count
        assert detector.dumps == 0
        beats = [e for e in recorder.tail() if e.get("kind") == "serve/step"]
        assert len(beats) >= steps

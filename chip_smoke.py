#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer and the server still start on the chip.

Run from the repo root on a machine with a TPU::

    python3 chip_smoke.py              # one chip: train, serve, kernels
    python3 chip_smoke.py --chips 4    # four chips: fsdp=4, four replicas, tp=4 — and nothing else

ONE process, which is the only one that touches JAX (a chip belongs to one
process at a time): the HTTP front door runs on threads inside it and is
spoken to with ``http.client``.  Every phase prints one JSON line; a phase
that fails ends the run non-zero, nothing is caught and carried past.  On any
platform other than ``tpu`` the script exits non-zero and prints no result —
there is no rehearsal mode.  The last line of standard output is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (one chip):

* **train** — the BERT-base-width decoder of ``bench.py`` (hidden 768, 12
  layers, 12 heads, vocab 30522, seq 128, batch 128) through
  ``Accelerator(mixed_precision="bf16")``, ``prepare(SimpleDataLoader)``,
  ``create_train_state`` and ``compile_train_step`` on one fixed seeded batch:
  loss finite and falling, exactly one compile, and a ``save_state`` /
  ``load_state`` round trip whose next-step loss matches.
* **serve** — ``serve.build_service`` with the ``gpt2-xl`` preset (hidden
  1600, 48 layers, 25 heads of 64, vocab 50257), context 1024, paged, weights
  from ``--seed``: completions over ``/v1/completions`` (one streamed), greedy
  tokens compared with ``generate`` on the same chip.
* **kernels** — the same requests through ``decode_kernel="pallas"`` /
  ``prefill_kernel="pallas"``, through int8 pages, and with speculation on;
  each arm against its XLA arm, each Pallas program checked for
  ``tpu_custom_call``.

Identity rule: greedy token identity is asserted as the tests assert it.
Where two arms differ the script prints the request, the first differing
position and the top-1 minus top-2 logit margin there, computed through the
attention program of each arm; the difference passes only where the two
arms' logits agree to the rounding of the compute dtype and both margins are
near-ties at that rounding (``_rounding_sigma``).  Any other difference fails
the phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import http.client
import json
import os
import shutil
import sys
import tempfile
import time

PROMPT_LEN = 650          # > 512: crosses both of the server's prefill buckets
NEW_TOKENS = 16
NUM_PROMPTS = 3
KERNEL_PAGE = 16          # the page size the paged kernels are compiled at
KERNEL_BUCKET = 128       # ... and their prefill chunk width
PROBE_WIDTH = 1024        # padded width of the identity rule's logit probe


# --------------------------------------------------------------- bookkeeping
class CompileClock:
    """Seconds JAX spent lowering and compiling, and persistent-cache hits,
    read from ``jax.monitoring`` — so a phase can report compile time and run
    time apart without guessing which call compiled."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    #: lowering and XLA compilation; tracing is left out because the trace
    #: events of nested jits overlap and would be counted twice
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def _on_duration(self, name, seconds, **_):
        if name in self.EVENTS:
            self.compile_s += seconds

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Times one phase and prints its JSON line on a clean exit."""

    def __init__(self, name, clock, cache_dir):
        self.name, self.clock, self.cache_dir = name, clock, cache_dir
        self.fields = {}

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._c0 = self.clock.compile_s
        self._h0 = self.clock.cache_hits
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        import jax

        wall = time.perf_counter() - self._t0
        compile_s = self.clock.compile_s - self._c0
        dev = jax.devices()[0]
        print(json.dumps({
            "phase": self.name,
            "compile_s": round(compile_s, 2),
            "run_s": round(wall - compile_s, 2),
            "compile_cache_hits": self.clock.cache_hits - self._h0,
            **self.fields,
            "jax": jax.__version__,
            "device_kind": dev.device_kind,
            "peak_bytes_in_use": max(_memory_stat("peak_bytes_in_use")),
            "compile_cache_dir": self.cache_dir,
        }), flush=True)
        return False


def _require(cond, message):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {message}")


def _free():
    gc.collect()


def _memory_stat(key):
    """One ``memory_stats()`` figure per device (the TPU runtime reports them)."""
    import jax

    return [d.memory_stats()[key] for d in jax.devices()]


def _nbytes(tree):
    import jax

    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)))


# ------------------------------------------------------------ identity rule
def _rounding_sigma(logits, compute_dtype, num_layers):
    """The standard deviation rounding gives ONE logit when the same network
    is computed by two programs: the unit roundoff of the compute dtype, grown
    as a random walk over the ``2 * num_layers`` residual additions each
    rounded to that dtype, at the scale of the logits (their RMS).  From the
    dtype and the depth alone.  Two arms' logits agree to rounding when their
    difference has an RMS within ``3 sigma``; a margin is a difference of two
    such logits, so a flip is a near-tie when both margins are within
    ``3 * sqrt(2) * sigma``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    u = float(jnp.finfo(compute_dtype).eps) / 2
    if jax.config.jax_default_matmul_precision in (None, "default", "bfloat16"):
        # the TPU multiplies bfloat16-rounded operands at the default matmul
        # precision whatever the array dtype (one MXU pass): a float32
        # model's matmuls round like bfloat16's unless more passes are asked for
        u = max(u, float(jnp.finfo(jnp.bfloat16).eps) / 2)
    return u * (2 * num_layers) ** 0.5 * float(np.sqrt(np.mean(np.square(logits))))


class LogitProbe:
    """Next-token logits after a token prefix, computed through the attention
    program an arm runs: one padded chunk over a single-lane paged cache with
    that arm's kernel and page dtype (the ``"xla"`` / native probe is, by the
    repo's own invariant, the slab program ``generate`` runs too)."""

    def __init__(self, model, params, page_size, width):
        self.model, self.params = model, params
        self.page_size, self.width = page_size, width
        self._fns = {}

    def __call__(self, prefix, paged_kernel, kv_dtype):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from accelerate_tpu.models.transformer import PagedKVCache, Transformer
        from accelerate_tpu.ops.paged_attention import kv_storage_dtype

        cfg = self.model.config
        key = (paged_kernel, kv_dtype)
        if key not in self._fns:
            arm = Transformer(dataclasses.replace(cfg, paged_kernel=paged_kernel))
            self._fns[key] = jax.jit(
                lambda params, ids, cache: arm.apply({"params": params}, ids, cache=cache)[0]
            )
        n_pages = self.width // self.page_size + 1          # + the null page
        shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, self.page_size,
                 cfg.resolved_head_dim)
        storage = kv_storage_dtype(kv_dtype, cfg.dtype)
        scales = jnp.ones((cfg.num_layers, n_pages, cfg.num_kv_heads), jnp.float32)
        cache = PagedKVCache(
            pages_k=jnp.zeros(shape, storage), pages_v=jnp.zeros(shape, storage),
            k_scales=scales, v_scales=scales,
            tables=jnp.arange(1, n_pages, dtype=jnp.int32)[None],
            index=jnp.zeros((1,), jnp.int32), active=jnp.ones((1,), bool),
            quant_err=jnp.float32(0.0),
        )
        ids = np.zeros((1, self.width), np.int32)
        ids[0, :len(prefix)] = prefix
        logits = self._fns[key](self.params, jnp.asarray(ids), cache)
        return np.asarray(logits[0, len(prefix) - 1], np.float32)


def check_identity(label, prompts, arm_a, arm_b, probe, compute_dtype):
    """``arm = (name, token lists, paged_kernel, kv_dtype)``.  Exact identity
    passes silently; each difference is printed and must satisfy the rule."""
    import jax.numpy as jnp
    import numpy as np

    name_a, toks_a, kernel_a, kv_a = arm_a
    name_b, toks_b, kernel_b, kv_b = arm_b
    _require(len(toks_a) == len(toks_b) == len(prompts), f"{label}: request count")
    differences = []
    for r, (ta, tb, prompt) in enumerate(zip(toks_a, toks_b, prompts)):
        ta, tb = list(map(int, ta)), list(map(int, tb))
        _require(len(ta) == len(tb), f"{label}: request {r} lengths {len(ta)} != {len(tb)}")
        if ta == tb:
            continue
        pos = next(i for i, (x, y) in enumerate(zip(ta, tb)) if x != y)
        prefix = np.concatenate([np.asarray(prompt, np.int32),
                                 np.asarray(ta[:pos], np.int32)])
        za, zb = probe(prefix, kernel_a, kv_a), probe(prefix, kernel_b, kv_b)
        margins = [float(np.diff(np.sort(z)[-2:])[0]) for z in (za, zb)]
        sigma = _rounding_sigma(za, compute_dtype, probe.model.config.num_layers)
        delta_rms = float(np.sqrt(np.mean(np.square(za - zb))))
        diff = {
            "identity_difference": label, "request": r, "position": pos,
            name_a: {"token": ta[pos], "top1_minus_top2": margins[0]},
            name_b: {"token": tb[pos], "top1_minus_top2": margins[1]},
            "logit_delta_rms": delta_rms,
            "logit_delta_max": float(np.max(np.abs(za - zb))),
            "rounding_sigma": sigma,
            "within_rounding": (delta_rms <= 3 * sigma
                                and max(margins) <= 3 * 2 ** 0.5 * sigma),
        }
        print(json.dumps(diff), flush=True)
        differences.append(diff)
    bad = [d for d in differences if not d["within_rounding"]]
    _require(not bad, f"{label}: {len(bad)} token difference(s) beyond the "
                      f"rounding of {jnp.dtype(compute_dtype).name}")
    return differences


def _prompts(seed, vocab_size):
    """Seeded prompts whose second half repeats the first: the n-gram drafter
    then has something to draft from, so the speculation arm's verify window
    runs whatever a random-weight model goes on to say."""
    import numpy as np

    rng = np.random.default_rng(seed)
    half = -(-PROMPT_LEN // 2)
    return [np.tile(rng.integers(1, vocab_size, (half,)), 2)[:PROMPT_LEN].astype(np.int32)
            for _ in range(NUM_PROMPTS)]


def _serve_arm(model, params, prompts, **kw):
    """One engine arm, driven in process: token lists plus the engine."""
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    eng = ServingEngine(
        model, params, num_slots=4, max_len=model.config.max_seq_len,
        registry=MetricsRegistry(), **kw,
    )
    gen = GenerationConfig(max_new_tokens=NEW_TOKENS, do_sample=False, eos_token_id=None)
    reqs = eng.serve([p.copy() for p in prompts], configs=gen)
    return [[int(t) for t in r.tokens] for r in reqs], eng


def _compiled_text(eng, name):
    """The compiled program text of one of the engine's executables, lowered
    from the signature its cost table captured at the first dispatch."""
    entry = eng.cost_table._entries[name]
    args, kwargs = entry["_avals"]
    return entry["_fn"].lower(*args, **kwargs).compile().as_text()


# -------------------------------------------------------------------- train
def _train_config():
    from accelerate_tpu.models.transformer import TransformerConfig

    # bench.py's BERT-base-width decoder: the one configuration with a chip history
    return TransformerConfig(
        vocab_size=30522, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=128,
    )


def _train_run(cfg, batch_size, seed, steps, *, mesh=None, fsdp_plugin=None,
               checkpoint=False):
    """A few steps on one fixed seeded batch through the Accelerator's normal
    entry points, with exactly one compile of the step; returns ``(losses,
    state, median warm step ms, accelerator)``."""
    import jax
    import numpy as np
    import optax

    import accelerate_tpu as at
    from accelerate_tpu.models.transformer import Transformer, lm_loss_fn
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.jax_compat import jit_cache_size

    GradientState._reset_state()
    AcceleratorState._reset_state(reset_partial_state=True)
    acc = at.Accelerator(mixed_precision="bf16", mesh=mesh, fsdp_plugin=fsdp_plugin)
    model = Transformer(cfg)
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, cfg.vocab_size, (batch_size, cfg.max_seq_len)).astype(np.int32)
    loader = acc.prepare(at.SimpleDataLoader(
        [{"input_ids": row} for row in rows], batch_size=batch_size,
    ))
    params = model.init(jax.random.PRNGKey(seed), rows[:1])["params"]
    state = acc.create_train_state(params=params, tx=optax.adamw(3e-4), seed=seed)
    step = acc.compile_train_step(lm_loss_fn(model), max_grad_norm=1.0)

    losses, times = [], []
    for _ in range(steps):                  # one batch per epoch: the same one
        for batch in loader:
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))   # waits for the step
            times.append(time.perf_counter() - t0)
    _require(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    _require(losses[-1] < losses[0], f"train: loss did not fall {losses}")
    _require(jit_cache_size(step._jitted) == 1,
             f"train: {jit_cache_size(step._jitted)} compiles of the step, want 1")

    if checkpoint:
        ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            acc.save_state(ckpt, state)
            state, metrics = step(state, batch)
            want = float(metrics["loss"])
            restored = acc.load_state(ckpt, state)
            _, metrics = step(restored, batch)
            got = float(metrics["loss"])
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        _require(abs(got - want) <= 1e-6 * max(1.0, abs(want)),
                 f"train: next-step loss after load_state {got} != {want}")
        losses.append(want)
    warm = sorted(times[1:])                # the first call compiles
    return losses, state, 1e3 * warm[len(warm) // 2], acc


def phase_train(phase, seed, cfg=None, batch_size=128, steps=6):
    cfg = cfg or _train_config()
    losses, state, step_ms, _ = _train_run(cfg, batch_size, seed, steps, checkpoint=True)
    phase.fields.update(
        model="bert-base-width decoder", params=_nbytes(state.params) // 4,
        batch=batch_size, seq=cfg.max_seq_len, steps=len(losses),
        loss_first=losses[0], loss_last=losses[-2], loss_after_restore=losses[-1],
        step_ms_median=round(step_ms, 2), step_compiles=1,
    )


# -------------------------------------------------------------------- serve
def _http_completion(server, prompt, stream=False, timeout=1100.0):
    conn = http.client.HTTPConnection(server.host, server.port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": [int(t) for t in prompt], "max_tokens": NEW_TOKENS,
            "temperature": 0, "stream": stream,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SystemExit(f"chip_smoke: FAILED — serve: HTTP {resp.status}: "
                             f"{resp.read()[:300]!r}")
        if not stream:
            return json.loads(resp.read())["choices"][0]["token_ids"]
        tokens = []
        for raw in iter(resp.readline, b""):
            line = raw.strip()
            if line == b"data: [DONE]":
                return tokens
            if line.startswith(b"data: "):
                tokens += json.loads(line[len(b"data: "):])["choices"][0]["token_ids"]
        raise SystemExit("chip_smoke: FAILED — serve: SSE stream ended without [DONE]")
    finally:
        conn.close()


def _serve_args(seed, preset, max_len, param_dtype, replicas=1):
    from accelerate_tpu import serve

    argv = ["--preset", preset, "--max-len", str(max_len), "--port", "0",
            "--seed", str(seed), "--replicas", str(replicas)]
    if param_dtype:
        argv += ["--param-dtype", param_dtype]
    return serve.parse_args(argv)


def _generate_reference(model, params, prompts):
    import numpy as np

    from accelerate_tpu.models.generation import GenerationConfig, generate

    gen = GenerationConfig(max_new_tokens=NEW_TOKENS, do_sample=False, eos_token_id=None)
    seqs, _ = generate(model, params, np.stack(prompts), gen)
    return [[int(t) for t in row[PROMPT_LEN:]] for row in np.asarray(seqs)]


def phase_serve(phase, seed, preset="gpt2-xl", max_len=1024, param_dtype="bfloat16"):
    """Returns ``(model, params, prompts, probe)`` for the kernels phase."""
    import jax

    from accelerate_tpu import serve

    args = _serve_args(seed, preset, max_len, param_dtype)
    router, frontdoor, server = serve.build_service(args)
    try:
        eng = router.engines[0]
        model, params = eng.model, eng.params
        prompts = _prompts(seed, model.config.vocab_size)
        t0 = time.perf_counter()
        served = [_http_completion(server, prompts[0])]           # cold: compiles
        first_s = time.perf_counter() - t0
        served.append(_http_completion(server, prompts[1], stream=True))
        served += [_http_completion(server, p) for p in prompts[2:]]
        repeat = _http_completion(server, prompts[0])             # warm, prefix-cached
        _require(repeat == served[0], "serve: the same request answered differently")
        _require(all(len(t) == NEW_TOKENS for t in served),
                 f"serve: wrong token counts {[len(t) for t in served]}")
        kv_bytes, kv_dtype = eng.kv_pool_bytes(), str(eng.kv.pages_k.dtype)
        page_size = eng.page_size
    finally:
        server.stop()
        frontdoor.stop()
    reference = _generate_reference(model, params, prompts)
    probe = LogitProbe(model, params, KERNEL_PAGE, PROBE_WIDTH)
    diffs = check_identity(
        "serve: /v1/completions vs generate", prompts,
        ("generate", reference, "xla", None), ("engine_xla", served, "xla", None),
        probe, model.config.dtype,
    )
    param_dtypes = sorted({str(x.dtype) for x in jax.tree_util.tree_leaves(params)})
    phase.fields.update(
        preset=preset, context=max_len, page_size=page_size,
        params=sum(x.size for x in jax.tree_util.tree_leaves(params)),
        param_bytes_on_device=_nbytes(params), param_dtype=param_dtypes,
        param_dtype_note=(
            "the preset's default float32 params (8.5 GB) leave too little of "
            "the 16 GB for the pool and the gather view: served in bfloat16"
            if param_dtype else "the preset's default"),
        kv_pool_bytes_on_device=kv_bytes, kv_dtype=kv_dtype,
        requests=len(served) + 1, streamed=1, prompt_tokens=PROMPT_LEN,
        new_tokens=NEW_TOKENS, first_request_s=round(first_s, 2),
        identity_differences=len(diffs),
    )
    del router, frontdoor, server, eng
    _free()
    return model, params, prompts, probe


# ------------------------------------------------------------------ kernels
def phase_kernels(phase, model, params, prompts, probe):
    arms, in_use_after, arm_seconds = {}, {}, {}

    def run(name, want_pallas, **kw):
        t0, c0 = time.perf_counter(), phase.clock.compile_s
        tokens, eng = _serve_arm(
            model, params, prompts, page_size=KERNEL_PAGE,
            prefill_buckets=(KERNEL_BUCKET,), prefix_cache_mb=None, **kw,
        )
        want = "pallas" if want_pallas else "xla"
        _require(eng.decode_kernel == want and eng.prefill_kernel == want,
                 f"kernels[{name}]: engine resolved decode={eng.decode_kernel} "
                 f"prefill={eng.prefill_kernel}, asked for {want}")
        if want_pallas:
            programs = ["serve/decode_window", f"serve/prefill_{KERNEL_BUCKET}"]
            if eng.speculate_k:
                _require(eng.stats["spec_drafted"] > 0,
                         f"kernels[{name}]: the verify window never ran")
                programs.append("serve/verify_window")
            for program in programs:
                _require("tpu_custom_call" in _compiled_text(eng, program),
                         f"kernels[{name}]: no tpu_custom_call in {program} — the "
                         f"kernel gave way to a reference or to interpret mode")
        arms[name] = tokens
        stats = dict(eng.stats)
        del eng
        _free()
        in_use_after[name] = _memory_stat("bytes_in_use")[0]
        compile_s = phase.clock.compile_s - c0
        arm_seconds[name] = {"compile_s": round(compile_s, 2),
                             "run_s": round(time.perf_counter() - t0 - compile_s, 2)}
        return stats

    pallas = dict(decode_kernel="pallas", prefill_kernel="pallas")
    run("xla", False)
    run("pallas", True, **pallas)
    run("int8_xla", False, kv_dtype="int8")
    run("int8_pallas", True, kv_dtype="int8", **pallas)
    spec = run("pallas_spec", True, speculate_k=3, **pallas)

    # the attention program and page dtype the identity rule probes each arm with
    probed_as = {
        "xla": ("xla", None), "pallas": ("flash_prefill", None),
        "int8_xla": ("xla", "int8"), "int8_pallas": ("flash_prefill", "int8"),
        "pallas_spec": ("flash_prefill", None),
    }
    diffs = []
    for a, b in [("xla", "pallas"), ("int8_xla", "int8_pallas"), ("xla", "pallas_spec")]:
        diffs += check_identity(
            f"kernels: {b} vs {a}", prompts,
            (a, arms[a], *probed_as[a]), (b, arms[b], *probed_as[b]),
            probe, model.config.dtype,
        )
    phase.fields.update(
        arms=sorted(arms), page_size=KERNEL_PAGE, prefill_chunk=KERNEL_BUCKET,
        spec_drafted=spec["spec_drafted"], spec_accepted=spec["spec_accepted"],
        tpu_custom_call="decode, prefill and verify programs of every pallas arm",
        identity_differences=len(diffs), bytes_in_use_after_arm=in_use_after,
        arm_seconds=arm_seconds,
    )


# --------------------------------------------------------------- four chips
def phase_fsdp4(phase, seed, cfg=None, batch_size=128, steps=5):
    """The train configuration under FSDP on an fsdp=4 mesh against the same
    steps on device 0 alone: same global batch, same seed."""
    import jax
    import numpy as np

    import accelerate_tpu as at

    cfg = cfg or _train_config()
    one, _, one_ms, _ = _train_run(cfg, batch_size, seed, steps, mesh={"dp": 1})
    _free()
    four, state, four_ms, acc = _train_run(
        cfg, batch_size, seed, steps, mesh={"fsdp": 4},
        fsdp_plugin=at.FullyShardedDataParallelPlugin(),
    )
    _require(np.allclose(four, one, rtol=2e-2, atol=2e-2),
             f"fsdp4: losses {four} differ from one chip {one} beyond bf16 tolerance")
    leaves = jax.tree_util.tree_leaves(state.params)
    spans = {len(x.sharding.device_set) for x in leaves}
    _require(spans == {4}, f"fsdp4: params span {spans} devices, want 4 each")
    sharded = sum("fsdp" in str(x.sharding.spec) for x in leaves)
    _require(sharded > 0, "fsdp4: no parameter is sharded over fsdp")
    in_use = _memory_stat("bytes_in_use")
    _require(all(b > 0 for b in in_use), f"fsdp4: bytes_in_use per device {in_use}")
    phase.fields.update(
        mesh=dict(acc.mesh.shape), losses_one_chip=one, losses_fsdp4=four,
        step_ms_one_chip=round(one_ms, 2), step_ms_fsdp4=round(four_ms, 2),
        step_compiles_each=1,
        params_sharded_over_fsdp=sharded, param_leaves=len(leaves),
        bytes_in_use_per_device=in_use,
    )


def _devices_of(tree):
    import jax

    return sorted({d.id for x in jax.tree_util.tree_leaves(tree) for d in x.devices()})


def phase_replicas4(phase, seed, preset="gpt2", max_len=1024, param_dtype=None):
    """Four one-chip replicas behind the router, built by ``build_service``:
    replica i's params and KV pages on chip i and nowhere else, tokens equal
    to a single engine's.  GPT-2's published widths (124M, 12 layers): each
    replica compiles its own programs for its own chip, and four sets of the
    48-layer preset's would be minutes of four chips' time."""
    import jax

    from accelerate_tpu import serve

    router, frontdoor, server = serve.build_service(
        _serve_args(seed, preset, max_len, param_dtype, replicas=4)
    )
    try:
        engines = list(router.engines)
        model = engines[0].model
        prompts = _prompts(seed, model.config.vocab_size) + _prompts(seed + 1, model.config.vocab_size)
        placement = []
        for i, eng in enumerate(engines):
            want = [jax.devices()[i].id]
            where = {"params": _devices_of(eng.params),
                     "kv_pages": _devices_of([eng.kv.pages_k, eng.kv.pages_v,
                                              eng.kv.k_scales, eng.kv.v_scales])}
            _require(where["params"] == want and where["kv_pages"] == want,
                     f"replicas4: replica {i} lives on {where}, want device {want}")
            placement.append(where)
        served = [_http_completion(server, prompts[0])]           # cold
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(len(prompts)) as pool:
            served += list(pool.map(lambda p: _http_completion(server, p), prompts[1:]))
        routed = [eng.stats["requests_completed"] for eng in engines]
    finally:
        server.stop()
        frontdoor.stop()
    _require(sum(routed) == len(prompts), f"replicas4: completed {routed}")
    _require(sum(n > 0 for n in routed) > 1, f"replicas4: only one replica served {routed}")
    single, eng = _serve_arm(engines[0].model, engines[0].params, prompts, mesh=engines[0].mesh)
    probe = LogitProbe(model, engines[0].params, KERNEL_PAGE, PROBE_WIDTH)
    diffs = check_identity(
        "replicas4: routed vs single engine", prompts,
        ("single_engine", single, "xla", None), ("four_replicas", served, "xla", None),
        probe, model.config.dtype,
    )
    phase.fields.update(
        preset=preset, param_dtype=param_dtype or "preset default",
        placement=placement, requests_per_replica=routed,
        identity_differences=len(diffs),
    )


def phase_tp4(phase, seed):
    """One tp=4 engine at GPT-2 width (12 KV heads divide by 4), float32 so
    argmax ties do not flip, against tp=1: tokens by the identity rule and a
    quarter of the KV pool per device."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.transformer import Transformer, TransformerConfig
    from accelerate_tpu.parallel.mesh import build_mesh

    cfg = TransformerConfig.gpt2(dtype=jnp.float32, param_dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = _prompts(seed, cfg.vocab_size)
    tp1, eng1 = _serve_arm(model, params, prompts)
    tp4, eng4 = _serve_arm(model, params, prompts, mesh=build_mesh({"tp": 4}))
    _require(eng4.tp_degree == 4, f"tp4: tp degree {eng4.tp_degree}")
    _require(eng4.kv_pool_bytes() * 4 == eng1.kv_pool_bytes(),
             f"tp4: per-device KV {eng4.kv_pool_bytes()} is not a quarter of {eng1.kv_pool_bytes()}")
    _require(len(eng4.kv.pages_k.sharding.device_set) == 4, "tp4: pool not on four devices")
    probe = LogitProbe(model, params, KERNEL_PAGE, PROBE_WIDTH)
    diffs = check_identity(
        "tp4: tp=4 vs tp=1", prompts,
        ("tp1", tp1, "xla", None), ("tp4", tp4, "xla", None), probe, cfg.dtype,
    )
    phase.fields.update(
        model="gpt2 (124M), float32", kv_pool_bytes_per_device_tp1=eng1.kv_pool_bytes(),
        kv_pool_bytes_per_device_tp4=eng4.kv_pool_bytes(), identity_differences=len(diffs),
    )


# --------------------------------------------------------------------- main
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the four-chip path and what it is compared "
                             "with, and no other phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    from accelerate_tpu.utils import _native
    from accelerate_tpu.utils.environment import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({len(devices)} device(s)) — no result"
        )
    if len(devices) != args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} needs {args.chips} TPU device(s); "
            f"found {len(devices)}"
        )
    clock = CompileClock()
    print(json.dumps({
        "phase": "start", "chips": args.chips, "seed": args.seed,
        "native_runtime": ("libatpu_runtime.so" if _native.is_available()
                           else "python fallbacks (no library built)"),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": _cache_entries(cache_dir),
    }), flush=True)

    def phase(name):
        return Phase(name, clock, cache_dir)

    if args.chips == 1:
        with phase("train") as p:
            phase_train(p, args.seed)
        _free()
        with phase("serve") as p:
            model, params, prompts, probe = phase_serve(p, args.seed)
        with phase("kernels") as p:
            phase_kernels(p, model, params, prompts, probe)
    else:
        with phase("fsdp4") as p:
            phase_fsdp4(p, args.seed)
        _free()
        with phase("replicas4") as p:
            phase_replicas4(p, args.seed)
        _free()
        with phase("tp4") as p:
            phase_tp4(p, args.seed)

    print(json.dumps({
        "phase": "cache", "compile_cache_dir": cache_dir,
        "compile_cache_entries": _cache_entries(cache_dir),
        "compile_cache_bytes": _cache_bytes(cache_dir),
        "compile_cache_hits": clock.cache_hits,
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _cache_bytes(cache_dir):
    if not os.path.isdir(cache_dir):
        return 0
    return sum(os.path.getsize(os.path.join(cache_dir, f)) for f in os.listdir(cache_dir))


if __name__ == "__main__":
    sys.exit(main())

"""Big-model streaming-inference benchmark — tokens/s with host-resident weights.

The reference's only published benchmark is big-model inference with CPU/disk
offload (``/root/reference/benchmarks/big_model_inference.py``;
``benchmarks/README.md:27-37``): e.g. OPT-30B fp16 with CPU offload generates
at 2.37 s/token on 2x Titan RTX — every token streams the full 60GB of weights
host→GPU, an effective ~25 GB/s of overlapped transfer.

This benchmark measures the same engine quality on TPU: model weights live in
host RAM, :class:`StreamingTransformer` double-buffers them layer-by-layer into
HBM while the MXU computes.  Tasks:

* ``--task decode`` (default) — THE reference workload: autoregressive
  generation with a KV cache, every token streaming the full weight set
  host→HBM.  Reports decode tokens/s and s/token
  (``benchmarks/big_model_inference.py:141-155`` measures exactly this);
* ``--task prefill`` — batch x seq tokens per forward / wall time;
* ``--task serve`` — the continuous-batching engine
  (:mod:`accelerate_tpu.serving`) on a log-normal mixed-length workload vs
  static ``generate`` over the same requests in FCFS groups padded to the
  workload max — the padding + lockstep waste the slot pool exists to
  reclaim.  HBM-resident weights (serving is not an offload bench); reports
  tokens/s, per-token latency percentiles, slot occupancy, and ``vs_baseline``
  = engine tokens/s over static tokens/s.
* ``--task spec`` — speculative decoding A/B: the SAME serving engine with
  ``speculate_k`` on vs off over a repetitive (tiled-motif) greedy workload —
  n-gram drafting's home turf.  Outputs must be token-identical between the
  runs (the bench hard-fails otherwise; verification is exact), and
  ``vs_baseline`` = speculation-on tokens/s over speculation-off, with the
  draft-acceptance rate in ``detail``.

Either way ``effective stream GB/s`` — model bytes transferred per step / wall
time — is the engine-quality number; ``vs_baseline`` compares it to the
reference's ~25 GB/s OPT-30B CPU-offload figure.

Presets: ``gpt2-xl`` is the offload-parity geometry (2.1B) — pass it
explicitly; TPU defaults to ``small`` (~0.53 GB), CPU to ``tiny``.
``--bits 8`` streams int8-quantized weights (4x less traffic — compose
quantization with streaming).

The engine minimizes host→HBM round-trips: one packed buffer per stage
(StreamingExecutor.pack_transfers), multi-layer chunks (layers_per_stage),
and transfer/compute double-buffering.

Prints ONE JSON line like bench.py.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.utils.imports import is_tpu_platform

# reference benchmarks/README.md:36 — OPT-30B fp16 CPU offload, 2.37 s/token,
# ~60GB of fp16 weights streamed per token => ~25.3 GB/s effective.
REFERENCE_STREAM_GBPS = 25.3

def _presets():
    """Named geometries — canonical ones come from TransformerConfig so the
    benchmark can never drift from the model the name promises."""
    from accelerate_tpu.models.transformer import TransformerConfig

    return {
        "gpt2-xl": TransformerConfig.gpt2_xl_equiv,
        "tiny": TransformerConfig.tiny,
        "small": lambda **kw: TransformerConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=4096,
            num_layers=12, num_heads=16, num_kv_heads=16, max_seq_len=512, **kw
        ),
    }


def _cost_detail(eng, dt_engine):
    """XLA cost-table numbers for the serve JSON contract: ``mfu`` and
    ``hbm_peak_bytes``.  Decode MFU = window invocations x decode-window FLOPs
    over wall time against the chip peak — prefill FLOPs are excluded, so this
    understates true utilization (it is the steady-state decode number).
    Empty when XLA cost analysis is unavailable on this backend."""
    eng.analyze_costs()
    out = {}
    decode_flops = eng.cost_table.flops("serve/decode_window")
    if decode_flops:
        windows = eng.stats["decode_steps"] / eng.window
        out["mfu"] = round(
            min(1.0, windows * decode_flops / dt_engine / eng.device_peaks.flops_per_s), 6
        )
        out["mfu_source"] = "xla_cost_analysis"
        out["decode_flops_per_token"] = round(
            decode_flops / (eng.window * eng.num_slots), 1
        )
    hbm = eng.cost_table.max_hbm_peak_bytes()
    if hbm:
        out["hbm_peak_bytes"] = int(hbm)
    return out


def _shared_prefix_result(args, preset, shared, prompt_lens, out_lens,
                          useful_tokens, run_engine, eng, reqs, dt_on,
                          registry, samples, buckets, slots, window):
    """Cache-on vs cache-off on the shared-prefix workload (one JSON result).

    The cache-off engine is the baseline — identical requests, identical
    executables minus the copies — so ``vs_baseline`` isolates exactly what
    prefix reuse buys.  Outputs must be token-identical between the runs (the
    cache skips compute, never changes it); the bench hard-fails otherwise.
    """
    eng_off, reqs_off, dt_off, registry_off, _ = run_engine(0)
    if [q.tokens for q in reqs] != [q.tokens for q in reqs_off]:
        raise SystemExit(
            "prefix cache changed outputs: cache-on tokens differ from "
            "cache-off on the same workload"
        )
    tps_on = useful_tokens / dt_on
    tps_off = useful_tokens / dt_off
    hit = eng.stats["prefix_hit_tokens"]
    miss = eng.stats["prefix_miss_tokens"]
    ttft_on = registry.get("serve/ttft_s").snapshot()
    ttft_off = registry_off.get("serve/ttft_s").snapshot()
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "num_slots": slots,
        "decode_window": window,
        "prefill_buckets": list(buckets),
        "shared_prefix": shared,
        "prefix_cache_mb": args.prefix_cache_mb,
        "prompt_len_p50_max": [int(np.median(prompt_lens)), int(prompt_lens.max())],
        "out_len_p50_max": [int(np.median(out_lens)), int(out_lens.max())],
        "useful_tokens": useful_tokens,
        "engine_wall_s": round(dt_on, 3),
        "cache_off_wall_s": round(dt_off, 3),
        "cache_off_tokens_per_s": round(tps_off, 2),
        "prefix_hit_rate": round(hit / (hit + miss), 3) if hit + miss else 0.0,
        "prefix_hit_tokens": hit,
        "prefix_cache": eng.prefix_cache_stats(),
        "outputs_token_identical": True,
        "token_latency_p50_ms": round(1e3 * float(np.percentile(samples, 50)), 2),
        "token_latency_p99_ms": round(1e3 * float(np.percentile(samples, 99)), 2),
        "ttft_ms": {k: round(1e3 * ttft_on[k], 2) for k in ("p50", "p90", "p99", "mean")},
        "cache_off_ttft_ms": {
            k: round(1e3 * ttft_off[k], 2) for k in ("p50", "p90", "p99", "mean")
        },
        "mean_slot_occupancy": round(eng.mean_slot_occupancy(), 3),
        "compiled_executables": eng.compiled_executable_counts(),
    }
    detail.update(_cost_detail(eng, dt_on))
    return {
        "metric": "serving_prefix_cache_tokens_per_sec",
        "value": round(tps_on, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / tps_off, 3),
        "detail": detail,
    }


def _spec_bench(args, model, cfg, params, preset):
    """Speculation on vs off on a repetitive greedy workload (one JSON result).

    The speculation-off engine is the baseline — identical requests, identical
    executables minus the verify window — so ``vs_baseline`` isolates exactly
    what n-gram drafting + batched verification buy.  The workload is tiled
    short motifs (the structured/repetitive shape — code, JSON, quoting — that
    prompt-lookup drafting targets); greedy outputs must be token-identical
    between the two runs and the bench hard-fails if they are not.

    ``--tree-ab`` switches to the draft-model + token-tree A/B
    (:func:`_tree_ab_bench`): identity matrix across pools / KV dtypes /
    tp, an acceptance-rate-vs-speedup curve on a non-repetitive workload,
    and compiled-budget hard checks.
    """
    import dataclasses

    if getattr(args, "tree_ab", False):
        return _tree_ab_bench(args, model, cfg, params, preset)

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.models.transformer import Transformer
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)  # HBM-resident: speculation is a decode bench
    slots = args.batch
    window = args.decode_window
    k = args.speculate_k
    if k < 1:
        raise SystemExit("--task spec needs --speculate-k >= 1")
    max_len = cfg.max_seq_len
    mp = max(8, min(args.seq, max_len) // 2)
    buckets = tuple(sorted({max(8, mp // 4), max(8, mp // 2)}))
    span = max(window, k + 1)

    # Speculation pays off in the steady state — once generation locks into
    # the motif's cycle, drafts verify near-perfectly — so the bench wants
    # generations long enough for steady state to dominate the chaotic
    # opening tokens.  Rope params carry no position table, so the context
    # window can be widened to fit the requested generation with the SAME
    # weights (both A/B arms get the identical widened model).
    need = mp + args.spec_new_tokens + span
    if need > max_len and cfg.positional == "rope":
        max_len = min(need, 1024)
        cfg = dataclasses.replace(cfg, max_seq_len=max_len)
        model = Transformer(cfg)

    r = np.random.default_rng(args.serve_seed)
    out_len = int(min(args.spec_new_tokens, max_len - mp - span))
    prompts = []
    for _ in range(args.requests):
        motif = r.integers(1, cfg.vocab_size, (int(r.integers(3, 8)),)).astype(np.int32)
        prompts.append(np.tile(motif, mp // motif.size + 1)[:mp])
    gen = GenerationConfig(max_new_tokens=out_len)
    useful_tokens = args.requests * out_len
    slot_len = min(max_len, mp + out_len + span)

    def run(spec_k):
        """One warmed, timed engine pass (prefix cache off: one variable)."""
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=slot_len,
            prefill_buckets=buckets, max_prompt_len=mp, decode_window=window,
            registry=registry, prefix_cache_mb=0, speculate_k=spec_k,
        )
        # warmup compiles every executable before timing: non-drafting random
        # prompts exercise each prefill bucket + insert + the decode window;
        # a tiled prompt drives the verify window when speculation is on
        for b in buckets:
            eng.submit(r.integers(1, cfg.vocab_size, (b,)).astype(np.int32),
                       config=GenerationConfig(max_new_tokens=2 * span),
                       speculate=False)
            eng.run()
        eng.submit(np.tile(np.arange(1, 4, dtype=np.int32), mp)[:mp],
                   config=GenerationConfig(max_new_tokens=2 * span))
        eng.run()
        for key in eng.stats:
            eng.stats[key] = 0
        registry.reset()
        t0 = time.perf_counter()
        reqs = eng.serve(prompts, gen)
        dt = time.perf_counter() - t0
        return eng, reqs, dt, registry

    eng_on, reqs_on, dt_on, registry = run(k)
    eng_off, reqs_off, dt_off, _ = run(0)
    if [q.tokens for q in reqs_on] != [q.tokens for q in reqs_off]:
        raise SystemExit(
            "speculative decoding changed greedy outputs: speculation-on "
            "tokens differ from speculation-off on the same workload"
        )
    tps_on = useful_tokens / dt_on
    tps_off = useful_tokens / dt_off
    drafted = eng_on.stats["spec_drafted"]
    accepted = eng_on.stats["spec_accepted"]
    tok = registry.get("serve/token_latency_s").snapshot()
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "num_slots": slots,
        "decode_window": window,
        "speculate_k": k,
        "prompt_len": mp,
        "new_tokens_per_request": out_len,
        "useful_tokens": useful_tokens,
        "spec_on_wall_s": round(dt_on, 3),
        "spec_off_wall_s": round(dt_off, 3),
        "spec_off_tokens_per_s": round(tps_off, 2),
        "spec_accept_rate": round(accepted / drafted, 3) if drafted else 0.0,
        "spec_drafted": drafted,
        "spec_accepted": accepted,
        "outputs_token_identical": True,
        "token_latency_p50_ms": round(1e3 * tok["p50"], 2),
        "token_latency_p99_ms": round(1e3 * tok["p99"], 2),
        "compiled_executables": eng_on.compiled_executable_counts(),
        "watchdog_over_budget": any(
            wd.over_budget()
            for wd in [eng_on._decode, eng_on._verify, eng_on._insert,
                       *eng_on._prefill.values()]
        ),
    }
    return {
        "metric": "serving_speculative_tokens_per_sec",
        "value": round(tps_on, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tps_on / tps_off, 3),
        "detail": detail,
    }


def _tree_ab_bench(args, model, cfg, params, preset):
    """Tree speculation with an on-device draft model: identity matrix,
    acceptance-vs-speedup curve, and compiled-budget gates (one JSON result).

    Three hard checks, each a nonzero exit:

    * **Identity matrix** — greedy outputs token-identical between the tree
      arm and speculation-off on the SAME engine configuration, across
      {slab, paged} x {bf16, int8 KV} x {tp=1, tp=2} (the tp=2 paged arm
      runs the XLA kernel: the single-chip Pallas kernel does not shard
      and is refused under a tp mesh).  int8 pages only
      exist on the paged pool, so the matrix is six arms, not eight; the
      tp=2 arms run float32 for the same precision reason ``--tp-ab``
      documents.
    * **Speedup on a non-repetitive workload** — the draft-model + tree arm
      must reach >= 1.4x tokens/s over speculation-off at a curve point
      where the n-gram drafter, run on the *same* prompts and params,
      measures an accept rate < 0.05.  Prompts are drawn WITHOUT token
      replacement from an 8k vocab, so no trailing n-gram recurs in the
      context and prompt-lookup drafting has nothing to match — exactly the
      workload regime the draft model exists for.
    * **Compiled budget** — relative to speculation-off, the tree engine's
      executable set grows by exactly {draft_forward, tree_verify_window}
      (one entry each), and repeat serve passes add zero retraces.

    The curve sweeps draft fidelity on one geometry: the draft is the
    target's own first two layers (``draft_model=2``), and the layers the
    draft does NOT share are scaled by ``eps``.  At ``eps=0`` the target
    effectively *is* its two-layer head, so drafts verify near-exactly
    (the draft's sliding context window is the only divergence); at
    ``eps=1`` the target is the unmodified 8-layer model and the
    truncated draft is near-random (accept ~0).  Each point re-measures its own
    speculation-off baseline and n-gram arm on the softened params, so
    ``curve`` in the JSON is acceptance rate vs speedup with everything
    else held fixed.  The headline gate takes the best point whose n-gram
    accept qualifies (< 0.05).  Each point times its two arms in paired
    interleaved passes and compares medians: CPU wall clocks drift on the
    scale of a bench run, and a baseline measured minutes before the tree
    arm would put that drift straight into the gated ratio.

    Bench-local geometry: the preset models are 2 layers on CPU, too
    shallow for a truncated-layer head to be meaningfully cheaper than its
    target, so the bench builds its own 8-layer float32 target (the
    identity arms recast it to bf16).  ``decode_window=1`` for every arm:
    both sides then pay one dispatch per landed token batch, which is the
    cost speculation amortizes — window fusion is the orthogonal axis
    ``--task serve`` measures.  ``num_slots=1`` keeps the arms
    dispatch-bound rather than batch-bound, the regime the tree targets:
    with one lane the baseline pays one dispatch per token, the tree two
    dispatches per ``depth+1`` tokens.

    The tp=2 arms need >= 2 devices; on fewer the bench exits nonzero up
    front, naming the count it found.
    """
    import re as _re

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.models.transformer import Transformer
    from accelerate_tpu.parallel.mesh import build_mesh
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    if len(jax.devices()) < 2:
        raise SystemExit(
            f"--tree-ab's tp=2 identity arms need 2 devices; found "
            f"{len(jax.devices())} ({jax.devices()[0].platform})"
        )
    cfg = dataclasses.replace(
        cfg, num_layers=8, vocab_size=8192, max_seq_len=256,
        hidden_size=64, intermediate_size=128, num_heads=4, num_kv_heads=2,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(args.serve_seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    draft_layers = 2

    def soften(eps):
        """Scale the layers the draft does not share by ``eps``."""
        out = {}
        for key, val in params.items():
            m = _re.fullmatch(r"layers_(\d+)", key)
            if m and int(m.group(1)) >= draft_layers:
                out[key] = jax.tree_util.tree_map(
                    lambda a: (np.asarray(a) * eps).astype(a.dtype), val)
            else:
                out[key] = val
        return out

    # distinct-token prompts: with no repeated token anywhere in the
    # context, the n-gram drafter's suffix index never finds a match to
    # extend — the workload is non-repetitive by construction.  The draft
    # is TWO layers, not one: a single attention layer is near-Markov
    # (next token mostly a function of the last), so its greedy stream
    # revisits a token and loops, and the n-gram drafter starts scoring
    # on the loop; attention over attention conditions on the whole
    # prefix and the softened streams never recur
    n_req, plen, out_len, reps = 8, 24, 24, 4
    tree_kw = dict(draft_model=draft_layers, tree_width=1, tree_depth=11,
                   draft_ctx=60)
    r = np.random.default_rng(args.serve_seed)
    prompts = [
        r.choice(cfg.vocab_size - 1, size=plen, replace=False).astype(np.int32) + 1
        for _ in range(n_req)
    ]
    gen = GenerationConfig(max_new_tokens=out_len)
    useful_tokens = n_req * out_len

    def run(arm_model, arm_params, n_reps=reps, out=out_len, **kw):
        """One warmed engine; best-of-``n_reps`` timed serve passes."""
        eng = ServingEngine(
            arm_model, arm_params, num_slots=1, max_len=256,
            prefill_buckets=(8, 24), decode_window=1,
            registry=MetricsRegistry(), prefix_cache_mb=0, **kw,
        )
        for b in (8, 24):
            eng.submit(r.integers(1, cfg.vocab_size, (b,)).astype(np.int32),
                       config=GenerationConfig(max_new_tokens=8))
        eng.run()
        g = GenerationConfig(max_new_tokens=out)
        best, toks = 0.0, None
        for _ in range(n_reps):
            for key in eng.stats:
                eng.stats[key] = 0
            t0 = time.perf_counter()
            reqs = eng.serve([p.copy() for p in prompts], g)
            dt = time.perf_counter() - t0
            best = max(best, sum(len(q.tokens) for q in reqs) / dt)
            toks = [q.tokens for q in reqs]
        return eng, toks, best

    def timed_pair(arm_params, **extra_tree_kw):
        """Speculation-off and tree engines timed in ALTERNATING passes.

        CPU wall clocks drift on the scale of a bench run (load, thermal,
        cache state); measuring the baseline once and every tree point
        minutes later puts that drift straight into the speedup ratio.
        Interleaving the passes and taking the ratio of medians cancels
        it — both arms sample the same seconds of machine."""
        eng_off, _, _ = run(model, arm_params, n_reps=1)
        eng_tree, _, _ = run(model, arm_params, n_reps=1,
                             **{**tree_kw, **extra_tree_kw})
        offs, trees = [], []
        toks_off = toks_tree = None
        for _ in range(reps):
            for eng, acc in ((eng_off, offs), (eng_tree, trees)):
                for key in eng.stats:
                    eng.stats[key] = 0
                t0 = time.perf_counter()
                reqs = eng.serve([p.copy() for p in prompts], gen)
                dt = time.perf_counter() - t0
                acc.append(sum(len(q.tokens) for q in reqs) / dt)
                toks = [q.tokens for q in reqs]
                if eng is eng_off:
                    toks_off = toks
                else:
                    toks_tree = toks
        return (eng_off, eng_tree, toks_off, toks_tree,
                float(np.median(offs)), float(np.median(trees)))

    def run_tp2_arms():
        """The three tp=2 identity arms (float32 — see the matrix note)."""
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        int8_kw = dict(paged=True, kv_dtype="int8", page_size=1)
        rows = []
        for name, kw in [
            ("slab_f32_tp2", dict(mesh=mesh)),
            ("paged_f32_tp2", dict(paged=True, mesh=mesh)),
            ("paged_int8_tp2", dict(int8_kw, mesh=mesh)),
        ]:
            _, toks_off, _ = run(model, params, n_reps=1, out=12, **kw)
            eng_on, toks_on, _ = run(model, params, n_reps=1, out=12,
                                     **kw, **tree_kw)
            if toks_on != toks_off:
                raise SystemExit(
                    f"tree speculation changed greedy outputs on the "
                    f"{name} arm: tree tokens differ from speculation-off"
                )
            rows.append({
                "arm": name, "token_identical": True,
                "decode_kernel": getattr(eng_on, "decode_kernel", None),
            })
        return rows

    # --- acceptance-rate-vs-speedup curve -------------------------------
    curve = []
    budget_off = budget_tree = budget_first = None
    for eps in (0.0, 0.25, 0.5, 1.0):
        pe = soften(eps)
        eng_off, eng_tree, t_off, t_tree, tps_off, tps_tree = timed_pair(pe)
        eng_ng, _, _ = run(model, pe, n_reps=1, speculate_k=args.speculate_k)
        if eps == 0.0:
            budget_off = eng_off.compiled_executable_counts()
            budget_tree = eng_tree.compiled_executable_counts()
            # one more full pass AFTER the budget snapshot: any retrace
            # (shape drift, cache miss) would grow the counts
            eng_tree.serve([p.copy() for p in prompts], gen)
            budget_first = eng_tree.compiled_executable_counts()
        if t_tree != t_off:
            raise SystemExit(
                f"tree speculation changed greedy outputs at eps={eps}: "
                "tree-arm tokens differ from speculation-off on the same "
                "softened params"
            )
        dd, aa = eng_tree.stats["spec_drafted"], eng_tree.stats["spec_accepted"]
        dn, an = eng_ng.stats["spec_drafted"], eng_ng.stats["spec_accepted"]
        curve.append({
            "eps": eps,
            "accept_rate": round(aa / dd, 3) if dd else 0.0,
            "ngram_accept_rate": round(an / dn, 3) if dn else 0.0,
            "ngram_drafted": int(dn),
            "tokens_per_s": round(tps_tree, 2),
            "baseline_tokens_per_s": round(tps_off, 2),
            "speedup": round(tps_tree / tps_off, 3),
        })

    # --- compiled-budget gates ------------------------------------------
    if budget_tree != budget_first:
        raise SystemExit(
            f"tree engine retraced across repeat serve passes: "
            f"{budget_tree} -> {budget_first}"
        )
    grown = {k for k, n in budget_tree.items() if n and not budget_off.get(k, 0)}
    if grown != {"draft_forward", "tree_verify_window"} or (
        budget_tree["draft_forward"] != 1
        or budget_tree["tree_verify_window"] != 1
    ):
        raise SystemExit(
            "tree speculation must grow the compiled budget by exactly "
            f"{{draft_forward, tree_verify_window}}, one entry each; got "
            f"growth {sorted(grown)} with counts {budget_tree}"
        )

    # --- headline gate ---------------------------------------------------
    eligible = [p for p in curve if p["ngram_accept_rate"] < 0.05]
    if not eligible:
        raise SystemExit(
            "no curve point qualifies as non-repetitive: the n-gram "
            "drafter's accept rate is >= 0.05 at every eps — "
            f"{[(p['eps'], p['ngram_accept_rate']) for p in curve]}"
        )
    head = max(eligible, key=lambda p: p["speedup"])
    if head["speedup"] < 1.4:
        raise SystemExit(
            f"draft-model tree speculation reached only {head['speedup']}x "
            f"tokens/s over speculation-off (eps={head['eps']}, accept "
            f"{head['accept_rate']}, n-gram accept "
            f"{head['ngram_accept_rate']}); the bench requires >= 1.4x"
        )

    # width-2 reference point (not gated): same node budget rules, the
    # extra branch pays node compute for branch diversity the near-exact
    # draft does not need — visible in the JSON, useful on real models
    pe = soften(0.0)
    _, _, t_off0, t_w2, tps_off0, tps_w2 = timed_pair(pe, tree_width=2)
    if t_w2 != t_off0:
        raise SystemExit(
            "tree speculation changed greedy outputs at width=2"
        )
    curve.append({
        "eps": 0.0, "tree_width": 2,
        "tokens_per_s": round(tps_w2, 2),
        "speedup": round(tps_w2 / tps_off0, 3),
    })

    # --- identity matrix: {slab, paged} x {bf16, int8} x {tp1, tp2} ------
    # the tp=2 arms run float32 for the same reason --tp-ab does: token-
    # exactness under a mesh needs full-precision argmax margins — bf16
    # rounding differs between the stepwise decode and the batched verify
    # forward just enough to flip tied argmaxes once reductions are sharded
    bcfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    bmodel = Transformer(bcfg)
    bparams = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.bfloat16), params
    )
    int8_kw = dict(paged=True, kv_dtype="int8", page_size=1)
    identity = []
    for name, kw in [
        ("slab_bf16_tp1", {}),
        ("paged_bf16_tp1", dict(paged=True)),
        ("paged_int8_tp1", dict(int8_kw)),
    ]:
        _, toks_off, _ = run(bmodel, bparams, n_reps=1, out=12, **kw)
        eng_on, toks_on, _ = run(bmodel, bparams, n_reps=1, out=12,
                                 **kw, **tree_kw)
        if toks_on != toks_off:
            raise SystemExit(
                f"tree speculation changed greedy outputs on the {name} "
                "arm: tree tokens differ from speculation-off"
            )
        identity.append({
            "arm": name, "token_identical": True,
            "decode_kernel": getattr(eng_on, "decode_kernel", None),
        })
    identity += run_tp2_arms()

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "geometry": {
            "num_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
        },
        "workload": {
            "requests": n_req, "prompt_len": plen,
            "new_tokens_per_request": out_len,
            "useful_tokens": useful_tokens,
            "distinct_token_prompts": True,
        },
        "tree": dict(tree_kw),
        "num_slots": 1,
        "decode_window": 1,
        "headline_eps": head["eps"],
        "headline_accept_rate": head["accept_rate"],
        "headline_ngram_accept_rate": head["ngram_accept_rate"],
        "curve": curve,
        "identity_matrix": identity,
        "compiled_executables": budget_tree,
        "executable_growth": sorted(grown),
        "retraces": 0,
        "outputs_token_identical": True,
    }
    return {
        "metric": "serving_tree_spec_tokens_per_sec",
        "value": round(head["tokens_per_s"], 2),
        "unit": "tokens/s",
        "vs_baseline": head["speedup"],
        "detail": detail,
    }


def _paged_ab_bench(args, model, cfg, params, preset):
    """Paged KV allocator vs legacy slab pool at the SAME KV HBM budget.

    The workload is heavy-tailed chat traffic: every 8th request carries a
    long prompt (0.75-1x the longest admissible), the rest are short turns.
    The legacy arm reserves a full ``max_len`` slab per lane, so its KV
    budget — ``(slots + 1)`` slabs counting the prefill scratch — admits only
    a couple of lanes.  The paged arm gets a page pool of the same byte
    budget rounded DOWN to whole pages, scale arrays included (asserted
    ``<=`` via ``kv_pool_bytes``), but allocates per page, so short
    requests stop paying for the tail's worst case.  The headline
    metric is the ratio of peak concurrent lanes; outputs must be
    token-identical between the arms or the bench exits nonzero.

    Both arms run with ``max_prompt_len == max_len``: the paged prefill
    gathers a full-width view, and bitwise-identical logits across the arms
    require the legacy scratch to span that same width.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    window = args.decode_window
    mp = max(16, min(args.seq, cfg.max_seq_len) // 2)
    page = max(4, mp // 4)
    buckets = (page, 2 * page)
    max_len = (min(cfg.max_seq_len, 2 * mp) // page) * page

    r = np.random.default_rng(args.serve_seed)
    n = args.requests
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(4, mp // 12)), 0.6, n)), 4, page - 1
    ).astype(int)
    long_idx = np.arange(0, n, 8)
    prompt_lens[long_idx] = r.integers(3 * mp // 4, mp + 1, long_idx.size)
    prompts = [
        r.integers(1, cfg.vocab_size, (int(p),)).astype(np.int32)
        for p in prompt_lens
    ]
    out_cap = max(window, (max_len - mp - window) // 2)
    out_lens = np.clip(
        np.rint(r.lognormal(np.log(max(window, out_cap // 4)), 0.6, n)),
        window, out_cap,
    ).astype(int)
    gens = [GenerationConfig(max_new_tokens=int(o)) for o in out_lens]
    useful_tokens = int(out_lens.sum())

    legacy_slots = 2
    pages_per_lane = max_len // page
    # equal KV HBM: legacy pays (slots + 1) full-width slabs (pool + prefill
    # scratch); the paged pool gets AT MOST that many bytes worth of pages.
    # A paged page costs more than its slab-equivalent span: since the
    # quantized-KV PR every page carries per-(page, kv-head) f32 scale
    # arrays even at native dtype, so the page count comes from dividing the
    # legacy byte budget by the full per-page cost (scales included) and
    # rounding DOWN — the paged arm absorbs both the rounding and the
    # reserved null page rather than rounding the budget up.
    from accelerate_tpu.serving.paging import PagedKVPool

    # 2-page probe (1-page lane + null) just to read the per-page byte cost
    probe = PagedKVPool(cfg, 1, page, page, 2, registry=MetricsRegistry())
    page_data_bytes = (int(probe.pages_k.nbytes) + int(probe.pages_v.nbytes)) // 2
    legacy_bytes = (legacy_slots + 1) * pages_per_lane * page_data_bytes
    num_pages = max(pages_per_lane + 1, legacy_bytes // probe.page_kv_bytes)
    del probe

    def run_arm(paged):
        registry = MetricsRegistry()
        kwargs = dict(
            num_slots=args.batch if paged else legacy_slots,
            max_len=max_len, max_prompt_len=max_len, prefill_buckets=buckets,
            decode_window=window, registry=registry, prefix_cache_mb=0,
        )
        if paged:
            kwargs.update(paged=True, page_size=page, num_pages=num_pages)
        eng = ServingEngine(model, params, **kwargs)
        warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        eng.peak_active_lanes = 0
        registry.reset()
        t0 = time.perf_counter()
        reqs = eng.serve(prompts, gens)
        dt = time.perf_counter() - t0
        return eng, reqs, dt

    eng_paged, reqs_paged, dt_paged = run_arm(True)
    eng_slab, reqs_slab, dt_slab = run_arm(False)
    if [q.tokens for q in reqs_paged] != [q.tokens for q in reqs_slab]:
        raise SystemExit(
            "paged KV allocator changed greedy outputs: paged-arm tokens "
            "differ from the legacy slab arm on the same workload"
        )
    if eng_paged.kv_pool_bytes() > eng_slab.kv_pool_bytes():
        raise SystemExit(
            f"KV budgets diverged: paged arm holds {eng_paged.kv_pool_bytes()} "
            f"bytes vs legacy {eng_slab.kv_pool_bytes()} — the A/B is only "
            "meaningful when the paged arm fits the legacy byte budget"
        )
    peak_ratio = eng_paged.peak_active_lanes / max(1, eng_slab.peak_active_lanes)

    def arm_detail(eng, reqs, dt):
        return {
            "num_slots": eng.num_slots,
            "peak_active_lanes": eng.peak_active_lanes,
            "kv_pool_bytes": eng.kv_pool_bytes(),
            "wall_s": round(dt, 3),
            "tokens_per_s": round(useful_tokens / dt, 2),
            "preemptions": eng.stats.get("preemptions", 0),
            "cow_copies": eng.stats.get("cow_copies", 0),
            "compiled_executables": eng.compiled_executable_counts(),
        }

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "decode_window": window,
        "prefill_buckets": list(buckets),
        "page_size": page,
        "num_pages": num_pages,
        "max_len": max_len,
        "prompt_len_p50_max": [int(np.median(prompt_lens)), int(prompt_lens.max())],
        "out_len_p50_max": [int(np.median(out_lens)), int(out_lens.max())],
        "useful_tokens": useful_tokens,
        "outputs_token_identical": True,
        "paged": arm_detail(eng_paged, reqs_paged, dt_paged),
        "legacy": arm_detail(eng_slab, reqs_slab, dt_slab),
    }
    return {
        "metric": "serving_paged_peak_lanes_ratio",
        "value": round(peak_ratio, 3),
        "unit": "x",
        "vs_baseline": round(peak_ratio, 3),
        "detail": detail,
    }


def _async_ab_bench(args, model, cfg, params, preset):
    """Depth-1 pipelined serve loop vs the synchronous loop.

    Two claims, both hard-enforced:

    * **Token identity** — ``async_depth=1`` must produce bitwise-identical
      outputs to ``async_depth=0`` on the same request stream, across every
      sampling/pool mode the pipeline threads through: greedy and sampled on
      the slab pool, speculative decoding, the paged pool, and int8
      quantized KV pages.  Any divergence exits nonzero.
    * **Overlap pays** — on a timed greedy arm whose decode window carries
      real compute (a fixed ~10M-param float32 geometry; the identity
      presets price a CPU window near zero, where an A/B only measures
      scheduler noise), with every token streamed through an ``on_token``
      consumer with ~100us of client delivery latency (the network flush a
      real streaming server pays per token — exactly the host-side time the
      pipeline exists to hide), the async loop must be >= 10% faster
      tokens/s, publish ``serve/host_overlap_ratio > 0``, and compile
      EXACTLY the same executable set as the sync loop (the pipeline
      re-orders host work; it must never add device programs).  Arm timings
      are best-of-two, interleaved, to keep background-load drift
      symmetric.

    The headline metric is the async/sync tokens/s ratio; ``detail.overlap``
    records the published overlap ratio and cumulative device idle ms of
    both arms.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    STREAM_DELAY_S = 100e-6  # per-token client delivery latency, timed arms

    params = jax.device_put(params)
    window = args.decode_window
    max_len = cfg.max_seq_len
    mp = max(8, min(args.seq, max_len) // 2)
    # bucket pair with bucket[0] | bucket[1] so the paged arms' default
    # page_size (the bucket gcd) divides every bucket and the page-aligned
    # slot length below
    page = max(8, mp // 4)
    buckets = (page, 2 * page)

    r = np.random.default_rng(args.serve_seed)
    n = args.requests
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, n)), 4, mp
    ).astype(int)
    prompts = [
        r.integers(1, cfg.vocab_size, (int(p),)).astype(np.int32)
        for p in prompt_lens
    ]
    out_cap = min(max_len - window - mp, 2 * mp)
    out_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, out_cap // 4)), 0.8, n)),
        window, out_cap,
    ).astype(int)
    useful_tokens = int(out_lens.sum())
    need = int(max(p + o for p, o in zip(prompt_lens, out_lens))) + window
    slot_len = min((max_len // page) * page, -(-need // page) * page)

    def run(async_depth, configs, timed=False, bundle=None, **kw):
        b_model, b_params, b_vocab, b_slot_len, b_buckets, b_mp, b_prompts = (
            bundle if bundle is not None
            else (model, params, cfg.vocab_size, slot_len, buckets, mp, prompts)
        )
        registry = MetricsRegistry()
        eng = ServingEngine(
            b_model, b_params, num_slots=args.batch, max_len=b_slot_len,
            prefill_buckets=b_buckets, max_prompt_len=b_mp, decode_window=window,
            registry=registry, prefix_cache_mb=0, async_depth=async_depth,
            **kw,
        )
        # warm must cover every executable the timed serve dispatches,
        # including the ``lane_install`` scatter — that one only compiles on
        # an admission AFTER the first decode window (the device lane mirror
        # must already exist), so warm with more requests than slots
        warm = [r.integers(1, b_vocab, (b_buckets[0],)).astype(np.int32)
                for _ in range(args.batch + 2)]
        warm[:len(b_buckets)] = [
            r.integers(1, b_vocab, (b,)).astype(np.int32) for b in b_buckets
        ]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        registry.reset()
        # streaming consumers: each token is delivered to a client that takes
        # ~100us to flush (the SSE/network round-trip every streaming server
        # pays).  The wait releases the GIL, so the in-flight window computes
        # right through it — this is exactly the host-side latency the
        # pipeline hides.  The sync loop pays it serially: its drain runs
        # with nothing in flight.  Kept as a wait, not spin: on a shared-core
        # CPU host, busy host work would steal cycles from the "device"
        stamps = {}

        def on_token(req, tok):
            stamps.setdefault(req.rid, []).append(tok)
            time.sleep(STREAM_DELAY_S)

        t0 = time.perf_counter()
        reqs = eng.serve(b_prompts, configs, on_token=on_token if timed else None)
        dt = time.perf_counter() - t0
        return eng, [q.tokens for q in reqs], dt, registry

    greedy = [GenerationConfig(max_new_tokens=int(o)) for o in out_lens]
    sampled = [
        GenerationConfig(max_new_tokens=int(o), do_sample=True,
                         temperature=0.8, top_k=40, top_p=0.9)
        for o in out_lens
    ]
    arms = {
        "greedy_slab": (greedy, {}),
        "sampled_slab": (sampled, {}),
        "speculative": (greedy, {"speculate_k": 4}),
        "paged": (greedy, {"paged": True}),
        "paged_int8_kv": (greedy, {"paged": True, "kv_dtype": "int8"}),
    }
    identity = {}
    for name, (configs, kw) in arms.items():
        _, toks_async, _, _ = run(1, configs, **kw)
        _, toks_sync, _, _ = run(0, configs, **kw)
        if toks_async != toks_sync:
            raise SystemExit(
                f"async pipelined loop changed outputs on the {name} arm: "
                "async_depth=1 tokens differ from async_depth=0 on the same "
                "request stream"
            )
        identity[name] = True

    # Timed arm: greedy + streaming callbacks.  Overlap can only pay when a
    # decode window *costs* something next to the host/stream side it hides —
    # on the identity presets a CPU window is ~1ms against ~15ms of streaming
    # waits, so an A/B there measures scheduler noise, not the pipeline.  The
    # timed arm therefore runs a fixed geometry that prices a window at
    # ~20ms on a CPU host (comparable to emit + admission + streaming), with
    # short prompts so prefill stays a sliver of the wall.  Interleaved
    # best-of-two per arm — single-run wall times on a small shared host
    # swing with background load, and alternating keeps any drift symmetric.
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig

    cfg_t = TransformerConfig(
        vocab_size=2048, hidden_size=192, intermediate_size=768,
        num_layers=3, num_heads=6, num_kv_heads=6, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    model_t = Transformer(cfg_t)
    params_t = jax.device_put(
        model_t.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    prompts_t = [
        r.integers(1, cfg_t.vocab_size, (16,)).astype(np.int32) for _ in range(n)
    ]
    out_t = [int(o) for o in r.integers(6 * window, 12 * window + 1, n)]
    timed_tokens = int(sum(out_t))
    greedy_t = [GenerationConfig(max_new_tokens=o) for o in out_t]
    bundle_t = (model_t, params_t, cfg_t.vocab_size,
                16 + 12 * window + 2 * window, (16, 32), 32, prompts_t)
    eng_s, _, dt_s1, reg_s = run(0, greedy_t, timed=True, bundle=bundle_t)
    eng_a, _, dt_a1, reg_a = run(1, greedy_t, timed=True, bundle=bundle_t)
    _, _, dt_s2, _ = run(0, greedy_t, timed=True, bundle=bundle_t)
    _, _, dt_a2, _ = run(1, greedy_t, timed=True, bundle=bundle_t)
    dt_sync = min(dt_s1, dt_s2)
    dt_async = min(dt_a1, dt_a2)
    tps_sync = timed_tokens / dt_sync
    tps_async = timed_tokens / dt_async
    speedup = tps_async / tps_sync
    overlap = float(reg_a.get("serve/host_overlap_ratio").value)
    overlap_sync = float(reg_s.get("serve/host_overlap_ratio").value)
    if eng_a.compiled_executable_counts() != eng_s.compiled_executable_counts():
        raise SystemExit(
            f"async loop changed the compiled-executable budget: "
            f"{eng_a.compiled_executable_counts()} vs "
            f"{eng_s.compiled_executable_counts()}"
        )
    if overlap <= 0.0:
        raise SystemExit(
            "async arm published serve/host_overlap_ratio == 0: the pipeline "
            "never overlapped host work with device compute"
        )
    if speedup < 1.10:
        raise SystemExit(
            f"async pipelined loop too slow: {tps_async:.1f} vs "
            f"{tps_sync:.1f} tokens/s ({speedup:.3f}x, need >= 1.10x)"
        )
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": args.batch,
        "decode_window": window,
        "useful_tokens": useful_tokens,
        "timed_tokens": timed_tokens,
        "timed_config": {
            "hidden_size": cfg_t.hidden_size, "num_layers": cfg_t.num_layers,
            "vocab_size": cfg_t.vocab_size, "dtype": "float32",
        },
        "stream_delay_us": round(STREAM_DELAY_S * 1e6, 1),
        "outputs_token_identical": identity,
        "tokens_per_s": {"async": round(tps_async, 2), "sync": round(tps_sync, 2)},
        "wall_s": {"async": round(dt_async, 3), "sync": round(dt_sync, 3)},
        "overlap": {
            "host_overlap_ratio": round(overlap, 4),
            "host_overlap_ratio_sync": round(overlap_sync, 4),
        },
        "compiled_executables": eng_a.compiled_executable_counts(),
    }
    return {
        "metric": "serving_async_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "detail": detail,
    }


def _tp_ab_bench(args, model, cfg, params, preset):
    """Tensor-parallel serving A/B: tp=2 vs tp=1, then router affinity vs
    round-robin — the multi-chip serve entry (MULTICHIP_r06).

    Arm 1/2 (tp identity): the SAME engine, workload, and request stream on a
    single chip and on a ``{"tp": 2}`` mesh (params column-parallel under
    ``SERVING_TP_RULES``, KV pool head-sharded).  Hard checks, each a
    nonzero exit:

    * greedy outputs token-identical between the arms (SERVING_TP_RULES
      shard no contraction, so sharded reductions run in the tp=1 order);
    * per-device KV pool bytes at tp=2 at most 55% of tp=1 — the whole point
      of sharding the pool;
    * ``compiled_executable_counts()`` identical — the mesh must not cost
      executables, only shard the existing ones.

    The identity arms run in float32 (prompts and params recast) for the same
    reason ``tests/test_serving.py`` does: token-exactness needs full-precision
    argmax margins, not bf16 ties.

    Arm 3/4 (router A/B): two engine replicas behind a
    :class:`~accelerate_tpu.serving.ReplicaRouter`, a shared-prefix workload
    submitted in waves (each wave drains before the next arrives, so the
    radix trees the router probes reflect served traffic).  The affinity
    policy must beat round-robin on the aggregate token-weighted prefix-hit
    rate — strictly, or the bench exits nonzero.

    Needs >= 2 devices and exits nonzero, naming the count it found, on
    fewer — it never answers a multi-chip question on stand-in devices.
    """
    if len(jax.devices()) < 2:
        raise SystemExit(
            f"--tp-ab needs 2 devices; found {len(jax.devices())} "
            f"({jax.devices()[0].platform})"
        )

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.models.transformer import Transformer
    from accelerate_tpu.parallel.mesh import build_mesh
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    cfg = dataclasses.replace(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    model = Transformer(cfg)
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), params)
    )
    window = args.decode_window
    mp = max(16, min(args.seq, cfg.max_seq_len) // 2)
    buckets = (max(8, mp // 4), max(8, mp // 2))
    max_len = min(cfg.max_seq_len, 2 * mp)

    r = np.random.default_rng(args.serve_seed)
    n = args.requests
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(4, mp // 3)), 0.6, n)), 4, mp
    ).astype(int)
    prompts = [
        r.integers(1, cfg.vocab_size, (int(p),)).astype(np.int32)
        for p in prompt_lens
    ]
    out_cap = max(window, (max_len - mp - window) // 2)
    out_lens = np.clip(
        np.rint(r.lognormal(np.log(max(window, out_cap // 2)), 0.6, n)),
        window, out_cap,
    ).astype(int)
    gens = [GenerationConfig(max_new_tokens=int(o)) for o in out_lens]
    useful_tokens = int(out_lens.sum())

    def run_arm(mesh):
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=args.batch, max_len=max_len,
            max_prompt_len=mp, prefill_buckets=buckets, decode_window=window,
            registry=registry, prefix_cache_mb=0, paged=True, mesh=mesh,
        )
        warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        registry.reset()
        t0 = time.perf_counter()
        reqs = eng.serve(prompts, gens)
        dt = time.perf_counter() - t0
        return eng, reqs, dt

    mesh2 = build_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng1, reqs1, dt1 = run_arm(None)
    eng2, reqs2, dt2 = run_arm(mesh2)
    if [q.tokens for q in reqs1] != [q.tokens for q in reqs2]:
        raise SystemExit(
            "tensor-parallel serving changed greedy outputs: tp=2 tokens "
            "differ from tp=1 on the same workload"
        )
    bytes1, bytes2 = eng1.kv_pool_bytes(), eng2.kv_pool_bytes()
    if bytes2 > 0.55 * bytes1:
        raise SystemExit(
            f"tp=2 per-device KV pool holds {bytes2} bytes vs {bytes1} at "
            "tp=1 — sharding the pool on the head axis must at least halve it"
        )
    counts1 = eng1.compiled_executable_counts()
    counts2 = eng2.compiled_executable_counts()
    if counts1 != counts2:
        raise SystemExit(
            f"mesh changed the compiled-executable budget: tp=1 {counts1} "
            f"vs tp=2 {counts2}"
        )

    # ---- router A/B: shared-prefix waves, affinity vs round-robin --------
    # 3 prefix groups over 2 replicas: coprime, so round-robin rotates each
    # group across replicas wave over wave (repaying the prefill everywhere)
    # while affinity pins each group to the replica that first served it
    n_groups, n_waves = 3, 5
    shared = buckets[1]
    commons = [
        r.integers(1, cfg.vocab_size, (shared,)).astype(np.int32)
        for _ in range(n_groups)
    ]
    waves = []
    for _ in range(n_waves):
        wave = []
        for c in commons:
            sfx = r.integers(1, cfg.vocab_size, (int(r.integers(4, 12)),))
            wave.append(np.concatenate([c, sfx.astype(np.int32)]))
        waves.append(wave)
    router_gen = GenerationConfig(max_new_tokens=window)

    def run_router(policy):
        registry = MetricsRegistry()
        engines = [
            ServingEngine(
                model, params, num_slots=args.batch, max_len=max_len,
                max_prompt_len=mp, prefill_buckets=buckets,
                decode_window=window, registry=MetricsRegistry(),
                prefix_cache_mb=args.prefix_cache_mb, paged=True,
            )
            for _ in range(2)
        ]
        router = ReplicaRouter(engines, policy=policy, registry=registry)
        for wave in waves:
            for p in wave:
                router.submit(p, config=router_gen)
            router.run()
        return router

    router_aff = run_router("affinity")
    router_rr = run_router("round_robin")
    hit_aff = router_aff.prefix_cache_stats()["hit_rate"]
    hit_rr = router_rr.prefix_cache_stats()["hit_rate"]
    if not hit_aff > hit_rr:
        raise SystemExit(
            f"prefix-affinity routing found no more cached tokens than "
            f"round-robin ({hit_aff:.3f} vs {hit_rr:.3f}) on a shared-prefix "
            "workload it was built for"
        )

    n_dev = len(jax.devices())
    tail = (
        f"serve_tp_ab({n_dev}): mesh={{'tp': 2}} token_identical=True "
        f"kv_per_device_ratio={bytes2 / bytes1:.2f} "
        f"router_hit affinity={hit_aff:.3f} > round_robin={hit_rr:.3f} OK"
    )
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "MULTICHIP_r06.json"), "w") as f:
        json.dump({"n_devices": n_dev,
                   "platform": jax.devices()[0].platform, "rc": 0, "ok": True,
                   "skipped": False, "tail": tail}, f)

    def arm_detail(eng, dt):
        return {
            "kv_pool_bytes_per_device": eng.kv_pool_bytes(),
            "tp_degree": eng.tp_degree,
            "wall_s": round(dt, 3),
            "tokens_per_s": round(useful_tokens / dt, 2),
            "compiled_executables": eng.compiled_executable_counts(),
        }

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev,
        "requests": n,
        "decode_window": window,
        "prefill_buckets": list(buckets),
        "max_len": max_len,
        "useful_tokens": useful_tokens,
        "outputs_token_identical": True,
        "tp1": arm_detail(eng1, dt1),
        "tp2": arm_detail(eng2, dt2),
        "router": {
            "replicas": 2,
            "waves": n_waves,
            "prefix_groups": n_groups,
            "shared_prefix": int(shared),
            "affinity_hit_rate": round(hit_aff, 4),
            "round_robin_hit_rate": round(hit_rr, 4),
            "affinity_routed_hits": router_aff.health()["affinity_hit_rate"],
        },
    }
    return {
        "metric": "serving_tp_kv_per_device_ratio",
        "value": round(bytes2 / bytes1, 3),
        "unit": "x",
        "vs_baseline": round((useful_tokens / dt2) / (useful_tokens / dt1), 3),
        "detail": detail,
    }


def _quantized_logit_divergence(model, cfg, params, seq, plen, page, kv_dtype):
    """True logit-divergence oracle for quantized KV pages.

    Teacher-forces one completed sequence two ways and compares logits
    position by position over the decode region:

    * the exact reference — one full causal forward with no cache at all;
    * a single-lane quantized :class:`PagedKVCache` replay, one token per
      step through the SAME XLA paged-attention program the engine decodes
      with, so every page requantization the engine would perform happens
      here too.

    Returns ``max |logits_quantized - logits_exact|`` — the number the
    ``serve/kv_quant_error`` gauge only upper-bounds by proxy.
    """
    from accelerate_tpu.models.transformer import PagedKVCache
    from accelerate_tpu.ops.paged_attention import kv_storage_dtype

    seq = np.asarray(seq, np.int32)
    t_total = len(seq)
    exact = model.apply({"params": params}, jnp.asarray(seq)[None])

    storage = kv_storage_dtype(kv_dtype, cfg.dtype)
    n_pages = (t_total + page - 1) // page + 1  # + the null page
    shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, page, cfg.resolved_head_dim)
    cache = PagedKVCache(
        pages_k=jnp.zeros(shape, storage), pages_v=jnp.zeros(shape, storage),
        k_scales=jnp.ones((cfg.num_layers, n_pages, cfg.num_kv_heads), jnp.float32),
        v_scales=jnp.ones((cfg.num_layers, n_pages, cfg.num_kv_heads), jnp.float32),
        tables=jnp.arange(1, n_pages, dtype=jnp.int32)[None],
        index=jnp.zeros((1,), jnp.int32), active=jnp.ones((1,), bool),
        quant_err=jnp.float32(0.0),
    )

    def step(c, tok):
        logits, c = model.apply({"params": params}, tok[:, None], cache=c)
        return c, logits[:, 0]

    _, replay = jax.jit(lambda c, xs: jax.lax.scan(step, c, xs))(
        cache, jnp.asarray(seq[:-1])[:, None]
    )
    # position t's logits predict token t+1; the decode region starts at the
    # last prompt position (the engine's first generated token)
    diff = jnp.abs(replay[:, 0] - exact[0, :-1])
    return float(jnp.max(diff[plen - 1:]))


def _kernel_ab_bench(args, model, cfg, params, preset):
    """Decode-kernel / KV-dtype A/B on the paged engine (one JSON line).

    Four arms, all paged, all the same heavy-tail workload:

    * **xla** (baseline) — the PR-6 gathered reference program, native KV;
    * **pallas** — the in-place paged-attention kernel, native KV.  Greedy
      outputs must be token-identical to the xla arm or the bench exits
      nonzero (the kernel swap must be invisible in the tokens);
    * **quantized** (``--kv-dtype``, default int8) at the SAME lane/page
      config — checked against a true max-logit-divergence oracle
      (:func:`_quantized_logit_divergence`; hard limit ``--kv-quant-tol``)
      and required to cut the KV pool bytes >= 40% and strictly shrink the
      decode window's ``hbm_peak_bytes`` (whose weight/activation share
      quantized KV cannot touch — the measured drop rides in ``detail``);
    * a **capacity probe** pair at BYTE-EQUAL KV HBM — a page-starved native
      arm vs a quantized arm whose pool holds the same bytes (so ~2x the
      pages at bf16->int8): quantized peak concurrent lanes must be >= 1.8x.

    The headline metric is the pallas/xla tokens/s ratio; everything else
    rides in ``detail``.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    window = args.decode_window
    mp = max(16, min(args.seq, cfg.max_seq_len) // 2)
    page = max(4, mp // 4)
    buckets = (page, 2 * page)
    max_len = (min(cfg.max_seq_len, 2 * mp) // page) * page
    pages_per_lane = max_len // page
    slots = args.batch

    # the paged-ab heavy-tail chat mix: every 8th prompt long, the rest short
    r = np.random.default_rng(args.serve_seed)
    n = args.requests
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(4, mp // 12)), 0.6, n)), 4, page - 1
    ).astype(int)
    long_idx = np.arange(0, n, 8)
    prompt_lens[long_idx] = r.integers(3 * mp // 4, mp + 1, long_idx.size)
    prompts = [
        r.integers(1, cfg.vocab_size, (int(p),)).astype(np.int32)
        for p in prompt_lens
    ]
    out_cap = max(window, (max_len - mp - window) // 2)
    out_lens = np.clip(
        np.rint(r.lognormal(np.log(max(window, out_cap // 4)), 0.6, n)),
        window, out_cap,
    ).astype(int)
    gens = [GenerationConfig(max_new_tokens=int(o)) for o in out_lens]
    useful_tokens = int(out_lens.sum())

    def run_arm(kernel, kv_dtype, num_pages, num_slots, workload):
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=num_slots, max_len=max_len,
            max_prompt_len=max_len, prefill_buckets=buckets,
            decode_window=window, registry=registry, prefix_cache_mb=0,
            paged=True, page_size=page, num_pages=num_pages,
            decode_kernel=kernel, kv_dtype=kv_dtype,
        )
        warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        eng.peak_active_lanes = 0
        registry.reset()
        t0 = time.perf_counter()
        reqs = eng.serve(workload[0], workload[1])
        dt = time.perf_counter() - t0
        return eng, reqs, dt, registry

    roomy = slots * pages_per_lane + 1  # pressure never binds the equal arms
    mix = (prompts, gens)
    eng_x, reqs_x, dt_x, reg_x = run_arm("xla", None, roomy, slots, mix)
    eng_p, reqs_p, dt_p, reg_p = run_arm("pallas", None, roomy, slots, mix)
    eng_q, reqs_q, dt_q, reg_q = run_arm("xla", args.kv_dtype, roomy, slots, mix)

    if [q.tokens for q in reqs_p] != [q.tokens for q in reqs_x]:
        raise SystemExit(
            "pallas decode kernel changed greedy outputs: pallas-arm tokens "
            "differ from the xla reference arm on the same workload"
        )

    # quantized accuracy: replay the longest completed sequence against the
    # exact no-cache forward and bound the true logit divergence
    longest = max(range(n), key=lambda i: len(prompts[i]) + len(reqs_q[i].tokens))
    seq = np.concatenate([prompts[longest], np.asarray(reqs_q[longest].tokens, np.int32)])
    divergence = _quantized_logit_divergence(
        model, cfg, params, seq, len(prompts[longest]), page, args.kv_dtype
    )
    if divergence > args.kv_quant_tol:
        raise SystemExit(
            f"quantized KV ({args.kv_dtype}) max logit divergence {divergence:.3f} "
            f"exceeds --kv-quant-tol {args.kv_quant_tol} on the replay oracle"
        )

    # quantized memory: the page pool itself, and the decode executable's
    # XLA-reported HBM peak, must both shrink >= 40% at the SAME lane count
    kv_drop = 1.0 - eng_q.kv.kv_bytes() / eng_x.kv.kv_bytes()
    if kv_drop < 0.4:
        raise SystemExit(
            f"quantized KV pool shrank only {100 * kv_drop:.1f}% "
            f"({eng_q.kv.kv_bytes()} vs {eng_x.kv.kv_bytes()} bytes); >= 40% required"
        )
    # the executable-wide serve/hbm_peak_bytes also carries weights and
    # activations, which quantized KV cannot touch — so the hard check there
    # is strict improvement, with the measured drop reported alongside
    eng_x.analyze_costs()
    eng_q.analyze_costs()
    hbm_x = eng_x.cost_table.max_hbm_peak_bytes()
    hbm_q = eng_q.cost_table.max_hbm_peak_bytes()
    hbm_drop = 1.0 - hbm_q / hbm_x if hbm_x else None
    if hbm_x and hbm_q >= hbm_x:
        raise SystemExit(
            f"quantized KV failed to shrink serve/hbm_peak_bytes "
            f"({hbm_q} vs {hbm_x}) at equal lanes"
        )

    # capacity probe at byte-equal KV HBM: uniform near-full-lane requests so
    # concurrency is page-bound, a native pool two lanes wide vs a quantized
    # pool of exactly the same bytes (integer page count rounds DOWN — the
    # quantized arm absorbs the handicap)
    probe_n = max(8, n // 2)
    probe_prompts = [
        r.integers(1, cfg.vocab_size, (mp,)).astype(np.int32) for _ in range(probe_n)
    ]
    probe_gens = [GenerationConfig(max_new_tokens=max_len - mp - window)] * probe_n
    probe_slots = max(slots, 8)
    pages_native = 2 * pages_per_lane + 1
    native_bytes = pages_native * eng_x.kv.page_kv_bytes
    pages_quant = native_bytes // eng_q.kv.page_kv_bytes
    probe = (probe_prompts, probe_gens)
    eng_cn, _, dt_cn, _ = run_arm("xla", None, pages_native, probe_slots, probe)
    eng_cq, _, dt_cq, _ = run_arm("xla", args.kv_dtype, pages_quant, probe_slots, probe)
    if eng_cq.kv.kv_bytes() > eng_cn.kv.kv_bytes():
        raise SystemExit(
            f"capacity probe budgets diverged: quantized pool {eng_cq.kv.kv_bytes()} "
            f"bytes exceeds native {eng_cn.kv.kv_bytes()} — only meaningful at "
            "byte-equal KV HBM"
        )
    lane_ratio = eng_cq.peak_active_lanes / max(1, eng_cn.peak_active_lanes)
    if lane_ratio < 1.8:
        raise SystemExit(
            f"byte-equal quantized pool peaked at {eng_cq.peak_active_lanes} lanes vs "
            f"native {eng_cn.peak_active_lanes} ({lane_ratio:.2f}x); >= 1.8x required"
        )

    def arm_detail(eng, reqs, dt, registry):
        ttft = registry.get("serve/ttft_s").snapshot()
        out = {
            "tokens_per_s": round(useful_tokens / dt, 2),
            "wall_s": round(dt, 3),
            "ttft_p50_ms": round(1e3 * ttft["p50"], 2),
            "kv_pool_bytes": eng.kv.kv_bytes(),
            "peak_active_lanes": eng.peak_active_lanes,
            "outputs_token_identical": [q.tokens for q in reqs] == [q.tokens for q in reqs_x],
            "compiled_executables": eng.compiled_executable_counts(),
            "watchdog_over_budget": eng._decode.over_budget(),
        }
        snap = registry.snapshot()
        if "serve/kv_quant_error" in snap:
            out["kv_quant_error"] = round(snap["serve/kv_quant_error"], 6)
        # set once at pool construction (the pre-timing registry reset wiped
        # the gauge), so recompute from the pool itself
        out["kv_bytes_per_token"] = round(eng.kv.page_kv_bytes / eng.kv.page_size, 2)
        return out

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": slots,
        "decode_window": window,
        "page_size": page,
        "num_pages": roomy,
        "max_len": max_len,
        "kv_dtype": args.kv_dtype,
        "useful_tokens": useful_tokens,
        "xla": arm_detail(eng_x, reqs_x, dt_x, reg_x),
        "pallas": arm_detail(eng_p, reqs_p, dt_p, reg_p),
        "quantized": arm_detail(eng_q, reqs_q, dt_q, reg_q),
        "quantized_max_logit_divergence": round(divergence, 6),
        "kv_quant_tol": args.kv_quant_tol,
        "kv_pool_drop": round(kv_drop, 3),
        "hbm_peak_drop": round(hbm_drop, 3) if hbm_drop is not None else None,
        "capacity_probe": {
            "requests": probe_n,
            "num_slots": probe_slots,
            "native_pages": pages_native,
            "quantized_pages": int(pages_quant),
            "native_peak_lanes": eng_cn.peak_active_lanes,
            "quantized_peak_lanes": eng_cq.peak_active_lanes,
            "native_wall_s": round(dt_cn, 3),
            "quantized_wall_s": round(dt_cq, 3),
            "peak_lanes_ratio": round(lane_ratio, 3),
        },
    }
    return {
        "metric": "serving_pallas_vs_xla_tokens_per_sec_ratio",
        "value": round((useful_tokens / dt_p) / (useful_tokens / dt_x), 3),
        "unit": "x",
        "vs_baseline": round(dt_x / dt_p, 3),
        "detail": detail,
    }


def _prefill_ab_bench(args, model, cfg, params, preset):
    """Flash-prefill kernel + decode-interleaved chunked prefill A/B.

    The adversarial tenant mix the interleave exists for: one bulk tenant
    streaming near-context-length prompts (the scaled stand-in for 100k-token
    prompts) woven through chat traffic with heavy-tail log-normal output
    lengths, every request labelled via ``request_class`` so the per-class
    TTFT histograms split the two populations.  Three arms, same workload,
    same page pool:

    * **base** — non-interleaved, XLA gather/scatter prefill (the PR-6 path:
      admit-then-decode, one open prefill at a time);
    * **inter** — interleaved chunked prefill, XLA prefill program (chunks
      dispatched behind the decode window, SRTF across open prefills, joint
      per-cycle token budget);
    * **flash** — interleaved + ``prefill_kernel="pallas"`` (the paged
      flash-prefill kernel writing pages in place; interpreted off-TPU).

    Hard checks, each a nonzero exit:

    * greedy outputs of BOTH treatment arms token-identical to base — the
      kernel swap and the dispatch reorder must be invisible in the tokens;
    * ``compiled_executable_counts()`` identical across all three arms and
      every watchdog within budget — the flash kernel REPLACES each
      per-bucket prefill executable and the interleave only reorders
      dispatch; neither may add a compiled shape;
    * the treatment arms actually interleaved (``interleaved_chunks > 0``);
    * chat-class p99 TTFT >= 1.3x better than base.  On TPU the gate runs
      against the full treatment (flash); off-TPU against the XLA
      interleaved arm — interpret-mode pallas prices a prefill chunk at
      pure-Python cost, which would measure the interpreter, not the
      interleave;
    * on TPU only: flash-arm prefill tokens/s >= 0.9x the gather/scatter
      base (off-TPU the interpreted kernel makes the ratio meaningless —
      reported, not gated).

    The headline metric is the chat p99 TTFT improvement (base over
    treatment); prefill throughput and the bulk tenant's numbers ride in
    ``detail``.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    window = args.decode_window
    # small pages so a bulk prompt takes MANY chunk cycles — that is the
    # window chat traffic must not be starved through
    max_len = cfg.max_seq_len
    page = max(4, max_len // 32)
    buckets = (page, 2 * page)
    max_len = (max_len // page) * page
    pages_per_lane = max_len // page
    mp = max_len - 2 * window  # longest admissible (bulk) prompt
    slots = args.batch

    # chat: short prompts (single chunk), heavy-tail log-normal outputs
    r = np.random.default_rng(args.serve_seed)
    n_chat = args.requests
    chat_plens = np.clip(
        np.rint(r.lognormal(np.log(max(3, page // 2)), 0.5, n_chat)), 2, page
    ).astype(int)
    out_cap = max_len - 2 * page - window
    chat_olens = np.clip(
        np.rint(r.lognormal(np.log(max(window, out_cap // 6)), 1.0, n_chat)),
        window, out_cap,
    ).astype(int)
    # bulk: near-mp prompts, minimal outputs (the tenant streams prompts in)
    n_bulk = max(2, n_chat // 8)
    bulk_plens = r.integers(3 * mp // 4, mp + 1, n_bulk)

    workload = []  # (prompt, config, class) in submission order
    for i in range(n_chat):
        workload.append((
            r.integers(1, cfg.vocab_size, (int(chat_plens[i]),)).astype(np.int32),
            GenerationConfig(max_new_tokens=int(chat_olens[i])),
            "chat",
        ))
    # bulk requests woven in FIRST in each stripe: FCFS admission puts the
    # long prefill ahead of the chat requests behind it — the starvation the
    # interleave must break
    stride = max(1, len(workload) // n_bulk)
    for j in range(n_bulk):
        workload.insert(j * (stride + 1), (
            r.integers(1, cfg.vocab_size, (int(bulk_plens[j]),)).astype(np.int32),
            GenerationConfig(max_new_tokens=window),
            "bulk",
        ))
    useful_tokens = int(chat_olens.sum()) + n_bulk * window
    roomy = slots * pages_per_lane + 1  # page pressure never binds

    def run_arm(interleave, prefill_kernel):
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            max_prompt_len=mp, prefill_buckets=buckets,
            decode_window=window, registry=registry, prefix_cache_mb=0,
            paged=True, page_size=page, num_pages=roomy,
            prefill_kernel=prefill_kernel, interleave_prefill=interleave,
        )
        # warm every executable the timed serve dispatches, including the
        # lane_install scatter (compiles only on an admission AFTER the
        # first window — warm with more requests than slots)
        warm = [r.integers(1, cfg.vocab_size, (buckets[0],)).astype(np.int32)
                for _ in range(slots + 2)]
        warm[:len(buckets)] = [
            r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets
        ]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        registry.reset()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, config=g, request_class=c) for p, g, c in workload]
        eng.run()
        dt = time.perf_counter() - t0
        return eng, reqs, dt, registry

    eng_b, reqs_b, dt_b, reg_b = run_arm(False, "xla")
    eng_i, reqs_i, dt_i, reg_i = run_arm(True, "xla")
    eng_f, reqs_f, dt_f, reg_f = run_arm(True, "pallas")

    for name, reqs in (("interleaved", reqs_i), ("flash-prefill", reqs_f)):
        if [q.tokens for q in reqs] != [q.tokens for q in reqs_b]:
            raise SystemExit(
                f"{name} arm changed greedy outputs: tokens differ from the "
                "non-interleaved xla-prefill base arm on the same workload"
            )
    for name, eng in (("interleaved", eng_i), ("flash-prefill", eng_f)):
        if eng.compiled_executable_counts() != eng_b.compiled_executable_counts():
            raise SystemExit(
                f"{name} arm changed the compiled-executable budget: "
                f"{eng.compiled_executable_counts()} vs "
                f"{eng_b.compiled_executable_counts()}"
            )
        if eng.stats["interleaved_chunks"] <= 0:
            raise SystemExit(
                f"{name} arm never interleaved a chunk behind a decode "
                "window — the bench is not measuring interleaved prefill"
            )
        if any(f.over_budget() for f in eng._prefill.values()) or eng._decode.over_budget():
            raise SystemExit(f"{name} arm blew a recompile-watchdog budget")

    def klass_p99(reg, cls):
        return reg.get(f"serve/ttft_s_class_{cls}").snapshot()["p99"]

    ttft_base = klass_p99(reg_b, "chat")
    ttft_inter = klass_p99(reg_i, "chat")
    ttft_flash = klass_p99(reg_f, "chat")
    # off-TPU the flash arm prices prefill chunks at interpret cost; gate the
    # interleave on the kernel-equal arm there, the full treatment on TPU
    gate_ttft = ttft_flash if on_tpu else ttft_inter
    ttft_ratio = ttft_base / max(gate_ttft, 1e-9)
    if ttft_ratio < 1.3:
        raise SystemExit(
            f"interleaved chunked prefill left chat p99 TTFT at "
            f"{1e3 * gate_ttft:.1f}ms vs base {1e3 * ttft_base:.1f}ms "
            f"({ttft_ratio:.2f}x; >= 1.3x required)"
        )

    pf_tps = {
        "base": eng_b.stats["prefill_tokens"] / dt_b,
        "inter": eng_i.stats["prefill_tokens"] / dt_i,
        "flash": eng_f.stats["prefill_tokens"] / dt_f,
    }
    pf_ratio = pf_tps["flash"] / max(pf_tps["base"], 1e-9)
    if on_tpu and pf_ratio < 0.9:
        raise SystemExit(
            f"flash prefill kernel slowed prefill throughput: "
            f"{pf_tps['flash']:.1f} vs gather/scatter {pf_tps['base']:.1f} "
            f"prompt tokens/s ({pf_ratio:.2f}x; >= 0.9x required)"
        )

    def arm_detail(eng, dt, reg):
        snap = reg.snapshot()
        out = {
            "tokens_per_s": round(useful_tokens / dt, 2),
            "wall_s": round(dt, 3),
            "prefill_tokens_per_s": round(eng.stats["prefill_tokens"] / dt, 2),
            "interleaved_chunks": eng.stats["interleaved_chunks"],
            "prefill_chunks": eng.stats["prefill_chunks"],
            "interleave_ratio": round(
                float(snap.get("serve/prefill_interleave_ratio", 0.0)), 3),
            "compiled_executables": eng.compiled_executable_counts(),
        }
        for cls in ("chat", "bulk"):
            h = snap.get(f"serve/ttft_s_class_{cls}")
            if h:
                out[f"ttft_{cls}_p50_ms"] = round(1e3 * h["p50"], 2)
                out[f"ttft_{cls}_p99_ms"] = round(1e3 * h["p99"], 2)
        return out

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "chat_requests": n_chat,
        "bulk_requests": n_bulk,
        "num_slots": slots,
        "decode_window": window,
        "page_size": page,
        "max_len": max_len,
        "bulk_prompt_lens": [int(p) for p in bulk_plens],
        "useful_tokens": useful_tokens,
        "ttft_gate_arm": "flash" if on_tpu else "inter",
        "chat_ttft_p99_ratio_inter": round(ttft_base / max(ttft_inter, 1e-9), 3),
        "chat_ttft_p99_ratio_flash": round(ttft_base / max(ttft_flash, 1e-9), 3),
        "prefill_tokens_per_s_ratio_flash": round(pf_ratio, 3),
        "prefill_tps_gate": "hard" if on_tpu else "report-only (interpret)",
        "base": arm_detail(eng_b, dt_b, reg_b),
        "inter": arm_detail(eng_i, dt_i, reg_i),
        "flash": arm_detail(eng_f, dt_f, reg_f),
    }
    return {
        "metric": "serving_chat_ttft_p99_interleave_speedup",
        "value": round(ttft_ratio, 3),
        "unit": "x",
        "vs_baseline": round(ttft_ratio, 3),
        "detail": detail,
    }


def _http_ab_bench(args, model, cfg, params, preset):
    """Over-the-wire A/B of the OpenAI front door against the in-process engine.

    Four arms over one workload, each a HARD check (SystemExit on failure):

    * identity — concurrent greedy ``POST /v1/completions`` must return
      token-identical outputs to the same engine driven in-process
      (``eng.serve``) before the HTTP stack was attached;
    * streaming — every streamed request's first SSE token chunk must arrive
      strictly before its own completion ([DONE]) — TTFT < full latency;
    * flood — a burst far past ``max_queue`` must surface >= 1 HTTP 429
      (with Retry-After) and NOTHING but 200/429: admission refusals never
      become engine errors, and every 200 stays token-identical;
    * hot-swap — workers keep requests in flight while the main thread
      rolls new weights through ``FrontDoor.hot_swap``; zero failed
      requests, and every response must equal ENTIRELY the old-weights or
      ENTIRELY the new-weights in-process reference (the drain barrier
      means no request ever sees both).

    ``value`` is over-the-wire tokens/s; ``vs_baseline`` divides by the
    in-process ``eng.serve`` tokens/s on the same workload — the full HTTP +
    SSE + ticket-crossing overhead in one ratio.
    """
    import http.client
    import threading

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine
    from accelerate_tpu.serving.api import ApiServer, FrontDoor
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    slots = args.batch
    window = args.decode_window
    max_len = cfg.max_seq_len
    mp = max(8, min(args.seq, max_len) // 4)
    buckets = tuple(sorted({max(8, mp // 2), mp}))
    new_tokens = 4 * window                    # >= 2 decode windows: the first
    n = args.requests                          # SSE chunk beats [DONE]

    r = np.random.default_rng(args.serve_seed)
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, n)), 4, mp
    ).astype(int)
    prompts = [r.integers(1, cfg.vocab_size, (int(k),)).astype(np.int32)
               for k in prompt_lens]
    gen = GenerationConfig(max_new_tokens=new_tokens)
    useful_tokens = n * new_tokens

    # the queue must hold the whole in-process reference workload (serve()
    # submits every request before stepping); the flood arm scales past it
    mq = max(8, slots, n)
    registry = MetricsRegistry()
    eng = ServingEngine(
        model, params, num_slots=slots, max_len=min(max_len, mp + new_tokens + window),
        prefill_buckets=buckets, max_prompt_len=mp, decode_window=window,
        registry=registry, max_queue=mq,
    )
    warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets]
    eng.serve(warm, GenerationConfig(max_new_tokens=window))

    # in-process reference + baseline timing: same engine, same executables
    t0 = time.perf_counter()
    reqs = eng.serve(prompts, [gen] * n)
    dt_inproc = time.perf_counter() - t0
    old_ref = [[int(t) for t in q.tokens] for q in reqs]

    router = ReplicaRouter([eng])
    fd = FrontDoor(router, model_name=f"bench-{preset}").start()
    srv = ApiServer(fd, registry=registry)
    host, port = srv.host, srv.port

    def post_json(path, payload, timeout=600.0):
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", path, json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            return resp.status, dict(resp.getheaders()), json.loads(raw)
        finally:
            conn.close()

    def completion(i, max_tokens=new_tokens):
        return post_json("/v1/completions", {
            "prompt": [int(t) for t in prompts[i]],
            "max_tokens": max_tokens, "temperature": 0,
        })

    def fanout(fn, work):
        """Run ``fn(*item)`` for every work item on its own thread."""
        out = [None] * len(work)

        def run(k, item):
            try:
                out[k] = fn(*item)
            except Exception as exc:  # surfaced as a hard bench failure
                out[k] = exc

        threads = [threading.Thread(target=run, args=(k, item), daemon=True)
                   for k, item in enumerate(work)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = [o for o in out if isinstance(o, Exception)]
        if errs:
            raise SystemExit(f"--http-ab: client transport error: {errs[0]!r}")
        return out

    # ---- arm 1: identity (concurrent, timed — the throughput number)
    t0 = time.perf_counter()
    responses = fanout(completion, [(i,) for i in range(n)])
    dt_http = time.perf_counter() - t0
    for i, (status, _, body) in enumerate(responses):
        if status != 200:
            raise SystemExit(f"--http-ab identity: request {i} got HTTP "
                             f"{status}: {body}")
        got = body["choices"][0]["token_ids"]
        if got != old_ref[i]:
            raise SystemExit(
                f"--http-ab identity: request {i} over-the-wire tokens "
                f"{got[:8]}... != in-process {old_ref[i][:8]}..."
            )

    # ---- arm 2: streaming — TTFT strictly before the same request's [DONE]
    def stream_one(i):
        conn = http.client.HTTPConnection(host, port, timeout=600.0)
        try:
            t0 = time.perf_counter()
            conn.request("POST", "/v1/completions", json.dumps({
                "prompt": [int(t) for t in prompts[i]],
                "max_tokens": new_tokens, "temperature": 0, "stream": True,
            }), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise SystemExit(f"--http-ab stream: request {i} got HTTP "
                                 f"{resp.status}")
            toks, t_first, saw_done = [], None, False
            for raw in iter(resp.readline, b""):
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[len(b"data: "):]
                if data == b"[DONE]":
                    saw_done = True
                    break
                ids = json.loads(data)["choices"][0]["token_ids"]
                if ids and t_first is None:
                    t_first = time.perf_counter() - t0
                toks.extend(int(t) for t in ids)
            return t_first, time.perf_counter() - t0, toks, saw_done
        finally:
            conn.close()

    n_stream = min(n, 8)
    ttfts, fulls = [], []
    for i in range(n_stream):
        ttft, full, toks, saw_done = stream_one(i)
        if not saw_done:
            raise SystemExit(f"--http-ab stream: request {i} never got the "
                             "data: [DONE] terminator")
        if toks != old_ref[i]:
            raise SystemExit(f"--http-ab stream: request {i} streamed tokens "
                             "diverge from the in-process reference")
        if ttft is None or not ttft < full:
            raise SystemExit(
                f"--http-ab stream: request {i} first token at "
                f"{ttft}s did not beat its own completion ({full:.3f}s) — "
                "SSE is buffering the whole response"
            )
        ttfts.append(ttft)
        fulls.append(full)

    # ---- arm 3: flood — burst far past max_queue; 429s, never engine errors
    flood_n = 6 * mq
    flood = fanout(lambda i: completion(i % n, window),
                   [(i,) for i in range(flood_n)])
    n_429 = sum(1 for status, _, _ in flood if status == 429)
    bad = [(status, body) for status, _, body in flood
           if status not in (200, 429)]
    if bad:
        raise SystemExit(f"--http-ab flood: non-200/429 response: {bad[0]}")
    if n_429 < 1:
        raise SystemExit(
            f"--http-ab flood: {flood_n} concurrent requests against "
            f"max_queue={mq} produced zero 429s — backpressure is not wired"
        )
    for status, headers, _ in flood:
        if status == 429 and "Retry-After" not in headers:
            raise SystemExit("--http-ab flood: 429 without a Retry-After hint")
    for k, (status, _, body) in enumerate(flood):
        if status == 200 and body["choices"][0]["token_ids"] != old_ref[k % n][:window]:
            raise SystemExit(f"--http-ab flood: admitted request {k} returned "
                             "corrupted tokens under load")

    # ---- arm 4: hot-swap under fire — zero failed, zero mixed-weight outputs
    params2 = jax.tree_util.tree_map(lambda x: x * 1.01, params)
    n_probe = min(n, 8)
    swap_results = []
    swap_lock = threading.Lock()
    stop = threading.Event()

    def hammer(widx):
        k = 0
        while not stop.is_set():
            i = (widx + k) % n_probe
            k += 1
            status, _, body = completion(i)
            with swap_lock:
                swap_results.append((i, status, body))

    workers = [threading.Thread(target=hammer, args=(w,), daemon=True)
               for w in range(3)]
    for t in workers:
        t.start()
    time.sleep(0.2)                      # get requests genuinely in flight
    n_swapped = fd.hot_swap(params2, version="v1")
    time.sleep(0.2)                      # a few post-swap requests too
    stop.set()
    for t in workers:
        t.join()
    if n_swapped != 1:
        raise SystemExit(f"--http-ab hot-swap: swapped {n_swapped} replicas, "
                         "expected 1")

    srv.stop()
    fd.stop()
    # the engine is single-threaded again: new-weights in-process reference
    new_reqs = eng.serve([prompts[i] for i in range(n_probe)], [gen] * n_probe)
    new_ref = [[int(t) for t in q.tokens] for q in new_reqs]
    n_old = n_new = 0
    for i, status, body in swap_results:
        if status != 200:
            raise SystemExit(f"--http-ab hot-swap: in-flight request failed "
                             f"with HTTP {status}: {body}")
        got = body["choices"][0]["token_ids"]
        if got == old_ref[i]:
            n_old += 1
        elif got == new_ref[i]:
            n_new += 1
        else:
            raise SystemExit(
                f"--http-ab hot-swap: probe {i} returned tokens matching "
                "NEITHER weights version entirely — a request crossed the "
                "swap barrier mid-decode"
            )
    if not swap_results:
        raise SystemExit("--http-ab hot-swap: no requests were in flight")

    http_tps = useful_tokens / dt_http
    snap = registry.snapshot()
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": slots,
        "decode_window": window,
        "max_queue": mq,
        "new_tokens_per_request": new_tokens,
        "useful_tokens": useful_tokens,
        "http_wall_s": round(dt_http, 3),
        "inproc_wall_s": round(dt_inproc, 3),
        "inproc_tokens_per_s": round(useful_tokens / dt_inproc, 2),
        "outputs_token_identical": True,       # hard-checked above
        "streaming": {
            "requests": n_stream,
            "ttft_p50_s": round(float(np.median(ttfts)), 4),
            "full_p50_s": round(float(np.median(fulls)), 4),
            "ttft_beats_completion": True,     # hard-checked above
        },
        "flood": {
            "requests": flood_n,
            "http_429": n_429,
            "http_200": sum(1 for s, _, _ in flood if s == 200),
            "engine_errors": 0,                # hard-checked above
        },
        "hot_swap": {
            "replicas_swapped": n_swapped,
            "in_flight_requests": len(swap_results),
            "served_old_weights": n_old,
            "served_new_weights": n_new,
            "failed": 0,                       # hard-checked above
        },
        "http_requests_total": int(snap.get("serve/http_requests_total", 0)),
        "http_429_total": int(snap.get("serve/http_429_total", 0)),
        "hot_swaps_total": int(snap.get("serve/hot_swaps_total", 0)),
    }
    return {
        "metric": "http_serving_tokens_per_sec",
        "value": round(http_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(http_tps / (useful_tokens / dt_inproc), 3),
        "detail": detail,
    }


def _chaos_ab_bench(args, model, cfg, params, preset):
    """Chaos A/B: replica failure, seeded fault soak, zero-cost-when-off.

    Three arms over one greedy workload, each a HARD check (SystemExit):

    * kill — two paged replicas behind the front door; the busy one is
      poisoned mid-decode (``ServingEngine.kill``, the ``replica_kill``
      stand-in for a device loss).  Every concurrent request must still
      return HTTP 200 with tokens identical to the pre-chaos in-process
      reference (in-flight lanes replay on the survivor from prompt +
      generated prefix; greedy replay is token-exact), the router must
      record >= 1 ejection, and the dead replica must re-admit through the
      half-open circuit breaker before the arm ends;
    * soak — a seeded probabilistic fault mix (stalled fetches, injected
      page exhaustion, a one-shot fetch failure and a one-shot dispatch
      error) runs under a 2x concurrent burst: >= 99% of requests must
      complete HTTP 200 token-identical, and ZERO ``serve/driver_error``
      flight events may land — infrastructure faults never crash the
      FrontDoor driver thread;
    * off — with faults disabled the hot path must cost nothing: the
      disabled serve must be within 1% of an armed-but-inert run
      (interleaved best-of-N mins damp CPU noise), and the compile counts
      of every watchdog on both replicas must be IDENTICAL to the
      pre-chaos snapshot — kill, replay, preemption and the fault checks
      compiled zero new executables.

    ``value`` is over-the-wire tokens/s during the kill arm;
    ``vs_baseline`` divides by the in-process ``eng.serve`` tokens/s on the
    same workload — what surviving a replica loss costs end to end.
    """
    import http.client
    import threading

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine, faults
    from accelerate_tpu.serving.api import ApiServer, FrontDoor
    from accelerate_tpu.telemetry import MetricsRegistry, get_flight_recorder

    params = jax.device_put(params)
    slots = args.batch
    window = args.decode_window
    page = 4
    # page-aligned geometry: paged replicas so the injected page_exhaustion
    # point exercises the real preemption ladder
    mp = -(-max(8, min(args.seq, cfg.max_seq_len) // 4) // page) * page
    buckets = tuple(sorted({max(8, -(-(mp // 2) // page) * page), mp}))
    new_tokens = 4 * window
    n = args.requests
    max_len = min(cfg.max_seq_len, -(-(mp + new_tokens + window) // page) * page)
    # generous pool: exhaustion in this bench is INJECTED, a tight pool
    # would add real (but still deterministic) preemptions on top
    num_pages = 2 * slots * (max_len // page) + 1
    # the soak arm replays one replica's whole in-flight set plus a 2x burst
    # onto the survivor; the queue must absorb all of it without 429s
    mq = max(8, slots, 4 * n)

    r = np.random.default_rng(args.serve_seed)
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, n)), 4, mp
    ).astype(int)
    prompts = [r.integers(1, cfg.vocab_size, (int(k),)).astype(np.int32)
               for k in prompt_lens]
    gen = GenerationConfig(max_new_tokens=new_tokens)
    useful_tokens = n * new_tokens

    registry = MetricsRegistry()

    def build():
        return ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            prefill_buckets=buckets, decode_window=window,
            registry=registry, max_queue=mq, paged=True, page_size=page,
            num_pages=num_pages, prefix_cache_mb=0,
        )

    e1, e2 = build(), build()
    warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32)
            for b in buckets]
    for e in (e1, e2):
        e.serve(warm, GenerationConfig(max_new_tokens=window))

    # in-process reference + baseline timing (identical weights on both
    # replicas: greedy tokens are replica-independent)
    t0 = time.perf_counter()
    reqs = e1.serve(prompts, [gen] * n)
    dt_inproc = time.perf_counter() - t0
    ref = [[int(t) for t in q.tokens] for q in reqs]

    def compile_counts():
        return {f"r{k}/{wd.name}": wd.compile_count
                for k, e in enumerate((e1, e2))
                for wd in [e._decode, e._lane_install, e._copy_page,
                           *e._prefill.values()]
                if wd is not None}

    compiles_before = compile_counts()
    flight = get_flight_recorder()

    def driver_errors():
        return sum(1 for ev in flight.tail()
                   if ev.get("kind") == "serve/driver_error")

    derr_before = driver_errors()

    router = ReplicaRouter([e1, e2], registry=registry, breaker_base_s=0.05)
    fd = FrontDoor(router, model_name=f"bench-{preset}").start()
    srv = ApiServer(fd, registry=registry)
    host, port = srv.host, srv.port

    def post_json(path, payload, timeout=600.0):
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", path, json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            return resp.status, dict(resp.getheaders()), json.loads(raw)
        finally:
            conn.close()

    def completion(i, max_tokens=new_tokens):
        return post_json("/v1/completions", {
            "prompt": [int(t) for t in prompts[i]],
            "max_tokens": max_tokens, "temperature": 0,
        })

    def fanout(fn, work):
        out = [None] * len(work)

        def run(k, item):
            try:
                out[k] = fn(*item)
            except Exception as exc:  # surfaced as a hard bench failure
                out[k] = exc

        threads = [threading.Thread(target=run, args=(k, item), daemon=True)
                   for k, item in enumerate(work)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = [o for o in out if isinstance(o, Exception)]
        if errs:
            raise SystemExit(f"--chaos-ab: client transport error: {errs[0]!r}")
        return out

    # ---- arm 1: replica kill mid-generation — zero failed, token identity
    killed = {}

    def assassin():
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            for name, e in (("r1", e2), ("r0", e1)):
                if e in router.engines and e._active.any():
                    e.kill("chaos-ab: injected mid-decode device loss")
                    killed["replica"] = name
                    return
            time.sleep(0.002)

    kt = threading.Thread(target=assassin, daemon=True)
    kt.start()
    t0 = time.perf_counter()
    responses = fanout(completion, [(i,) for i in range(n)])
    dt_chaos = time.perf_counter() - t0
    kt.join()
    if "replica" not in killed:
        raise SystemExit("--chaos-ab kill: no replica ever had in-flight "
                         "lanes to kill — the workload never got going")
    for i, (status, _, body) in enumerate(responses):
        if status != 200:
            raise SystemExit(f"--chaos-ab kill: request {i} failed with HTTP "
                             f"{status} after the replica kill: {body}")
        got = body["choices"][0]["token_ids"]
        if got != ref[i]:
            raise SystemExit(
                f"--chaos-ab kill: request {i} returned {got[:8]}... != "
                f"in-process reference {ref[i][:8]}... — replay after the "
                "kill was not token-identical"
            )
    snap = registry.snapshot()
    ejections = int(snap.get("serve/replica_ejections_total", 0))
    if ejections < 1:
        raise SystemExit("--chaos-ab kill: a replica was poisoned but "
                         "serve/replica_ejections_total is 0 — the router "
                         "supervisor never ejected it")
    replays = sum(e.stats["requests_replayed"] for e in (e1, e2))
    t_end = time.monotonic() + 30.0
    while time.monotonic() < t_end and len(router.engines) < 2:
        time.sleep(0.01)
    if len(router.engines) < 2:
        raise SystemExit("--chaos-ab kill: the ejected replica never "
                         "re-admitted through the half-open circuit breaker")

    # ---- arm 2: seeded fault-mix soak — >= 99% completion, driver survives
    soak_n = 2 * n
    soak_plan = (f"seed={args.serve_seed},fetch_slow=0.05,slow_ms=5,"
                 f"page_exhaustion=0.01,fetch_fail@7,decode_dispatch@29")
    faults.install(soak_plan, registry=registry)
    try:
        soak = fanout(completion, [(i % n,) for i in range(soak_n)])
    finally:
        faults.clear()
    completed = sum(
        1 for k, (status, _, body) in enumerate(soak)
        if status == 200 and body["choices"][0]["token_ids"] == ref[k % n]
    )
    for k, (status, _, body) in enumerate(soak):
        if status == 200 and body["choices"][0]["token_ids"] != ref[k % n]:
            raise SystemExit(
                f"--chaos-ab soak: request {k} returned HTTP 200 with "
                "tokens diverging from the reference — a fault corrupted a "
                "surviving lane"
            )
    rate = completed / soak_n
    if rate < 0.99:
        bad = [(k, s) for k, (s, _, _) in enumerate(soak) if s != 200]
        raise SystemExit(
            f"--chaos-ab soak: {completed}/{soak_n} completed "
            f"({rate:.1%}) under the fault mix; gate is >= 99%. "
            f"non-200s: {bad[:5]}"
        )
    derr = driver_errors() - derr_before
    if derr != 0:
        raise SystemExit(
            f"--chaos-ab soak: {derr} serve/driver_error flight event(s) — "
            "an injected fault escaped containment and crashed the "
            "FrontDoor driver thread"
        )
    faults_fired = int(registry.snapshot().get(
        "serve/faults_injected_total", 0))
    if faults_fired < 1:
        raise SystemExit("--chaos-ab soak: the fault plan never fired — the "
                         "soak arm tested nothing")
    t_end = time.monotonic() + 30.0
    while time.monotonic() < t_end and len(router.engines) < 2:
        time.sleep(0.01)

    srv.stop()
    fd.stop()

    # ---- arm 3: faults disabled — zero hot-path cost, zero new executables
    # interleave disabled and armed-but-inert (one-shot parked far beyond
    # the workload: every check consults the injector, none fire) runs,
    # alternating which goes first, and gate on the MEDIAN of per-rep
    # paired ratios: back-to-back pairs cancel machine drift, alternation
    # cancels ordering bias, the median kills outlier pairs — min-of-N on
    # its own still carries multi-percent jitter on shared hosts
    reps = 8
    rounds = 3  # serve() calls per timed sample — lifts each sample well
    # above scheduler/timer jitter so the 1% gate measures the hot path
    t_off, t_armed = [], []
    inert = f"seed={args.serve_seed},decode_dispatch@1000000000"
    faults.clear()
    e1.serve(prompts, [gen] * n)  # discarded warm-up

    def _timed_off():
        faults.clear()
        t0 = time.perf_counter()
        for _ in range(rounds):
            e1.serve(prompts, [gen] * n)
        t_off.append(time.perf_counter() - t0)

    def _timed_armed():
        faults.install(inert, registry=registry)
        try:
            t0 = time.perf_counter()
            for _ in range(rounds):
                e1.serve(prompts, [gen] * n)
            t_armed.append(time.perf_counter() - t0)
        finally:
            faults.clear()

    for k in range(reps):
        first, second = ((_timed_off, _timed_armed) if k % 2 == 0
                         else (_timed_armed, _timed_off))
        first()
        second()
    best_off, best_armed = min(t_off), min(t_armed)
    ratios = sorted(o / a for o, a in zip(t_off, t_armed))
    mid = len(ratios) // 2
    med_ratio = (ratios[mid] if len(ratios) % 2
                 else 0.5 * (ratios[mid - 1] + ratios[mid]))
    if med_ratio > 1.01:
        raise SystemExit(
            f"--chaos-ab off: faults-disabled serve is {med_ratio - 1.0:+.1%} "
            f"vs the armed-but-inert run (median of {reps} paired ratios; "
            f"mins {best_off:.3f}s vs {best_armed:.3f}s) — the disabled "
            "path is doing work; gate is <= 1%"
        )
    compiles_after = compile_counts()
    if compiles_after != compiles_before:
        diff = {k: (compiles_before.get(k), v)
                for k, v in compiles_after.items()
                if compiles_before.get(k) != v}
        raise SystemExit(f"--chaos-ab off: chaos compiled new executables "
                         f"(name: before -> after): {diff}")

    chaos_tps = useful_tokens / dt_chaos
    snap = registry.snapshot()
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": slots,
        "decode_window": window,
        "new_tokens_per_request": new_tokens,
        "useful_tokens": useful_tokens,
        "chaos_wall_s": round(dt_chaos, 3),
        "inproc_wall_s": round(dt_inproc, 3),
        "inproc_tokens_per_s": round(useful_tokens / dt_inproc, 2),
        "kill": {
            "killed_replica": killed["replica"],
            "failed": 0,                       # hard-checked above
            "outputs_token_identical": True,   # hard-checked above
            "ejections": ejections,
            "requests_replayed": replays,
            "breaker_readmitted": True,        # hard-checked above
        },
        "soak": {
            "plan": soak_plan,
            "requests": soak_n,
            "completed": completed,
            "completion_rate": round(rate, 4),
            "faults_injected": faults_fired,
            "driver_errors": 0,                # hard-checked above
        },
        "off": {
            "repeats": reps,
            "disabled_best_s": round(best_off, 4),
            "armed_inert_best_s": round(best_armed, 4),
            "disabled_vs_armed": round(best_off / best_armed, 4),
            "disabled_vs_armed_median": round(med_ratio, 4),
            "new_executables": 0,              # hard-checked above
        },
        "replica_ejections_total": int(
            snap.get("serve/replica_ejections_total", 0)),
        "requests_replayed_total": sum(
            e.stats["requests_replayed"] for e in (e1, e2)),
        "faults_injected_total": int(
            snap.get("serve/faults_injected_total", 0)),
        "deadline_shed_total": sum(
            e.stats["deadline_shed"] for e in (e1, e2)),
    }
    return {
        "metric": "chaos_serving_tokens_per_sec",
        "value": round(chaos_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(chaos_tps / (useful_tokens / dt_inproc), 3),
        "detail": detail,
    }


def _trace_ab_bench(args, model, cfg, params, preset):
    """Request-trace A/B: waterfall fidelity on vs zero cost off.

    Three arms over one greedy workload, each a HARD check (SystemExit):

    * waterfall — two paged replicas behind the front door; the busy one is
      killed mid-decode.  Every request must return HTTP 200 token-identical
      to the in-process reference, and every response's ``X-Request-Id``
      must resolve at ``GET /debug/requests/<id>`` to a waterfall whose
      tiled phase sum attributes the trace's own TTFT within 5% (20ms
      noise floor on shared CPU hosts).  At least one surviving request
      must carry a ``failover`` phase spanning BOTH replica ids — the
      trace rode ``export_inflight``/``adopt`` instead of restarting —
      and the ``/debug/requests`` index must hold populated slowest-K
      rings (the tail the tracing exists to explain);
    * off — tracing toggled off (``reqtrace.set_enabled(False)``) must
      serve token-identical to tracing on, and the null-calibrated paired
      overhead (pooled median of rotating on/off/control min-of-2 samples)
      must be <= 1% beyond the off-vs-off control drift measured in the
      same run — per-request attribution may not tax serve throughput;
    * budget — compile counts of every watchdog on both replicas must be
      IDENTICAL before and after: tracing is host-side bookkeeping and
      compiles NOTHING.

    ``value`` is over-the-wire tokens/s during the kill arm (the traced,
    failover-surviving path); ``vs_baseline`` divides by in-process
    ``eng.serve`` tokens/s on the same workload.
    """
    import http.client
    import threading

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine
    from accelerate_tpu.serving.api import ApiServer, FrontDoor
    from accelerate_tpu.telemetry import MetricsRegistry, get_reqtrace
    from accelerate_tpu.telemetry import reqtrace as reqtrace_mod

    params = jax.device_put(params)
    slots = args.batch
    window = args.decode_window
    page = 4
    mp = -(-max(8, min(args.seq, cfg.max_seq_len) // 4) // page) * page
    buckets = tuple(sorted({max(8, -(-(mp // 2) // page) * page), mp}))
    new_tokens = 4 * window
    n = args.requests
    max_len = min(cfg.max_seq_len, -(-(mp + new_tokens + window) // page) * page)
    num_pages = 2 * slots * (max_len // page) + 1
    mq = max(8, slots, 2 * n)

    r = np.random.default_rng(args.serve_seed)
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, n)), 4, mp
    ).astype(int)
    prompts = [r.integers(1, cfg.vocab_size, (int(k),)).astype(np.int32)
               for k in prompt_lens]
    gen = GenerationConfig(max_new_tokens=new_tokens)
    useful_tokens = n * new_tokens

    registry = MetricsRegistry()
    reqtrace_mod.set_enabled(None)
    get_reqtrace().reset()

    def build():
        return ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            prefill_buckets=buckets, decode_window=window,
            registry=registry, max_queue=mq, paged=True, page_size=page,
            num_pages=num_pages, prefix_cache_mb=0,
        )

    e1, e2 = build(), build()
    warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32)
            for b in buckets]
    for e in (e1, e2):
        e.serve(warm, GenerationConfig(max_new_tokens=window))

    t0 = time.perf_counter()
    reqs = e1.serve(prompts, [gen] * n)
    dt_inproc = time.perf_counter() - t0
    ref = [[int(t) for t in q.tokens] for q in reqs]

    def compile_counts():
        return {f"r{k}/{wd.name}": wd.compile_count
                for k, e in enumerate((e1, e2))
                for wd in [e._decode, e._lane_install, e._copy_page,
                           *e._prefill.values()]
                if wd is not None}

    compiles_before = compile_counts()
    get_reqtrace().reset()  # warmup/reference traces are not part of the arm

    router = ReplicaRouter([e1, e2], registry=registry, breaker_base_s=0.05)
    fd = FrontDoor(router, model_name=f"bench-{preset}").start()
    srv = ApiServer(fd, registry=registry)
    host, port = srv.host, srv.port

    def http_json(method, path, payload=None, timeout=600.0):
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            body = None if payload is None else json.dumps(payload)
            headers = {} if payload is None else {
                "Content-Type": "application/json"}
            conn.request(method, path, body, headers)
            resp = conn.getresponse()
            raw = resp.read()
            return resp.status, dict(resp.getheaders()), json.loads(raw)
        finally:
            conn.close()

    def completion(i):
        return http_json("POST", "/v1/completions", {
            "prompt": [int(t) for t in prompts[i]],
            "max_tokens": new_tokens, "temperature": 0,
        })

    def fanout(fn, work):
        out = [None] * len(work)

        def run(k, item):
            try:
                out[k] = fn(*item)
            except Exception as exc:
                out[k] = exc

        threads = [threading.Thread(target=run, args=(k, item), daemon=True)
                   for k, item in enumerate(work)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = [o for o in out if isinstance(o, Exception)]
        if errs:
            raise SystemExit(f"--trace-ab: client transport error: {errs[0]!r}")
        return out

    # ---- arm 1: traced workload + mid-generation kill — waterfall fidelity
    killed = {}

    def assassin():
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            for name, e in (("r1", e2), ("r0", e1)):
                if e in router.engines and e._active.any():
                    e.kill("trace-ab: injected mid-decode device loss")
                    killed["replica"] = name
                    return
            time.sleep(0.002)

    kt = threading.Thread(target=assassin, daemon=True)
    kt.start()
    t0 = time.perf_counter()
    responses = fanout(completion, [(i,) for i in range(n)])
    dt_traced = time.perf_counter() - t0
    kt.join()
    if "replica" not in killed:
        raise SystemExit("--trace-ab: no replica ever had in-flight lanes "
                         "to kill — the workload never got going")

    failovers = 0
    worst_attr_err = 0.0
    for i, (status, headers, body) in enumerate(responses):
        if status != 200:
            raise SystemExit(f"--trace-ab: request {i} failed with HTTP "
                             f"{status} after the replica kill: {body}")
        got = body["choices"][0]["token_ids"]
        if got != ref[i]:
            raise SystemExit(
                f"--trace-ab: request {i} returned {got[:8]}... != "
                f"in-process reference {ref[i][:8]}... under tracing"
            )
        rid = headers.get("X-Request-Id")
        if not rid:
            raise SystemExit(f"--trace-ab: request {i} response carried no "
                             "X-Request-Id header")
        wstatus, _, wf = http_json("GET", f"/debug/requests/{rid}")
        if wstatus != 200:
            raise SystemExit(
                f"--trace-ab: GET /debug/requests/{rid} -> {wstatus}; the "
                "completed trace fell out of retention while addressable"
            )
        if wf["status"] != "done":
            raise SystemExit(f"--trace-ab: request {i} trace status "
                             f"{wf['status']!r} != 'done'")
        ttft, attr = wf["ttft_s"], wf["ttft_attributed_s"]
        err = abs(attr - ttft)
        worst_attr_err = max(worst_attr_err, err / max(ttft, 1e-9))
        if err > max(0.05 * ttft, 0.02):
            raise SystemExit(
                f"--trace-ab: request {i} ({rid}) phase sum {attr:.4f}s "
                f"diverges from measured TTFT {ttft:.4f}s by more than "
                "max(5%, 20ms) — the waterfall does not attribute latency"
            )
        if wf["failover"]:
            failovers += 1
            if len(wf["replicas"]) < 2:
                raise SystemExit(
                    f"--trace-ab: failover trace {rid} lists replicas "
                    f"{wf['replicas']} — the trace did not span both"
                )
            if not any(p["phase"] == "failover" for p in wf["phase_list"]):
                raise SystemExit(
                    f"--trace-ab: failover trace {rid} has no 'failover' "
                    "phase — adoption restarted the waterfall"
                )
    if failovers < 1:
        raise SystemExit("--trace-ab: a replica died mid-generation but no "
                         "completed trace records a failover — the trace "
                         "did not ride export_inflight/adopt")
    istatus, _, index = http_json("GET", "/debug/requests")
    if istatus != 200:
        raise SystemExit(f"--trace-ab: GET /debug/requests -> {istatus}")
    if not index["slowest_ttft"] or not index["slowest_total"]:
        raise SystemExit("--trace-ab: the slowest-K retention rings are "
                         "empty after a full workload — tail-based "
                         "retention is not retaining the tail")

    t_end = time.monotonic() + 30.0
    while time.monotonic() < t_end and len(router.engines) < 2:
        time.sleep(0.01)
    srv.stop()
    fd.stop()

    # ---- arm 2: tracing off — token identity + <= 1% interleaved overhead
    reqtrace_mod.set_enabled(False)
    try:
        off_reqs = e1.serve(prompts, [gen] * n)
    finally:
        reqtrace_mod.set_enabled(None)
    off_tokens = [[int(t) for t in q.tokens] for q in off_reqs]
    if off_tokens != ref:
        raise SystemExit("--trace-ab: tokens with tracing disabled diverge "
                         "from the traced reference — the trace hooks "
                         "touch the decode path")

    # Overhead is measured as a NULL-CALIBRATED paired A/B.  Three arms
    # rotate back to back per pair — tracing ON, tracing OFF, and a second
    # tracing-off CONTROL with identical plumbing.  Each sample is the min
    # of two consecutive serves (host contention is one-sided; the min
    # filters the spike tail), and the pooled medians are re-checked after
    # each sequential batch with early exit.  The gate is
    #
    #     median(on/off)  <=  1.01 + |median(ctl/off) - 1|
    #
    # i.e. tracing may cost at most 1% BEYOND what the instrument itself
    # drifts between two IDENTICAL arms in the same run.  On a quiet host
    # the control median sits at 1.000 and the gate is a strict 1%; on a
    # host where two identical arms differ by 2%, a 1% verdict would be
    # astrology — the demonstrated noise floor widens the gate by exactly
    # what the null shows, and a real multi-percent regression still fails
    # because the control does not move with the treatment.
    # The arm runs on a FRESH replica with a reset registry: e1's
    # post-kill state differs run to run (it may or may not be the revived
    # victim), and the retention rings full of HTTP-arm traces were already
    # hard-checked above — what this arm isolates is the steady marginal
    # cost of tracing on a healthy replica.
    # One more defence: pairs where EITHER sample sits far above its own
    # arm's floor were hit by a contention burst mid-pair — both medians
    # drop them (symmetrically, so a real regression cannot hide: a serve
    # that is slower BECAUSE of tracing raises the on-arm floor itself and
    # survives the trim).  The gate judges the uncontended regime, which
    # is the regime "<= 1% overhead" is a statement about.
    pairs_per_batch = 24
    max_batches = 4
    min_kept = 12
    t_on, t_off, t_ctl = [], [], []
    e3 = build()
    e3.serve(warm, GenerationConfig(max_new_tokens=window))
    get_reqtrace().reset()
    for _ in range(2):  # discarded warm-up; also settles server teardown
        e3.serve(prompts, [gen] * n)

    def _timed(flag, sink):
        reqtrace_mod.set_enabled(flag)
        try:
            best = None
            for _ in range(2):
                t0 = time.perf_counter()
                e3.serve(prompts, [gen] * n)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            sink.append(best)
        finally:
            reqtrace_mod.set_enabled(None)

    def _median(vals):
        vals = sorted(vals)
        mid = len(vals) // 2
        return (vals[mid] if len(vals) % 2
                else 0.5 * (vals[mid - 1] + vals[mid]))

    arms = [(True, t_on), (False, t_off), (False, t_ctl)]
    med_ratio = null_ratio = allowance = None
    for _ in range(max_batches):
        for k in range(pairs_per_batch):
            for flag, sink in arms[k % 3:] + arms[:k % 3]:
                _timed(flag, sink)
        lim_on = 1.25 * min(t_on)
        lim_off = 1.25 * min(t_off)
        lim_ctl = 1.25 * min(t_ctl)
        kept = [(on, off, c) for on, off, c in zip(t_on, t_off, t_ctl)
                if on <= lim_on and off <= lim_off and c <= lim_ctl]
        if len(kept) < min_kept:
            continue
        med_ratio = _median([on / off for on, off, _ in kept])
        null_ratio = _median([c / off for _, off, c in kept])
        allowance = abs(null_ratio - 1.0)
        if med_ratio <= 1.01 + allowance:
            break
    if med_ratio is None:
        raise SystemExit(
            f"--trace-ab: host contention too heavy to measure — fewer than "
            f"{min_kept} of {len(t_on)} paired samples survived the burst "
            f"trim; rerun on a quieter host"
        )
    if med_ratio > 1.01 + allowance:
        raise SystemExit(
            f"--trace-ab: tracing-on serve is {med_ratio - 1.0:+.1%} vs "
            f"tracing-off (pooled median of {len(t_on)} paired min-of-2 "
            f"samples after burst trim) while the off-vs-off control shows "
            f"{null_ratio - 1.0:+.1%} instrument drift — tracing costs "
            f">1% beyond the demonstrated noise floor; gate is <= "
            f"{1.01 + allowance - 1.0:.1%}"
        )

    # ---- arm 3: tracing compiled nothing
    compiles_after = compile_counts()
    if compiles_after != compiles_before:
        diff = {k: (compiles_before.get(k), v)
                for k, v in compiles_after.items()
                if compiles_before.get(k) != v}
        raise SystemExit(f"--trace-ab: tracing compiled new executables "
                         f"(name: before -> after): {diff}")

    traced_tps = useful_tokens / dt_traced
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": slots,
        "decode_window": window,
        "new_tokens_per_request": new_tokens,
        "useful_tokens": useful_tokens,
        "traced_wall_s": round(dt_traced, 3),
        "inproc_wall_s": round(dt_inproc, 3),
        "inproc_tokens_per_s": round(useful_tokens / dt_inproc, 2),
        "waterfall": {
            "killed_replica": killed["replica"],
            "outputs_token_identical": True,   # hard-checked above
            "failover_traces": failovers,
            "worst_ttft_attribution_error": round(worst_attr_err, 4),
            "slowest_ttft_retained": len(index["slowest_ttft"]),
            "slowest_total_retained": len(index["slowest_total"]),
        },
        "off": {
            "pairs": len(t_on),
            "outputs_token_identical": True,   # hard-checked above
            "on_best_s": round(min(t_on), 4),
            "off_best_s": round(min(t_off), 4),
            "on_vs_off_median": round(med_ratio, 4),
            "off_vs_off_control_median": round(null_ratio, 4),
            "gate": round(1.01 + allowance, 4),
            "new_executables": 0,              # hard-checked above
        },
    }
    return {
        "metric": "traced_serving_tokens_per_sec",
        "value": round(traced_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(traced_tps / (useful_tokens / dt_inproc), 3),
        "detail": detail,
    }


def _slo_ab_bench(args, model, cfg, params, preset):
    """Fleet-health A/B: exact tenant attribution, forced burn, zero cost.

    Four arms over one greedy workload, each a HARD check (SystemExit):

    * tenants — two tenants flood the HTTP front door over two paged
      replicas, half resolved from the ``X-Tenant`` header and half from
      the ``Authorization: Bearer <tenant>-...`` key prefix.  Every 200
      response must echo ``X-Tenant`` and return tokens identical to the
      in-process reference, and for EVERY per-request counter key the
      engines bumped, the per-tenant family deltas must sum EXACTLY to the
      global counter delta (attribution is accounting, not sampling) — the
      per-tenant TTFT histogram counts likewise, and the
      ``stats()["tenants"]`` rollup must agree with the counter families;
    * burn — a TTFT SLO sized off a clean run of the same workload must
      NOT burn clean, then ``fetch_slow`` stalls (the ``ATPU_FAULTS``
      injector) push every TTFT over threshold and the engine must capture
      EXACTLY ONE diagnostics bundle — the cooldown must hold across
      several more fast-burning ticks — whose JSON carries the triggering
      verdict, stacks, the flight-ring tail, and the time-series window
      that shows the burn itself;
    * off — SLOs + tenant attribution + ring sampling on, vs all of it
      off: the null-calibrated paired overhead (same methodology and gate
      as ``--trace-ab``) must be <= 1% beyond the off-vs-off control
      drift, with outputs token-identical;
    * budget — compile counts of every watchdog on all three replicas
      must be IDENTICAL before and after: the fleet-health layer is
      host-side bookkeeping and compiles NOTHING.

    ``value`` is over-the-wire tokens/s during the tenant flood (the
    attributed path); ``vs_baseline`` divides by in-process ``eng.serve``
    tokens/s on the same workload.
    """
    import http.client
    import tempfile
    import threading

    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ReplicaRouter, ServingEngine, faults
    from accelerate_tpu.serving.api import ApiServer, FrontDoor
    from accelerate_tpu.telemetry import (
        MetricsRegistry,
        SloSpec,
        TimeSeriesStore,
        default_specs,
        install_slos,
        uninstall_slos,
    )

    params = jax.device_put(params)
    slots = args.batch
    window = args.decode_window
    page = 4
    mp = -(-max(8, min(args.seq, cfg.max_seq_len) // 4) // page) * page
    buckets = tuple(sorted({max(8, -(-(mp // 2) // page) * page), mp}))
    new_tokens = 4 * window
    n = args.requests
    max_len = min(cfg.max_seq_len, -(-(mp + new_tokens + window) // page) * page)
    num_pages = 2 * slots * (max_len // page) + 1
    mq = max(8, slots, 2 * n)

    r = np.random.default_rng(args.serve_seed)
    prompt_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, n)), 4, mp
    ).astype(int)
    prompts = [r.integers(1, cfg.vocab_size, (int(k),)).astype(np.int32)
               for k in prompt_lens]
    gen = GenerationConfig(max_new_tokens=new_tokens)
    useful_tokens = n * new_tokens
    tenants = ("acme", "umbrella")

    registry = MetricsRegistry()
    uninstall_slos()  # a leftover global engine would tick into our arms

    def build():
        return ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            prefill_buckets=buckets, decode_window=window,
            registry=registry, max_queue=mq, paged=True, page_size=page,
            num_pages=num_pages, prefix_cache_mb=0,
        )

    e1, e2, e3 = build(), build(), build()
    warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32)
            for b in buckets]
    for e in (e1, e2, e3):
        e.serve(warm, GenerationConfig(max_new_tokens=window))

    t0 = time.perf_counter()
    reqs = e1.serve(prompts, [gen] * n)
    dt_inproc = time.perf_counter() - t0
    ref = [[int(t) for t in q.tokens] for q in reqs]

    def compile_counts():
        return {f"r{k}/{wd.name}": wd.compile_count
                for k, e in enumerate((e1, e2, e3))
                for wd in [e._decode, e._lane_install, e._copy_page,
                           *e._prefill.values()]
                if wd is not None}

    compiles_before = compile_counts()

    # the probe is the tentpole's own windowed store: two manual samples
    # bracket the flood, and every gate below is a windowed delta over them
    probe = TimeSeriesStore(registry=registry, capacity=8, interval_s=0.0)

    def rollup():
        merged = {}
        for e in (e1, e2):
            for t, keys in e.stats().get("tenants", {}).items():
                bucket = merged.setdefault(t, {})
                for key, v in keys.items():
                    bucket[key] = bucket.get(key, 0) + v
        return merged

    router = ReplicaRouter([e1, e2], registry=registry)
    fd = FrontDoor(router, model_name=f"bench-{preset}").start()
    srv = ApiServer(fd, registry=registry)
    host, port = srv.host, srv.port

    def http_json(method, path, payload=None, headers=None, timeout=600.0):
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            body = None if payload is None else json.dumps(payload)
            hdrs = dict(headers or {})
            if payload is not None:
                hdrs.setdefault("Content-Type", "application/json")
            conn.request(method, path, body, hdrs)
            resp = conn.getresponse()
            raw = resp.read()
            return resp.status, dict(resp.getheaders()), json.loads(raw)
        finally:
            conn.close()

    def completion(i):
        # even requests carry the explicit header, odd ones the API-key
        # prefix — both resolution paths must attribute identically
        tenant = tenants[i % 2]
        if i % 4 < 2:
            hdrs = {"X-Tenant": tenant}
        else:
            hdrs = {"Authorization": f"Bearer {tenant}-s3cr3t{i}"}
        return http_json("POST", "/v1/completions", {
            "prompt": [int(t) for t in prompts[i]],
            "max_tokens": new_tokens, "temperature": 0,
        }, headers=hdrs)

    def fanout(fn, work):
        out = [None] * len(work)

        def run(k, item):
            try:
                out[k] = fn(*item)
            except Exception as exc:
                out[k] = exc

        threads = [threading.Thread(target=run, args=(k, item), daemon=True)
                   for k, item in enumerate(work)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = [o for o in out if isinstance(o, Exception)]
        if errs:
            raise SystemExit(f"--slo-ab: client transport error: {errs[0]!r}")
        return out

    # ---- arm 1: tenant flood — attribution must sum exactly to globals
    before = probe.sample()
    roll_before = rollup()
    t0 = time.perf_counter()
    responses = fanout(completion, [(i,) for i in range(n)])
    dt_flood = time.perf_counter() - t0
    after = probe.sample()
    roll_after = rollup()
    srv.stop()
    fd.stop()

    for i, (status, headers, body) in enumerate(responses):
        if status != 200:
            raise SystemExit(
                f"--slo-ab: request {i} failed with HTTP {status}: {body}")
        got = body["choices"][0]["token_ids"]
        if got != ref[i]:
            raise SystemExit(
                f"--slo-ab: request {i} returned {got[:8]}... != in-process "
                f"reference {ref[i][:8]}... under tenant attribution")
        echo = headers.get("X-Tenant")
        if echo != tenants[i % 2]:
            raise SystemExit(
                f"--slo-ab: request {i} (tenant {tenants[i % 2]!r}, "
                f"{'header' if i % 4 < 2 else 'api-key'}-resolved) echoed "
                f"X-Tenant {echo!r} — the front door lost the attribution")

    def cdelta(name):
        return (after["counters"].get(name, 0.0)
                - before["counters"].get(name, 0.0))

    keys = set()
    for name in after["counters"]:
        for t in tenants:
            tag = f"_tenant_{t}_total"
            if name.startswith("serve/") and name.endswith(tag):
                keys.add(name[len("serve/"):-len(tag)])
    if not {"requests_submitted", "tokens_generated"} <= keys:
        raise SystemExit(
            f"--slo-ab: tenant counter families missing after the flood — "
            f"saw keys {sorted(keys)}; attribution never engaged")
    for key in sorted(keys):
        by_tenant = {t: cdelta(f"serve/{key}_tenant_{t}_total")
                     for t in tenants}
        total = cdelta(f"serve/{key}_total")
        if sum(by_tenant.values()) != total:
            raise SystemExit(
                f"--slo-ab: serve/{key}_total grew by {total} during the "
                f"flood but the tenant families account for {by_tenant} — "
                f"per-tenant attribution does not sum to the global counter")
        for t in tenants:
            r_delta = (roll_after.get(t, {}).get(key, 0)
                       - roll_before.get(t, {}).get(key, 0))
            if r_delta != by_tenant[t]:
                raise SystemExit(
                    f"--slo-ab: stats()['tenants'][{t!r}][{key!r}] delta "
                    f"{r_delta} != counter-family delta {by_tenant[t]} — "
                    f"the rollup and the registry disagree")

    def hist_count(sample, name):
        return sample["hists"].get(name, {}).get("count", 0)

    ttft_total = (hist_count(after, "serve/ttft_s")
                  - hist_count(before, "serve/ttft_s"))
    ttft_by_tenant = {
        t: (hist_count(after, f"serve/ttft_s_tenant_{t}")
            - hist_count(before, f"serve/ttft_s_tenant_{t}"))
        for t in tenants}
    if ttft_total != n or sum(ttft_by_tenant.values()) != ttft_total:
        raise SystemExit(
            f"--slo-ab: serve/ttft_s observed {ttft_total} TTFTs for {n} "
            f"requests and the tenant histograms hold {ttft_by_tenant} — "
            f"per-tenant TTFT attribution is lossy")

    # ---- arm 2: forced fast-burn — exactly one bundle, cooldown holds
    t0 = time.perf_counter()
    tiny_ref = e1.serve(prompts[:2], [GenerationConfig(max_new_tokens=window)] * 2)
    dt_tiny = time.perf_counter() - t0
    del tiny_ref
    bounds = None
    for name, metric in registry.items():
        if name == "serve/ttft_s":
            bounds = metric.bucket_snapshot()["bounds"]
    if not bounds:
        raise SystemExit("--slo-ab: serve/ttft_s histogram missing")
    # round the threshold UP to a bucket bound: clean TTFTs then always
    # land in buckets wholly at-or-under it (counted good, no split-bucket
    # interpolation), and stalled TTFTs wholly above it (never good)
    thr_raw = max(3.0 * dt_tiny, 0.05)
    thr = next((b for b in bounds if b >= thr_raw), bounds[-1])
    stall_s = max(0.25, 2.0 * thr)
    store = TimeSeriesStore(registry=registry, capacity=512, interval_s=0.02)
    eng_slo = install_slos(
        specs=[SloSpec(name="ttft_burn", kind="latency", objective=0.99,
                       hist="serve/ttft_s", threshold_s=thr)],
        store=store, registry=registry,
        fast_window_s=0.3, slow_window_s=1.2, cooldown_s=3600.0)
    flight_dir = tempfile.mkdtemp(prefix="slo-ab-")
    env_before = os.environ.get("ATPU_FLIGHT_DIR")
    os.environ["ATPU_FLIGHT_DIR"] = flight_dir
    try:
        e1.serve(prompts[:2], [GenerationConfig(max_new_tokens=window)] * 2,
                 metrics_interval=0.01)
        store.sample()
        clean = eng_slo.evaluate()["ttft_burn"]
        if clean["fast_burning"] or eng_slo.bundles:
            raise SystemExit(
                f"--slo-ab: the CLEAN workload fast-burned a "
                f"{thr:.3f}s TTFT SLO ({clean}) — either the threshold "
                f"sizing is astrology or the host is too contended; rerun "
                f"on a quieter host")
        fault_counter = "serve/faults_injected_total"
        fired_before = next(
            (m.value for nm, m in registry.items() if nm == fault_counter), 0.0)
        faults.install(
            f"seed={args.serve_seed},fetch_slow=1.0,slow_ms={stall_s * 1e3}",
            registry=registry)
        try:
            e1.serve(prompts[:2],
                     [GenerationConfig(max_new_tokens=window)] * 2,
                     metrics_interval=0.01)
            # keep ticking while fast-burning: the first tick captures, the
            # cooldown must swallow every later one
            ticks_while_burning = 0
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline and ticks_while_burning < 6:
                if eng_slo.tick() and eng_slo.bundles:
                    ticks_while_burning += 1
                time.sleep(0.03)
        finally:
            faults.clear()
        fired = next(
            (m.value for nm, m in registry.items() if nm == fault_counter), 0.0
        ) - fired_before
        if not eng_slo.bundles:
            raise SystemExit(
                f"--slo-ab: {stall_s * 1e3:.0f}ms fetch stalls "
                f"({fired:.0f} injected) never tripped the {thr:.3f}s TTFT "
                f"SLO — the burn-rate trigger is dead")
        artifacts = sorted(f for f in os.listdir(flight_dir)
                           if f.startswith("slo-") and f.endswith(".json"))
        if len(eng_slo.bundles) != 1 or len(artifacts) != 1:
            raise SystemExit(
                f"--slo-ab: expected EXACTLY ONE diagnostics bundle after "
                f"{ticks_while_burning} fast-burning ticks, got "
                f"{len(eng_slo.bundles)} recorded / {artifacts} on disk — "
                f"the per-SLO cooldown does not rate-limit capture")
        with open(os.path.join(flight_dir, artifacts[0])) as fh:
            bundle = json.load(fh)
        verdict = bundle.get("slo", {})
        series = bundle.get("timeseries", [])
        burned = (
            bundle.get("kind") == "slo_bundle"
            and verdict.get("slo") == "ttft_burn"
            and verdict.get("fast_burning") is True
            and verdict.get("fast_burn", 0.0) >= 14.4
            and "stacks" in bundle and "events" in bundle
            and len(series) >= 2
            and (hist_count(series[-1], "serve/ttft_s")
                 - hist_count(series[0], "serve/ttft_s")) >= 1
        )
        if not burned:
            raise SystemExit(
                f"--slo-ab: bundle {artifacts[0]} does not contain the "
                f"offending window (kind={bundle.get('kind')!r}, "
                f"verdict={verdict}, {len(series)} time-series samples) — "
                f"the diagnostics froze the wrong evidence")
    finally:
        uninstall_slos()
        if env_before is None:
            os.environ.pop("ATPU_FLIGHT_DIR", None)
        else:
            os.environ["ATPU_FLIGHT_DIR"] = env_before

    # ---- arm 3: fleet health on vs off — <= 1% null-calibrated overhead
    # Same instrument as --trace-ab: rotating on/off/control arms, min-of-2
    # samples, 1.25x burst trim on each arm's own floor, pooled medians
    # re-checked per batch, gate = 1.01 + |control drift|.  The ON arm is
    # the full feature stack (SLO engine installed over a fresh ring store,
    # every request tenant-attributed, the run loop ticking at 20ms); the
    # OFF arms are a plain untenanted serve with no engine installed.
    pairs_per_batch = 24
    max_batches = 4
    min_kept = 12
    t_on, t_off, t_ctl = [], [], []
    for _ in range(2):  # discarded warm-up; also settles server teardown
        e3.serve(prompts, [gen] * n)

    def _serve_on():
        install_slos(
            specs=default_specs(ttft_threshold_s=3600.0,
                                tokens_floor_per_s=1e-9),
            store=TimeSeriesStore(registry=registry, capacity=1024,
                                  interval_s=0.02),
            registry=registry, cooldown_s=3600.0)
        try:
            out = [e3.submit(p, config=gen, tenant=tenants[i % 2])
                   for i, p in enumerate(prompts)]
            e3.run(metrics_interval=0.02)
            return out
        finally:
            uninstall_slos()

    on_reqs = _serve_on()
    on_tokens = [[int(t) for t in q.tokens] for q in on_reqs]
    if on_tokens != ref:
        raise SystemExit(
            "--slo-ab: tokens with the fleet-health layer on diverge from "
            "the reference — attribution touches the decode path")

    def _timed(on, sink):
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            if on:
                _serve_on()
            else:
                e3.serve(prompts, [gen] * n)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        sink.append(best)

    def _median(vals):
        vals = sorted(vals)
        mid = len(vals) // 2
        return (vals[mid] if len(vals) % 2
                else 0.5 * (vals[mid - 1] + vals[mid]))

    arms = [(True, t_on), (False, t_off), (False, t_ctl)]
    med_ratio = null_ratio = allowance = None
    for _ in range(max_batches):
        for k in range(pairs_per_batch):
            for flag, sink in arms[k % 3:] + arms[:k % 3]:
                _timed(flag, sink)
        lim_on = 1.25 * min(t_on)
        lim_off = 1.25 * min(t_off)
        lim_ctl = 1.25 * min(t_ctl)
        kept = [(on, off, c) for on, off, c in zip(t_on, t_off, t_ctl)
                if on <= lim_on and off <= lim_off and c <= lim_ctl]
        if len(kept) < min_kept:
            continue
        med_ratio = _median([on / off for on, off, _ in kept])
        null_ratio = _median([c / off for _, off, c in kept])
        allowance = abs(null_ratio - 1.0)
        if med_ratio <= 1.01 + allowance:
            break
    if med_ratio is None:
        raise SystemExit(
            f"--slo-ab: host contention too heavy to measure — fewer than "
            f"{min_kept} of {len(t_on)} paired samples survived the burst "
            f"trim; rerun on a quieter host")
    if med_ratio > 1.01 + allowance:
        raise SystemExit(
            f"--slo-ab: fleet-health-on serve is {med_ratio - 1.0:+.1%} vs "
            f"off (pooled median of {len(t_on)} paired min-of-2 samples "
            f"after burst trim) while the off-vs-off control shows "
            f"{null_ratio - 1.0:+.1%} instrument drift — attribution + SLO "
            f"ticking cost >1% beyond the demonstrated noise floor; gate "
            f"is <= {1.01 + allowance - 1.0:.1%}")

    # ---- arm 4: the fleet-health layer compiled nothing
    compiles_after = compile_counts()
    if compiles_after != compiles_before:
        diff = {k: (compiles_before.get(k), v)
                for k, v in compiles_after.items()
                if compiles_before.get(k) != v}
        raise SystemExit(f"--slo-ab: the fleet-health layer compiled new "
                         f"executables (name: before -> after): {diff}")

    flood_tps = useful_tokens / dt_flood
    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "num_slots": slots,
        "decode_window": window,
        "new_tokens_per_request": new_tokens,
        "useful_tokens": useful_tokens,
        "flood_wall_s": round(dt_flood, 3),
        "inproc_wall_s": round(dt_inproc, 3),
        "inproc_tokens_per_s": round(useful_tokens / dt_inproc, 2),
        "tenants": {
            "labels": list(tenants),
            "counter_keys_checked": sorted(keys),
            "sums_exact": True,                 # hard-checked above
            "ttft_observations": ttft_total,
        },
        "burn": {
            "ttft_threshold_s": round(thr, 4),
            "stall_ms": round(stall_s * 1e3, 1),
            "faults_injected": int(fired),
            "bundles": 1,                       # hard-checked above
            "fast_burn": round(verdict["fast_burn"], 1),
            "timeseries_samples": len(series),
        },
        "off": {
            "pairs": len(t_on),
            "outputs_token_identical": True,    # hard-checked above
            "on_best_s": round(min(t_on), 4),
            "off_best_s": round(min(t_off), 4),
            "on_vs_off_median": round(med_ratio, 4),
            "off_vs_off_control_median": round(null_ratio, 4),
            "gate": round(1.01 + allowance, 4),
            "new_executables": 0,               # hard-checked above
        },
    }
    return {
        "metric": "tenant_attributed_serving_tokens_per_sec",
        "value": round(flood_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(flood_tps / (useful_tokens / dt_inproc), 3),
        "detail": detail,
    }


def _hier_ab_bench(args, model, cfg, params, preset):
    """Hierarchical prefix cache A/B: host-RAM spill tier on vs off.

    The workload is grouped shared-prefix traffic whose distinct-prefix
    working set is ~10x the device-tier budget (``prefix_cache_mb`` holds ~1
    cached prefix, the rounds cycle through 10): without the host tier the
    device LRU thrashes and every returning group re-prefills its prefix from
    scratch; with it the evicted prefix spills to host RAM and each return is
    an H2D promotion enqueued behind the in-flight decode window.  Every
    check is HARD (SystemExit on failure):

    * greedy outputs token-identical between the arms (promotions land
      mid-decode under ``async_depth=1`` and must be invisible);
    * the on-arm actually serves prefix tokens from the host tier
      (``prefix_hit_tokens_host`` and ``serve/prefix_hit_rate_host`` > 0);
    * tokens/s >= 1.25x the spill-off arm and mean TTFT improved — the spill
      tier must BUY something on the oversubscribed mix, not just not lose;
    * promotion is overlapped, not serial: ``serve/host_overlap_ratio``
      stays > 0 and at least one ``serve/promote_h2d`` flight event carries
      ``behind_window=True`` (no synchronous fetch at admission);
    * zero new blocking readbacks on the hot path: in-process atpu-lint over
      the repo surface stays clean;
    * the compiled-executable budget grows by EXACTLY the documented set —
      one ``spill_<bucket>`` D2H gather + one ``promote_<bucket>`` H2D
      install per prefill bucket, each compiled at most once.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.serving.paging import PagedKVPool
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    window = args.decode_window
    mp_full = max(16, min(args.seq, cfg.max_seq_len) // 2)
    page = max(4, mp_full // 4)
    buckets = (page, 4 * page)
    prefix_len = 4 * page              # exactly one full cacheable chunk
    mp = prefix_len + page             # room for a partial (uncached) suffix
    max_len = min(
        (cfg.max_seq_len // page) * page,
        ((mp + 4 * window) // page + 1) * page,
    )
    # few slots + a deep queue: decode windows stay in flight across every
    # admission (promotions genuinely overlap) and TTFT is queue-dominated,
    # so it tracks throughput instead of per-request scheduling jitter
    slots = min(args.batch, 4)

    groups = 10
    rounds = max(6, args.requests // groups)
    r = np.random.default_rng(args.serve_seed)
    prefixes = [
        r.integers(1, cfg.vocab_size, (prefix_len,)).astype(np.int32)
        for _ in range(groups)
    ]
    # round-robin across groups: by the time a group returns, the 9 prefixes
    # in between have thrashed it out of the 1-node device tier
    prompts = [
        np.concatenate(
            [prefixes[g],
             r.integers(1, cfg.vocab_size, (int(r.integers(2, page)),))
             .astype(np.int32)]
        )
        for _ in range(rounds) for g in range(groups)
    ]
    n = len(prompts)
    gens = [GenerationConfig(max_new_tokens=window) for _ in range(n)]
    useful_tokens = n * window

    # size the device tier from the pool's own accounting (a prefix node
    # costs 2 pages' data + scale slabs): ~1 resident node -> 10x working set
    probe = PagedKVPool(cfg, 1, page, page, 2, registry=MetricsRegistry())
    node_bytes = (prefix_len // page) * probe.page_kv_bytes
    del probe
    dev_mb = 1.05 * node_bytes / 2**20
    host_mb = 4.0 * groups * node_bytes / 2**20
    num_pages = slots * (max_len // page) + 4 * (prefix_len // page) + 1

    def run_arm(arm_host_mb):
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            max_prompt_len=mp, prefill_buckets=buckets, decode_window=window,
            paged=True, page_size=page, num_pages=num_pages,
            prefix_cache_mb=dev_mb, prefix_host_mb=arm_host_mb,
            async_depth=1, registry=registry,
        )
        # warmup compiles every executable the timed region touches: both
        # prefill buckets + insert + decode (A, B), the spill gather (B's
        # insert evicts A), and the promote install (A's return hits its
        # spilled node)
        wa = r.integers(1, cfg.vocab_size, (prefix_len + 2,)).astype(np.int32)
        wb = r.integers(1, cfg.vocab_size, (prefix_len + 2,)).astype(np.int32)
        eng.serve([wa, wb, wa.copy()], GenerationConfig(max_new_tokens=window))
        if eng.prefix_cache is not None:
            eng.prefix_cache.flush()
        for k in eng.stats:
            eng.stats[k] = 0
        registry.reset()
        eng.recorder.clear()
        # best-of-N walls: the timed region is sub-second, so a single OS
        # scheduling stall swamps the ratio — transient noise only ever
        # inflates a wall, so min is the stable estimator.  Repeats start
        # from the steady tier state the previous pass left (exactly the
        # long-running-service shape this bench models) and double as a
        # no-retrace check: the compiled-budget gate still requires <= 1
        # compile per executable across every pass.
        dt = float("inf")
        for _ in range(max(3, args.iters)):
            t0 = time.perf_counter()
            reqs = eng.serve(prompts, gens)
            dt = min(dt, time.perf_counter() - t0)
        # snapshot now: the recorder is process-global and the other arm's
        # clear() would wipe these events
        events = list(eng.recorder.tail())
        return eng, reqs, dt, registry, events

    eng_on, reqs_on, dt_on, reg_on, events_on = run_arm(host_mb)
    eng_off, reqs_off, dt_off, reg_off, _ = run_arm(0.0)

    if [q.tokens for q in reqs_on] != [q.tokens for q in reqs_off]:
        raise SystemExit(
            "--hier-ab identity: host spill tier changed greedy outputs vs "
            "the spill-off arm on the same workload"
        )
    host_hit_tokens = eng_on.stats["prefix_hit_tokens_host"]
    host_hit_rate = float(reg_on.get("serve/prefix_hit_rate_host").value)
    if host_hit_tokens <= 0 or host_hit_rate <= 0:
        raise SystemExit(
            f"--hier-ab: no prefix tokens were served from the host tier "
            f"(hit tokens {host_hit_tokens}, rate {host_hit_rate}) on a "
            "10x-oversubscribed mix — the spill tier never engaged"
        )
    tps_on = useful_tokens / dt_on
    tps_off = useful_tokens / dt_off
    speedup = tps_on / tps_off
    if speedup < 1.25:
        raise SystemExit(
            f"--hier-ab: spill tier bought only {speedup:.3f}x tokens/s "
            f"({tps_on:.2f} vs {tps_off:.2f}) — gate is >= 1.25x on the "
            "oversubscribed shared-prefix mix"
        )
    ttft_on = reg_on.get("serve/ttft_s").snapshot()["mean"]
    ttft_off = reg_off.get("serve/ttft_s").snapshot()["mean"]
    if ttft_on >= ttft_off:
        raise SystemExit(
            f"--hier-ab: mean TTFT did not improve with the host tier "
            f"({1e3 * ttft_on:.2f}ms vs {1e3 * ttft_off:.2f}ms spill-off)"
        )
    overlap = float(reg_on.get("serve/host_overlap_ratio").value)
    if overlap <= 0:
        raise SystemExit(
            "--hier-ab: serve/host_overlap_ratio is 0 — the promotion path "
            "serialized the async loop"
        )
    promote_events = [e for e in events_on
                      if e.get("kind") == "serve/promote_h2d"]
    if not any(e.get("behind_window") for e in promote_events):
        raise SystemExit(
            "--hier-ab: no promotion was enqueued behind an in-flight decode "
            "window — promotions ran serially at admission"
        )

    import io
    from tools.atpu_lint.cli import main as atpu_lint_main
    buf = io.StringIO()
    if atpu_lint_main([], stdout=buf, stderr=buf) != 0:
        raise SystemExit(
            "--hier-ab: atpu-lint found new hot-path violations (blocking "
            f"readbacks / host syncs):\n{buf.getvalue()}"
        )

    counts_on = eng_on.compiled_executable_counts()
    counts_off = eng_off.compiled_executable_counts()
    expected_extra = ({f"spill_{b}" for b in buckets}
                      | {f"promote_{b}" for b in buckets})
    extra = set(counts_on) - set(counts_off)
    if extra != expected_extra:
        raise SystemExit(
            f"--hier-ab: compiled-executable budget grew by {sorted(extra)}, "
            f"expected exactly {sorted(expected_extra)}"
        )
    over = {k: v for k, v in counts_on.items() if v > 1}
    if over or counts_on[f"spill_{prefix_len}"] != 1 \
            or counts_on[f"promote_{prefix_len}"] != 1:
        raise SystemExit(
            f"--hier-ab: spill/install executables retraced or never "
            f"compiled: over-budget {over}, "
            f"spill_{prefix_len}={counts_on[f'spill_{prefix_len}']}, "
            f"promote_{prefix_len}={counts_on[f'promote_{prefix_len}']}"
        )

    def arm_detail(eng, dt, reg):
        ttft = reg.get("serve/ttft_s").snapshot()
        return {
            "wall_s": round(dt, 3),
            "tokens_per_s": round(useful_tokens / dt, 2),
            "ttft_mean_ms": round(1e3 * ttft["mean"], 2),
            "ttft_p99_ms": round(1e3 * ttft["p99"], 2),
            "prefix_hit_tokens": eng.stats["prefix_hit_tokens"],
            "prefix_hit_tokens_host": eng.stats["prefix_hit_tokens_host"],
            "prefix_cache": eng.prefix_cache_stats(),
            "compiled_executables": eng.compiled_executable_counts(),
        }

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": n,
        "groups": groups,
        "rounds": rounds,
        "prefix_len": prefix_len,
        "page_size": page,
        "prefill_buckets": list(buckets),
        "prefix_cache_mb": round(dev_mb, 5),
        "prefix_host_mb": round(host_mb, 5),
        "working_set_over_device_budget": round(
            groups * node_bytes / (dev_mb * 2**20), 2),
        "useful_tokens": useful_tokens,
        "outputs_token_identical": True,
        "host_hit_rate": round(host_hit_rate, 4),
        "host_overlap_ratio": round(overlap, 4),
        "promotions_behind_window": sum(
            1 for e in promote_events if e.get("behind_window")),
        "atpu_lint_clean": True,
        "spill_on": arm_detail(eng_on, dt_on, reg_on),
        "spill_off": arm_detail(eng_off, dt_off, reg_off),
    }
    return {
        "metric": "serving_hier_cache_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "detail": detail,
    }


def _disagg_ab_bench(args, model, cfg, params, preset):
    """Disaggregated prefill/decode A/B: role split + live KV page migration.

    Four arms over greedy/sampled workloads, every check HARD (SystemExit):

    * identity — the same submission order served by one monolithic engine
      and by a ``policy="disaggregated"`` router (prefill replica + decode
      replica, every lane handed off after its last prefill chunk) must
      return bit-identical tokens, greedy AND sampled (the live RNG row
      rides the migration), with one ``serve/prefill_handoffs_total`` per
      request and ZERO decode steps on the prefill replica;
    * crossover — migrate-vs-replay on a ladder of context lengths: move a
      2-token-deep lane to a warm peer either by page migration or by the
      failover replay path (export + adopt + re-prefill) and time until the
      next token lands.  Replay cost grows with the context it re-prefills;
      migration moves bytes.  The bench reports the crossover context
      length and HARD-requires migration to win at the top of the ladder —
      the regime ``migrate_lane()`` and failover-upgrade exist for;
    * chat TTFT — the adversarial mix: a flood of long bulk prefills, then
      short chat requests behind them.  Monolithic baseline: two
      ``role="both"`` replicas under the affinity router, each interleaving
      bulk prefill chunks with its decode windows.  Disaggregated arm: the
      same two-engine footprint split prefill/decode (the decode replica
      runs wider slots — it needs no prefill headroom; page pools are
      unchanged).  Chat p99 TTFT must IMPROVE: that is the one number the
      role split is for — decode windows never stall behind a bulk chunk,
      prefill drains at full duty, and prefill-replica slots recycle at
      handoff instead of being held through decode;
    * kill — a prefill replica is poisoned mid-handoff with a spare
      prefill-capable replica attached.  Zero failed requests: every
      request must finish with tokens identical to the monolithic greedy
      reference (readable pages migrate off the corpse; the rest replay).

    ``value``/``vs_baseline`` is the chat-p99-TTFT improvement (monolithic
    over disaggregated, > 1 is a win).  The compiled budget is gated too:
    the migration pair appears ONLY on engines that migrated, at most once
    each.
    """
    from accelerate_tpu.models.generation import GenerationConfig
    from accelerate_tpu.serving import PageMigrator, ReplicaRouter, ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)
    window = args.decode_window
    page = 4
    mp = -(-max(16, min(args.seq, cfg.max_seq_len) * 3 // 4) // page) * page
    buckets = tuple(sorted({max(8, -(-(mp // 4) // page) * page), mp}))
    max_len = min((cfg.max_seq_len // page) * page,
                  -(-(mp + 6 * window) // page) * page)
    slots = max(2, min(args.batch, 4))
    r = np.random.default_rng(args.serve_seed)

    def build(role, n_slots, registry, win=None, **kw):
        return ServingEngine(
            model, params, num_slots=n_slots, max_len=max_len,
            max_prompt_len=mp, prefill_buckets=buckets,
            decode_window=window if win is None else win,
            paged=True, page_size=page,
            num_pages=2 * n_slots * (max_len // page) + 1,
            prefix_cache_mb=0, async_depth=1, role=role, registry=registry,
            max_queue=max(64, 8 * args.requests),
            prefill_token_budget=buckets[0], **kw,
        )

    def prompt(n):
        return r.integers(1, cfg.vocab_size, (int(n),)).astype(np.int32)

    def gen(sampled, n):
        if sampled:
            return GenerationConfig(max_new_tokens=n, do_sample=True,
                                    temperature=0.8, top_k=50,
                                    eos_token_id=None)
        return GenerationConfig(max_new_tokens=n, do_sample=False,
                                eos_token_id=None)

    # ---- arm 1: token identity vs the monolithic baseline, greedy + sampled
    # fresh engines, no warmup: rid sequences must align between the mono
    # engine and the prefill replica so the sampled streams fold identically
    n_id = 6
    id_prompts = [prompt(int(r.integers(4, mp))) for _ in range(n_id)]
    id_gens = [gen(sampled=bool(k % 2), n=2 * window) for k in range(n_id)]
    mono = build("both", 2 * slots, MetricsRegistry())
    mono_reqs = mono.serve(id_prompts, id_gens)

    reg_id = MetricsRegistry()
    pre = build("prefill", slots, reg_id)
    dec = build("decode", 2 * slots, reg_id)
    dis = ReplicaRouter([pre, dec], policy="disaggregated", registry=reg_id)
    dis_reqs = [dis.submit(p, config=g) for p, g in zip(id_prompts, id_gens)]
    dis.run()
    for k, (qm, qd) in enumerate(zip(mono_reqs, dis_reqs)):
        if [int(t) for t in qm.tokens] != [int(t) for t in qd.tokens]:
            raise SystemExit(
                f"--disagg-ab identity: request {k} "
                f"({'sampled' if k % 2 else 'greedy'}) diverged between the "
                f"monolithic engine and the disaggregated split — migration "
                f"is not bit-transparent"
            )
    handoffs = int(reg_id.get("serve/prefill_handoffs_total").value)
    if handoffs != n_id:
        raise SystemExit(
            f"--disagg-ab identity: expected {n_id} prefill handoffs, "
            f"recorded {handoffs} — lanes are not leaving the prefill replica"
        )
    if pre.stats["decode_steps"] != 0:
        raise SystemExit(
            f"--disagg-ab identity: the prefill replica ran "
            f"{pre.stats['decode_steps']} decode steps; role='prefill' must "
            "never decode"
        )
    for e, name, expect in ((pre, "prefill", "migrate_extract"),
                            (dec, "decode", "migrate_install")):
        counts = e.compiled_executable_counts()
        if counts.get(expect) != 1:
            raise SystemExit(
                f"--disagg-ab budget: {name} replica compiled "
                f"{expect}={counts.get(expect)} (want exactly 1 across "
                f"{n_id} handoffs — fixed-width executables must not retrace)"
            )
    if set(mono.compiled_executable_counts()) & {"migrate_extract",
                                                 "migrate_install"}:
        raise SystemExit(
            "--disagg-ab budget: the monolithic engine compiled migration "
            "executables without ever migrating"
        )

    # ---- arm 2: migrate-vs-replay crossover over context length
    ladder = sorted({4 * page, mp // 4, mp // 2, mp})
    ladder = [-(-v // page) * page for v in ladder if v >= 2 * page]
    migrator = PageMigrator(MetricsRegistry())
    # a 2-token window keeps the lane shallow at migration time so the
    # timed differential is transfer-vs-re-prefill, not decode headroom
    src_m, dst_m, rep = (build("both", 2, MetricsRegistry(), win=2)
                         for _ in range(3))
    warm = [prompt(b) for b in buckets]
    wgen = gen(False, window)

    def slot_of(eng, req):
        return next(s for s in range(eng.num_slots)
                    if eng._slot_req[s] is req)

    def migrate_time(L):
        """Wall seconds from initiating the migration of a shallow lane with
        ``L`` prompt tokens until its next token lands on ``dst_m``."""
        req = src_m.submit(prompt(L), config=gen(False, 12))
        while len(req.tokens) < 2:
            src_m.step()
        t0 = time.perf_counter()
        migrator.migrate(src_m, dst_m, slot_of(src_m, req))
        before = len(req.tokens)  # in-flight windows land during the drain
        while len(req.tokens) <= before:
            dst_m.step()
        dt = time.perf_counter() - t0
        dst_m.run()
        src_m.run()
        return dt

    def replay_time(L):
        """The failover-replay cost for the same lane: ``adopt`` re-prefills
        ``prompt + generated`` (``Request.prefill_tokens``) on the survivor,
        so time a fresh (L+2)-token submission until its first token —
        identical work, without needing a corpse to export from."""
        t0 = time.perf_counter()
        req = rep.submit(prompt(min(L + 2, mp)), config=gen(False, 4))
        while len(req.tokens) < 1:
            rep.step()
        dt = time.perf_counter() - t0
        rep.run()
        return dt

    for e in (src_m, dst_m, rep):
        e.serve(warm, wgen)
    migrate_time(ladder[0])  # warm the migrate pair end to end

    curve = []
    for L in ladder:
        dt_m = min(migrate_time(L) for _ in range(max(3, args.iters)))
        dt_r = min(replay_time(L) for _ in range(max(3, args.iters)))
        curve.append({"context": L + 2, "migrate_ms": round(1e3 * dt_m, 3),
                      "replay_ms": round(1e3 * dt_r, 3)})
    if curve[-1]["migrate_ms"] >= curve[-1]["replay_ms"]:
        raise SystemExit(
            f"--disagg-ab crossover: migration never beat replay — at "
            f"context {curve[-1]['context']} migrate took "
            f"{curve[-1]['migrate_ms']}ms vs replay "
            f"{curve[-1]['replay_ms']}ms.  Curve: {curve}"
        )
    crossover = next(p["context"] for p in curve
                     if p["migrate_ms"] < p["replay_ms"])

    # ---- arm 3: chat p99 TTFT on the adversarial bulk-prefill + chat mix
    # mix-local geometry: the disaggregation scenario is a chat arriving
    # while bulk lanes are mid-decode, so bulk decode must be LONG relative
    # to its prefill — a short window with all remaining slot capacity spent
    # on decode.  The monolithic replicas hold a slot through prefill AND
    # that whole decode; the split recycles prefill slots at handoff.
    mw = min(4, window)
    mpx = min(-(-max(4 * page, mp // 2) // page) * page, max_len - 8 * mw)
    bx = tuple(sorted({max(8, -(-(mpx // 2) // page) * page), mpx}))
    bulk_new = max_len - mpx - mw

    def build_mix(role, n_slots, registry, budget=None):
        # the prefill-token budget exists to protect decode latency from
        # prefill interference; a prefill-only replica has no decode to
        # protect, so it runs the full bucket per step
        return ServingEngine(
            model, params, num_slots=n_slots, max_len=max_len,
            max_prompt_len=mpx, prefill_buckets=bx, decode_window=mw,
            paged=True, page_size=page,
            num_pages=2 * n_slots * (max_len // page) + 1,
            prefix_cache_mb=0, async_depth=1, role=role, registry=registry,
            max_queue=max(64, 8 * args.requests),
            prefill_token_budget=bx[0] if budget is None else budget,
        )

    n_chat = 6
    n_bulk = max(6, args.requests - n_chat)
    bulk_prompts = [prompt(mpx) for _ in range(n_bulk)]
    chat_prompts = [prompt(8) for _ in range(n_chat)]
    bulk_gen, chat_gen = gen(False, bulk_new), gen(False, mw)
    warm_x = [prompt(b) for b in bx]
    wgen_x = gen(False, mw)
    reps = max(2, args.iters // 2)

    def run_mix(router, registry, engines):
        for e in engines:  # compile everything outside the timed region
            if getattr(e, "role", "both") != "prefill":
                e.serve(warm_x, wgen_x)
        if any(getattr(e, "role", "both") == "prefill" for e in engines):
            for w in warm_x:
                router.submit(w, config=wgen_x)
            router.run()
        for e in engines:
            for k in e.stats:
                e.stats[k] = 0
        registry.reset()
        toks = []
        t0 = time.perf_counter()
        for _ in range(reps):
            qs = [router.submit(p, config=bulk_gen, request_class="bulk")
                  for p in bulk_prompts]
            # chats arrive mid-burst, once half the bulk lanes are decoding
            while sum(1 for q in qs if len(q.tokens) > 0) < n_bulk // 2:
                router.step()
            qs += [router.submit(p, config=chat_gen, request_class="chat")
                   for p in chat_prompts]
            router.run()
            toks.append([[int(t) for t in q.tokens] for q in qs])
        dt = time.perf_counter() - t0
        p99 = registry.get("serve/ttft_s_class_chat").snapshot()["p99"]
        return toks, dt, p99

    reg_m = MetricsRegistry()
    mono_engines = [build_mix("both", slots, reg_m) for _ in range(2)]
    mono_router = ReplicaRouter(mono_engines, registry=reg_m)
    mono_toks, dt_mono, p99_mono = run_mix(mono_router, reg_m, mono_engines)

    reg_d = MetricsRegistry()
    pre2 = build_mix("prefill", slots, reg_d, budget=bx[-1])
    dec2 = build_mix("decode", 4 * slots, reg_d)
    dis2 = ReplicaRouter([pre2, dec2], policy="disaggregated",
                         registry=reg_d)
    dis_toks, dt_dis, p99_dis = run_mix(dis2, reg_d, (pre2, dec2))

    if dis_toks != mono_toks:
        raise SystemExit(
            "--disagg-ab mix: greedy tokens diverged between the "
            "disaggregated split and the monolithic router on the same "
            "workload"
        )
    improvement = p99_mono / p99_dis if p99_dis > 0 else float("inf")
    if p99_dis >= p99_mono:
        raise SystemExit(
            f"--disagg-ab TTFT: chat p99 TTFT did not improve under the "
            f"disaggregated split — {1e3 * p99_dis:.2f}ms vs "
            f"{1e3 * p99_mono:.2f}ms monolithic on the bulk-prefill + chat "
            "mix"
        )

    # ---- arm 4: prefill replica killed mid-handoff — zero failed requests
    n_k = max(4, min(8, args.requests // 2))
    k_prompts = [prompt(mp) for _ in range(n_k)]
    k_gen = gen(False, 2 * window)
    ref = [[int(t) for t in q.tokens]
           for q in mono.serve(k_prompts, [k_gen] * n_k)]

    reg_k = MetricsRegistry()
    kills = [build("prefill", slots, reg_k), build("prefill", slots, reg_k),
             build("decode", 2 * slots, reg_k)]
    kr = ReplicaRouter(kills, policy="disaggregated", registry=reg_k,
                       breaker_base_s=3600.0)
    kr.migrator  # materialize the migration counters before polling them
    kreqs = [kr.submit(p, config=k_gen) for p in k_prompts]
    victim, steps = None, 0
    while victim is None:
        kr.step()
        steps += 1
        # mid-handoff: at least one lane already crossed to the decode
        # replica and the victim still owns work (mid-prefill lanes, lanes
        # awaiting the sweep, or queue) — the full failover ladder fires
        if int(reg_k.get("serve/prefill_handoffs_total").value) >= 1:
            busy = [e for e in kills[:2] if e.has_work]
            if busy:
                victim = max(busy, key=lambda e: sum(
                    q is not None for q in e._slot_req))
        if victim is None and steps > 300:
            raise SystemExit("--disagg-ab kill: never caught a prefill "
                             "replica mid-handoff; workload too small")
    victim.kill("disagg-ab: injected prefill replica loss")
    kr.run()
    got = [[int(t) for t in q.tokens] for q in kreqs]
    failed = [k for k, (g, want) in enumerate(zip(got, ref)) if g != want]
    if failed:
        raise SystemExit(
            f"--disagg-ab kill: {len(failed)}/{n_k} requests failed or "
            f"diverged after the prefill replica died mid-handoff "
            f"(first: request {failed[0]}, got {got[failed[0]][:6]}... want "
            f"{ref[failed[0]][:6]}...)"
        )
    k_migrated = int(reg_k.get("serve/migrations_total").value)
    k_replayed = kr.stats().get("requests_replayed", 0)

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "page_size": page,
        "prefill_buckets": list(buckets),
        "decode_window": window,
        "slots_monolithic": [slots, slots],
        "slots_disaggregated": {"prefill": slots, "decode": 2 * slots},
        "identity_requests": n_id,
        "prefill_handoffs": handoffs,
        "outputs_token_identical": True,
        "crossover_context_tokens": crossover,
        "migrate_vs_replay_curve": curve,
        "mix": {
            "bulk_requests": reps * n_bulk, "chat_requests": reps * n_chat,
            "bulk_prompt_len": mpx, "bulk_new_tokens": bulk_new,
            "decode_window": mw, "chat_prompt_len": 8,
            "decode_slots": 4 * slots,
            "chat_ttft_p99_ms_monolithic": round(1e3 * p99_mono, 2),
            "chat_ttft_p99_ms_disaggregated": round(1e3 * p99_dis, 2),
            "wall_s_monolithic": round(dt_mono, 3),
            "wall_s_disaggregated": round(dt_dis, 3),
        },
        "kill": {"requests": n_k, "failed": 0, "migrated_off": k_migrated,
                 "replayed": k_replayed, "steps_before_kill": steps},
    }
    return {
        "metric": "serving_disagg_chat_ttft_p99_improvement",
        "value": round(improvement, 3),
        "unit": "x",
        "vs_baseline": round(improvement, 3),
        "detail": detail,
    }


def _serve_bench(args, model, cfg, params, preset):
    """Continuous batching vs static ``generate`` on one mixed-length workload.

    Both sides decode greedily and both get credited only the USEFUL tokens
    (each request's own output length).  The static baseline runs the
    requests FCFS in groups of ``--batch``, every group padded to the
    workload's max prompt / max output — ONE compiled shape, warmed up before
    timing, exactly how ``generate`` would serve this queue.  The engine
    serves the same queue through the slot pool with chunked prefill and
    in-flight admission.

    ``--shared-prefix N`` switches to the prefix-caching workload: every
    prompt is one common N-token system prefix plus a per-request log-normal
    suffix.  The baseline becomes the SAME engine with the prefix cache off
    (``vs_baseline`` = cache-on tokens/s over cache-off tokens/s on identical
    requests), outputs are asserted token-identical between the two runs, and
    ``detail.prefix_hit_rate`` records the reuse the radix cache found.
    """
    if sum([bool(getattr(args, "paged_ab", False)),
            bool(getattr(args, "kernel_ab", False)),
            bool(getattr(args, "tp_ab", False)),
            bool(getattr(args, "async_ab", False)),
            bool(getattr(args, "http_ab", False)),
            bool(getattr(args, "chaos_ab", False)),
            bool(getattr(args, "trace_ab", False)),
            bool(getattr(args, "slo_ab", False)),
            bool(getattr(args, "prefill_ab", False)),
            bool(getattr(args, "hier_ab", False)),
            bool(getattr(args, "disagg_ab", False)),
            bool(args.shared_prefix)]) > 1:
        raise SystemExit("--paged-ab, --kernel-ab, --tp-ab, --async-ab, "
                         "--http-ab, --chaos-ab, --trace-ab, --slo-ab, "
                         "--prefill-ab, --hier-ab, --disagg-ab and "
                         "--shared-prefix are separate serve workloads; "
                         "pick one")
    if getattr(args, "paged_ab", False):
        return _paged_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "disagg_ab", False):
        return _disagg_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "hier_ab", False):
        return _hier_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "http_ab", False):
        return _http_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "chaos_ab", False):
        return _chaos_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "trace_ab", False):
        return _trace_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "slo_ab", False):
        return _slo_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "kernel_ab", False):
        return _kernel_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "prefill_ab", False):
        return _prefill_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "tp_ab", False):
        return _tp_ab_bench(args, model, cfg, params, preset)
    if getattr(args, "async_ab", False):
        return _async_ab_bench(args, model, cfg, params, preset)

    from accelerate_tpu.models.generation import GenerationConfig, generate
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.telemetry import MetricsRegistry

    params = jax.device_put(params)  # HBM-resident: serving is not an offload bench
    slots = args.batch
    window = args.decode_window
    max_len = cfg.max_seq_len
    mp = max(8, min(args.seq, max_len) // 2)          # longest admissible prompt
    buckets = tuple(sorted({max(8, mp // 4), max(8, mp // 2)}))

    # log-normal mixed lengths — the serving-paper workload shape (most
    # requests short, a heavy tail; ShareGPT-like sigma ~1), clipped to the
    # slot capacity
    r = np.random.default_rng(args.serve_seed)
    out_cap = min(max_len - window - mp, 2 * mp)
    shared = int(args.shared_prefix or 0)
    if shared:
        if shared > mp - 4:
            raise SystemExit(
                f"--shared-prefix {shared} leaves no room for per-request "
                f"suffixes (max admissible prompt is {mp})"
            )
        common = r.integers(1, cfg.vocab_size, (shared,)).astype(np.int32)
        suffix_lens = np.clip(
            np.rint(r.lognormal(np.log(max(4, (mp - shared) // 3)), 0.8, args.requests)),
            2, mp - shared,
        ).astype(int)
        prompt_lens = shared + suffix_lens
        prompts = [
            np.concatenate([common, r.integers(1, cfg.vocab_size, (int(n),)).astype(np.int32)])
            for n in suffix_lens
        ]
    else:
        prompt_lens = np.clip(
            np.rint(r.lognormal(np.log(max(8, mp // 3)), 0.8, args.requests)), 4, mp
        ).astype(int)
        prompts = [r.integers(1, cfg.vocab_size, (int(n),)).astype(np.int32) for n in prompt_lens]
    out_lens = np.clip(
        np.rint(r.lognormal(np.log(max(8, out_cap // 8)), 1.0, args.requests)), 4, out_cap
    ).astype(int)
    gens = [GenerationConfig(max_new_tokens=int(n)) for n in out_lens]
    useful_tokens = int(out_lens.sum())

    # slot capacity sized to the workload (like the static baseline's cache:
    # prompt + new tokens), not the model's full context — attention cost per
    # decode step scales with slot width
    slot_len = min(
        max_len,
        int(max(p + o for p, o in zip(prompt_lens, out_lens))) + window,
    )

    def run_engine(prefix_mb):
        """One warmed, timed engine pass over the workload.

        A private registry per run: the telemetry percentiles must cover the
        timed workload only, so warmup observations are wiped with the stats.
        """
        registry = MetricsRegistry()
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=slot_len,
            prefill_buckets=buckets, max_prompt_len=mp, decode_window=window,
            registry=registry, prefix_cache_mb=prefix_mb,
        )
        # warmup: one request per bucket length compiles every executable
        # (each prefill bucket, insert, the decode window); with the cache on,
        # a duplicate of each drives one hit through every copy executable so
        # the timed region never pays a compile
        warm = [r.integers(1, cfg.vocab_size, (b,)).astype(np.int32) for b in buckets]
        if prefix_mb:
            warm = warm + [w.copy() for w in warm]
        eng.serve(warm, GenerationConfig(max_new_tokens=window))
        for k in eng.stats:
            eng.stats[k] = 0
        registry.reset()

        stamps = {}

        def on_token(req, tok):
            stamps.setdefault(req.rid, []).append(time.perf_counter())

        t0 = time.perf_counter()
        reqs = eng.serve(prompts, gens, on_token=on_token)
        dt = time.perf_counter() - t0
        # per-token latency samples at decode-window granularity, queue wait
        # included (what a caller actually observes)
        samples = np.concatenate(
            [np.diff(np.asarray([t0] + stamps[req.rid])) for req in reqs]
        )
        return eng, reqs, dt, registry, samples

    eng, reqs, dt_engine, registry, samples = run_engine(
        args.prefix_cache_mb if shared else 0
    )
    engine_tps = useful_tokens / dt_engine

    if shared:
        return _shared_prefix_result(
            args, preset, shared, prompt_lens, out_lens, useful_tokens,
            run_engine, eng, reqs, dt_engine, registry, samples, buckets, slots,
            window,
        )

    # static baseline: FCFS groups of `slots`, padded to the workload max —
    # one compiled (prompt, new_tokens) shape for every group
    P, N = int(prompt_lens.max()), int(out_lens.max())
    static_gen = GenerationConfig(max_new_tokens=N)
    batch = np.zeros((slots, P), np.int32)

    def run_group(idx):
        batch[:] = 0
        for row, i in enumerate(idx):
            batch[row, : len(prompts[i])] = prompts[i]
        seqs, _ = generate(model, params, jnp.asarray(batch), static_gen)
        return jax.block_until_ready(seqs)

    run_group(range(min(slots, len(prompts))))  # warmup / compile
    t0 = time.perf_counter()
    for start in range(0, len(prompts), slots):
        run_group(range(start, min(start + slots, len(prompts))))
    dt_static = time.perf_counter() - t0
    static_tps = useful_tokens / dt_static

    detail = {
        "preset": preset,
        "platform": jax.devices()[0].platform,
        "requests": args.requests,
        "num_slots": slots,
        "decode_window": window,
        "prefill_buckets": list(buckets),
        "prompt_len_p50_max": [int(np.median(prompt_lens)), int(prompt_lens.max())],
        "out_len_p50_max": [int(np.median(out_lens)), int(out_lens.max())],
        "useful_tokens": useful_tokens,
        "engine_wall_s": round(dt_engine, 3),
        "static_wall_s": round(dt_static, 3),
        "static_tokens_per_s": round(static_tps, 2),
        "token_latency_p50_ms": round(1e3 * float(np.percentile(samples, 50)), 2),
        "token_latency_p99_ms": round(1e3 * float(np.percentile(samples, 99)), 2),
        "mean_slot_occupancy": round(eng.mean_slot_occupancy(), 3),
        "compiled_executables": eng.compiled_executable_counts(),
    }
    detail.update(_cost_detail(eng, dt_engine))
    # Engine-side telemetry (ISSUE: TTFT + per-token percentiles and compile
    # counts in the bench contract).  TTFT here includes queue wait — it is
    # submit-to-first-token as a caller observes it, not prefill time alone.
    ttft = registry.get("serve/ttft_s").snapshot()
    tok = registry.get("serve/token_latency_s").snapshot()
    detail["telemetry"] = {
        "ttft_ms": {k: round(1e3 * ttft[k], 2) for k in ("p50", "p90", "p99", "mean")},
        "token_latency_ms": {k: round(1e3 * tok[k], 2) for k in ("p50", "p90", "p99", "mean")},
        "compile_counts": {
            wd.name: wd.compile_count
            for wd in [eng._decode, eng._insert, *eng._prefill.values()]
        },
        "watchdog_over_budget": any(
            wd.over_budget()
            for wd in [eng._decode, eng._insert, *eng._prefill.values()]
        ),
    }
    return {
        "metric": "serving_tokens_per_sec",
        "value": round(engine_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(engine_tps / static_tps, 3),
        "detail": detail,
    }


def main():
    presets = _presets()
    parser = argparse.ArgumentParser()
    parser.add_argument("--task", choices=["decode", "prefill", "serve", "spec"],
                        default="decode")
    parser.add_argument("--requests", type=int, default=16,
                        help="serve task: total queued requests (depth > --batch slots)")
    parser.add_argument("--decode_window", type=int, default=8,
                        help="serve task: decode steps fused per engine iteration")
    parser.add_argument("--serve_seed", type=int, default=0,
                        help="serve task: workload RNG seed")
    parser.add_argument("--shared-prefix", dest="shared_prefix", type=int, default=0,
                        help="serve task: common system-prompt length shared by "
                             "every request (0 = off); benches the prefix KV "
                             "cache against a cache-off run of the same workload")
    parser.add_argument("--paged-ab", dest="paged_ab", action="store_true",
                        help="--task serve: A/B the paged KV allocator against "
                             "the legacy slab pool at the same KV HBM budget "
                             "on a heavy-tail workload (token-identical check)")
    parser.add_argument("--kernel-ab", dest="kernel_ab", action="store_true",
                        help="--task serve: A/B decode kernels and KV dtypes on "
                             "the paged engine (xla vs pallas, native vs "
                             "--kv-dtype) — token-identity and logit-divergence "
                             "hard checks, plus a byte-equal capacity probe")
    parser.add_argument("--tp-ab", dest="tp_ab", action="store_true",
                        help="--task serve: multi-chip A/B — tp=2 vs tp=1 "
                             "(token-identity, per-device KV bytes, and "
                             "executable-budget hard checks) plus router "
                             "affinity vs round-robin on a shared-prefix "
                             "workload; writes MULTICHIP_r06.json on success")
    parser.add_argument("--async-ab", dest="async_ab", action="store_true",
                        help="--task serve: A/B the depth-1 pipelined serve "
                             "loop (async_depth=1) against the synchronous "
                             "loop — token-identity across greedy/sampled/"
                             "speculative/paged/int8-KV arms, >= 10% tokens/s "
                             "on the streaming greedy arm, overlap gauge > 0, "
                             "and an unchanged compiled-executable budget")
    parser.add_argument("--http-ab", dest="http_ab", action="store_true",
                        help="--task serve: drive the OpenAI front door over "
                             "the wire — token-identity vs in-process submit, "
                             "per-request SSE TTFT < completion, a 429 flood "
                             "with zero engine errors, and a mid-bench weight "
                             "hot-swap with zero failed or mixed-weight "
                             "in-flight requests (all hard checks)")
    parser.add_argument("--chaos-ab", dest="chaos_ab", action="store_true",
                        help="--task serve: chaos the serving stack — kill a "
                             "replica mid-generation (zero failed requests, "
                             "token-identical replay on the survivor), soak "
                             "a seeded fault mix (>=99%% completion, zero "
                             "driver crashes), then prove faults-off costs "
                             "nothing (<=1%% A/B, zero new executables; all "
                             "hard checks)")
    parser.add_argument("--trace-ab", dest="trace_ab", action="store_true",
                        help="--task serve: gate per-request tracing — kill a "
                             "replica mid-generation and require every "
                             "response's X-Request-Id to resolve to a "
                             "waterfall whose phase sum matches its TTFT "
                             "within 5%%, a failover trace spanning both "
                             "replicas, populated slowest-K retention, "
                             "token-identity traces on vs off, <=1%% paired "
                             "overhead, and an unchanged compiled-executable "
                             "budget (all hard checks)")
    parser.add_argument("--slo-ab", dest="slo_ab", action="store_true",
                        help="--task serve: gate the fleet-health layer — a "
                             "two-tenant HTTP flood whose per-tenant counter "
                             "and TTFT-histogram deltas must sum EXACTLY to "
                             "the globals, a fetch_slow-forced SLO fast-burn "
                             "that must capture exactly one diagnostics "
                             "bundle containing the offending window, <=1%% "
                             "null-calibrated paired overhead with the layer "
                             "on, and an unchanged compiled-executable "
                             "budget (all hard checks)")
    parser.add_argument("--prefill-ab", dest="prefill_ab", action="store_true",
                        help="--task serve: A/B the flash-prefill kernel and "
                             "decode-interleaved chunked prefill against the "
                             "admit-then-decode gather/scatter base on an "
                             "adversarial long-prompt-tenant + chat mix — "
                             "token-identity, executable-budget, and chat "
                             "p99-TTFT >= 1.3x hard checks; prefill tokens/s "
                             "gated on TPU")
    parser.add_argument("--hier-ab", dest="hier_ab", action="store_true",
                        help="--task serve: A/B the hierarchical prefix cache "
                             "(host-RAM spill tier + decode-overlapped H2D "
                             "promotion) against spill-off on a shared-prefix "
                             "mix whose working set is ~10x prefix_cache_mb — "
                             "token-identity, host hit rate > 0, tokens/s >= "
                             "1.25x, mean-TTFT, overlap, atpu-lint, and "
                             "executable-budget hard checks")
    parser.add_argument("--disagg-ab", dest="disagg_ab", action="store_true",
                        help="--task serve: A/B disaggregated prefill/decode "
                             "(role split + live KV page migration) against "
                             "the monolithic router — token identity greedy "
                             "AND sampled, a migrate-vs-replay crossover "
                             "curve (migration must win at the top), chat "
                             "p99 TTFT improvement on the adversarial "
                             "bulk-prefill + chat mix, zero failed requests "
                             "when a prefill replica dies mid-handoff, and "
                             "executable-budget hard checks")
    parser.add_argument("--kv-dtype", dest="kv_dtype", choices=["int8", "fp8"],
                        default="int8",
                        help="--kernel-ab: quantized KV page format for the "
                             "quantized arms")
    parser.add_argument("--kv-quant-tol", dest="kv_quant_tol", type=float,
                        default=1.5,
                        help="--kernel-ab: max tolerated logit divergence on "
                             "the quantized replay oracle (the bench exits "
                             "nonzero above it)")
    parser.add_argument("--prefix-cache-mb", dest="prefix_cache_mb", type=float,
                        default=64.0,
                        help="serve task: prefix KV cache byte budget (MiB) for "
                             "the --shared-prefix run")
    parser.add_argument("--speculate-k", dest="speculate_k", type=int, default=8,
                        help="spec task: draft tokens verified per cycle")
    parser.add_argument("--tree-ab", dest="tree_ab", action="store_true",
                        help="--task spec: A/B tree speculation with an "
                             "on-device draft model — token-identity across "
                             "{slab, paged} x {bf16, int8 KV} x {tp=1, tp=2}, "
                             ">= 1.4x tokens/s over speculation-off on a "
                             "non-repetitive workload (n-gram accept < 0.05 "
                             "in the same run), an acceptance-vs-speedup "
                             "curve in the JSON, and an executable budget "
                             "that grows by exactly {draft_forward, "
                             "tree_verify_window} with zero retraces "
                             "(all hard checks)")
    parser.add_argument("--spec_new_tokens", type=int, default=384,
                        help="spec task: generated tokens per request (long "
                             "enough for greedy decode to settle into the "
                             "repetitive pattern drafting exploits)")
    parser.add_argument("--preset", choices=list(presets), default=None,
                        help="default: small on TPU, tiny elsewhere (gpt2-xl = parity geometry)")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=512,
                        help="prefill length (decode task: prompt length = seq)")
    parser.add_argument("--new_tokens", type=int, default=4,
                        help="decode task: timed generated tokens (each token "
                             "streams the full weight set; size the count to "
                             "the host link)")
    parser.add_argument("--iters", type=int, default=4)
    parser.add_argument("--bits", type=int, choices=[8, 4], default=None,
                        help="stream int-quantized weights")
    parser.add_argument("--layers_per_stage", type=int, default=None,
                        help="layers streamed per chunk (default: ~6 chunks)")
    parser.add_argument("--checkpoint", type=str, default=None,
                        help="REAL checkpoint dir (raw HF gpt2/llama snapshot or "
                             "converted native): streams actual weights instead "
                             "of a synthetic preset")
    args = parser.parse_args()

    from accelerate_tpu import StreamingTransformer
    from accelerate_tpu.models.transformer import Transformer, TransformerConfig

    on_tpu = is_tpu_platform(jax.devices()[0].platform)
    if args.checkpoint is not None:
        # real-weights path: HF-dir auto-convert (models/hf_compat) + host load
        from accelerate_tpu.big_modeling import _checkpoint_files, _read_tensors
        from accelerate_tpu.models.hf_compat import (
            config_from_hf, convert_hf_checkpoint, is_hf_checkpoint,
        )
        from accelerate_tpu.utils.modeling import unflatten_tree

        ckpt = args.checkpoint
        t_ckpt_load = time.perf_counter()
        if is_hf_checkpoint(ckpt):
            cfg = config_from_hf(ckpt, dtype=jnp.bfloat16)
            ckpt = convert_hf_checkpoint(ckpt, dtype=jnp.bfloat16)
        elif os.path.isfile(os.path.join(ckpt, "atpu_conversion.json")):
            # already-converted native dir: the stamp carries the source config
            cfg = config_from_hf(ckpt, dtype=jnp.bfloat16)
        else:
            raise SystemExit(
                f"--checkpoint {ckpt}: neither a supported raw HF model dir nor "
                "a converted _atpu_native dir"
            )
        files = _checkpoint_files(ckpt)
        params = unflatten_tree(_read_tensors(files, list(files)))  # host numpy
        # the reference's published pairs are (load time, s/token) —
        # benchmarks/README.md:31-37; conversion is cached so steady-state
        # load time is the disk -> host read
        checkpoint_load_s = time.perf_counter() - t_ckpt_load
        preset = f"checkpoint:{os.path.basename(os.path.abspath(args.checkpoint))}"
        model = Transformer(cfg)
        seq = min(args.seq, cfg.max_seq_len)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (args.batch, seq)).astype(np.int32)
    else:
        # Default: "small" (~0.53 GB) on TPU.  The measured metric (stream
        # GB/s, s/token) is model-size-normalized; pass `--preset gpt2-xl`
        # (4.25 GB of weights per token) explicitly.
        preset = args.preset or ("small" if on_tpu else "tiny")
        cfg = presets[preset](dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
        seq = min(args.seq, cfg.max_seq_len)
        model = Transformer(cfg)

        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (args.batch, seq)).astype(np.int32)

        # abstract init, then materialize straight to HOST numpy — the weights
        # must not be HBM-resident for this benchmark to mean anything.
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.ones((1, seq), jnp.int32)))["params"]
        leaves, treedef = jax.tree_util.tree_flatten(params)
        host_leaves = []
        for i, leaf in enumerate(leaves):
            # cheap deterministic host-side init (no device round-trip for huge models)
            r = np.random.default_rng(i)
            host_leaves.append((r.standard_normal(leaf.shape, dtype=np.float32) * 0.02).astype(jnp.bfloat16))
        params = jax.tree_util.tree_unflatten(treedef, host_leaves)

    if args.task in ("serve", "spec"):
        if args.bits is not None:
            raise SystemExit(f"--task {args.task} benches HBM-resident decode; "
                             "--bits applies to the streaming tasks")
        bench = _serve_bench if args.task == "serve" else _spec_bench
        result = bench(args, model, cfg, params, preset)
        print(json.dumps(result))
        return

    # parameter count BEFORE quantization (int4 packing halves the element
    # count, which would skew the analytic-FLOPs MFU below)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )

    stream_cfg = cfg
    if args.bits is not None:
        from accelerate_tpu import Int4Config, Int8Config, quantize_model_params

        qconf = Int8Config() if args.bits == 8 else Int4Config()
        # quantize on the host CPU backend: on the default (TPU) device this
        # would round-trip the whole fp model through the transport first
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            params = quantize_model_params(params, qconf)
        params = jax.tree_util.tree_map(np.asarray, params)
        stream_cfg = dataclasses.replace(cfg, quantization=args.bits)

    model_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
    )

    def force(x):
        # a small D2H materialization as the completion barrier: it waits
        # for the value the timing is about
        return float(jnp.asarray(x).ravel()[0])

    lps = args.layers_per_stage or max(1, cfg.num_layers // 6)
    streamer = StreamingTransformer(stream_cfg, params, layers_per_stage=lps)

    detail = {
        "preset": preset,
        "model_gb": round(model_bytes / 1e9, 2),
        "baseline_stream_gbps": REFERENCE_STREAM_GBPS,
        "batch": args.batch,
        "seq": seq,
        "bits": args.bits or 16,
        "layers_per_stage": lps,
        **({"checkpoint_load_s": round(checkpoint_load_s, 2)} if args.checkpoint else {}),
        "platform": jax.devices()[0].platform,
    }

    if args.task == "decode":
        # the reference's published workload: per-token generation with every
        # token streaming the whole weight set (AlignDevicesHook offload loop)
        prompt = ids
        t_load = time.perf_counter()
        cache = streamer.init_cache(args.batch, prompt.shape[1] + args.new_tokens + 1)
        logits, cache = streamer.forward_with_cache(prompt, cache)  # prefill + compile
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        # warmup decode step (compiles the S=1 executables)
        logits, cache = streamer.forward_with_cache(tok[:, None], cache)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        force(tok)
        prefill_s = time.perf_counter() - t_load

        t0 = time.perf_counter()
        for _ in range(args.new_tokens):
            logits, cache = streamer.forward_with_cache(tok[:, None], cache)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        force(tok)
        dt = time.perf_counter() - t0

        s_per_token = dt / args.new_tokens
        tokens_per_s = args.batch * args.new_tokens / dt
        stream_gbps = model_bytes * args.new_tokens / dt / 1e9
        # Streaming dispatches per-stage executables, so there is no single
        # lowered callable to ask XLA about — analytic 2N FLOPs/token.  For
        # offload decode MFU is dominated by the host link, not the MXU.
        from accelerate_tpu.telemetry import detect_device_peaks

        peaks = detect_device_peaks()
        mfu = 2.0 * n_params * args.batch * args.new_tokens / dt / peaks.flops_per_s
        detail.update(
            {
                "s_per_token": round(s_per_token, 4),
                "new_tokens": args.new_tokens,
                "prefill_and_warmup_s": round(prefill_s, 2),
                "effective_stream_gbps": round(stream_gbps, 2),
                "mfu": round(min(1.0, mfu), 6),
                "mfu_source": "analytic_2N",
            }
        )
        result = {
            "metric": "streaming_decode_tokens_per_sec",
            "value": round(tokens_per_s, 2),
            "unit": "tokens/s",
            "vs_baseline": round(stream_gbps / REFERENCE_STREAM_GBPS, 3),
            "detail": detail,
        }
    else:
        force(streamer(ids))  # warmup: compiles the 3 stage executables
        t0 = time.perf_counter()
        for _ in range(args.iters):
            force(streamer(ids))
        dt = time.perf_counter() - t0

        tokens = args.batch * seq * args.iters
        stream_gbps = model_bytes * args.iters / dt / 1e9
        from accelerate_tpu.telemetry import detect_device_peaks

        peaks = detect_device_peaks()
        detail.update(
            {
                "iters": args.iters,
                "effective_stream_gbps": round(stream_gbps, 2),
                "forward_ms": round(1e3 * dt / args.iters, 1),
                "mfu": round(min(1.0, 2.0 * n_params * tokens / dt / peaks.flops_per_s), 6),
                "mfu_source": "analytic_2N",
            }
        )
        result = {
            "metric": "streaming_prefill_tokens_per_sec",
            "value": round(tokens / dt, 1),
            "unit": "tokens/s",
            "vs_baseline": round(stream_gbps / REFERENCE_STREAM_GBPS, 3),
            "detail": detail,
        }

    print(json.dumps(result))


if __name__ == "__main__":
    main()

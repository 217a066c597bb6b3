"""Tree speculative decoding with an on-device draft model: correctness pins.

The contract mirrors linear speculation's: the whole apparatus — the
truncated-layer draft head, the one-forward token-tree verify, branch
selection, per-lane KV commit/rollback — must be INVISIBLE in greedy token
streams (bitwise identical to the speculation-off engine, gathered and in-place,
float and quantized KV alike) and visible only in the stats.  On top of
that the device program set grows by exactly two executables
(``draft_forward`` + ``tree_verify_window``), each with one signature.

Identity tests run float32 for the same reason ``test_serving.py`` does:
token-exactness needs full-precision argmax margins, not bf16 ties.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.generation import GenerationConfig, generate
from accelerate_tpu.models.transformer import KVCache, Transformer, TransformerConfig
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.paging import DraftContextWindow
from accelerate_tpu.serving.pool import make_paged_tree_verify_window
from accelerate_tpu.serving.spec import propose_ngram_draft
from accelerate_tpu.serving.spec_exec import (
    NgramDrafter,
    TreeSpec,
    build_draft,
    default_draft_layers,
    make_draft_forward,
)
from accelerate_tpu.telemetry import MetricsRegistry
from accelerate_tpu.utils.jax_compat import jit_cache_supported


def _tiny_model(seed=0, **kw):
    # float32 everywhere: token-exactness comparisons need the argmax margins
    # of full precision, not bf16 ties
    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64, **kw
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompts(rng, lengths, vocab):
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]


def _expected(model, params, prompt, gen):
    """The static-``generate`` tokens for one request, pad tail trimmed."""
    seqs, _ = generate(model, params, jnp.asarray(prompt, jnp.int32)[None], gen)
    out = np.asarray(seqs[0])[len(prompt):]
    if gen.eos_token_id is not None:
        hits = np.nonzero(out == gen.eos_token_id)[0]
        if hits.size:
            out = out[: hits[0] + 1]
    return out.tolist()


TREE_KW = dict(draft_model=1, tree_width=2, tree_depth=3, draft_ctx=16)


def _engine(model, params, **kw):
    defaults = dict(num_slots=2, max_len=64, prefill_buckets=(4, 8),
                    prefill_token_budget=8, decode_window=2)
    defaults.update(kw)
    return ServingEngine(model, params, **defaults)


class TestTreeSpec:
    def test_chains_topology(self):
        t = TreeSpec(2, 3)
        assert (t.width, t.depth, t.nodes) == (2, 3, 7)
        # node(b, lvl) = 1 + b * depth + (lvl - 1); chains under a shared root
        assert t.parent.tolist() == [0, 0, 1, 2, 0, 4, 5]
        assert t.depth_arr.tolist() == [0, 1, 2, 3, 1, 2, 3]
        assert t.paths.tolist() == [[0, 1, 2, 3], [0, 4, 5, 6]]

    def test_ancestor_mask(self):
        t = TreeSpec(3, 2)
        for i in range(t.nodes):
            assert t.anc[i, i] and t.anc[i, 0]          # self + root visible
        # siblings and cross-branch nodes are mutually invisible
        for b in range(t.width):
            for other in range(t.width):
                if other == b:
                    continue
                for lvl in (1, 2):
                    assert not t.anc[t.paths[b, 1], t.paths[other, lvl]]
        # each path row is exactly the visible set of its leaf
        leaf = t.paths[1, t.depth]
        assert set(np.nonzero(t.anc[leaf])[0].tolist()) == set(t.paths[1].tolist())

    def test_width_one_degenerates_to_linear_chain(self):
        t = TreeSpec(1, 4)
        assert t.nodes == 5
        assert t.parent.tolist() == [0, 0, 1, 2, 3]
        assert t.depth_arr.tolist() == [0, 1, 2, 3, 4]
        assert np.array_equal(t.anc, np.tril(np.ones((5, 5), bool)))

    def test_rejects_degenerate_shapes(self):
        with pytest.raises(ValueError):
            TreeSpec(0, 3)
        with pytest.raises(ValueError):
            TreeSpec(2, 0)


class TestDraftContextWindow:
    def test_begin_keeps_prompt_tail(self):
        w = DraftContextWindow(2, 4, pad=0)
        w.begin(0, np.arange(1, 8, dtype=np.int32))      # 7 tokens into width 4
        assert w.tokens[0].tolist() == [4, 5, 6, 7] and w.length[0] == 4
        w.begin(1, [9, 9])
        assert w.tokens[1].tolist() == [9, 9, 0, 0] and w.length[1] == 2

    def test_push_slides_on_overflow(self):
        w = DraftContextWindow(1, 4)
        w.begin(0, [1, 2])
        w.push(0, [3])
        assert w.tokens[0].tolist() == [1, 2, 3, 0] and w.length[0] == 3
        w.push(0, [4, 5])                                 # spills one
        assert w.tokens[0].tolist() == [2, 3, 4, 5] and w.length[0] == 4
        w.push(0, [6, 7, 8, 9, 10])                       # wider than window
        assert w.tokens[0].tolist() == [7, 8, 9, 10] and w.length[0] == 4

    def test_tail_tracks_last_visible_token(self):
        # the invariant the engine relies on: after any begin/push sequence
        # the window's tail token is the lane's most recent visible token —
        # the draft forward's column 0 (tree root) must equal the pending
        # token the verify window scores first
        rng = np.random.default_rng(0)
        w = DraftContextWindow(1, 8)
        w.begin(0, rng.integers(1, 99, (11,)))
        last = None
        for _ in range(20):
            toks = rng.integers(1, 99, (int(rng.integers(1, 12)),))
            w.push(0, toks)
            last = int(toks[-1])
            assert int(w.tokens[0, w.length[0] - 1]) == last

    def test_retire_resets(self):
        w = DraftContextWindow(2, 4, pad=7)
        w.begin(0, [1, 2, 3])
        w.retire(0)
        assert w.tokens[0].tolist() == [7, 7, 7, 7] and w.length[0] == 0


class TestNgramDrafterSync:
    """The lazily-synced per-slot index must be token-identical to the
    brute-force rescan, cycle by cycle, while consuming only the delta."""

    def _draft(self, d):
        return None if d is None else d.tolist()

    def test_matches_bruteforce_over_growing_context(self):
        rng = np.random.default_rng(50)
        drafter = NgramDrafter()
        ctx = rng.integers(1, 6, (4,)).astype(np.int32).tolist()
        for _ in range(60):
            ctx.extend(rng.integers(1, 6, (int(rng.integers(1, 4)),)).tolist())
            k = int(rng.integers(1, 5))
            got = drafter.propose(0, np.asarray(ctx, np.int32), k)
            want = propose_ngram_draft(np.asarray(ctx, np.int32), k)
            assert self._draft(got) == self._draft(want)

    def test_slot_reuse_without_retire_rebuilds(self):
        drafter = NgramDrafter()
        long = np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int32)
        assert drafter.propose(0, long, 2) is not None
        # a NEW request landed in slot 0 with a shorter context: the stale
        # index (len 8 > len 5) must be dropped, not extended
        fresh = np.array([4, 5, 4, 5, 4], np.int32)
        got = drafter.propose(0, fresh, 3)
        want = propose_ngram_draft(fresh, 3)
        assert got.tolist() == want.tolist()

    def test_retire_drops_state_and_slots_are_independent(self):
        drafter = NgramDrafter()
        a = np.array([1, 2, 1, 2, 1], np.int32)
        b = np.array([7, 8, 9, 7, 8], np.int32)
        da, db = drafter.propose(0, a, 2), drafter.propose(1, b, 2)
        assert da.tolist() == propose_ngram_draft(a, 2).tolist()
        assert db.tolist() == propose_ngram_draft(b, 2).tolist()
        drafter.retire(0)
        assert 0 not in drafter._idx and 1 in drafter._idx


class TestBuildDraft:
    def test_int_slices_served_params(self):
        model, params = _tiny_model()
        cfg, dp = build_draft(model.config, params, 1, draft_ctx=16, depth=3)
        assert cfg.num_layers == 1
        assert cfg.paged_kernel == "xla"          # draft runs a slab scratch
        assert cfg.max_seq_len == model.config.max_seq_len
        # the head keeps embeddings/norm/lm-head and exactly one layer; a
        # 1-layer Transformer must accept the sliced tree as-is
        logits = Transformer(cfg).apply({"params": dp},
                                        jnp.zeros((1, 4), jnp.int32))
        assert logits.shape == (1, 4, cfg.vocab_size)

    def test_min_seq_len_covers_context_plus_rollout(self):
        model, params = _tiny_model()
        cfg, _ = build_draft(model.config, params, 1, draft_ctx=200, depth=3)
        assert cfg.max_seq_len == 204              # ctx + depth + 1

    def test_tuple_passthrough(self):
        model, params = _tiny_model()
        cfg, dp = build_draft(model.config, params,
                              (model.config, params), draft_ctx=8, depth=2)
        assert cfg is model.config
        assert jax.tree_util.tree_structure(dp) == jax.tree_util.tree_structure(params)

    def test_rejects_bad_specs(self):
        model, params = _tiny_model()
        for bad in (0, 3, -1):                     # tiny has 2 layers
            with pytest.raises(ValueError, match="out of range"):
                build_draft(model.config, params, bad, draft_ctx=8, depth=2)
        for bad in (True, 1.5, [1]):
            with pytest.raises(ValueError, match="draft_model must be"):
                build_draft(model.config, params, bad, draft_ctx=8, depth=2)

    def test_default_draft_layers(self):
        assert default_draft_layers(32) == 8
        assert default_draft_layers(2) == 1        # floors at one layer


class TestDraftForward:
    def test_matches_stepwise_greedy_rollout(self):
        """The fused two-phase forward (padded-context prefill -> top-W
        branch -> KV-tiled chain rollout) emits exactly the tokens a naive
        per-branch sequential rollout would, ragged lane lengths included."""
        model, params = _tiny_model()
        tree = TreeSpec(2, 3)
        ctx_len = 16
        draft_cfg, dp = build_draft(model.config, params, 1,
                                    draft_ctx=ctx_len, depth=tree.depth)
        dmodel = Transformer(draft_cfg)
        fwd = make_draft_forward(dmodel, tree, ctx_len)
        rng = np.random.default_rng(40)
        lens = (5, ctx_len)
        ctx = np.zeros((2, ctx_len), np.int32)
        for i, n in enumerate(lens):
            ctx[i, :n] = rng.integers(1, draft_cfg.vocab_size, (n,))
        out = np.asarray(fwd(dp, jnp.asarray(ctx), jnp.asarray(lens, jnp.int32)))
        assert out.shape == (2, tree.nodes)
        for i, n in enumerate(lens):
            assert out[i].tolist() == self._oracle(dmodel, dp, ctx[i], n,
                                                   tree, ctx_len)

    def _oracle(self, dmodel, dp, row, length, tree, ctx_len):
        cache = KVCache.create(dmodel.config, 1, max_len=ctx_len + tree.depth,
                               per_lane_index=True)
        logits, cache = dmodel.apply({"params": dp}, jnp.asarray(row)[None],
                                     cache=cache)
        cand = jax.lax.top_k(logits[0, length - 1], tree.width)[1]
        out = [int(row[length - 1])]                # column 0: the tree root
        for b in range(tree.width):
            c = cache.replace(index=jnp.full((1,), length, jnp.int32))
            tok = jnp.asarray([[int(cand[b])]], jnp.int32)
            chain = [int(cand[b])]
            for _ in range(tree.depth - 1):
                step, c = dmodel.apply({"params": dp}, tok, cache=c)
                nxt = int(jnp.argmax(step[0, 0]))
                chain.append(nxt)
                tok = jnp.asarray([[nxt]], jnp.int32)
            out.extend(chain)
        return out


PAGE = 4


def _paged(cache):
    """A lane's contiguous cache cut into pages 1..P of a fresh pool (page 0
    is the null page), with the block table that maps them back in order.
    Fresh arrays every call: the verify window donates its pages."""
    heads, dim = 2, cache.k.shape[2] // 2            # TransformerConfig.tiny: 2 kv heads

    def cut(x):                                      # [L, 1, H*D, T]
        L, _, _, T = x.shape
        pages = x[:, 0].reshape(L, heads, dim, T // PAGE, PAGE).transpose(0, 3, 1, 4, 2)
        return jnp.concatenate([jnp.zeros_like(pages[:, :1]), pages], axis=1)

    tables = jnp.arange(1, cache.max_len // PAGE + 1, dtype=jnp.int32)[None]
    return cut(cache.k), cut(cache.v), tables


def _rows(pages, lo, hi):
    """Positions ``[lo, hi)`` of the lane laid out by :func:`_paged`."""
    L, _, H, _, D = pages.shape
    return np.asarray(pages[:, 1:].swapaxes(2, 3).reshape(L, -1, H, D)[:, lo:hi])


def _cache_rows(x, lo, hi):
    """The same positions of lane 0 of a per-head ``KVCache`` array ``[L, B,
    H*D, M]``, as rows ``[L, hi - lo, H, D]``."""
    L, _, HD, _ = x.shape
    return np.asarray(x[:, 0, :, lo:hi]).swapaxes(1, 2).reshape(L, hi - lo, 2, HD // 2)


class TestTreeVerifyWindowDirect:
    """The jitted window probed in isolation: branch selection, EOS clamps,
    and the sampled arm's point-mass degeneration."""

    def _lane(self, model, params, prompt):
        cache = KVCache.create(model.config, 1, max_len=32, per_lane_index=True)
        logits, cache = model.apply({"params": params},
                                    jnp.asarray(prompt)[None], cache=cache)
        return cache, int(jnp.argmax(logits[0, -1]))

    def _greedy_chain(self, model, params, cache, pending, n):
        """``n`` sequential greedy steps: the tokens, and the cache they
        leave (what a tree verify's committed rows are held to)."""
        c, tok, out = cache, pending, []
        for _ in range(n):
            lg, c = model.apply({"params": params},
                                jnp.asarray([[tok]], jnp.int32), cache=c)
            tok = int(jnp.argmax(lg[0, 0]))
            out.append(tok)
        return out, c

    def _call(self, win, params, cache, tokens, eos=-1, do_sample=False,
              top_k=0):
        """``(pages_k, pages_v, out, n_commit, pending, rngs)``."""
        pages_k, pages_v, tables = _paged(cache)
        return win(params, pages_k, pages_v, tables, cache.index,
                   jnp.asarray(tokens, jnp.int32),
                   jnp.ones(1, bool), jnp.full(1, eos, jnp.int32),
                   jnp.full(1, do_sample, bool), jnp.ones(1, jnp.float32),
                   jnp.full(1, top_k, jnp.int32), jnp.ones(1, jnp.float32),
                   jnp.zeros(1, jnp.int32), jnp.zeros((1, 2), jnp.uint32))

    @pytest.fixture(scope="class")
    def scene(self):
        model, params = _tiny_model()
        prompt = np.random.default_rng(42).integers(
            1, model.config.vocab_size, (8,)).astype(np.int32)
        cache, pending = self._lane(model, params, prompt)
        tree = TreeSpec(2, 3)
        win = make_paged_tree_verify_window(model, tree)
        g, linear = self._greedy_chain(model, params, cache, pending,
                                       tree.depth + 1)
        alt = next(t for t in range(1, model.config.vocab_size)
                   if t not in set(g) and t != pending)
        # branch 0 carries the true greedy chain, branch 1 a loser made of a
        # single distinct token (so ok[] fails at its first node)
        tokens = np.array([[pending, g[0], g[1], g[2], alt, alt, alt]],
                          np.int32)
        return dict(model=model, params=params, cache=cache, win=win,
                    tree=tree, g=g, alt=alt, tokens=tokens, plen=len(prompt),
                    linear=linear)

    def _assert_committed_rows_are_linear_decodes(self, scene, pages_k,
                                                  pages_v, n):
        """The ``n`` rows the window committed at the lane frontier are the
        rows sequential decode writes there (the winning path compacted)."""
        lo, hi = scene["plen"], scene["plen"] + n
        for pages, want in ((pages_k, scene["linear"].k),
                            (pages_v, scene["linear"].v)):
            np.testing.assert_allclose(
                _rows(pages, lo, hi), _cache_rows(want, lo, hi),
                rtol=1e-5, atol=1e-5)

    def test_full_accept_commits_depth_plus_bonus(self, scene):
        pages_k, pages_v, out, n_commit, _, _ = self._call(
            scene["win"], scene["params"], scene["cache"], scene["tokens"])
        assert int(n_commit[0]) == scene["tree"].depth + 1
        assert np.asarray(out)[0].tolist() == scene["g"]
        self._assert_committed_rows_are_linear_decodes(
            scene, pages_k, pages_v, scene["tree"].depth + 1)

    def test_eos_on_losing_branch_does_not_terminate(self, scene):
        # the loser branch is ALL eos tokens; the winning path must commit
        # in full and never emit the eos that only losing nodes carried
        _, _, out, n_commit, _, _ = self._call(
            scene["win"], scene["params"], scene["cache"], scene["tokens"],
            eos=scene["alt"])
        assert int(n_commit[0]) == scene["tree"].depth + 1
        committed = np.asarray(out)[0].tolist()
        assert committed == scene["g"] and scene["alt"] not in committed

    def test_eos_on_accepted_path_masks_deeper_commits(self, scene):
        pages_k, pages_v, out, n_commit, _, _ = self._call(
            scene["win"], scene["params"], scene["cache"], scene["tokens"],
            eos=scene["g"][1])
        assert int(n_commit[0]) == 2                 # g0, then the eos itself
        assert np.asarray(out)[0].tolist()[:2] == scene["g"][:2]
        assert np.asarray(out)[0, 2:].tolist() == [0, 0]   # pad past the clamp
        self._assert_committed_rows_are_linear_decodes(scene, pages_k, pages_v, 2)

    def test_sampled_point_mass_equals_greedy(self, scene):
        # top_k=1 collapses every node distribution to its argmax: the
        # multi-try branch point and the Leviathan chain both accept exactly
        # the greedy path, bonus draw included
        _, _, out, n_commit, _, _ = self._call(
            scene["win"], scene["params"], scene["cache"], scene["tokens"],
            do_sample=True, top_k=1)
        assert int(n_commit[0]) == scene["tree"].depth + 1
        assert np.asarray(out)[0].tolist() == scene["g"]


class TestTreeEngine:
    """Engine-level: tree speculation invisible in tokens, visible in stats,
    bounded in executables."""

    def _workload(self, model, rng, lens=(9, 5, 12)):
        return _prompts(rng, lens, model.config.vocab_size)

    @pytest.mark.parametrize("arm", [
        {}, dict(decode_kernel="pallas"),
    ], ids=["gathered", "direct"])
    def test_greedy_token_exact(self, arm):
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(41))
        gens = [GenerationConfig(max_new_tokens=n) for n in (12, 8, 10)]
        outs = {}
        for tree_on in (False, True):
            eng = _engine(model, params, **arm,
                          **(TREE_KW if tree_on else {}))
            reqs = eng.serve(prompts, gens)
            outs[tree_on] = [r.tokens for r in reqs]
            if tree_on:
                assert eng.stats["spec_drafted"] > 0
        assert outs[True] == outs[False]
        for toks, p, g in zip(outs[False], prompts, gens):
            assert toks == _expected(model, params, p, g)

    def test_pallas_within_arm_identity(self):
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(42))
        gen = GenerationConfig(max_new_tokens=10)
        base = _engine(model, params, decode_kernel="pallas")
        tree = _engine(model, params, decode_kernel="pallas",
                       **TREE_KW)
        t0 = [r.tokens for r in base.serve(prompts, gen)]
        t1 = [r.tokens for r in tree.serve(prompts, gen)]
        assert t1 == t0
        assert tree.stats["spec_drafted"] > 0

    def test_int8_within_arm_identity(self):
        # page_size=1 keeps int8 scale groups per-position, the config under
        # which quantized verify is bitwise replayable
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(43))
        gen = GenerationConfig(max_new_tokens=10)
        kw = dict(kv_dtype="int8", page_size=1)
        t0 = [r.tokens for r in _engine(model, params, **kw).serve(prompts, gen)]
        t1 = [r.tokens
              for r in _engine(model, params, **kw, **TREE_KW).serve(prompts, gen)]
        assert t1 == t0

    def test_tp2_matches_and_refuses_the_pallas_kernel(self):
        mesh = build_mesh({"tp": 2}, devices=jax.devices()[:2])
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(44))
        gen = GenerationConfig(max_new_tokens=10)
        t1 = [r.tokens
              for r in _engine(model, params, **TREE_KW)
              .serve(prompts, gen)]
        with pytest.raises(ValueError, match="single-chip"):
            _engine(model, params, mesh=mesh,
                    decode_kernel="pallas", **TREE_KW)
        e2 = _engine(model, params, mesh=mesh, **TREE_KW)
        t2 = [r.tokens for r in e2.serve(prompts, gen)]
        assert e2.decode_kernel == "xla"
        assert t2 == t1

    @pytest.mark.parametrize("arm", [
        {}, dict(decode_kernel="pallas"),
    ], ids=["gathered", "direct"])
    @pytest.mark.parametrize("sampled", [False, True])
    def test_eos_on_accepted_path_truncates(self, arm, sampled):
        """An EOS the model itself emits mid-window must cut the stream at
        exactly the point sequential decode would — deeper committed tokens
        from the same verify pass never surface."""
        model, params = _tiny_model()
        prompt = np.random.default_rng(45).integers(
            1, model.config.vocab_size, (9,)).astype(np.int32)
        probe = GenerationConfig(max_new_tokens=10)
        base = _expected(model, params, prompt, probe)
        eos = base[4]
        # top_k=1 sampling is greedy with the sampled accept/commit code path
        gen = GenerationConfig(max_new_tokens=10, eos_token_id=eos,
                               do_sample=sampled, temperature=0.8,
                               top_k=1 if sampled else 0)
        want = _expected(model, params, prompt, gen)
        assert want[-1] == eos and len(want) < 10
        for kw in ({}, TREE_KW):
            (req,) = _engine(model, params, **arm, **kw).serve(
                [prompt], [gen])
            assert req.tokens == want

    def test_sampled_deterministic_and_in_vocab(self):
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(46))
        gen = GenerationConfig(max_new_tokens=8, do_sample=True, temperature=0.8)
        runs = []
        for _ in range(2):
            eng = _engine(model, params, rng_seed=123, **TREE_KW)
            reqs = eng.serve(prompts, gen)
            for r in reqs:
                assert len(r.tokens) == 8
                assert all(0 <= t < model.config.vocab_size for t in r.tokens)
            runs.append([r.tokens for r in reqs])
        assert runs[0] == runs[1]

    def test_compiled_budget_adds_exactly_draft_and_tree_verify(self):
        if not jit_cache_supported():
            pytest.skip("this jax hides the pjit executable-cache counter")
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(47))
        gens = [GenerationConfig(max_new_tokens=n) for n in (10, 6, 8)]
        eng = _engine(model, params, **TREE_KW)
        eng.serve(prompts, gens)
        eng.serve(prompts, gens)         # a second pass must retrace nothing
        assert eng.stats["spec_drafted"] > 0
        # every decode cycle rode the draft+tree pair; ONE signature each,
        # and the plain decode window never compiled (copy_page: the
        # 12-token prompt is two whole cached chunks, so its tail page is
        # shared with the prefix cache and copied before decode writes it)
        assert eng.compiled_executable_counts() == {
            "decode_window": 0, "copy_page": 1, "tree_verify_window": 1,
            "draft_forward": 1, "lane_install": 1, "prefill_4": 1,
            "prefill_8": 1,
        }
        assert not eng._verify.over_budget()
        assert not eng._draft_fwd.over_budget()

    def test_per_request_opt_out(self):
        model, params = _tiny_model()
        prompts = self._workload(model, np.random.default_rng(48))
        gen = GenerationConfig(max_new_tokens=8)
        eng = _engine(model, params, **TREE_KW)
        reqs = [eng.submit(p, config=gen, speculate=False) for p in prompts]
        eng.run()
        assert eng.stats["spec_drafted"] == 0
        counts = eng.compiled_executable_counts()
        assert counts["tree_verify_window"] == 0 and counts["draft_forward"] == 0
        assert counts["decode_window"] == 1
        for req, prompt in zip(reqs, prompts):
            assert req.tokens == _expected(model, params, prompt, gen)

    def test_capacity_check_covers_tree_span(self):
        model, params = _tiny_model()
        eng = _engine(model, params, draft_model=1, tree_width=4,
                      tree_depth=3, draft_ctx=16)
        # span = max(window, nodes) = 13: 8 + 44 + 13 > 64 slot capacity
        with pytest.raises(ValueError, match="speculation span"):
            eng.submit(np.ones(8, np.int32), max_new_tokens=44)
        eng.submit(np.ones(8, np.int32), max_new_tokens=43)

    def test_config_validation(self):
        model, params = _tiny_model()
        with pytest.raises(ValueError, match="tree_width"):
            _engine(model, params, tree_width=2)   # no draft model
        with pytest.raises(ValueError, match="32"):
            _engine(model, params, decode_kernel="pallas",
                    draft_model=1, tree_width=8, tree_depth=4)  # 33 nodes
        sw_model, sw_params = _tiny_model(sliding_window=8)
        with pytest.raises(ValueError, match="sliding"):
            _engine(sw_model, sw_params, **TREE_KW)

    def test_spec_metrics_flow_through_registry(self):
        model, params = _tiny_model()
        reg = MetricsRegistry()
        eng = _engine(model, params, registry=reg, **TREE_KW)
        eng.serve(self._workload(model, np.random.default_rng(49)),
                  GenerationConfig(max_new_tokens=10))
        snap = reg.snapshot()
        assert snap["serve/spec_drafted_total"] == eng.stats["spec_drafted"] > 0
        assert snap["serve/spec_accepted_total"] == eng.stats["spec_accepted"]
        assert snap["serve/spec_tree_nodes"] > 0
        assert snap["serve/draft_ms"]["count"] > 0
        assert snap["serve/spec_accept_len"]["count"] > 0

    def test_swap_params_reslices_draft_head(self):
        """Hot-swapping served weights must re-slice the self-speculation
        draft from the NEW params — and stay token-exact against a fresh
        speculation-off engine on those weights."""
        model, params = _tiny_model()
        _, params2 = _tiny_model(seed=1)
        prompt = np.random.default_rng(51).integers(
            1, model.config.vocab_size, (9,)).astype(np.int32)
        gen = GenerationConfig(max_new_tokens=10)
        eng = _engine(model, params, **TREE_KW)
        eng.serve([prompt], [gen])
        before = jax.tree_util.tree_leaves(eng._draft_params)[0]
        eng.swap_params(params2, version="v1")
        after = jax.tree_util.tree_leaves(eng._draft_params)[0]
        assert not np.array_equal(np.asarray(before), np.asarray(after))
        (req,) = eng.serve([prompt], [gen])
        assert req.tokens == _expected(model, params2, prompt, gen)

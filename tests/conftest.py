"""Test harness: force an 8-device CPU mesh (the reference's debug_launcher analog).

Reference tests exercise "distributed" logic without a cluster via multi-process
gloo (`launchers.py:263-296`); here the analog is XLA's forced host-platform device
count — 8 virtual CPU devices in one process, over which real meshes/shardings/
collectives run (SURVEY.md §4 lesson).

Env vars must be set before JAX initializes a backend, hence at conftest import.
The persistent compile cache (``utils.environment.enable_compile_cache``, which
``PartialState`` calls) stays OFF in tests and in the subprocesses they start:
tests measure what the tree compiles, not what an earlier run left behind.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

# Modules whose tests are compile-heavy (big jitted programs, pallas interpret
# mode), fork real processes, or smoke-run example scripts.  `make test_fast`
# deselects them (`-m "not slow"`) for a < 3 min developer loop — the
# reference's Makefile test-split analog (Makefile:25-72).
SLOW_MODULES = {
    "test_examples",
    "test_multiprocess",
    "test_generation",
    "test_pipeline",
    "test_serving",
    "test_serving_async",
    "test_serving_mesh",
    "test_flash_attention",
    "test_ring_attention",
    "test_fp8",
    "test_quantization",
    "test_big_modeling",
    "test_moe",
    "test_memory_and_local_sgd",
    "test_tensor_parallel",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def reset_singleton_state():
    """Reset Borg singletons between tests (reference ``AccelerateTestCase``,
    ``test_utils/testing.py:429-441``)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    GradientState._reset_state()
    AcceleratorState._reset_state(reset_partial_state=True)


@pytest.fixture()
def mesh8():
    import jax

    from accelerate_tpu.parallel.mesh import build_mesh

    return build_mesh({"dp": 2, "fsdp": 4}, devices=jax.devices())


@pytest.fixture()
def cycling_prompts():
    """Factory ``(model, params, lens, new_tokens, k) -> prompts`` for
    speculative-decoding tests that must see drafts ACCEPTED.

    The prompts are prefixes of one greedy trajectory of the model itself, cut
    where the trajectory already runs in a cycle.  Each prompt's reference
    continuation (``generate``) is then checked against the n-gram drafter at
    every context a verify cycle can start from: the drafter's first token is
    right at all of them.  An engine that is token-identical to ``generate``
    therefore accepts at least one draft — by construction, not because a
    random init under some jax release happens to continue a pattern."""
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import GenerationConfig, generate
    from accelerate_tpu.serving.spec import propose_ngram_draft

    def greedy(model, params, prompt, n):
        gen = GenerationConfig(max_new_tokens=n, do_sample=False, eos_token_id=None)
        seq, _ = generate(model, params, jnp.asarray(prompt, jnp.int32)[None], gen)
        return np.asarray(seq[0]).astype(np.int32)

    def drafter_predicts(seq, n_prompt, k):
        return all(
            (d := propose_ngram_draft(seq[:i], k)) is not None and d[0] == seq[i]
            for i in range(n_prompt + 1, len(seq))
        )

    def build(model, params, lens=(20, 24, 20), new_tokens=8, k=2, seed_len=6):
        vocab = model.config.vocab_size
        for seed in range(32):
            start = np.random.default_rng(seed).integers(1, vocab, (seed_len,))
            traj = greedy(model, params, start, max(lens) - seed_len)
            prompts = [traj[:n] for n in lens]
            if all(
                drafter_predicts(greedy(model, params, p, new_tokens), len(p), k)
                for p in prompts
            ):
                return prompts
        pytest.fail("no greedy trajectory of this model cycles within 32 seeds")

    return build

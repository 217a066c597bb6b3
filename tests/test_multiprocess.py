"""Tier-3 integration: the REAL launcher over bundled scripts in separate
processes (reference tests/test_multigpu.py:47-99 — `accelerate launch` over
test_utils scripts — and tests/test_state_checkpointing.py).

Tier 1 = unit tests, tier 2 = 8-virtual-device mesh in-process (conftest),
tier 3 = here: multi-process CPU rendezvous through `accelerate-tpu launch
--num_processes 2`, exercising jax.distributed init, the dispatcher/shard
dataloader across real process boundaries, per-process RNG, and
checkpoint-resume in a FRESH process.
"""

import os
import sys

import numpy as np
import pytest

from accelerate_tpu.test_utils import testing
from accelerate_tpu.test_utils.testing import execute_subprocess, launch_cmd, require_fork

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "accelerate_tpu", "test_utils")


def _env():
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE")}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(SCRIPTS))  # repo root
    # workers must not inherit the 8-virtual-device flag: each launched process
    # is its own single-device rank (the whole point of tier 3)
    env["XLA_FLAGS"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    return env


@require_fork
class TestLauncherSelfTest(testing.TempDirTestCase):
    def test_self_test_two_processes(self):
        out = execute_subprocess(
            launch_cmd(os.path.join(SCRIPTS, "test_script.py"), num_processes=2),
            env=_env(),
        )
        assert "All self-tests passed." in out
        assert "distributed == single-process losses: OK" in out
        assert "grad sync across accumulate boundary: OK" in out

    def test_debug_mode_shape_mismatch_raises_before_deadlock(self):
        """ACCELERATE_DEBUG_MODE=1 + a rank-dependent gather shape: operation
        verification must raise DistributedOperationException on every rank
        instead of letting the mismatched collective deadlock (reference
        utils/operations.py:361-421 behavior, across REAL processes)."""
        env = _env()
        env["ACCELERATE_DEBUG_MODE"] = "1"
        with pytest.raises(RuntimeError) as exc:
            execute_subprocess(
                launch_cmd(os.path.join(SCRIPTS, "debug_script.py"), num_processes=2),
                env=env,
            )
        out = str(exc.value)
        assert "DistributedOperationException" in out, out[-2000:]
        assert "caught mismatch before the collective ran" in out, out[-2000:]

    def test_checkpoint_resume_across_processes(self):
        """save mid-epoch in one 2-process run; resume in a FRESH 2-process run;
        final params must equal an uninterrupted run."""
        script = os.path.join(SCRIPTS, "checkpoint_script.py")
        for mode in ("full", "save", "resume"):
            execute_subprocess(
                launch_cmd(script, "--mode", mode, "--dir", self.tmpdir, num_processes=2),
                env=_env(),
            )
        full = np.load(os.path.join(self.tmpdir, "full.npz"))
        resumed = np.load(os.path.join(self.tmpdir, "resumed.npz"))
        for key in full.files:
            np.testing.assert_allclose(resumed[key], full[key], rtol=1e-5, atol=1e-6)

    clear_on_setup = False  # checkpoint test needs files across one method only


@require_fork
class TestElasticRestarts(testing.TempDirTestCase):
    """First-party launcher supervision (the torchelastic analog):
    --max_restarts relaunches after failure; a dead rank tears down the gang
    instead of hanging the survivors."""

    def test_simple_restart_succeeds_second_try(self):
        marker = os.path.join(self.tmpdir, "attempted")
        script = os.path.join(self.tmpdir, "flaky.py")
        with open(script, "w") as f:
            f.write(
                "import os, sys\n"
                f"marker = {marker!r}\n"
                "if not os.path.exists(marker):\n"
                "    open(marker, 'w').write('x')\n"
                "    sys.exit(3)\n"
                "print('second attempt ok')\n"
            )
        out = execute_subprocess(
            [sys.executable, "-m", "accelerate_tpu", "launch", "--cpu",
             "--max_restarts", "1", script],
            env=_env(),
        )
        assert "second attempt ok" in out

    def test_simple_no_restart_fails(self):
        script = os.path.join(self.tmpdir, "fail.py")
        with open(script, "w") as f:
            f.write("import sys; sys.exit(3)\n")
        with pytest.raises(RuntimeError, match="rc=3"):
            execute_subprocess(
                [sys.executable, "-m", "accelerate_tpu", "launch", "--cpu", script],
                env=_env(),
            )

    def test_gang_teardown_on_dead_rank(self):
        """rank 1 dies immediately; rank 0 would sleep forever — the monitor
        must terminate it and exit (or restart) instead of hanging."""
        script = os.path.join(self.tmpdir, "gang.py")
        with open(script, "w") as f:
            f.write(
                "import os, sys, time\n"
                "if os.environ['ACCELERATE_PROCESS_ID'] == '1':\n"
                "    sys.exit(5)\n"
                "time.sleep(600)\n"
            )
        import time

        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="rc="):
            execute_subprocess(
                [sys.executable, "-m", "accelerate_tpu", "launch", "--cpu",
                 "--num_processes", "2", "--monitor_interval", "0.2", script],
                env=_env(),
                timeout=120,
            )
        assert time.perf_counter() - t0 < 60, "gang teardown hung"


class TestRequireDecorators:
    def test_require_cpu_runs_here(self):
        ran = []

        @testing.require_cpu
        def probe(self=None):
            ran.append(True)

        probe()
        assert ran  # conftest forces the CPU platform

    def test_require_tpu_skips_here(self):
        @testing.require_tpu
        def probe(self=None):
            raise AssertionError("should have been skipped")

        with pytest.raises(Exception) as err:
            probe()
        assert "SkipTest" in type(err.value).__name__ or "skip" in str(err.value).lower()

    def test_require_multi_device_runs_on_mesh(self):
        ran = []

        @testing.require_multi_device
        def probe(self=None):
            ran.append(True)

        probe()
        assert ran  # 8 virtual devices in the test rig

    def test_require_tracker(self):
        @testing.require_tracker("definitely_not_installed_pkg")
        def probe(self=None):
            raise AssertionError("should have been skipped")

        with pytest.raises(Exception):
            probe()

    def test_slow_gate(self):
        assert os.environ.get("RUN_SLOW") is None

        @testing.slow
        def probe(self=None):
            raise AssertionError("should have been skipped")

        with pytest.raises(Exception):
            probe()


class TestRegressionFixtures(testing.AccelerateTestCase):
    def test_regression_model_converges(self):
        import optax

        from accelerate_tpu import Accelerator, SimpleDataLoader
        from accelerate_tpu.test_utils.training import RegressionModel, regression_dataset

        acc = Accelerator()
        dl = acc.prepare(SimpleDataLoader(regression_dataset(), batch_size=16, shuffle=True))
        state = acc.create_train_state(params=RegressionModel().init_params(), tx=optax.adam(5e-2))
        step = acc.compile_train_step(RegressionModel.loss_fn)
        for _ in range(30):
            for batch in dl:
                state, metrics = step(state, batch)
        assert float(metrics["loss"]) < 1e-2
        np.testing.assert_allclose(float(state.params["a"][0]), 2.0, atol=0.1)
        np.testing.assert_allclose(float(state.params["b"][0]), 3.0, atol=0.1)

"""Test-harness utilities shipped with the package.

TPU-native analog of reference ``src/accelerate/test_utils/testing.py``
(``require_*`` capability decorators ``:124-393``, ``AccelerateTestCase``
``:429-441``, ``TempDirTestCase`` ``:396``, ``execute_subprocess_async``
``:544-563``).  Decorators work on both unittest and pytest test functions.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import unittest
from typing import List, Optional

import functools

import jax

from ..utils.imports import is_tpu_available


def _skip_unless(predicate, reason: str):
    """Lazy skip decorator: ``predicate`` is evaluated at TEST time, not at
    decoration/import time.  This matters because most predicates touch
    ``jax.devices()``, which initializes the XLA backend — under
    ``accelerate-tpu launch`` that must not happen before
    ``jax.distributed.initialize`` (see the matching guard in state.py).
    Works on test functions/methods and on unittest classes (via setUp).
    """

    def decorator(test_case):
        if isinstance(test_case, type):
            orig_setup = test_case.setUp

            def setUp(self):
                if not predicate():
                    raise unittest.SkipTest(reason)
                orig_setup(self)

            test_case.setUp = setUp
            return test_case

        @functools.wraps(test_case)
        def wrapper(*args, **kwargs):
            if not predicate():
                raise unittest.SkipTest(reason)
            return test_case(*args, **kwargs)

        return wrapper

    return decorator


def device_platform() -> str:
    """The active accelerator platform ("cpu", "tpu") — the
    ``get_backend()`` analog (reference ``testing.py:61-80``)."""
    return jax.devices()[0].platform


def require_cpu(test_case):
    """Run only when no accelerator is active (reference ``require_cpu``)."""
    return _skip_unless(lambda: device_platform() == "cpu", "test requires a CPU-only runtime")(test_case)


def require_non_cpu(test_case):
    return _skip_unless(lambda: device_platform() != "cpu", "test requires an accelerator")(test_case)


def require_tpu(test_case):
    return _skip_unless(is_tpu_available, "test requires a TPU")(test_case)


def require_multi_device(test_case):
    """Needs >= 2 devices (real chips or the forced host-platform mesh)."""
    return _skip_unless(lambda: len(jax.devices()) > 1, "test requires multiple devices")(test_case)


def require_single_device(test_case):
    return _skip_unless(lambda: len(jax.devices()) == 1, "test requires exactly one device")(test_case)


def require_pallas(test_case):
    """Pallas TPU kernels compile on TPU backends only (interpret mode aside)."""
    return _skip_unless(is_tpu_available, "test requires pallas TPU support")(test_case)


def require_fork(test_case):
    """Multi-process CPU tests need working subprocess spawn (absent on some
    sandboxes/WASM)."""
    return _skip_unless(
        lambda: hasattr(os, "fork") or sys.platform == "win32",
        "test requires process spawning",
    )(test_case)


def require_tracker(name: str):
    """Skip unless the given experiment tracker's package is importable
    (reference per-tracker ``require_wandb``/``require_comet_ml``/...)."""
    def available() -> bool:
        from ..utils import imports

        probe = getattr(imports, f"is_{name}_available", None)
        return probe() if probe is not None else imports._is_package_available(name)

    def decorator(test_case):
        return _skip_unless(available, f"test requires {name}")(test_case)

    return decorator


def require_env_true(var: str):
    """Gate slow/integration tiers behind an env opt-in (the reference gates
    heavy suites behind RUN_SLOW)."""

    def decorator(test_case):
        return _skip_unless(
            lambda: os.environ.get(var, "").lower() in ("1", "true", "yes"),
            f"test requires {var}=1",
        )(test_case)

    return decorator


slow = require_env_true("RUN_SLOW")


def execute_subprocess(cmd: List[str], env: Optional[dict] = None, timeout: int = 600) -> str:
    """Run a command, raise with captured output on failure, return stdout
    (reference ``execute_subprocess_async``, ``testing.py:544-563``)."""
    result = subprocess.run(
        cmd,
        env=env if env is not None else os.environ.copy(),
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if result.returncode != 0:
        raise RuntimeError(
            f"command {' '.join(cmd)} failed with rc={result.returncode}\n"
            f"--- stdout ---\n{result.stdout}\n--- stderr ---\n{result.stderr}"
        )
    return result.stdout


def launch_cmd(
    script: str,
    *script_args: str,
    num_processes: int = 2,
    extra_flags: Optional[List[str]] = None,
) -> List[str]:
    """Command line for the real launcher over a bundled/user script — the
    tier-3 pattern (reference ``tests/test_multigpu.py:47-99`` execs
    ``accelerate launch``)."""
    return [
        sys.executable,
        "-m",
        "accelerate_tpu",
        "launch",
        "--cpu",
        "--num_processes",
        str(num_processes),
        *(extra_flags or []),
        script,
        *script_args,
    ]


class AccelerateTestCase(unittest.TestCase):
    """Resets the Borg singletons between tests (reference ``testing.py:429-441``)."""

    def tearDown(self):
        from ..state import AcceleratorState, GradientState, PartialState  # noqa: F401

        GradientState._reset_state()
        AcceleratorState._reset_state(reset_partial_state=True)
        super().tearDown()


class TempDirTestCase(unittest.TestCase):
    """Provides ``self.tmpdir``, cleared between tests (reference ``testing.py:396``).

    Set ``clear_on_setup = False`` to keep contents across test methods.
    """

    clear_on_setup = True
    tmpdir: str

    @classmethod
    def setUpClass(cls):
        super().setUpClass()
        cls.tmpdir = tempfile.mkdtemp(prefix="accelerate_tpu_test_")

    @classmethod
    def tearDownClass(cls):
        shutil.rmtree(cls.tmpdir, ignore_errors=True)
        super().tearDownClass()

    def setUp(self):
        super().setUp()
        if self.clear_on_setup:
            for entry in os.listdir(self.tmpdir):
                path = os.path.join(self.tmpdir, entry)
                shutil.rmtree(path, ignore_errors=True) if os.path.isdir(path) else os.remove(path)

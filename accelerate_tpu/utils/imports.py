"""Availability probes for optional dependencies.

TPU-native analog of the reference's ``src/accelerate/utils/imports.py`` (407 LoC of
``is_*_available`` probes).  On the JAX stack most of the reference's probes are
irrelevant (no CUDA/NPU/XPU/MLU); we keep the ones that gate real features here plus
TPU-specific ones.
"""

from __future__ import annotations

import functools
import importlib.metadata
import importlib.util


@functools.lru_cache()
def _is_package_available(pkg_name: str) -> bool:
    if importlib.util.find_spec(pkg_name) is None:
        return False
    try:
        importlib.metadata.version(pkg_name)
    except importlib.metadata.PackageNotFoundError:
        # Namespace packages / vendored modules without dist metadata still count.
        pass
    return True


def is_torch_available() -> bool:
    """CPU torch is an optional *data* dependency (users hand us torch DataLoaders)."""
    return _is_package_available("torch")


def is_tensorboard_available() -> bool:
    return _is_package_available("tensorboardX") or _is_package_available("tensorboard")


def is_wandb_available() -> bool:
    return _is_package_available("wandb")


def is_comet_ml_available() -> bool:
    return _is_package_available("comet_ml")


def is_mlflow_available() -> bool:
    return _is_package_available("mlflow")


def is_aim_available() -> bool:
    return _is_package_available("aim")


def is_clearml_available() -> bool:
    return _is_package_available("clearml")


def is_dvclive_available() -> bool:
    return _is_package_available("dvclive")


def is_safetensors_available() -> bool:
    return _is_package_available("safetensors")


def is_transformers_available() -> bool:
    return _is_package_available("transformers")


def is_datasets_available() -> bool:
    return _is_package_available("datasets")


def is_orbax_available() -> bool:
    return _is_package_available("orbax-checkpoint") or _is_package_available("orbax")


def is_rich_available() -> bool:
    return _is_package_available("rich")


def is_tqdm_available() -> bool:
    return _is_package_available("tqdm")


def is_pandas_available() -> bool:
    return _is_package_available("pandas")


def is_tpu_platform(platform: str) -> bool:
    """THE predicate for "this is the accelerator the package targets": a JAX
    platform string names a TPU iff it is ``"tpu"``.  Every on-chip/off-chip
    decision in the package (distributed type, host offload, Pallas interpret
    mode, the bench presets) reads this one function."""
    return platform == "tpu"


@functools.lru_cache()
def is_tpu_available() -> bool:
    """True when a real TPU backend is attached (not the CPU emulation mesh).
    A backend that fails to start raises — it is not reported as "no TPU"."""
    import jax

    return is_tpu_platform(jax.devices()[0].platform)


@functools.lru_cache()
def is_pallas_available() -> bool:
    try:
        from jax.experimental import pallas  # noqa: F401

        return True
    except ImportError:
        return False


def is_native_runtime_available() -> bool:
    """True when the C++ host-runtime extension is built (accelerate_tpu/native/)."""
    from . import _native

    return _native.is_available()


# backwards-compatible alias (pre-0.1 name)
is_native_dataloader_available = is_native_runtime_available

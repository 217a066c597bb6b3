"""The one sanctioned reader of a private jax attribute.

:func:`jit_cache_size` reads the pjit compiled-executable counter
(``f._cache_size()``) that the serving compiled-shape assertions and the
telemetry recompile watchdog rely on — the attribute is internal and has no
stability promise, so every consumer goes through this probe instead of
touching it directly.  (Checked against the pinned jax line in
``pyproject.toml``: the probe answers there.)
"""

from __future__ import annotations

from typing import Optional

# pjit-internal spellings of the compiled-executable counter, newest first.
_CACHE_SIZE_ATTRS = ("_cache_size",)


def jit_cache_size(fn) -> Optional[int]:
    """Compiled-executable count of a jitted callable, or ``None`` if unknown.

    jax exposes the per-function executable-cache size as the private
    ``f._cache_size()`` (0 until the first call).  Wrappers that forward
    attribute access to a wrapped jitted fn (the telemetry
    ``RecompileWatchdog``) work transparently.  When no known probe exists —
    a jax minor bump renamed the internal — this returns ``None`` instead of
    raising, so callers degrade to watchdog-signature counting rather than
    crashing the serving path; exact-count test assertions should skip via
    :func:`jit_cache_supported`.
    """
    for attr in _CACHE_SIZE_ATTRS:
        probe = getattr(fn, attr, None)
        if probe is None:
            continue
        try:
            return int(probe() if callable(probe) else probe)
        except Exception:
            continue
    return None


def jit_cache_supported() -> bool:
    """True when this jax exposes a readable executable-cache counter."""
    import jax

    return jit_cache_size(jax.jit(lambda x: x)) is not None


__all__ = ["jit_cache_size", "jit_cache_supported"]

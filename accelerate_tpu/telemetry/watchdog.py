"""Recompile watchdog: catch silent retraces, the dominant TPU perf failure.

A jitted callable that quietly compiles a new executable for every incoming
shape turns a hardware-speed loop into a compile loop — and nothing in JAX
shouts when it happens.  :class:`RecompileWatchdog` wraps any callable and
keys each call by the ``(shape, dtype)`` (plus static-value) signature of its
arguments:

* a **new** signature is recorded with the wall time of that first call (for a
  jitted fn that is trace + lower + compile time) and bumps the
  ``<name>/compile_count`` gauge in the registry;
* crossing the declared ``budget`` emits ONE ``get_logger`` warning listing
  the distinct signatures seen — the generalization of the executable-budget
  assertion the serving tests pin by hand;
* attribute access forwards to the wrapped fn, so pjit internals
  (``_cache_size`` et al.) and ``jit_cache_sizes`` keep working on the
  wrapped object.

The signature is computed host-side from the pytree of arguments — O(leaves)
tuple hashing, no device interaction — so watching a hot step costs far less
than the step's own host dispatch.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..logging import get_logger
from .metrics import MetricsRegistry, enabled, get_registry
from .tracer import get_tracer

logger = get_logger(__name__)


def arg_signature(args: Tuple[Any, ...], kwargs: Dict[str, Any]) -> Tuple:
    """Hashable ``(shape, dtype)``-level signature of a call's arguments.

    Array-likes contribute ``(shape, dtype)``; hashable non-arrays contribute
    their value (they would be jit *static* or weak-typed scalars — a changed
    value can mean a retrace); unhashable leaves contribute their type only.
    """
    import jax

    def leaf_sig(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            return ("arr", tuple(shape), str(dtype))
        try:
            hash(leaf)
        except TypeError:
            return ("type", type(leaf).__name__)
        return ("val", leaf)

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(leaf_sig(leaf) for leaf in leaves))


class RecompileWatchdog:
    """Wrap a (jitted) callable; account one entry per distinct call signature.

    Parameters
    ----------
    fn: the callable (typically ``jax.jit(...)`` output) to guard.
    name: metric/log name; defaults to the fn's ``__name__``.
    budget: max distinct signatures before the warning fires (None = just
        count).  The warning fires once per budget crossing, not per call.
    registry: metrics registry for the ``<name>/compile_count`` gauge and
        ``<name>/compile_time_s`` counter (default: the process registry).
    span: name of a tracer span opened around the call into ``fn`` alone,
        the signature pass left outside it (the trainer's ``train/dispatch``);
        None opens nothing (the serve watchdogs: their callers' spans are
        already there).
    """

    def __init__(
        self,
        fn: Callable,
        name: Optional[str] = None,
        budget: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        span: Optional[str] = None,
    ):
        self._fn = fn
        self._span = span
        self.name = name or getattr(fn, "__name__", type(fn).__name__)
        self.budget = budget
        self.signatures: Dict[Tuple, Dict[str, float]] = {}
        self._warned = False
        registry = registry or get_registry()
        self._count_gauge = registry.gauge(
            f"compile/{self.name}/count", help="distinct call signatures observed"
        )
        self._time_counter = registry.counter(
            f"compile/{self.name}/first_call_s",
            help="cumulative wall time of first-signature calls (≈ trace+compile)",
        )

    @property
    def compile_count(self) -> int:
        return len(self.signatures)

    def over_budget(self) -> bool:
        return self.budget is not None and len(self.signatures) > self.budget

    def _dispatch(self, args, kwargs):
        if self._span is None:
            return self._fn(*args, **kwargs)
        with get_tracer().span(self._span):
            return self._fn(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        if not enabled():
            return self._fn(*args, **kwargs)
        sig = arg_signature(args, kwargs)
        known = sig in self.signatures
        if known:
            return self._dispatch(args, kwargs)
        t0 = time.perf_counter()
        out = self._dispatch(args, kwargs)
        dt = time.perf_counter() - t0
        self.signatures[sig] = {"first_call_s": dt, "at": time.time()}
        self._count_gauge.set(len(self.signatures))
        self._time_counter.inc(dt)
        # a recompile inside a traced window shows with its name and its time
        get_tracer().record(f"compile/{self.name}", t0, t0 + dt, n=len(self.signatures))
        if self.over_budget() and not self._warned:
            self._warned = True
            shapes = "; ".join(
                ", ".join(f"{s[1]}:{s[2]}" for s in leaf_sigs if s[0] == "arr") or "(no arrays)"
                for _, leaf_sigs in list(self.signatures)[:8]
            )
            logger.warning(
                f"RecompileWatchdog[{self.name}]: {len(self.signatures)} distinct "
                f"call signatures exceed the compile budget of {self.budget} — a "
                f"shape or dtype is varying across calls and forcing retraces "
                f"(signatures: {shapes}). Pad or bucket the offending argument."
            )
        return out

    def jit_cache_size(self) -> Optional[int]:
        """Wrapped fn's compiled-executable count, via the jax_compat probe
        (None when this jax hides the counter) — prefer this over touching
        the forwarded ``_cache_size`` internal directly."""
        from ..utils.jax_compat import jit_cache_size

        return jit_cache_size(self._fn)

    def __getattr__(self, attr):
        # forward pjit internals (_cache_size, lower, ...) to the wrapped fn
        if attr == "_fn":  # guard pre-__init__ lookups from recursing
            raise AttributeError(attr)
        return getattr(self._fn, attr)

    def report(self) -> Dict[str, Any]:
        """Snapshot: count, budget, total first-call seconds, per-sig timings."""
        return {
            "name": self.name,
            "count": len(self.signatures),
            "budget": self.budget,
            "over_budget": self.over_budget(),
            "first_call_s_total": round(
                sum(s["first_call_s"] for s in self.signatures.values()), 4
            ),
        }


def watch_recompiles(
    fn: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    budget: Optional[int] = None,
    registry: Optional[MetricsRegistry] = None,
):
    """Decorator form: ``@watch_recompiles(budget=1)`` above a jitted fn."""
    if fn is None:
        import functools

        return functools.partial(
            watch_recompiles, name=name, budget=budget, registry=registry
        )
    return RecompileWatchdog(fn, name=name, budget=budget, registry=registry)

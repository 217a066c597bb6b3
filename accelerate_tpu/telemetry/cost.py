"""XLA cost and HBM accounting for owned executables.

(No analog in the reference — upstream accelerate has no notion of compiled
executables, let alone their FLOP/byte budgets. MegaScale-style per-step MFU
accounting is table stakes for TPU fleets; this module is the substrate.)

Every compiled function this library owns — the train/eval step, the serving
pool's prefill/decode/copy/insert executables — can be asked two questions
through XLA's AOT introspection APIs:

- ``lowered.cost_analysis()``: estimated FLOPs and bytes accessed for one
  call (available pre-compile, so it works even where compilation is slow).
- ``compiled.memory_analysis()``: argument / output / temp / generated-code
  buffer sizes, i.e. the executable's peak HBM footprint.

Both APIs are best-effort: backends may not implement them, analysis of a
Python-dispatch wrapper (the gradient-accumulation splitter, the chunked
offload step) is impossible, and numbers can be missing per-key. Every
accessor here degrades to ``None`` rather than raising.

The design splits *capture* from *analysis* so the hot path stays hot:

- :meth:`CostTable.capture` runs once per executable on its first call. It
  records only the abstract signature (``jax.ShapeDtypeStruct`` tree) of the
  arguments — no buffers are retained, so donation and GC are unaffected.
- :meth:`CostTable.analyze` lazily re-lowers from that signature and runs
  both XLA APIs. Callers (benches, the debug server's scrape collector, the
  flight recorder's dump path) invoke it off the step loop; per-step MFU
  gauge updates are then plain dict lookups.

MFU is measured FLOPs/s divided by the chip's peak from
:data:`HARDWARE_PEAKS` (TPU v4/v5e/v5p/v6e, plus a generic CPU fallback so
CPU CI exercises the full path), clamped into ``(0, 1]``.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

from ..logging import get_logger
from .metrics import MetricsRegistry, enabled, get_registry

logger = get_logger(__name__)

__all__ = [
    "DevicePeaks",
    "HARDWARE_PEAKS",
    "CPU_FALLBACK_PEAKS",
    "detect_device_peaks",
    "CostTable",
]


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Peak dense throughput for one accelerator chip.

    ``flops_per_s`` is the bf16 dense-matmul peak (the MFU denominator the
    TPU literature uses); ``hbm_bytes_per_s`` is peak memory bandwidth.
    ``source`` distinguishes a datasheet number from the generic fallback so
    downstream consumers can label MFU figures honestly.
    """

    kind: str
    flops_per_s: float
    hbm_bytes_per_s: float
    source: str = "spec"


# THE peaks table (bench.py reads it too).  Matched by substring against
# ``device.device_kind.lower()``; first hit wins.  bf16 dense peaks and
# per-chip HBM bandwidths from the public Cloud TPU spec sheets.
HARDWARE_PEAKS: Tuple[Tuple[str, DevicePeaks], ...] = (
    ("v6e", DevicePeaks("tpu-v6e", 918e12, 1.64e12)),
    ("v5p", DevicePeaks("tpu-v5p", 459e12, 2.765e12)),
    ("v5 lite", DevicePeaks("tpu-v5e", 197e12, 0.82e12)),
    ("v5e", DevicePeaks("tpu-v5e", 197e12, 0.82e12)),
    ("v4", DevicePeaks("tpu-v4", 275e12, 1.228e12)),
)

# The CPU test rig's labelled stand-in (source="fallback"): a deliberately
# round number so the MFU arithmetic the tests read stays finite.  CPU
# devices only — an accelerator never gets it.
CPU_FALLBACK_PEAKS = DevicePeaks("generic-cpu", 2e12, 0.1e12, source="fallback")


def detect_device_peaks(device: Any = None) -> DevicePeaks:
    """Return peaks for ``device`` (default: ``jax.devices()[0]``).

    A CPU device gets :data:`CPU_FALLBACK_PEAKS`; an accelerator whose
    ``device_kind`` is not in :data:`HARDWARE_PEAKS` raises — a utilization
    figure against made-up peaks is worse than none.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", ""))
    for needle, peaks in HARDWARE_PEAKS:
        if needle in kind.lower():
            return peaks
    if getattr(device, "platform", None) == "cpu":
        return CPU_FALLBACK_PEAKS
    raise ValueError(
        f"no peak FLOP/s and bandwidth entry for accelerator {kind!r}: add "
        f"it to telemetry.cost.HARDWARE_PEAKS with its source"
    )


def _abstractify(x: Any) -> Any:
    """Map an array-like leaf to its ShapeDtypeStruct; pass scalars through."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        import jax

        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return x


def _normalize_cost(cost: Any) -> Optional[Dict[str, float]]:
    # Lowered.cost_analysis() returns a dict; Compiled.cost_analysis()
    # historically returned a one-element list of dicts. Accept both.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    return cost


class CostTable:
    """Per-executable FLOP and HBM accounting, keyed by a stable name.

    Thread-safe; ``capture`` is safe to call every step (a dict-membership
    check after the first call), ``analyze`` compiles and is meant for
    off-loop callers.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict[str, Any]] = {}

    def captured(self, name: str) -> bool:
        return name in self._entries

    def capture(
        self,
        name: str,
        fn: Callable,
        args: Tuple[Any, ...] = (),
        kwargs: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Record the abstract call signature of ``fn`` once.

        Returns True iff a new entry was created. Cheap after the first
        call; stores no device buffers.
        """
        if not enabled() or name in self._entries:
            return False
        entry: Dict[str, Any] = {
            "name": name,
            "analyzed": False,
            "flops": None,
            "bytes_accessed": None,
            "hbm_peak_bytes": None,
            "memory": None,
            "error": None,
        }
        try:
            import jax

            avals_args, avals_kwargs = jax.tree_util.tree_map(
                _abstractify, (tuple(args), dict(kwargs or {}))
            )
            entry["_fn"] = fn
            entry["_avals"] = (avals_args, avals_kwargs)
        except Exception as exc:  # non-pytree args, exotic leaves
            entry["analyzed"] = True
            entry["error"] = f"signature capture failed: {exc!r}"
        with self._lock:
            if name in self._entries:
                return False
            self._entries[name] = entry
        return True

    def analyze(self, name: str) -> Optional[Dict[str, Any]]:
        """Lower + compile from the captured signature and run both XLA
        introspection APIs. Idempotent; returns the public entry dict or
        ``None`` if ``name`` was never captured."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            return None
        if entry["analyzed"]:
            return self._public(entry)
        fn = entry.get("_fn")
        lower = getattr(fn, "lower", None)
        if lower is None:
            # Python-dispatch wrappers (grad-accumulation splitter, chunked
            # offload) have no single XLA program to analyze.
            entry["error"] = "executable has no .lower (python dispatch)"
            entry["analyzed"] = True
            return self._public(entry)
        avals_args, avals_kwargs = entry["_avals"]
        try:
            lowered = lower(*avals_args, **avals_kwargs)
        except Exception as exc:
            entry["error"] = f"lower failed: {exc!r}"
            entry["analyzed"] = True
            return self._public(entry)
        try:
            cost = _normalize_cost(lowered.cost_analysis())
            if cost is not None:
                flops = cost.get("flops")
                if flops is not None and flops > 0:
                    entry["flops"] = float(flops)
                ba = cost.get("bytes accessed")
                if ba is not None and ba > 0:
                    entry["bytes_accessed"] = float(ba)
        except Exception as exc:  # backend without cost_analysis
            entry["error"] = f"cost_analysis failed: {exc!r}"
        try:
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            if mem is not None:
                memory = {
                    key: float(val)
                    for key in (
                        "argument_size_in_bytes",
                        "output_size_in_bytes",
                        "temp_size_in_bytes",
                        "alias_size_in_bytes",
                        "generated_code_size_in_bytes",
                    )
                    for val in [getattr(mem, key, None)]
                    if val is not None
                }
                if memory:
                    entry["memory"] = memory
                    # Aliased (donated) buffers are counted in both argument
                    # and output size; subtract once for the live peak.
                    peak = (
                        memory.get("argument_size_in_bytes", 0.0)
                        + memory.get("output_size_in_bytes", 0.0)
                        + memory.get("temp_size_in_bytes", 0.0)
                        - memory.get("alias_size_in_bytes", 0.0)
                    )
                    entry["hbm_peak_bytes"] = max(peak, 0.0)
        except Exception as exc:  # backend without memory_analysis
            if entry["error"] is None:
                entry["error"] = f"memory_analysis failed: {exc!r}"
        entry["analyzed"] = True
        self._publish(entry)
        return self._public(entry)

    def analyze_all(self) -> Dict[str, Dict[str, Any]]:
        """Analyze every captured executable; returns the full snapshot."""
        with self._lock:
            names = list(self._entries)
        for name in names:
            self.analyze(name)
        return self.snapshot()

    def flops(self, name: str) -> Optional[float]:
        entry = self._entries.get(name)
        return entry["flops"] if entry is not None else None

    def bytes_accessed(self, name: str) -> Optional[float]:
        entry = self._entries.get(name)
        return entry["bytes_accessed"] if entry is not None else None

    def hbm_peak_bytes(self, name: str) -> Optional[float]:
        entry = self._entries.get(name)
        return entry["hbm_peak_bytes"] if entry is not None else None

    def max_hbm_peak_bytes(self) -> Optional[float]:
        """Largest per-executable HBM peak across the table (the number that
        predicts whether the workload fits on the chip)."""
        with self._lock:
            peaks = [
                e["hbm_peak_bytes"]
                for e in self._entries.values()
                if e["hbm_peak_bytes"] is not None
            ]
        return max(peaks) if peaks else None

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: self._public(e) for name, e in self._entries.items()}

    def _publish(self, entry: Dict[str, Any]) -> None:
        """Mirror one analyzed entry into ``cost/<name>/*`` gauges."""
        try:
            name = entry["name"]
            if entry["flops"] is not None:
                self.registry.gauge(f"cost/{name}/flops").set(entry["flops"])
            if entry["bytes_accessed"] is not None:
                self.registry.gauge(f"cost/{name}/bytes_accessed").set(
                    entry["bytes_accessed"]
                )
            if entry["hbm_peak_bytes"] is not None:
                self.registry.gauge(f"cost/{name}/hbm_peak_bytes").set(
                    entry["hbm_peak_bytes"]
                )
        except Exception:  # registry disabled mid-flight
            logger.debug("cost gauge publish failed", exc_info=True)

    @staticmethod
    def _public(entry: Dict[str, Any]) -> Dict[str, Any]:
        return {k: v for k, v in entry.items() if not k.startswith("_")}

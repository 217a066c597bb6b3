"""What every cell's driver shares: the manifest, the device check, the
compile cache and clock, the table of peaks, percentiles, and the result line.

Nothing here imports JAX at module import; call :func:`setup_cache` before the
first ``import jax`` of the process.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoResult(SystemExit):
    """Ends the run with a non-zero code and no result line."""

    def __init__(self, message):
        print(f"bench: no result - {message}", file=sys.stderr, flush=True)
        super().__init__(2)


def log(**fields):
    """One JSON line of progress on standard error."""
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name):
    """``(manifest, cell entry, cell file, configuration file)`` for a workload
    name: the entry from ``BENCHMARK.json``, the rest found by name."""
    manifest = read_json(ROOT / "BENCHMARK.json")
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise NoResult(f"BENCHMARK.json names no workload {name!r}")
    entry = entries[0]
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    cell = read_json(BENCH / "workloads" / f"{name}.json")
    config = read_json(ROOT / config_entry["file"])
    return manifest, entry, cell, config


def metric_names(manifest, cell_name, group):
    """Names of the ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m["name"] for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reducer(metric_name):
    """``(spec, function)`` of one per-layer metric: ``metrics/<name>.json``
    names a module under ``reducers/`` whose ``reduce(ctx, **params)`` returns
    the value, or ``None`` where it finds nothing to read."""
    spec = read_json(BENCH / "metrics" / f"{metric_name}.json")
    module = importlib.import_module(f"reducers.{spec['reducer']}")
    return spec, module.reduce


# ------------------------------------------------------------------ the device
def setup_cache(cell_name):
    """JAX's persistent cache in a directory of the cell's own, under
    ``JAX_COMPILATION_CACHE_DIR`` if set and else under the fixed
    ``bench/.jax_cache``; everything is cached, however quick to compile.  A
    directory to a cell, because where the outer directory is capped in size
    (the chip tool caps it at 192 MiB, about what two cells' programs take) one
    cell's entries push out another's and set-up swings by a program's compile
    time.  The program's own ``enable_compile_cache`` honours the variable, so
    it takes this directory too."""
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(BENCH / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, cell_name)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def require_chips(chips, rehearse):
    """The devices, or no result: a measured run needs ``chips`` TPU devices."""
    import jax

    devices = jax.devices()
    if rehearse:
        return devices
    if devices[0].platform != "tpu":
        raise NoResult(f"needs a TPU; JAX found platform {devices[0].platform!r}")
    if len(devices) != chips:
        raise NoResult(f"the cell asks for {chips} chip(s); JAX found {len(devices)}")
    return devices


def peaks(device_kind):
    table = read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise NoResult(f"peaks.json has no row for device_kind {device_kind!r}")
    return table[device_kind]


def memory_peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def device_block(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak_bytes(devices)}


class CompileClock:
    """Seconds JAX spent lowering and compiling, how many programs it compiled
    and how many it found in the persistent cache (``jax.monitoring``).
    Copied from ``chip_smoke.py``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.slow = []          # (program, seconds) of every backend compile over half a second
        self.names = []         # every program handed to the backend, in order
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, seconds, **kw):
        if name in self.EVENTS:
            self.compile_s += seconds
        if name == self.EVENTS[1]:
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))
            if seconds >= 0.5:
                self.slow.append((str(kw.get("fun_name", "?")), round(seconds, 2)))

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {"compile_s": round(self.compile_s, 3), "backend_compiles": self.compiles,
                "cache_hits": self.cache_hits, "slow_compiles": list(self.slow)}


def free_program():
    """Drops what JAX still holds of the program under test (compiled
    executables and their constants), so that the reference has the chip."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------ arithmetic
def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between order
    statistics; ``None`` of nothing."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


# ---------------------------------------------------------------------- output
def end_to_end(manifest, cell_name, values):
    """The cell's end-to-end metrics as the result line wants them, units from
    the manifest; a value that is ``None`` is left out."""
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    return {name: {"value": values[name], "unit": units[name]}
            for name in metric_names(manifest, cell_name, "end_to_end") if values.get(name) is not None}



def judge(compared):
    """``compared`` is name -> ``{"value": x, "limit": y}``; correct where every
    value is a number no larger than its limit."""
    ok = bool(compared)
    for item in compared.values():
        v = item["value"]
        if v is None or v != v or v > item["limit"]:
            ok = False
    return ok


def emit(correct, attempted, failed, metrics, device, compared, breakdown=None, rehearse=False):
    """The compared numbers beside their limits as the last lines of standard
    error, then the one result line as the last line of standard output.  A
    rehearsal (not on the chip) prints its line to standard error only."""
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    sys.stdout.flush()
    for name, item in compared.items():
        print(f"compared {name} = {item['value']} limit {item['limit']}", file=sys.stderr)
    print(f"correct = {bool(correct)}", file=sys.stderr, flush=True)
    if rehearse:
        print("rehearsal (no result): " + json.dumps(line), file=sys.stderr, flush=True)
    else:
        print(json.dumps(line), flush=True)
    return line


def process_start():
    """``time.time()`` at which this process began, from /proc where it is there."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None

"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` alone.  A TPU's plane is
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
operation and ``XLA Modules`` one per executed program.  Host threads are lines
of ``/host:CPU``; ``TraceAnnotation`` spans (the window marker, and the
program's tracer spans while a capture is active) are events there, on the
same clock.

The traced window is the ``bench_window`` annotation the harness holds open
around the traced slice.  Busy time is the union of the device's operation
intervals inside it, averaged over the chips used; an idle gap is a maximal
interval inside the window with no operation running on a chip, named by the
innermost host span open at its middle.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_FLOOR_NS = 20_000          # shorter gaps are launch latency, not idleness


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_op_name(name):
    """``%fusion.592 = bf16[50257,1024]{...} fusion(...)`` -> ``fusion.592
    bf16[50257,1024]``: the operation and the shape of its (first) result."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:64]


def read(path, allow_host_ops=False):
    """``{"window": (start, end) | None, "devices": [{"ops": [...], "modules":
    [...]}], "host": {line name: [...]}}``, every event ``(name, start_ns,
    duration_ns)``.  ``allow_host_ops`` (rehearsal on the CPU only) takes host
    lines whose events carry an ``hlo_op`` stat as the one device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = [], {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append({
                    "name": plane.name,
                    "ops": _events(lines[OPS_LINE]),
                    "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
                })
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = _events(line)
                host[line.name] = events
                for name, start, dur in events:
                    if name == WINDOW_SPAN:
                        window = (start, start + dur)
                if allow_host_ops:
                    ops = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
                           if any(k == "hlo_op" for k, _ in e.stats)]
                    if ops:
                        if not devices:
                            devices.append({"name": "host-ops", "ops": [], "modules": []})
                        devices[0]["ops"] += ops
    if allow_host_ops:
        devices = [d for d in devices if d["name"] == "host-ops"] or devices
    return {"window": window, "devices": devices, "host": host}


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _union(intervals):
    """Merged ``(start, end)`` intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


_PROGRAM_SPAN = re.compile(r"^[a-z_]+/")      # the program's tracer spans: ``serve/decode_window``


def _name_gaps(host, gaps):
    """For each ``(start, end)`` gap the innermost (shortest) host span open at
    its middle - one of the program's own spans (``layer/name``) where one is
    open, else whatever the runtime had open - the window's own marker aside:
    one sweep over spans sorted by
    start and gaps sorted by middle, the open spans kept in a heap by end."""
    import heapq

    spans = sorted((start, start + dur, dur, name) for events in host.values()
                   for name, start, dur in events if name != WINDOW_SPAN and dur > 0)
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    names, open_spans, nxt = [None] * len(gaps), [], 0
    for i in order:
        t = (gaps[i][0] + gaps[i][1]) / 2
        while nxt < len(spans) and spans[nxt][0] <= t:
            start, end, dur, name = spans[nxt]
            heapq.heappush(open_spans, (end, dur, name))
            nxt += 1
        while open_spans and open_spans[0][0] <= t:
            heapq.heappop(open_spans)
        own = [s for s in open_spans if _PROGRAM_SPAN.match(s[2])]
        pick = own or open_spans
        names[i] = min(pick, key=lambda s: s[1])[2] if pick else "no span open"
    return names


_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* ")


def is_container(name):
    """A ``while``, ``conditional`` or ``call`` event spans the operations of
    its body, which have events of their own: it counts towards busy time (the
    union) but not towards the ranking of operations."""
    return bool(_CONTAINER.match(name))


def summarize(trace, top=10):
    """``window_s``, ``busy_s`` (mean over the devices traced), the device
    operations that took most time (summed over devices, by full name), the
    programs' device seconds and counts by name, and the idle seconds by host
    span."""
    window = trace["window"]
    devices = trace["devices"]
    if window is None or not devices:
        return None
    busy, ops, modules, gaps = [], {}, {}, {}
    for dev in devices:
        clipped = _clip(dev["ops"], window)
        merged = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in clipped:
            if not is_container(name):
                ops[name] = ops.get(name, 0.0) + (e - s)
        for name, s, e in _clip(dev["modules"], window):
            entry = modules.setdefault(name, [0.0, 0])
            entry[0] += e - s
            entry[1] += 1
        edges = [window[0]] + [t for pair in merged for t in pair] + [window[1]]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b - a >= GAP_FLOOR_NS]
        for (a, b), name in zip(idle, _name_gaps(trace["host"], idle)):
            gaps[name] = gaps.get(name, 0.0) + (b - a)
    n = len(devices)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "device_ops": [[short_op_name(k), v / n / 1e9] for k, v in rank(ops)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in rank(gaps)],
        "modules": {k: {"seconds": v[0] / n / 1e9, "count": v[1] / n} for k, v in modules.items()},
    }

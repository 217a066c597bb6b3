"""The traced slice of a window, shared by the drivers: starting and stopping
the profiler around it, and reading the per-layer metrics out of the trace."""

from __future__ import annotations

import shutil

from lib import common, xplane


def start_trace(trace_dir):
    """Starts the profiler (host annotations on, Python call tracing off),
    mirrors the program's tracer spans into it and opens the window's marker."""
    import jax

    from accelerate_tpu.telemetry.tracer import set_device_trace_active

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    set_device_trace_active(True)
    marker = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
    marker.__enter__()
    return marker


def stop_trace(marker):
    import jax

    from accelerate_tpu.telemetry.tracer import set_device_trace_active

    marker.__exit__(None, None, None)
    set_device_trace_active(False)
    jax.profiler.stop_trace()


def traced_metrics(manifest, entry, cell, published, window, devices, trace_dir, args):
    """Reads the trace and runs each per-layer metric's reader.  Returns
    ``(metrics, breakdown, trace summary)``."""
    path = xplane.newest_xplane(str(trace_dir))
    summary = None
    if path is not None:
        summary = xplane.summarize(xplane.read(path, allow_host_ops=args.rehearse))
    if not getattr(args, "keep_trace", False):
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"window": window, "trace": summary, "published": published, "cell": cell,
           "peaks": None if args.rehearse else common.peaks(devices[0].device_kind),
           "chips": len(devices)}
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    out = {}
    for name in common.metric_names(manifest, entry["name"], "per_layer"):
        spec, reduce = common.load_reducer(name)
        value = reduce(ctx, **spec.get("params", {}))
        if value is not None:
            out[name] = {"value": value, "unit": units[name]}
    breakdown = None
    if summary is not None:
        breakdown = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
        common.log(event="trace", window_s=summary["window_s"], busy_s=summary["busy_s"],
                   modules=summary["modules"])
    return out, breakdown, summary

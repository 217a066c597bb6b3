"""Share of the HBM roofline the decode program of a stack of window and full
layers with routed experts reached in the traced slice: the least bytes for
the tokens the clients received there (the cache rows a live lane's step could
see, ``min(context, window)`` in each window layer and ``context`` in each full
one, by the program's own ``kv_rows_live`` over the slice; each token's share
of one read of the non-expert weights and the head slice by a full batch; one
read of each routed expert that got a token, by ``moe_experts_hit``; the
reference module's ``decode_least_bytes``) over the peak bytes/s, over the
decode program's device time.  Every term is at most what the device read (the
XLA path reads each kind's whole gathered view).  Nothing where the program
has no such counters."""

import importlib

from reducers.program_device_time import matching_seconds


def reduce(ctx, pattern):
    trace, window, peaks = ctx["trace"], ctx["window"], ctx["peaks"]
    work, counters = window.get("slice_work"), window.get("slice_counters")
    if (trace is None or peaks is None or not work or not work["decode_contexts"] or not counters
            or not counters.get("kv_rows_live") or "moe_experts_hit" not in counters
            or "reference" not in window):
        return None
    seconds = matching_seconds(trace, pattern)
    if not seconds:
        return None
    reference = importlib.import_module(f"reference.{window['reference']}")
    least = reference.decode_least_bytes(ctx["published"], work["decode_contexts"], window["num_slots"],
                                         counters["moe_experts_hit"], rows_live=counters["kv_rows_live"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / seconds

"""Share of the HBM roofline the decode program of a routed-experts model
reached in the traced slice: the least bytes for the tokens the clients
received there (each token's context x its cache bytes a token, plus its share
of one read of the non-expert weights by a full batch, plus one read of each
routed expert that got a token, by the program's own ``moe_experts_hit`` over
the slice; the reference module's ``decode_least_bytes``) over the peak
bytes/s, over the decode program's device time.  Every term is at most what
the device read.  Nothing where the program has no such counter."""

import importlib

from reducers.program_device_time import matching_seconds


def reduce(ctx, pattern):
    trace, window, peaks = ctx["trace"], ctx["window"], ctx["peaks"]
    work, counters = window.get("slice_work"), window.get("slice_counters")
    if (trace is None or peaks is None or not work or not work["decode_contexts"] or not counters
            or "moe_experts_hit" not in counters or "reference" not in window):
        return None
    seconds = matching_seconds(trace, pattern)
    if not seconds:
        return None
    reference = importlib.import_module(f"reference.{window['reference']}")
    least = reference.decode_least_bytes(ctx["published"], work["decode_contexts"], window["num_slots"],
                                         counters["moe_experts_hit"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / seconds

"""Share of the HBM roofline the decode program of a model whose cache is a
recurrent state reached in the traced slice: the least bytes for the tokens
the clients received there (each token's share of one read of the weights by a
full batch, plus one read and one write of its lane's float32 state; the
reference module's ``decode_least_bytes``, which counts the state at its
distinct entries, not at the program's layout) over the peak bytes/s, over the
decode program's device time.  It is the whole decode step's share: the state
and the weights are all a step reads.  Nothing where the program has no
``state_*`` counters."""

import importlib

from reducers.program_device_time import matching_seconds


def reduce(ctx, pattern):
    trace, window, peaks = ctx["trace"], ctx["window"], ctx["peaks"]
    work, counters = window.get("slice_work"), window.get("slice_counters")
    if (trace is None or peaks is None or not work or not work["decode_contexts"] or not counters
            or "state_lane_steps" not in counters or "reference" not in window):
        return None
    seconds = matching_seconds(trace, pattern)
    if not seconds:
        return None
    reference = importlib.import_module(f"reference.{window['reference']}")
    least = reference.decode_least_bytes(ctx["published"], work["decode_contexts"], window["num_slots"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / seconds

"""Share of the HBM roofline the decode program reached in the traced slice:
the least bytes for the tokens the clients received there (each token's
context x KV bytes a token, plus its share of one read of the weights by a
full batch; ``lib/counts.py``) over the peak bytes/s, over the decode
program's device time.  Decode is bound by bytes, not by FLOPs, at these
batch sizes."""

from lib import counts
from reducers.program_device_time import matching_seconds


def reduce(ctx, pattern):
    trace, work, peaks = ctx["trace"], ctx["window"].get("slice_work"), ctx["peaks"]
    if trace is None or not work or peaks is None or not work["decode_contexts"]:
        return None
    seconds = matching_seconds(trace, pattern)
    if not seconds:
        return None
    least = counts.decode_min_bytes(ctx["published"], work["decode_contexts"], ctx["window"]["num_slots"])
    return 100.0 * (least / peaks["hbm_bytes_per_s"]) / seconds

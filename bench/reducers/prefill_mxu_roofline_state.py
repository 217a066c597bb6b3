"""Share of the matrix units' peak the prefill programs of a model whose cache
is a recurrent state reached in the traced slice: the forward FLOPs the
algorithm requires for the prompt tokens of the requests whose first token
arrived there (the reference module's ``forward_flops_span``: projections, MLP
and the retention's own products, no head: a chunk's logits are not computed)
over the peak bf16 FLOP/s, over the ``prefill`` programs' device time.  Prefill
is bound by FLOPs at these chunk sizes.  Nothing where the program has no
``state_*`` counters."""

import importlib

from reducers.program_device_time import matching_seconds


def reduce(ctx, pattern):
    trace, window, peaks = ctx["trace"], ctx["window"], ctx["peaks"]
    work, counters = window.get("slice_work"), window.get("slice_counters")
    if (trace is None or peaks is None or not work or not work["prompt_tokens"] or not counters
            or "state_lane_steps" not in counters or "reference" not in window):
        return None
    seconds = matching_seconds(trace, pattern)
    if not seconds:
        return None
    reference = importlib.import_module(f"reference.{window['reference']}")
    flops = reference.forward_flops_span(ctx["published"], 0, work["prompt_tokens"], 0)
    return 100.0 * (flops / peaks["bf16_flops_per_s"]) / seconds

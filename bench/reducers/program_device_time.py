"""Summed device time of the programs whose name matches ``pattern`` in the
traced slice, in ms, over a count of the slice's work (``per``: output tokens,
or prompt tokens / 1000) taken from what the clients received in the slice."""

import re


def matching_seconds(trace, pattern):
    rx = re.compile(pattern)
    hits = [v["seconds"] for k, v in trace["modules"].items() if rx.search(k)]
    return sum(hits) if hits else None


def reduce(ctx, pattern, per):
    trace, work = ctx["trace"], ctx["window"].get("slice_work")
    if trace is None or not work:
        return None
    seconds = matching_seconds(trace, pattern)
    units = {"output_token": work["output_tokens"], "prompt_ktoken": work["prompt_tokens"] / 1000.0}[per]
    if seconds is None or not units:
        return None
    return 1e3 * seconds / units

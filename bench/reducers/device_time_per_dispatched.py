"""Summed device time of the programs whose name matches ``pattern`` in the
traced slice over the work the ENGINE dispatched in it, read from the args of
the span round each dispatch: for every ``span`` event that began inside the
capture the ``product`` of the named args, summed (lanes live x steps of each
decode window; valid tokens of each prefill chunk; with no arg named, the
count of the events: device time a window dispatched).  ``program_device_time``
divides by what the clients happened to receive in the slice instead, which
swings with the slice.  The result is times ``scale`` (1e3: ms a unit).
Nothing without a trace, a capture, such a program, or events that carry the
args (a program from before its windows said their width)."""

import math

from lib.common import log
from reducers.program_device_time import matching_seconds
from reducers.span_where import capture, events_inside


def dispatched(events, product):
    """Sum over the events of the product of the named args; an event that
    lacks one is left out."""
    units = 0
    for e in events:
        args = e.get("args", {})
        if all(k in args for k in product):
            units += math.prod(args[k] for k in product)
    return units


def reduce(ctx, pattern, span, product, scale=1e3):
    trace, taken = ctx["trace"], capture()
    if trace is None or taken is None:
        return None
    events = events_inside(taken, span, "begin")
    seconds, units = matching_seconds(trace, pattern), dispatched(events, product)
    log(event="device_time_per_dispatched", pattern=pattern, span=span, events=len(events),
        device_s=seconds, units=units)
    if seconds is None or not units:
        return None
    return scale * seconds / units

"""A statistic over the program's own tracer spans of the traced slice.

The program's one tracer (``accelerate_tpu.telemetry.get_tracer``) notes when
the harness's capture began and ended and hands back the events that overlap
it (``capture()``); every event has an ``id``, the ``parent`` open on its
thread when it began, a duration and its ``args``.  Nothing where there was no
capture, where no such span was recorded, or where the program's tracer has no
``capture`` (a program from before its spans had ids).

``span`` is a name or a list of names whose events are pooled.  ``value`` is
``dur`` (seconds), ``self`` (``dur`` less the events whose ``parent`` is this
one) or the name of an arg.  ``stat`` is ``p50``, ``p95``, ``mean`` or ``sum``.
With ``sum``: ``minus`` takes off the summed duration of the named spans, and
``per_span`` divides by the count of another span.  The result is times
``scale`` (1e3: seconds to ms).
"""

from lib.common import percentile


def captured_events():
    """The events of the last capture, or ``None``."""
    from accelerate_tpu.telemetry import get_tracer

    capture = getattr(get_tracer(), "capture", None)
    taken = capture() if capture is not None else None
    return None if taken is None else taken["events"]


def span_values(events, names, value):
    picked = [e for e in events if e["name"] in names]
    if value == "dur":
        return [e["dur"] / 1e6 for e in picked]
    if value == "self":
        children = {}
        for e in events:
            if e.get("parent") is not None:
                children[e["parent"]] = children.get(e["parent"], 0.0) + e["dur"]
        return [(e["dur"] - children.get(e["id"], 0.0)) / 1e6 for e in picked]
    return [e["args"][value] for e in picked if value in e.get("args", {})]


def stat_of(events, span, stat, value="dur", minus=(), per_span=None, scale=1e3):
    names = {span} if isinstance(span, str) else set(span)
    values = span_values(events, names, value)
    if not values:
        return None
    if stat != "sum" and (minus or per_span):
        raise ValueError("minus and per_span go with stat 'sum' alone")
    if stat == "sum":
        out = sum(values) - sum(span_values(events, set(minus), "dur"))
        if per_span is not None:
            count = sum(1 for e in events if e["name"] == per_span)
            if not count:
                return None
            out /= count
    elif stat == "mean":
        out = sum(values) / len(values)
    else:
        out = percentile(values, {"p50": 50, "p95": 95}[stat])
    return scale * out


def reduce(ctx, span, stat, value="dur", minus=(), per_span=None, scale=1e3):
    events = captured_events()
    if events is None:
        return None
    return stat_of(events, span, stat, value, minus, per_span, scale)

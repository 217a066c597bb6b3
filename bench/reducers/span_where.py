"""A statistic over the program's tracer spans that BEGAN, or that ENDED,
inside the traced slice, filtered by their args.

``span_stat`` pools every event that overlaps the capture.  What the driver
dispatched is counted once, by its begin (``inside: "begin"``, ``t0 <= ts <
t1``: ``serve/step``, the window spans, ``serve/prefill_chunk``), and a wait by
its end (``inside: "end"``: a queue wait of 10 s that closes in a 3 s slice
began long before it).  Nothing where there was no capture, where no such
event was recorded, or where the program's events lack the args asked for (a
program from before its steps said what they carried).

``span`` is a name or a list of names whose events are pooled.  ``where`` keeps
the events whose args lie in ``{arg: [min, max]}`` (``null`` leaves a side
open).  ``value`` is ``dur`` (seconds) or the name of an arg; ``stat`` is
``pNN`` (any percentile), ``mean`` or ``sum``.  With ``sum``, ``over`` divides
by the summed arg of that name over the same events, ``over_times_slots`` that
sum times the cell's lanes.  The result is times ``scale`` (1e3: seconds to
ms).  The count of events read is logged: a short slice admits a handful of
requests.
"""

from lib.common import log, percentile


def capture():
    """``{"t0", "t1", "events"}`` of the last capture, or ``None``."""
    from accelerate_tpu.telemetry import get_tracer

    take = getattr(get_tracer(), "capture", None)
    return take() if take is not None else None


def events_inside(taken, names, inside, where=None):
    """The events named ``names`` whose begin (or end) lies in the capture and
    whose args meet ``where``."""
    names = {names} if isinstance(names, str) else set(names)
    at = {"begin": lambda e: e["ts"], "end": lambda e: e["ts"] + e["dur"]}[inside]
    out = []
    for e in taken["events"]:
        if e["name"] not in names or not taken["t0"] <= at(e) < taken["t1"]:
            continue
        args = e.get("args", {})
        if all(k in args and (lo is None or args[k] >= lo) and (hi is None or args[k] <= hi)
               for k, (lo, hi) in (where or {}).items()):
            out.append(e)
    return out


def value_of(event, value):
    """``dur`` in seconds or the named arg; ``None`` where the event lacks it."""
    if value == "dur":
        return event["dur"] / 1e6
    return event.get("args", {}).get(value)


def stat_of(events, stat, value="dur", over=None, slots=1, scale=1e3):
    values = [v for v in (value_of(e, value) for e in events) if v is not None]
    if not values:
        return None
    if over is not None:
        if stat != "sum":
            raise ValueError("over goes with stat 'sum' alone")
        below = slots * sum(e.get("args", {}).get(over, 0) for e in events)
        return scale * sum(values) / below if below else None
    if stat == "sum":
        return scale * sum(values)
    if stat == "mean":
        return scale * sum(values) / len(values)
    if not stat.startswith("p"):
        raise ValueError(f"stat {stat!r}: pNN, mean or sum")
    return scale * percentile(values, float(stat[1:]))


def reduce(ctx, span, stat, inside="begin", value="dur", where=None, over=None,
           over_times_slots=False, scale=1e3):
    taken = capture()
    if taken is None:
        return None
    events = events_inside(taken, span, inside, where)
    log(event="span_where", span=span, stat=stat, value=value, events=len(events))
    slots = ctx["window"]["num_slots"] if over_times_slots else 1
    return stat_of(events, stat, value, over, slots, scale)

"""A ratio of two of the engine's counters over the window (their deltas),
times ``scale``; nothing where the denominator did not move."""


def reduce(ctx, numerator, denominator, scale=1.0, denominator_times_slots=False):
    counters = ctx["window"].get("counters")
    if not counters or not counters.get(denominator):
        return None
    den = counters[denominator] * (ctx["window"]["num_slots"] if denominator_times_slots else 1)
    return scale * counters[numerator] / den

"""Share of the traced window in which no operation ran on a chip (mean over
the chips used), from the device trace."""


def reduce(ctx):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

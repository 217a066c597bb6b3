"""A percentile of the host-clock time of each step of the traced window
(every step waited for)."""

from lib.common import percentile


def reduce(ctx, q=50):
    return percentile(ctx["window"].get("step_ms") or [], q)

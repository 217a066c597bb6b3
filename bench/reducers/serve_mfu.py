"""Share of the chip's peak that the whole serving step reached: forward FLOPs
the algorithm requires for every prompt token prefilled and every output token
emitted in the window (client-side counts, ``lib/counts.py``) over the window
times chips times the peak."""


def reduce(ctx):
    window, peaks = ctx["window"], ctx["peaks"]
    if peaks is None or not window.get("work") or not window["work"]["forward_flops"]:
        return None
    return 100.0 * window["work"]["forward_flops"] / window["elapsed_s"] / (
        ctx["chips"] * peaks["bf16_flops_per_s"])

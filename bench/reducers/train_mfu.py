"""Model FLOP/s utilisation of the whole training step: forward and backward
FLOPs the algorithm requires per token (``lib/counts.py``; nothing recomputed)
times the tokens per second of the window, over chips times the peak."""

from lib import counts


def reduce(ctx):
    window, peaks = ctx["window"], ctx["peaks"]
    if peaks is None or not window.get("tokens"):
        return None
    flops = counts.train_flops_per_token(ctx["published"], window["seq_len"]) * window["tokens"]
    return 100.0 * flops / window["elapsed_s"] / (ctx["chips"] * peaks["bf16_flops_per_s"])

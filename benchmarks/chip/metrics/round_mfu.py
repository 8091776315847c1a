"""The whole round's share of the chip's peak FLOP rate, in percent: the
FLOPs a root round needs (leaf solves and the gap's passes over X) times
the rounds of the window, over the window's seconds and the bf16 peak.
It bounds every kernel share of the dual cells from below."""
from chipbench import work


def read(ctx):
    c = ctx["counts"]
    flops = work.dual_round_flops(c["m"], c["d"], c["topology"]) * c["rounds"]
    return 100.0 * flops / c["window_s"] / ctx["peaks"]["bf16_flops_per_s"]

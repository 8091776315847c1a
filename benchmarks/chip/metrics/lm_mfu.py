"""Model FLOP utilization of the LM step, in percent: the training FLOPs a
token needs (6 N for the matmul parameters plus causal attention; nothing
recomputed) times the tokens of the traced window, over its seconds and
the chips' bf16 peak."""
from chipbench import work


def read(ctx):
    c = ctx["counts"]
    flops = work.lm_flops_per_token(c["model"], c["seq"]) * c["tokens"]
    chips = ctx["workload"]["chips"]
    return 100.0 * flops / c["window_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])

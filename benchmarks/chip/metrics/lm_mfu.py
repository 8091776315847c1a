"""Model FLOP utilization of the LM step, in percent: the training FLOPs a
token needs, as the cell's reference counts them from shapes
(``flops_per_token``: for the dense decoder 6 N for the matmul parameters
plus causal attention; nothing recomputed), times the tokens of the traced
window, over its seconds and the chips' bf16 peak."""


def read(ctx):
    c = ctx["counts"]
    flops = ctx["reference"].flops_per_token(c["model"], c["seq"]) \
        * c["tokens"]
    chips = ctx["workload"]["chips"]
    return 100.0 * flops / c["window_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])

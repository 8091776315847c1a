"""The ``sdca`` leaf kernel's share of its roofline, in percent: the least
time the chip could take for the leaf solves of the traced rounds (the
larger of bytes over HBM bandwidth and FLOPs over peak; on the dual cells
bytes bound it) over the kernel's device time (the op ``sdca_kernel_ms``
reads: the chunk program's ``tpu_custom_call``)."""
from chipbench import work


def read(ctx):
    s = ctx["trace"].op_s(lambda op: op[3] == "tpu_custom_call"
                          and "solve_fn" in op[4])
    if s <= 0.0:
        return None
    c = ctx["counts"]
    per_round = work.sdca_round(c["topology"], c["d"])
    least = work.roofline_s(per_round, ctx["peaks"])["seconds"] * c["rounds"]
    return 100.0 * least / s

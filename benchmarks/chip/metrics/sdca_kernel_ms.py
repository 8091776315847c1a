"""Device milliseconds per root round of the Mosaic ``sdca`` leaf kernel
(every leaf's H coordinate steps, once per group round).  The kernel runs
as the only ``tpu_custom_call`` op of the chunk program; a later ``name=``
on its ``pallas_call`` would let this match by name."""


def kernel(op) -> bool:
    return op[3] == "tpu_custom_call" and "solve_fn" in op[4]


def read(ctx):
    s = ctx["trace"].op_s(kernel)
    if s <= 0.0:
        return None
    return 1e3 * s / ctx["counts"]["rounds"]

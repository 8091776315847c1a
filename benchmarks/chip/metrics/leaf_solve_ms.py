"""Device milliseconds per root round of the leaf solves: the ops under
the chunk program's ``leaf_solve`` scope (the coordinate draws and every
leaf's H steps, the ``sdca`` kernel or the XLA loop), on the busiest
chip."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, ("leaf_solve",), "rounds")

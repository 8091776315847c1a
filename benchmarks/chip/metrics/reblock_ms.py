"""Device milliseconds per root round of the chunk program's ``reblock``
scope: the gather of X and y into per-leaf blocks at every call."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, ("reblock",), "rounds")

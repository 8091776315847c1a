"""Device milliseconds per training step of the LM step's ``optimizer``
scope (the optimizer's update of parameters and moments)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, ("optimizer",), "steps")

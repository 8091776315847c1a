"""Device milliseconds per training step of the LM step's
``forward_backward`` scope (the loss and its gradient)."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, ("forward_backward",), "steps")

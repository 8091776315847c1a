"""Device milliseconds per root round of the tree syncs: the ops under
the ``level_sync`` scope (per-level aggregation, the deeper servers'
rebase, snapshot refresh, the collectives on the mesh backend) and the
``codec`` nested in it, on the busiest chip."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, ("level_sync", "codec"), "rounds")

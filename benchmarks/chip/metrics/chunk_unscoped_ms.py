"""Device milliseconds per root round of the chunk program's ops that no
scope names (the layout copy of X, the carry's scatter in and gather out,
the scan's own loop), on the busiest chip.  With ``leaf_solve_ms``,
``reblock_ms`` and ``level_sync_ms`` it sums to ``chunk_ms``."""
from chipbench import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, (None,), "rounds", chunk_only=True)

"""Device-idle milliseconds per root round inside ``Session.run``: idle
time whose innermost open program span is any ``repro:Session.*`` span
(mean over the chips)."""
from chipbench import scopes


def read(ctx):
    return scopes.idle_ms_per(ctx, ("Session.",), "rounds")

"""Device-idle milliseconds per root round while the host walks the
round's RNG key chain: idle time whose innermost open program span is
``repro:Session.key_plan`` (mean over the chips)."""
from chipbench import scopes


def read(ctx):
    return scopes.idle_ms_per(ctx, ("Session.key_plan",), "rounds")

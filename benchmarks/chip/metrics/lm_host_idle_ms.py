"""Device-idle milliseconds per training step while the host makes the
step's batch or dispatches the step: idle time whose innermost open
program span is ``repro:LMSession.batch`` or ``repro:LMSession.dispatch``
(mean over the chips)."""
from chipbench import scopes


def read(ctx):
    return scopes.idle_ms_per(
        ctx, ("LMSession.batch", "LMSession.dispatch"), "steps")

"""Host-to-device operand KiB per root round: the ``upload_bytes``
counter of the window's ``repro:Session.run`` spans (keys, step and
participation masks, scalars converted from host arrays) over their
rounds."""
from chipbench import scopes


def read(ctx):
    win = scopes.window_of(ctx)
    if win is None:
        return None
    sums = win.stat_sums(scopes.REPRO + "Session.run")
    if "upload_bytes" not in sums or not sums.get("rounds"):
        return None
    return sums["upload_bytes"] / sums["rounds"] / 1024.0

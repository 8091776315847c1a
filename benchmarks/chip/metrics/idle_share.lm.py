"""Share of the traced window in which no operation ran on the chips
(mean over the cell's chips), in percent."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share

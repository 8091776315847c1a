"""Device milliseconds per recorded call of the duality-gap program
(``Session``'s ``_objective``: X^T alpha, the margins and both
objectives)."""


def read(ctx):
    t = ctx["trace"]
    match = lambda name: "_objective" in name  # noqa: E731
    if t.program_count(match) == 0:
        return None
    return 1e3 * t.program_s(match) / ctx["counts"]["calls"]

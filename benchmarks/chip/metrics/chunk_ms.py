"""Device milliseconds per root round of the executor's chunk program (the
jitted tick scan of the host backend, or the shard_map program of the mesh
backend), leaf solves included."""

PROGRAMS = ("solve_fn", "program")


def read(ctx):
    t = ctx["trace"]
    match = lambda name: any(p in name for p in PROGRAMS)  # noqa: E731
    if t.program_count(match) == 0:
        return None
    return 1e3 * t.program_s(match) / ctx["counts"]["rounds"]

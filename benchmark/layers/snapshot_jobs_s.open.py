from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Session: the program's `snapshot.jobs` span (job clones), per cycle."""
    return span_per_cycle(ctx, "snapshot.jobs")

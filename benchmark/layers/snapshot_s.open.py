from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Session: the program's `snapshot` span (open_session), per cycle."""
    return span_per_cycle(ctx, "snapshot")

from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Session: the program's `session.close` span (close hooks and commit),
    per cycle."""
    return span_per_cycle(ctx, "session.close")

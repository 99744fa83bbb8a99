from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Store dispatch: the program's `dispatch` span (bind_many), per cycle."""
    return span_per_cycle(ctx, "dispatch")

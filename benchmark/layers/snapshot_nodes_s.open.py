from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Session: the program's `snapshot.nodes` span (node clones), per cycle."""
    return span_per_cycle(ctx, "snapshot.nodes")

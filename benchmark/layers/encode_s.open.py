from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Allocate host: the program's `encode` span, per cycle."""
    return span_per_cycle(ctx, "encode")

from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Device: the program's `solve` span (ends on block_until_ready), per cycle."""
    return span_per_cycle(ctx, "solve")

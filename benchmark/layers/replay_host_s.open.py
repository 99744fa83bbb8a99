from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Allocate host: the program's `replay` span (the replay up to the bind
    call, dispatch left out), per cycle."""
    return span_per_cycle(ctx, "replay")

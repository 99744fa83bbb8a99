from benchmark.layers.common import span_per_cycle


def read(ctx):
    """The loop: the program's `cycle` span (Scheduler.run_once), per cycle."""
    return span_per_cycle(ctx, "cycle")

from benchmark.layers.common import action_per_cycle


def read(ctx):
    """Admission: action_scheduling_latency{action=enqueue}, per cycle."""
    return action_per_cycle(ctx, "enqueue")

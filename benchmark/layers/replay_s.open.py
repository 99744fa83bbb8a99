from benchmark.layers.common import cycle_field


def read(ctx):
    """Allocate host: xla_allocate.last_timings["replay_s"], per cycle."""
    return cycle_field(ctx, "replay_s")

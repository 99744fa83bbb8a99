from benchmark.layers.common import idle_share_pct


def read(ctx):
    """Device: 1 - busy union / window, from the profiler trace, in %."""
    return idle_share_pct(ctx)

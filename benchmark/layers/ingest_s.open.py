from benchmark.layers.common import cycle_field


def read(ctx):
    """Cache ingest: the benchmark's clock around its store writes at each
    cycle boundary (the cache's event handlers run inside them)."""
    return cycle_field(ctx, "ingest_s")

from benchmark.layers.common import per_cycle


def read(ctx):
    """Loop: the cyclic collector, per cycle: the program's boundary `gc`
    span plus every `gc.pause` span (a collection the interpreter started
    on its own). None where the program records neither."""
    spans = ctx["spans"].get("gc", []) + ctx["spans"].get("gc.pause", [])
    return per_cycle(ctx, sum(spans)) if spans else None

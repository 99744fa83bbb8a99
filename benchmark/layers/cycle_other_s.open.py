from benchmark.layers.common import span_per_cycle


def read(ctx):
    """The loop: the `cycle` span less `session.open`, every `action.*` span
    and `session.close`, per cycle; None where any of them was not recorded."""
    cycle = span_per_cycle(ctx, "cycle")
    opened = span_per_cycle(ctx, "session.open")
    closed = span_per_cycle(ctx, "session.close")
    actions = [span_per_cycle(ctx, n) for n in ctx["spans"] if n.startswith("action.")]
    if cycle is None or opened is None or closed is None or not actions:
        return None
    return cycle - opened - closed - sum(actions)

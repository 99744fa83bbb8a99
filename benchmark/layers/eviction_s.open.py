from benchmark.layers.common import span_per_cycle


def read(ctx):
    """Eviction: the program's `action.xla_reclaim` and `action.xla_preempt`
    spans, per cycle; None where neither was recorded."""
    parts = [span_per_cycle(ctx, "action." + a) for a in ("xla_reclaim", "xla_preempt")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None

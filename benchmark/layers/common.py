"""Shared arithmetic of the per-layer readers. Each reader gets the run's
context: ``cycles`` (one dict per measured cycle: ``wall_s``,
``ingest_s``, ``replay_s``), ``spans`` ({span name: [seconds]} of the
program's spans in the measured cycles), ``actions`` ({action: seconds}
from the program's action-latency histogram over the measured cycles)
and ``trace`` (benchmark/trace.py's reduction, or None). A reader that
finds nothing to read returns None."""


def per_cycle(ctx, total):
    n = len(ctx["cycles"])
    return total / n if n and total is not None else None


def span_per_cycle(ctx, name):
    spans = ctx["spans"].get(name)
    return per_cycle(ctx, sum(spans)) if spans else None


def action_per_cycle(ctx, action):
    total = ctx["actions"].get(action)
    return per_cycle(ctx, total) if total else None


def cycle_field(ctx, key):
    vals = [c[key] for c in ctx["cycles"] if c.get(key) is not None]
    return sum(vals) / len(vals) if vals else None


def idle_share_pct(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("idle_share") is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["idle_share"]

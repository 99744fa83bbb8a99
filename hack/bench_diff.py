#!/usr/bin/env python3
"""Diff two bench result files — the regression gate for BENCH_*.json.

``bench.py`` emits its per-row numbers as a ``{"details": {row: {...}}}``
JSON line on stderr; a driver record wraps that whole invocation as
``{"n", "cmd", "rc", "tail", "parsed"}`` with the details line embedded
somewhere inside the ``tail`` string. This tool accepts EITHER form on
either side (plus a bare row-mapping), so

    python hack/bench_diff.py old_record.json new_record.json

compares two recorded runs and

    python hack/bench_diff.py old.json new.json --strict

gates a fresh run against a baseline in CI (also reachable as
``python hack/verify.py --bench-diff OLD NEW``).

Three classes of finding, each printed as one line:

- ``regression``: a row's p50 latency (``p50_s``, falling back to
  ``xla_s`` on rows without percentiles) grew by more than
  ``--threshold`` (default 15%);
- ``parity``: a parity bit (``placements_equal_serial``,
  ``placements_equal_full_cycle``, or the kill-drill acceptance bit
  ``p50_within_lease_window`` on ``federation_kill_mttr``) that was
  true in OLD is false or gone in NEW — the device solver stopped
  matching its oracle (or failover MTTR left its lease window), which
  no latency number excuses;
- ``compiles``: a compile-budget change — ``measured_compiles`` (or
  ``warm_encode_compiles``) grew, meaning a row started paying
  trace+compile inside its measured repeats.

Rows present on only one side are reported (``added``/``removed``) but
only ``removed`` counts as a finding: a vanished row is a silently
narrowed bench. Improvements are listed informationally.

Device-phase and fleet telemetry columns (``solve_device_s``,
``pipeline_overlap_fraction``, ``arena_hbm_watermark_bytes``, and any
``fleet_*`` column) are understood but NEVER flagged: solve_device_s is
a sub-phase of ``solve_s`` (already covered by the latency gate), the
overlap fraction and HBM watermark are descriptive telemetry whose
"right" value is config-dependent, and fleet columns are aggregator
state rather than per-row latency. Changes in them print as ``[info]``
lines and do not affect the exit code, even under ``--strict``.

Wire-transport columns (ISSUE 17) are the opposite: they ARE the
product of their rows, so they gate. A row carrying a ``wire_runs``
sub-list (the federation scale-out's v1-vs-v2 transport ladder) is
expanded into one pseudo-row per run, named
``<row>.wire_v<protocol>_n<shards>``, and within those rows
``binds_per_s`` and ``txn_batch*`` regress when they SHRINK by more
than ``--threshold`` while ``wire_bytes_per_bind`` and
``backend_rtt_*`` regress when they GROW — a v2 transport that slid
back to v1 throughput or v1 byte volume is a ``regression`` finding,
not an ``[info]`` line. Their ``exactly_once``/``union_parity`` bits
join the parity gate.

Admission-storm columns (ISSUE 18) gate the same way with their own
directions: ``storm_high_p99_s`` (the protected lane's tail under
overload) and ``storm_mttr_s`` (kill-cell recovery) regress when they
grow, ``storm_goodput_pods_per_s`` when it shrinks; ``storm_shed_*``
counts are ``[info]`` (shed volume is a policy outcome of offered
load, pinned by the row's own ``ok`` bit rather than diffed).

Node-class compression columns (ISSUE 20) split the same way:
``compression_ratio`` (valid nodes per node class on the compressed
solve) regresses when it SHRINKS — a workload row whose duplication
collapsed means the class key picked up an accidental splitter and the
solve cost silently reverted toward per-node scaling. ``class_count``
and the solve-cost split (``class_group_s`` host regroup vs
``class_kernel_s`` device solve, plus ``class_splits``) are ``[info]``:
they describe where the time went, and gating them would let a row
"pass" by shifting cost between phases while p50 — which still gates
on its own — tells the truth.

``--json`` emits one machine-readable summary line; ``--strict`` exits
nonzero when any finding fired (default exit is 0 — informational).
"""

from __future__ import annotations

import argparse
import json
import re
import sys

# latency key preference per row: tail-honest median first
_LATENCY_KEYS = ("p50_s", "xla_s")
# true->anything-else is a finding; covers placement parity, the
# kill-drill MTTR acceptance bit (p50 <= lease TTL + renew period) and
# the wire pseudo-rows' correctness bits
_PARITY_KEYS = (
    "placements_equal_serial",
    "placements_equal_full_cycle",
    "placements_equal_uncompressed",
    "p50_within_lease_window",
    "exactly_once",
    "union_parity",
)
_COMPILE_KEYS = ("measured_compiles", "warm_encode_compiles")
# never-flagged telemetry columns (see module docstring)
_INFO_KEYS = (
    "solve_device_s",
    "pipeline_overlap_fraction",
    "arena_hbm_watermark_bytes",
)
# wire-transport columns (see module docstring): gated, with direction.
# lower-better: bytes and round-trip latency; higher-better: throughput
# and txn coalescing depth (a batch mean collapsing to 1 means the v2
# path quietly degraded to per-gang writes).
_WIRE_LOWER = ("wire_bytes_per_bind",)
_WIRE_HIGHER = ("binds_per_s",)
# admission-storm columns (ISSUE 18): the protected lane's tail and the
# kill-cell MTTR regress when they GROW; storm goodput regresses when
# it SHRINKS. Shed counts are load-dependent policy outcomes (a faster
# solver sheds less at the same offered rate), so they print as [info]
# — the protected-lane zero-shed claim is asserted inside the row's
# own ``ok`` bit, not diffed across rounds.
_STORM_LOWER = ("storm_high_p99_s", "storm_mttr_s")
_STORM_HIGHER = ("storm_goodput_pods_per_s",)
# node-class compression (ISSUE 20): ratio shrink = the class key lost
# its duplication and the solve is drifting back to per-node cost;
# class_count / class_group_s / class_kernel_s / class_splits are the
# [info] solve-cost split (see module docstring).
_CLASS_HIGHER = ("compression_ratio",)


def _is_info_key(key: str) -> bool:
    return (key in _INFO_KEYS or key.startswith("fleet_")
            or key.startswith("storm_shed_") or key.startswith("class_"))


def _is_wire_lower(key: str) -> bool:
    return (key in _WIRE_LOWER or key in _STORM_LOWER
            or key.startswith("backend_rtt_"))


def _is_wire_higher(key: str) -> bool:
    return (key in _WIRE_HIGHER or key in _STORM_HIGHER
            or key in _CLASS_HIGHER or key.startswith("txn_batch"))


def _rows_from_obj(obj):
    """Extract the row mapping from any of the accepted shapes."""
    if not isinstance(obj, dict):
        return None
    if isinstance(obj.get("details"), dict):
        return obj["details"]
    if isinstance(obj.get("tail"), str):
        # driver wrapper: scan the captured output for the stderr
        # details line (bench.py prints exactly one such object)
        for line in obj["tail"].splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                inner = json.loads(line)
            except ValueError:
                continue
            if isinstance(inner, dict) and isinstance(
                inner.get("details"), dict
            ):
                return inner["details"]
        return _rows_from_fragment(obj["tail"])
    # bare mapping of row name -> row dict
    if obj and all(isinstance(v, dict) for v in obj.values()):
        return obj
    return None


def _rows_from_fragment(text: str) -> dict | None:
    """Recover rows from a FRONT-TRUNCATED details line: the archived
    wrappers keep only the trailing bytes of stderr, so the
    ``{"details": {`` prefix (and possibly the first row) may be cut
    off mid-object. Scan for ``"name": {...}`` pairs and keep every
    object that carries a bench latency key — partial first rows
    simply fail to decode and are skipped."""
    dec = json.JSONDecoder()
    rows = {}
    for m in re.finditer(r'"([A-Za-z0-9_./:-]+)":\s*\{', text):
        try:
            row, _ = dec.raw_decode(text, m.end() - 1)
        except ValueError:
            continue
        if isinstance(row, dict) and any(k in row for k in _LATENCY_KEYS):
            rows[m.group(1)] = row
    return rows or None


def _expand_wire_rows(rows: dict) -> dict:
    """Expand each row's ``wire_runs`` sub-list (the v1-vs-v2 transport
    ladder on the federation scale-out row) into first-class
    pseudo-rows named ``<row>.wire_v<protocol>_n<shards>`` so the
    per-key gates see every (protocol, shard-count) cell."""
    out = dict(rows)
    for name, row in rows.items():
        runs = row.get("wire_runs") if isinstance(row, dict) else None
        if not isinstance(runs, list):
            continue
        for run in runs:
            if not isinstance(run, dict):
                continue
            proto, shards = run.get("protocol"), run.get("shards")
            if proto is None or shards is None:
                continue
            out[f"{name}.wire_v{proto}_n{shards}"] = run
    return out


def load_rows(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        obj = json.load(fh)
    rows = _rows_from_obj(obj)
    if rows is None:
        raise SystemExit(
            f"bench_diff: {path}: no bench rows found (expected a "
            '{"details": ...} object, a BENCH_*.json wrapper whose tail '
            "embeds one, or a bare row mapping)"
        )
    return _expand_wire_rows(rows)


def _latency(row: dict):
    for k in _LATENCY_KEYS:
        v = row.get(k)
        if isinstance(v, (int, float)) and v > 0:
            return k, float(v)
    return None, None


def diff_rows(old: dict, new: dict, threshold: float) -> dict:
    findings = []
    improvements = []
    info = []
    added = sorted(set(new) - set(old))
    removed = sorted(set(old) - set(new))
    for name in removed:
        findings.append({
            "row": name, "kind": "removed",
            "msg": f"{name}: row present in OLD but missing from NEW "
                   "(bench coverage narrowed)",
        })
    for name in sorted(set(old) & set(new)):
        o, n = old[name], new[name]
        ok_key, ov = _latency(o)
        nk_key, nv = _latency(n)
        if ov is not None and nv is not None:
            delta = (nv - ov) / ov
            key = nk_key if nk_key == ok_key else f"{ok_key}->{nk_key}"
            if delta > threshold:
                findings.append({
                    "row": name, "kind": "regression",
                    "msg": f"{name}: {key} {ov:.4f}s -> {nv:.4f}s "
                           f"(+{delta:.1%}, threshold {threshold:.0%})",
                })
            elif delta < -threshold:
                improvements.append(
                    f"{name}: {key} {ov:.4f}s -> {nv:.4f}s ({delta:.1%})"
                )
        for k in _PARITY_KEYS:
            if o.get(k) is True and n.get(k) is not True:
                state = "flipped false" if k in n else "vanished"
                findings.append({
                    "row": name, "kind": "parity",
                    "msg": f"{name}: {k} {state} (was true in OLD)",
                })
        for k in _COMPILE_KEYS:
            oc, nc = o.get(k), n.get(k)
            if isinstance(nc, (int, float)) and nc > (
                oc if isinstance(oc, (int, float)) else 0
            ):
                findings.append({
                    "row": name, "kind": "compiles",
                    "msg": f"{name}: {k} {oc if oc is not None else 0} "
                           f"-> {nc} (measured repeats started compiling)",
                })
        for k in sorted(set(o) | set(n)):
            lower, higher = _is_wire_lower(k), _is_wire_higher(k)
            if not (lower or higher):
                continue
            ow, nw = o.get(k), n.get(k)
            if not isinstance(ow, (int, float)) or not isinstance(
                nw, (int, float)
            ) or ow <= 0:
                continue
            delta = (nw - ow) / ow
            worse = delta > threshold if lower else delta < -threshold
            better = delta < -threshold if lower else delta > threshold
            if worse:
                findings.append({
                    "row": name, "kind": "regression",
                    "msg": f"{name}: {k} {ow:g} -> {nw:g} ({delta:+.1%}, "
                           f"{'lower' if lower else 'higher'}-is-better, "
                           f"threshold {threshold:.0%})",
                })
            elif better:
                improvements.append(f"{name}: {k} {ow:g} -> {nw:g} ({delta:+.1%})")
        for k in sorted(set(o) | set(n)):
            if not _is_info_key(k):
                continue
            oi, ni = o.get(k), n.get(k)
            if oi == ni:
                continue
            info.append(f"{name}: {k} {oi} -> {ni}")
    return {
        "rows_old": len(old),
        "rows_new": len(new),
        "added": added,
        "removed": removed,
        "findings": findings,
        "improvements": improvements,
        "info": info,
        "ok": not findings,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="Diff two bench result files (regressions, parity "
                    "flips, compile-budget changes).",
    )
    ap.add_argument("old", help="baseline bench JSON (details/wrapper/rows)")
    ap.add_argument("new", help="candidate bench JSON (same shapes accepted)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="relative p50 regression threshold (default 0.15)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one machine-readable summary line")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when any finding fired")
    args = ap.parse_args(argv)

    summary = diff_rows(
        load_rows(args.old), load_rows(args.new), args.threshold
    )
    for f in summary["findings"]:
        print(f"bench_diff: [{f['kind']}] {f['msg']}")
    for line in summary["improvements"]:
        print(f"bench_diff: [improved] {line}")
    for line in summary["info"]:
        print(f"bench_diff: [info] {line}")
    for name in summary["added"]:
        print(f"bench_diff: [added] {name}: new row in NEW")
    print(
        "bench_diff:",
        "ok" if summary["ok"] else f"{len(summary['findings'])} finding(s)",
        f"({summary['rows_old']} -> {summary['rows_new']} rows,"
        f" threshold {args.threshold:.0%})",
    )
    if args.as_json:
        print(json.dumps(summary, sort_keys=True))
    return 1 if (args.strict and not summary["ok"]) else 0


if __name__ == "__main__":
    sys.exit(main())

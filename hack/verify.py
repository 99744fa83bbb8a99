#!/usr/bin/env python3
"""Static verification gate — the role of the reference's
`make verify` (Makefile:14-18 -> hack/verify-gofmt.sh, verify-golint.sh,
verify-boilerplate.sh), for a Python/C++ tree.

Runs, in order:

1. `compileall` — every tracked .py must byte-compile (syntax gate);
2. `tabnanny` — no ambiguous indentation;
3. an AST linter (stdlib-only, because this image ships no ruff/mypy
   and installs are off): unused imports (F401), bare except (E722),
   `== None` / `!= None` comparisons — both operand sides — (E711),
   mutable default arguments (B006), f-strings without placeholders
   (F541);
4. the domain-aware analysis suite (python -m kube_batch_tpu.analysis):
   lock-discipline (KBT-L*), JAX hazards (KBT-J*), registry consistency
   (KBT-R*), snapshot escape (KBT-S*), lock-order/deadlock (KBT-D*),
   against the committed hack/lint-baseline.toml (reason-less entries
   always fail; stale entries fail under ``--strict``), then the
   trace-level program auditor (python -m kube_batch_tpu.analysis.trace,
   KBT-P*: jaxpr callbacks, f64 leaks, captured constants, donation,
   cross-tier signature drift) under JAX_PLATFORMS=cpu against
   hack/trace-baseline.toml; with ``--interleave``, also the
   interleaving model checker (python -m
   kube_batch_tpu.analysis.interleave, KBT-I*: every distinguishable
   thread schedule of the fixed streaming/takeover scenarios,
   counterexamples replayable by trace id) against
   hack/interleave-baseline.toml;
5. ruff + mypy when importable (CI images that carry them get the full
   gate; their absence degrades to the stdlib checks, loudly — unless
   ``--strict``, which makes a missing tool a FAILURE, so an image
   rebuild that silently drops ruff/mypy cannot turn the gate green);
   mypy covers api/, framework/, conf/ and recovery/;
6. the chaos smoke (kube_batch_tpu.faults.smoke): one injected fault per
   subsystem — solver, native boundary, cache write, watch hub, lease
   elector — plus a seeded cache-mutation-detector violation, each
   through a real scheduling path, asserting binds still land;
6b. the wire-codec self-check (python -m kube_batch_tpu.apis.wire
   --json): seeded property round-trips over every kind — binary
   (KBW2) and JSON framings must decode back to equal objects, deltas
   must patch old into new field-for-field, and the binary framing
   must not be larger than JSON on the aggregate corpus;
7. the encode-cache parity smoke (python -m kube_batch_tpu.ops.encode_cache):
   warm and 1%-node-churn encodes must be byte-identical to a fresh
   cold encode on a seeded snapshot (KBT_ENCODE_CACHE default-on),
   then the pipelined-cycle parity smoke (same module, ``--pipeline``):
   one seeded world scheduled with KBT_PIPELINE off and on must bind
   pod-for-pod identically, with the pipelined run's dispatch deferred
   through the fence and the arena ping-ponging its device banks;
8. the streaming smoke (python -m kube_batch_tpu.streaming --json):
   event-driven micro-cycles must bind every arrival AND place it on
   the same node a pure full-cycle twin picks (parity), with at least
   one micro-cycle actually taken;
9. the obs tracing smoke (python -m kube_batch_tpu.obs --json): a
   seeded two-shard federated run over live loopback backends with a
   forced stale-dispatch conflict must produce a complete span tree
   (check_tree clean) whose conflicted gang.bind joins the arbiter's
   store.bind spans in one trace (cross-process propagation over the
   backend headers), fsck-clean, with the JSONL + Chrome trace pair
   exported. ``--obs`` requests it explicitly; it runs by default;
10. the explain forensics smoke (python -m kube_batch_tpu.obs.explain
    --json): on a seeded cluster with one stuck gang per feasibility
    plane, the batched device forensics must match the serial twin
    byte-for-byte, report each gang's designed dominant reason and
    would-fit-if planes, and land those reasons on PodGroup conditions;
11. the fleet-aggregation smoke (python -m kube_batch_tpu.obs.fleet
    --json) at 2 and 4 shards: merged fleet percentiles must land
    within the sketch's declared relative-error bound of the pooled
    raw samples;
12. the admission smoke (python -m kube_batch_tpu.admission --json):
    the deterministic virtual-clock 5x-overload plant — with lanes +
    the fleet-SLO brownout ladder armed the protected lane must hold
    its tail SLO with zero shed while the unprotected OFF twin
    collapses, and every shed decision must carry Retry-After
    guidance;
13. the node-class compression smoke (python -m
    kube_batch_tpu.ops.class_solve --json): serial, uncompressed and
    KBT_CLASS_COMPRESS=1 schedules of a seeded pooled fleet must bind
    pod-for-pod identically across two cycles, with in-solve splits
    and second-cycle re-merges both exercised.

With ``--bench-diff OLD NEW``, two bench artifacts (fresh bench.py
output or archived BENCH_*.json wrappers) are regression-gated via
hack/bench_diff.py --strict: >15% p50 regressions, parity flips,
compile-budget changes and vanished rows all fail the gate. With
``--bench-diff`` and no paths, the two newest ``BENCH_*.json`` in the
repo root are auto-discovered (mtime order, name as tie-break) and
diffed oldest-of-the-pair -> newest.

With ``--chaos``, two more gates run: the chaos-marked pytest subset
(tests/test_faults.py + tests/test_recovery.py + tests/test_federation.py
— fault drills, the crash-consistent failover e2e, the conflict chaos
drill), and ``kube_batch_tpu.recovery.fsck`` against a seeded journal
fixture (a known half-confirmed WAL must fsck clean with the expected
orphan count, and ``--strict`` must gate on it); plus the real-clock
admission storm drill (``python -m kube_batch_tpu.admission --storm
--json --duration 4`` — the three-cell ON/OFF/KILL comparison: the
protected lane's tail held under 5x overload, the OFF twin measurably
worse, and a mid-storm shard kill recovered with zero journal
orphans).

With ``--federation``, the federation gate runs: the wire-path smoke
(``python -m kube_batch_tpu.federation --json`` — N schedulers over one
loopback store process, exactly-once binds, fsck-clean union placement,
parity with a single-scheduler twin), a seeded in-process
two-scheduler conflict drill whose loser must win its refresh-retry and
leave store truth fsck-clean, and the kill-and-adopt drill
(``python -m kube_batch_tpu.federation --json --kill-one`` — one of
four leased shard owners killed mid-``bind_many``; a survivor must
adopt the orphaned slot within the lease window, reconcile the dead
owner's journal, and finish every gang exactly once, fsck-clean), and
the streaming-federation smoke (``python -m kube_batch_tpu.federation
--json --streaming`` — shards on event-driven micro-cycles absorbing
peer binds as occupancy patches must reach parity with the classic
federated run, micro-cycles actually taken, exactly-once, fsck-clean,
pumps and listeners shut down clean).

Exit 0 iff every gate is clean.
Usage:  python hack/verify.py [--strict] [--chaos] [--federation]
                              [--obs] [--interleave] [--json]
                              [--bench-diff [OLD.json NEW.json]]

``--json`` appends one machine-readable summary line to stdout
(per-gate pass/fail + finding counts) so bench/CI can record the
gate's state in artifacts.

CI/the deployment image run ``--strict`` (the Dockerfile installs ruff +
mypy via the ``dev`` extra); the bare container, which cannot install
packages, runs the default lenient mode.
"""

from __future__ import annotations

import ast
import compileall
import io
import os
import subprocess
import sys
import tabnanny
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGETS = ["kube_batch_tpu", "tests", "bench.py", "__graft_entry__.py", "chip_smoke.py", "hack"]

# Names a module may import without using (re-export / side-effect
# registration idioms used deliberately in this codebase).
SIDE_EFFECT_IMPORTS = {"kube_batch_tpu.actions", "kube_batch_tpu.plugins"}


def py_files() -> list[str]:
    out = []
    for t in TARGETS:
        p = os.path.join(REPO, t)
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs if d not in ("__pycache__",)]
            out.extend(os.path.join(root, f) for f in files if f.endswith(".py"))
    return sorted(out)


class _Lint(ast.NodeVisitor):
    """The checks: F401 / E722 / E711 / B006 / F541."""

    def __init__(self, path: str, tree: ast.AST, source: str) -> None:
        self.path = path
        self.problems: list[tuple[int, str]] = []
        self.imported: dict[str, tuple[int, str]] = {}  # name -> (line, full)
        self.used: set[str] = set()
        self.source = source
        self.visit(tree)
        self._flush_imports(tree)

    def _flush_imports(self, tree: ast.AST) -> None:
        exported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        if isinstance(node.value, (ast.List, ast.Tuple)):
                            exported = {
                                e.value
                                for e in node.value.elts
                                if isinstance(e, ast.Constant)
                            }
        is_init = os.path.basename(self.path) == "__init__.py"
        for name, (line, full) in self.imported.items():
            if name in self.used or name in exported or full in SIDE_EFFECT_IMPORTS:
                continue
            if is_init:
                continue  # package __init__ re-exports are the point
            if name.startswith("_"):
                continue
            # a `# noqa` on the import line silences it, same as ruff
            src_line = self.source.splitlines()[line - 1]
            if "noqa" in src_line:
                continue
            self.problems.append((line, f"F401 unused import: {full}"))

    # -- imports ------------------------------------------------------------
    def visit_If(self, node: ast.If) -> None:
        # TYPE_CHECKING blocks import names for quoted annotations the
        # runtime never loads — exempt them (ruff resolves the quoted
        # usage instead; the stdlib linter exempts the block).
        t = node.test
        if (isinstance(t, ast.Name) and t.id == "TYPE_CHECKING") or (
            isinstance(t, ast.Attribute) and t.attr == "TYPE_CHECKING"
        ):
            self.visit(t)  # the guard itself uses the TYPE_CHECKING name
            for n in node.orelse:
                self.visit(n)
            return
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            name = a.asname or a.name.split(".")[0]
            self.imported[name] = (node.lineno, a.name)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return  # compiler directive, not a binding
        for a in node.names:
            if a.name == "*":
                continue
            name = a.asname or a.name
            self.imported[name] = (node.lineno, f"{node.module}.{a.name}")

    # -- usage --------------------------------------------------------------
    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)

    # -- checks -------------------------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self.problems.append((node.lineno, "E722 bare except"))
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # check BOTH sides of each comparison: `None == x` puts the
        # constant in node.left (or, chained, in the previous
        # comparator), which the comparators-only loop missed
        operands = [node.left] + list(node.comparators)
        for op, left, right in zip(node.ops, operands, operands[1:]):
            if isinstance(op, (ast.Eq, ast.NotEq)) and any(
                isinstance(o, ast.Constant) and o.value is None
                for o in (left, right)
            ):
                self.problems.append(
                    (node.lineno, "E711 comparison to None (use `is`)")
                )
        self.generic_visit(node)

    def _check_defaults(self, node) -> None:
        for d in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                self.problems.append(
                    (d.lineno, "B006 mutable default argument")
                )

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_FormattedValue(self, node: ast.FormattedValue) -> None:
        # visit the expression only: a format spec is itself a synthetic
        # JoinedStr and must not trip F541
        self.visit(node.value)

    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self.problems.append((node.lineno, "F541 f-string without placeholders"))
        self.generic_visit(node)


def run_ast_lint(files: list[str]) -> int:
    n = 0
    for path in files:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source, path)
        except SyntaxError:
            continue  # compileall already reported it
        lint = _Lint(path, tree, source)
        for line, msg in sorted(lint.problems):
            rel = os.path.relpath(path, REPO)
            print(f"{rel}:{line}: {msg}")
            n += 1
    return n


def run_optional(tool: str, args: list[str]) -> int | None:
    """Run ruff/mypy when the image carries them; None = unavailable."""
    probe = subprocess.run(
        [sys.executable, "-m", tool, "--version"],
        capture_output=True,
    )
    if probe.returncode != 0:
        return None
    res = subprocess.run([sys.executable, "-m", tool, *args], cwd=REPO)
    return res.returncode


def seeded_journal_fixture(path: str) -> None:
    """A known WAL: 3 bind intents for one gang, first confirmed —
    exactly what a leader killed after 1 of 3 bulk writes leaves."""
    lines = [
        '{"rec":"intent","seq":1,"cycle":4,"op":"bind","gang":"default/g0","pod":"default/p0","node":"n0"}',
        '{"rec":"intent","seq":2,"cycle":4,"op":"bind","gang":"default/g0","pod":"default/p1","node":"n1"}',
        '{"rec":"intent","seq":3,"cycle":4,"op":"bind","gang":"default/g0","pod":"default/p2","node":"n0"}',
        '{"rec":"confirm","seq":1}',
    ]
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")


def run_chaos_gate(env: dict) -> bool:
    """--chaos: the chaos-marked test subset + fsck on a seeded journal.
    Returns True when clean."""
    import json
    import tempfile

    ok = True
    res = subprocess.run(
        [
            sys.executable, "-m", "pytest", "tests", "-q", "-m", "chaos",
            "-p", "no:cacheprovider",
        ],
        cwd=REPO, env=env,
    )
    if res.returncode != 0:
        print("verify: chaos test subset FAILED")
        ok = False
    with tempfile.TemporaryDirectory() as tmp:
        fixture = os.path.join(tmp, "seeded.wal")
        seeded_journal_fixture(fixture)
        res = subprocess.run(
            [sys.executable, "-m", "kube_batch_tpu.recovery.fsck", "--json", fixture],
            cwd=REPO, env=env, capture_output=True, text=True,
        )
        summary = {}
        if res.returncode == 0:
            try:
                summary = json.loads(res.stdout.strip().splitlines()[-1])
            except (ValueError, IndexError):
                pass
        if (
            res.returncode != 0
            or summary.get("intents") != 3
            or summary.get("orphaned") != 2
            or summary.get("corrupt_lines") != 0
        ):
            print(f"verify: recovery.fsck on the seeded journal FAILED ({summary})")
            ok = False
        # --strict must refuse a journal with in-flight intents
        res = subprocess.run(
            [sys.executable, "-m", "kube_batch_tpu.recovery.fsck", "--strict", fixture],
            cwd=REPO, env=env, capture_output=True, text=True,
        )
        if res.returncode != 1:
            print("verify: recovery.fsck --strict did not gate on orphans")
            ok = False
    return ok


# The seeded two-scheduler conflict drill: both caches snapshot the
# same store version, both dispatch onto ONE node — the second dispatch
# must lose its optimistic check and win the refresh-retry; store truth
# must end fsck-clean with all six pods bound.
_FED_DRILL = """
import json
from kube_batch_tpu.api.job_info import job_key
from kube_batch_tpu.api.types import TaskStatus
from kube_batch_tpu.cache import ClusterStore
from kube_batch_tpu.federation import FederatedCache, fsck, shard_index
from kube_batch_tpu.testing import (
    build_node, build_pod, build_pod_group, build_queue, build_resource_list,
)

store = ClusterStore()
store.create_queue(build_queue("default"))
store.create_node(
    build_node("n0", build_resource_list(cpu=16, memory="16Gi", pods=64))
)
for g in ("ga", "gb"):
    store.create_pod_group(build_pod_group(g, min_member=3))
    for m in range(3):
        store.create_pod(build_pod(
            name=f"{g}-p{m}", group_name=g,
            req=build_resource_list(cpu=1, memory="512Mi"),
        ))
caches = {
    g: FederatedCache(
        store, shard=shard_index(job_key("default", g), 2), shards=2,
        shard_key="gang",
    )
    for g in ("ga", "gb")
}
for c in caches.values():
    c.snapshot()  # same version: the second dispatch conflicts for real
for g, c in caches.items():
    job = c.jobs[job_key("default", g)]
    pending = list(job.task_status_index[TaskStatus.PENDING].values())
    c.bind_many([(t, "n0") for t in pending])
violations = fsck(store)
bound = sum(1 for p in store.list("pods") if p.node_name)
ok = not violations and bound == 6
print(json.dumps({"ok": ok, "bound": bound, "fsck_violations": violations}))
raise SystemExit(0 if ok else 1)
"""


def run_federation_gate(env: dict) -> dict:
    """--federation: the wire-path smoke (python -m
    kube_batch_tpu.federation --json), the seeded in-process
    two-scheduler conflict drill above, and the kill-and-adopt drill
    (python -m kube_batch_tpu.federation --json --kill-one): kill one
    of four shard owners mid-bind_many and require a survivor to adopt
    the orphaned slot within the lease window with zero lost or
    duplicate binds. Returns a summary for --json."""
    import json

    env = dict(env)
    # a shard spec or key armed in the shell would skew both halves
    env.pop("KBT_FEDERATION", None)
    env.pop("KBT_SHARD_KEY", None)
    ok = True
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.federation", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    summary: dict = {}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: federation smoke produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    if res.returncode != 0 or not summary.get("ok", False):
        print(f"verify: federation smoke FAILED ({summary})")
        ok = False
    res = subprocess.run(
        [sys.executable, "-c", _FED_DRILL],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    drill: dict = {}
    try:
        drill = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        pass
    if res.returncode != 0 or not drill.get("ok", False):
        print(res.stdout, res.stderr, sep="\n")
        print(f"verify: federation two-scheduler conflict drill FAILED ({drill})")
        ok = False
    # the kill-and-adopt drill (no --strict: the unowned-window fsck
    # observation is timing-dependent and covered deterministically by
    # tests/test_resharding.py)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.federation", "--json", "--kill-one"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    kill: dict = {}
    try:
        kill = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: federation kill drill produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    if res.returncode != 0 or not kill.get("ok", False):
        print(f"verify: federation kill-and-adopt drill FAILED ({kill})")
        ok = False
    # the streaming-federation smoke (ISSUE 18 tentpole): N shards on
    # event-driven micro-cycles absorbing peer binds as occupancy
    # patches — parity with the classic federated run, micro-cycles
    # actually taken, exactly-once, fsck clean, pumps and listeners
    # shut down clean
    env_st = dict(env)
    env_st.pop("KBT_STREAMING", None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.federation", "--json",
         "--streaming"],
        cwd=REPO, env=env_st, capture_output=True, text=True,
    )
    stream: dict = {}
    try:
        stream = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: streaming-federation smoke produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    if res.returncode != 0 or not stream.get("ok", False):
        print(f"verify: streaming-federation smoke FAILED ({stream})")
        ok = False
    return {
        "ok": ok,
        "shards": summary.get("shards"),
        "bound": summary.get("bound"),
        "exactly_once": summary.get("exactly_once"),
        "union_parity": summary.get("union_parity"),
        "drill_bound": drill.get("bound"),
        "kill_adopter": kill.get("adopter"),
        "kill_takeover_s": kill.get("takeover_s"),
        "kill_mttr_s": kill.get("mttr_s"),
        "streaming_micro_cycles": stream.get("micro_cycles"),
        "streaming_parity": stream.get("parity"),
    }


def run_obs_gate(env: dict) -> dict:
    """Default gate (and --obs): the tracing end-to-end self-check
    (python -m kube_batch_tpu.obs --json). Two federated shards over
    live loopback backends, a forced stale-dispatch conflict, and the
    smoke's own assertions: complete span tree, the conflicted
    gang.bind joined by the arbiter-side store.bind in one trace,
    fsck-clean store, JSONL + Chrome trace exported."""
    import json

    env = dict(env)
    # a tracing/federation override armed in the shell would skew the
    # smoke (it arms KBT_TRACE and the conf itself)
    for var in ("KBT_TRACE", "KBT_FEDERATION", "KBT_SHARD_KEY",
                "KBT_FLIGHT_RECORDER"):
        env.pop(var, None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.obs", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    summary: dict = {}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: obs tracing smoke produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and summary.get("ok", False)
    if not ok:
        print(f"verify: obs tracing smoke FAILED ({summary})")
    return {
        "ok": ok,
        "spans": summary.get("spans"),
        "conflicted_gang_binds": summary.get("conflicted_gang_binds"),
        "remote_spans_joined": summary.get("remote_spans_joined"),
        "tree_violations": len(summary.get("tree_violations") or []),
    }


def run_explain_gate(env: dict) -> dict:
    """Default gate: the unschedulability-forensics self-check
    (python -m kube_batch_tpu.obs.explain --json). A seeded cluster
    with one stuck gang per feasibility plane plus a bound control:
    the batched device forensics must agree byte-for-byte with the
    serial twin (parity), every gang must report its designed dominant
    reason, the would-fit-if planes must flag the designed single
    fixes, and the reasons must land on PodGroup conditions."""
    import json

    env = dict(env)
    # an explain/tracing override armed in the shell would skew the
    # smoke (it arms KBT_EXPLAIN itself)
    for var in ("KBT_EXPLAIN", "KBT_TRACE", "KBT_FEDERATION",
                "KBT_SHARD_KEY", "KBT_FLIGHT_RECORDER"):
        env.pop(var, None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.obs.explain", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    summary: dict = {}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: explain forensics smoke produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and summary.get("ok", False)
    if not ok:
        print(f"verify: explain forensics smoke FAILED ({summary})")
    return {
        "ok": ok,
        "parity": summary.get("parity"),
        "reasons_ok": summary.get("reasons_ok"),
        "would_fit_if_ok": summary.get("would_fit_if_ok"),
        "conditions_ok": summary.get("conditions_ok"),
    }


def run_fleet_gate(env: dict) -> dict:
    """Default gate: the fleet-aggregation self-check
    (python -m kube_batch_tpu.obs.fleet --json) at BOTH 2 and 4
    loopback shards. Per-shard SLO sketches served over live HTTP
    observatories, scraped and merged by the aggregator: merged
    p50/p90/p99 must land within the sketch's declared relative-error
    bound of the pooled-raw nearest-rank quantiles, with exactly-once
    binds and an fsck-clean store asserted in-row."""
    import json

    env = dict(env)
    # overrides armed in the shell would skew the smoke (it arms
    # KBT_FLEET itself and runs a federated world)
    for var in ("KBT_FLEET", "KBT_TRACE", "KBT_FEDERATION",
                "KBT_SHARD_KEY", "KBT_FLIGHT_RECORDER"):
        env.pop(var, None)
    out: dict = {"ok": True}
    for shards in (2, 4):
        res = subprocess.run(
            [sys.executable, "-m", "kube_batch_tpu.obs.fleet", "--json",
             "--shards", str(shards)],
            cwd=REPO, env=env, capture_output=True, text=True,
        )
        summary: dict = {}
        try:
            summary = json.loads(res.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            print(f"verify: fleet obs smoke ({shards} shards) produced "
                  "no parseable summary")
            print(res.stdout, res.stderr, sep="\n")
        ok = res.returncode == 0 and summary.get("ok", False)
        if not ok:
            print(f"verify: fleet obs smoke FAILED at {shards} shards "
                  f"({summary})")
            out["ok"] = False
        out[f"shards_{shards}"] = {
            "ok": ok,
            "max_rel_err": summary.get("max_rel_err"),
            "rel_err_bound": summary.get("rel_err_bound"),
            "exactly_once": summary.get("exactly_once"),
            "fsck_violations": len(summary.get("fsck_violations") or []),
        }
    return out


def run_bench_diff_gate(old: str, new: str) -> dict:
    """--bench-diff OLD NEW: hack/bench_diff.py in --strict mode — a
    >15% p50 regression, a parity flip, a compile-budget change or a
    vanished row in NEW vs OLD fails the gate."""
    import json

    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "hack", "bench_diff.py"),
         old, new, "--json", "--strict"],
        cwd=REPO, capture_output=True, text=True,
    )
    summary: dict = {}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: bench_diff produced no parseable summary")
    if res.returncode != 0 or not summary.get("ok", False):
        print(res.stdout.rstrip())
        print(f"verify: bench diff FAILED ({old} -> {new})")
    return {
        "ok": res.returncode == 0 and summary.get("ok", False),
        "findings": len(summary.get("findings", [])),
        "rows": summary.get("rows_new"),
    }


def run_analysis_gate(strict: bool) -> dict:
    """The domain-aware suite as a subprocess (same pattern as the fsck
    gate: the CLI is the contract). Returns a summary dict for --json."""
    import json

    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--json"]
        + (["--strict"] if strict else []),
        cwd=REPO, capture_output=True, text=True,
    )
    summary: dict = {"ok": False, "counts": {}}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: analysis suite produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and summary.get("ok", False)
    if not ok:
        for f in summary.get("findings", []) + summary.get("baseline_errors", []):
            print(f"{f['path']}:{f['line']}: {f['code']} {f['message']}")
        if strict:
            for f in summary.get("stale", []):
                print(f"{f['path']}:{f['line']}: {f['code']} {f['message']}")
        print("verify: analysis suite FAILED "
              "(python -m kube_batch_tpu.analysis --explain CODE for any code)")
    return {
        "ok": ok,
        "counts": summary.get("counts", {}),
        "suppressed": summary.get("suppressed", 0),
        "baseline_errors": len(summary.get("baseline_errors", [])),
        "stale": len(summary.get("stale", [])),
    }


def run_threads_gate(strict: bool) -> dict:
    """The concurrency sanitizer as its own gate (python -m
    kube_batch_tpu.analysis.threads): beyond the KBT-T pass the default
    suite already runs, the dedicated CLI also executes the seeded
    fixture self-check AND the RaceWitness determinism drills, so a
    regression in either detector fails the build even while the live
    tree is clean."""
    import json

    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis.threads", "--json"]
        + (["--strict"] if strict else []),
        cwd=REPO, capture_output=True, text=True,
    )
    summary: dict = {"ok": False, "counts": {}}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: threads analyzer produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and summary.get("ok", False)
    self_probs = summary.get("selfcheck") or {}
    problems = list(self_probs.get("static", ["?"])) + list(
        self_probs.get("witness", [])
    )
    if not ok:
        for f in summary.get("findings", []) + summary.get("baseline_errors", []):
            print(f"{f['path']}:{f['line']}: {f['code']} {f['message']}")
        for p in problems:
            print(f"selfcheck: {p}")
        print("verify: concurrency sanitizer FAILED "
              "(python -m kube_batch_tpu.analysis.threads --explain CODE)")
    return {
        "ok": ok,
        "counts": summary.get("counts", {}),
        "suppressed": summary.get("suppressed", 0),
        "selfcheck_ok": not problems,
        "stale": len(summary.get("stale", [])),
    }


def run_trace_gate(strict: bool) -> dict:
    """The jaxpr-level trace auditor (python -m
    kube_batch_tpu.analysis.trace) under JAX_PLATFORMS=cpu. Same
    contract as the AST suite gate; per-code counts ride the --json
    summary. Unlike every other gate this one traces the real solver
    programs, so it runs last among the analysis gates (a broken
    kernel fails here with a traceback, not a lint)."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis.trace", "--json"]
        + (["--strict"] if strict else []),
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    summary: dict = {"ok": False, "counts": {}}
    try:
        summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        print("verify: trace audit produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and summary.get("ok", False)
    if not ok:
        for f in summary.get("findings", []) + summary.get("baseline_errors", []):
            print(f"{f['path']}:{f['line']}: {f['code']} {f['message']}")
        if strict:
            for f in summary.get("stale", []):
                print(f"{f['path']}:{f['line']}: {f['code']} {f['message']}")
        print("verify: trace audit FAILED "
              "(python -m kube_batch_tpu.analysis.trace --explain CODE)")
    return {
        "ok": ok,
        "counts": summary.get("counts", {}),
        "suppressed": summary.get("suppressed", 0),
        "entries": summary.get("entries", {}),
        "stale": len(summary.get("stale", [])),
    }


def run_interleave_gate(strict: bool) -> dict:
    """The interleaving model checker (python -m
    kube_batch_tpu.analysis.interleave) under JAX_PLATFORMS=cpu: the
    four fixed streaming/takeover scenarios through every
    distinguishable schedule. Opt-in via --interleave (it runs real
    micro/full cycles per schedule, ~tens of solves); the Dockerfile
    build runs it --strict so the shipped image's scenarios are proven
    clean. Counterexamples print with their replay command."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis.interleave", "--json"]
        + (["--strict"] if strict else []),
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    summary: dict = {}
    try:
        summary = json.loads(res.stdout)
    except ValueError:
        print("verify: interleave explorer produced no parseable summary")
        print(res.stdout, res.stderr, sep="\n")
    ok = res.returncode == 0 and bool(summary)
    if not ok:
        for f in summary.get("findings", []):
            print(f)
        print("verify: interleave exploration FAILED (replay the trace id "
              "with python -m kube_batch_tpu.analysis.interleave --replay)")
    return {
        "ok": ok,
        "schedules": sum(
            s.get("schedules", 0) for s in summary.get("scenarios", [])
        ),
        "counterexamples": sum(
            len(s.get("counterexamples", [])) for s in summary.get("scenarios", [])
        ),
        "suppressed": summary.get("suppressed", 0),
    }


class _TimedGates(dict):
    """Gate-summary dict that stamps per-gate wall-clock (seconds since
    the previous gate finished) onto each entry as it is recorded, so
    slow gates (interleave, chaos) are visible in the ``--json``
    machine summary without touching every call site."""

    def __init__(self) -> None:
        super().__init__()
        self._mark = time.perf_counter()

    def __setitem__(self, key, value):
        now = time.perf_counter()
        if isinstance(value, dict) and "seconds" not in value:
            value = dict(value, seconds=round(now - self._mark, 3))
        self._mark = now
        super().__setitem__(key, value)


def main(argv: list[str] | None = None) -> int:
    import json

    argv = sys.argv[1:] if argv is None else argv
    strict = "--strict" in argv
    chaos = "--chaos" in argv
    as_json = "--json" in argv
    interleave = "--interleave" in argv
    federation = "--federation" in argv
    bench_diff: tuple[str, str] | None = None
    if "--bench-diff" in argv:
        i = argv.index("--bench-diff")
        paths = [a for a in argv[i + 1:i + 3] if not a.startswith("--")]
        if len(paths) == 1:
            print("verify: --bench-diff takes two bench JSON paths (OLD NEW) "
                  "or none, to auto-discover the two newest BENCH_*.json")
            return 2
        if not paths:
            import glob

            found = sorted(
                glob.glob(os.path.join(REPO, "BENCH_*.json")),
                key=lambda p: (os.path.getmtime(p), p),
            )
            if len(found) < 2:
                print("verify: --bench-diff auto-discovery needs at least "
                      "two BENCH_*.json artifacts in the repo root")
                return 2
            bench_diff = (found[-2], found[-1])
            print("verify: bench-diff auto-discovered "
                  f"{os.path.basename(found[-2])} -> "
                  f"{os.path.basename(found[-1])}")
        else:
            bench_diff = (paths[0], paths[1])
        argv = argv[:i] + argv[i + 1 + len(paths):]
    unknown = [
        a for a in argv
        if a not in ("--strict", "--chaos", "--json", "--interleave",
                     "--federation", "--obs")
    ]
    if unknown:
        print(f"verify: unknown argument(s): {' '.join(unknown)}")
        return 2
    files = py_files()
    failed = False
    gates: dict = _TimedGates()

    # 1. syntax
    ok = compileall.compile_dir(
        os.path.join(REPO, "kube_batch_tpu"), quiet=2, force=False
    )
    for single in files:
        ok = compileall.compile_file(single, quiet=2) and ok
    gates["compileall"] = {"ok": bool(ok)}
    if not ok:
        print("verify: compileall FAILED")
        failed = True

    # 2. indentation — tabnanny prints NannyNag diagnostics to STDOUT
    # (only I/O/token errors go to stderr), so both streams gate
    import contextlib

    tab_problems = 0
    for path in files:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
            tabnanny.check(path)
        if buf.getvalue():
            print(buf.getvalue().strip())
            tab_problems += 1
    gates["tabnanny"] = {"ok": tab_problems == 0, "flagged": tab_problems}
    if tab_problems:
        print(f"verify: tabnanny flagged {tab_problems} file(s)")
        failed = True

    # 3. AST lint
    n = run_ast_lint(files)
    gates["ast_lint"] = {"ok": n == 0, "findings": n}
    if n:
        print(f"verify: AST lint found {n} problem(s)")
        failed = True

    # 4. the domain-aware analysis suite (always on: it is stdlib-only,
    # so the bare image runs it; --strict additionally rejects stale
    # baseline entries)
    gates["analysis"] = run_analysis_gate(strict)
    if not gates["analysis"]["ok"]:
        failed = True

    # 4a. the concurrency sanitizer's own CLI (KBT-T0xx + RaceWitness):
    # runs the seeded fixture self-check and the witness determinism
    # drills on top of the live-tree pass the suite gate above did
    gates["threads"] = run_threads_gate(strict)
    if not gates["threads"]["ok"]:
        failed = True

    # 4b. the trace-level program auditor (KBT-P0xx): jaxpr lints +
    # donation + cross-tier signature drift over the real solver entry
    # points, on abstract inputs under JAX_PLATFORMS=cpu
    gates["trace_audit"] = run_trace_gate(strict)
    if not gates["trace_audit"]["ok"]:
        failed = True

    # 4c. (--interleave) the interleaving model checker (KBT-I0xx):
    # every distinguishable schedule of the fixed streaming/takeover
    # scenarios, with counterexamples replayable by trace id
    if interleave:
        gates["interleave"] = run_interleave_gate(strict)
        if not gates["interleave"]["ok"]:
            failed = True

    # 5. the full generic gate, when available (mypy beyond api/ per
    # VERDICT item 7: framework, conf and recovery carry the concurrency
    # and failover contracts, where a None slip is a 3am page)
    for tool, args in (
        ("ruff", ["check", "kube_batch_tpu"]),
        ("mypy", [
            "--ignore-missing-imports",
            "kube_batch_tpu/api",
            "kube_batch_tpu/framework",
            "kube_batch_tpu/conf",
            "kube_batch_tpu/recovery",
        ]),
    ):
        rc = run_optional(tool, args)
        if rc is None:
            gates[tool] = {"ok": not strict, "status": "unavailable"}
            if strict:
                print(f"verify: {tool} unavailable — FAILED (--strict: "
                      "install the 'dev' extra: pip install -e '.[dev]')")
                failed = True
            else:
                print(f"verify: {tool} unavailable in this image — skipped "
                      "(stdlib gates above still ran; --strict to require)")
        else:
            gates[tool] = {"ok": rc == 0, "status": "ran"}
            if rc != 0:
                print(f"verify: {tool} FAILED")
                failed = True

    # 6. chaos smoke — the failure drills must actually work here
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KBT_MIN_DEVICE_PAIRS="0",
        KBT_CACHE_MUTATION_DETECTOR="1",
    )
    env.pop("KBT_FAULTS", None)  # a drill armed in the shell would skew it
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.faults.smoke"], cwd=REPO, env=env
    )
    gates["chaos_smoke"] = {"ok": res.returncode == 0}
    if res.returncode != 0:
        print("verify: chaos smoke FAILED")
        failed = True

    # 6b. wire-codec self-check: seeded round-trip property pass over
    # every kind in both framings (python -m kube_batch_tpu.apis.wire).
    # A codec override armed in the shell must not skew it.
    env_wc = dict(env)
    env_wc.pop("KBT_WIRE_CODEC", None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.apis.wire", "--json"],
        cwd=REPO, env=env_wc, capture_output=True, text=True,
    )
    wire_summary: dict = {}
    try:
        wire_summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        pass
    wire_ok = res.returncode == 0 and wire_summary.get("ok", False)
    gates["wire_codec"] = {
        "ok": wire_ok,
        "cases": wire_summary.get("cases"),
        "json_bytes": wire_summary.get("json_bytes"),
        "binary_bytes": wire_summary.get("binary_bytes"),
    }
    if not wire_ok:
        print(res.stdout, res.stderr, sep="\n")
        print("verify: wire codec self-check FAILED")
        failed = True

    # 7. encode-cache parity smoke: warm and 1%-churn encodes must be
    # byte-identical to a fresh cold encode on a seeded snapshot
    # (python -m kube_batch_tpu.ops.encode_cache). Runs with the cache
    # at its default-on state — a shell override must not skew the gate.
    env_ec = dict(env)
    env_ec.pop("KBT_ENCODE_CACHE", None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.ops.encode_cache"],
        cwd=REPO, env=env_ec,
    )
    gates["encode_cache_smoke"] = {"ok": res.returncode == 0}
    if res.returncode != 0:
        print("verify: encode-cache parity smoke FAILED")
        failed = True

    # 7a. pipelined-cycle parity smoke: the same seeded world scheduled
    # with KBT_PIPELINE off then on must bind pod-for-pod identically,
    # with the pipelined run's dispatch actually deferred through the
    # fence and the arena ping-ponging its device banks
    # (python -m kube_batch_tpu.ops.encode_cache --pipeline). Pipeline
    # overrides armed in the shell must not skew either half.
    env_pl = dict(env_ec)
    for var in ("KBT_PIPELINE", "KBT_PIPELINE_FENCE_TIMEOUT_S",
                "KBT_EXCHANGE_BATCH"):
        env_pl.pop(var, None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.ops.encode_cache", "--pipeline"],
        cwd=REPO, env=env_pl,
    )
    gates["pipeline_smoke"] = {"ok": res.returncode == 0}
    if res.returncode != 0:
        print("verify: pipelined-cycle parity smoke FAILED")
        failed = True

    # 7b. streaming smoke: micro-cycles bind every arrival and agree
    # bind-for-bind with a full-cycle twin (python -m
    # kube_batch_tpu.streaming). The detector env from the chaos gate
    # stays on — micro-cycles must hold the no-mutation contract too.
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.streaming", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    stream_summary: dict = {}
    try:
        stream_summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        pass
    stream_ok = (
        res.returncode == 0
        and stream_summary.get("ok", False)
        and stream_summary.get("parity", False)
        and stream_summary.get("micro_cycles", 0) > 0
    )
    gates["streaming_smoke"] = {
        "ok": stream_ok,
        "micro_cycles": stream_summary.get("micro_cycles", 0),
        "p50_bind_ms": stream_summary.get("p50_bind_ms"),
    }
    if not stream_ok:
        print(res.stdout, res.stderr, sep="\n")
        print("verify: streaming smoke FAILED")
        failed = True

    # 7c. obs tracing smoke: span tree + cross-process propagation +
    # conflicted-bind join over the real wire path (--obs requests it
    # explicitly; it is part of the default gate set)
    gates["obs_tracing_smoke"] = run_obs_gate(env)
    if not gates["obs_tracing_smoke"]["ok"]:
        failed = True

    # 7c-bis. explain forensics smoke: batched device forensics vs the
    # serial twin on the seeded per-plane stuck-gang cluster (python -m
    # kube_batch_tpu.obs.explain). Part of the default gate set.
    gates["explain_smoke"] = run_explain_gate(env)
    if not gates["explain_smoke"]["ok"]:
        failed = True

    # 7c-ter. fleet observability smoke: per-shard sketches scraped and
    # merged over live loopback HTTP at 2 AND 4 shards, merged
    # quantiles within the sketch's error bound of pooled raw (python
    # -m kube_batch_tpu.obs.fleet). Part of the default gate set.
    gates["fleet_obs_smoke"] = run_fleet_gate(env)
    if not gates["fleet_obs_smoke"]["ok"]:
        failed = True

    # 7c-quater. admission smoke: the deterministic 5x-overload plant
    # (python -m kube_batch_tpu.admission --json) — the protected lane
    # holds its SLO tail with zero shed while the admission-OFF twin
    # collapses, the brownout ladder escalates and recovers without
    # flapping, and every shed carries Retry-After guidance. Part of
    # the default gate set (virtual clock: sub-second wall time).
    env_adm = dict(env)
    for var in ("KBT_ADMISSION", "KBT_ADMISSION_RATE",
                "KBT_ADMISSION_BURST", "KBT_ADMISSION_BACKLOG",
                "KBT_ADMISSION_P99_SLO_S", "KBT_ADMISSION_BAND",
                "KBT_ADMISSION_INTERVAL_S", "KBT_ADMISSION_MIN_RATE",
                "KBT_FLEET"):
        env_adm.pop(var, None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.admission", "--json"],
        cwd=REPO, env=env_adm, capture_output=True, text=True,
    )
    adm_summary: dict = {}
    try:
        adm_summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        pass
    adm_on = adm_summary.get("on") or {}
    adm_ok = res.returncode == 0 and adm_summary.get("ok", False)
    gates["admission_smoke"] = {
        "ok": adm_ok,
        "tail_p99_s": adm_on.get("tail_p99_s"),
        "high_shed": ((adm_on.get("counts") or {}).get("high") or {}).get("shed"),
        "level_final": adm_on.get("level_final"),
    }
    if not adm_ok:
        print(res.stdout, res.stderr, sep="\n")
        print("verify: admission smoke FAILED")
        failed = True

    # 7c-quinquies. node-class compressed solve smoke (python -m
    # kube_batch_tpu.ops.class_solve --json): the same seeded world
    # scheduled serial / uncompressed / KBT_CLASS_COMPRESS=1 must bind
    # pod-for-pod identically across two cycles (the second re-using
    # the class table with binds applied, so splits and re-merges both
    # fire), with the compressed tier actually engaged. Part of the
    # default gate set; shell overrides must not skew either half.
    env_cls = dict(env)
    for var in ("KBT_CLASS_COMPRESS", "KBT_MESH", "KBT_MESH_PALLAS"):
        env_cls.pop(var, None)
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.ops.class_solve", "--json"],
        cwd=REPO, env=env_cls, capture_output=True, text=True,
    )
    cls_summary: dict = {}
    try:
        cls_summary = json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        pass
    cls_ok = (
        res.returncode == 0
        and cls_summary.get("ok", False)
        and cls_summary.get("parity_cycle1", False)
        and cls_summary.get("parity_cycle2", False)
    )
    gates["class_solve_smoke"] = {
        "ok": cls_ok,
        "class_count": cls_summary.get("class_count"),
        "compression_ratio": cls_summary.get("compression_ratio"),
        "splits": cls_summary.get("splits"),
    }
    if not cls_ok:
        print(res.stdout, res.stderr, sep="\n")
        print("verify: class-solve parity smoke FAILED")
        failed = True

    # 7d. --federation: the wire-path smoke + the seeded two-scheduler
    # conflict drill (optimistic concurrency over the extracted backend)
    if federation:
        gates["federation"] = run_federation_gate(env)
        if not gates["federation"]["ok"]:
            failed = True

    # 8. --chaos: the full chaos-marked suite + fsck on a seeded journal
    if chaos:
        chaos_ok = run_chaos_gate(env)
        gates["chaos"] = {"ok": chaos_ok}
        if not chaos_ok:
            failed = True

        # 8b. the admission storm drill (real-clock, ~1 min): the
        # three-cell ON/OFF/KILL comparison — protected-lane tail held
        # under 5x overload, the OFF twin measurably worse, and
        # mid-storm shard death recovered with zero orphans
        env_storm = dict(env_adm)
        res = subprocess.run(
            [sys.executable, "-m", "kube_batch_tpu.admission", "--storm",
             "--json", "--duration", "4"],
            cwd=REPO, env=env_storm, capture_output=True, text=True,
        )
        storm: dict = {}
        try:
            storm = json.loads(res.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            print("verify: admission storm drill produced no parseable summary")
            print(res.stdout, res.stderr, sep="\n")
        storm_ok = res.returncode == 0 and storm.get("ok", False)
        gates["admission_storm"] = {
            "ok": storm_ok,
            "on_high_p99_s": (storm.get("on") or {}).get(
                "lane_p99_s", {}).get("high"),
            "kill_mttr_s": (storm.get("kill") or {}).get("mttr_s"),
        }
        if not storm_ok:
            print(f"verify: admission storm drill FAILED ({storm})")
            failed = True

    # 9. --bench-diff OLD NEW: regression-gate two bench artifacts
    # (hack/bench_diff.py --strict — p50 regressions, parity flips,
    # compile-budget changes, vanished rows)
    if bench_diff is not None:
        gates["bench_diff"] = run_bench_diff_gate(*bench_diff)
        if not gates["bench_diff"]["ok"]:
            failed = True

    print("verify:", "FAILED" if failed else "ok",
          f"({len(files)} files)")
    if as_json:
        print(json.dumps({
            "ok": not failed,
            "strict": strict,
            "files": len(files),
            "gates": gates,
        }, sort_keys=True))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

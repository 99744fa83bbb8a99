"""Tier-1 tests for the domain-aware static analysis suite
(kube_batch_tpu.analysis) and the stdlib lint checks it rides with
(hack/verify.py).

Each analyzer (A1 lock-discipline, A2 JAX hazards, A3 registry
consistency, A4 snapshot escape) is proven on a seeded-violation
fixture — source strings with exactly the defect class the analyzer
exists to catch — plus its negative twin (the compliant spelling must
NOT fire). The live tree runs as a smoke: the committed baseline must
leave zero unsuppressed findings, so `hack/verify.py` stays green.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from kube_batch_tpu.analysis import (
    SourceFile,
    apply_baseline,
    load_baseline,
    load_tree,
    run_suite,
)
from kube_batch_tpu.analysis import (
    jax_hazards,
    lock_discipline,
    lock_order,
    protocol,
    registry_consistency,
    snapshot_escape,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sf(path: str, source: str) -> SourceFile:
    return SourceFile(path, source, ast.parse(source, path))


def codes(findings) -> list[str]:
    return [f.code for f in findings]


# -- A1: lock discipline -----------------------------------------------------

A1_FIXTURE = '''
import threading

class Hub:
    def __init__(self):
        self._lock = threading.Lock()
        self._seq = 0        #: guarded_by _lock
        self._items = {}     #: guarded_by _lock

    def bad(self):
        self._seq += 1       # VIOLATION: no lock held

    def good(self):
        with self._lock:
            self._seq += 1
            return self._items.get(1)

    def _bump_locked(self):
        self._seq += 1       # exempt: _locked suffix

    @assume_locked
    def _peek(self):
        return self._items   # exempt: assume_locked marker

    def nested_ok(self):
        with self._lock:
            def inner():
                return self._seq   # lexically under the with: ok
            return inner()
'''


def test_lock_discipline_fires_on_unlocked_access():
    findings = lock_discipline.analyze([sf("kube_batch_tpu/x/hub.py", A1_FIXTURE)])
    assert codes(findings) == ["KBT-L001"]
    f = findings[0]
    assert f.symbol == "Hub.bad._seq"
    assert "_lock" in f.message


def test_lock_discipline_seed_map_applies_to_real_paths():
    src = (
        "import threading\n"
        "class RateLimitingQueue:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self._heap = []\n"
        "    def peek(self):\n"
        "        return self._heap[0]\n"
    )
    findings = lock_discipline.analyze([sf("kube_batch_tpu/utils/workqueue.py", src)])
    assert codes(findings) == ["KBT-L001"]
    assert findings[0].symbol == "RateLimitingQueue.peek._heap"


def test_lock_discipline_unknown_lock_annotation():
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._x = 1  #: guarded_by _mutex\n"
    )
    findings = lock_discipline.analyze([sf("kube_batch_tpu/x/c.py", src)])
    assert codes(findings) == ["KBT-L002"]


# -- A2: JAX hazards ---------------------------------------------------------

A2_FIXTURE = '''
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

@partial(jax.jit, static_argnames=("flag",))
def solve(x, flag):
    if flag:                       # static arg: ok
        x = x + 1
    if x is None:                  # identity: ok (fresh/resume dispatch)
        x = jnp.zeros(())
    v = x.item()                   # VIOLATION J001 host sync
    print("trace", v)              # VIOLATION J003 bare print
    y = np.asarray(x)              # VIOLATION J001 np materialization
    if jnp.any(x > 0):             # VIOLATION J002 truth test on traced
        y = y + 1
    return helper(y)

def helper(y):
    return float(y)                # VIOLATION J001 via call closure

def host_pack(a):
    return np.asarray(a).item()    # not jit-reachable: silent
'''


def test_jax_hazards_fire_in_jit_scope_only():
    findings = jax_hazards.analyze([sf("kube_batch_tpu/ops/fix.py", A2_FIXTURE)])
    got = sorted(codes(findings))
    assert got == ["KBT-J001", "KBT-J001", "KBT-J001", "KBT-J002", "KBT-J003"]
    # the host-side function stayed silent
    assert not any("host_pack" in f.symbol for f in findings)
    # the call-closure reached helper()
    assert any(f.symbol.startswith("helper.") for f in findings)


def test_jax_hazards_scope_is_ops_and_parallel():
    findings = jax_hazards.analyze([sf("kube_batch_tpu/cache/fix.py", A2_FIXTURE)])
    assert findings == []


J004_FIXTURE = '''
import numpy as np
from kube_batch_tpu.api.numerics import comparison_dtype

def share_bad(a, b):
    return float(np.float64(a) / np.float64(b))   # VIOLATION x2

def share_ok(a, b):
    dt = comparison_dtype()
    if dt is np.float64:                          # identity consult: ok
        return a / b
    return float(dt(a) / dt(b))
'''


def test_dtype_policy_fires_in_plugins_not_kernels():
    findings = jax_hazards.analyze([sf("kube_batch_tpu/plugins/fix.py", J004_FIXTURE)])
    # two literals on one line share (path, line, code, symbol): one finding
    assert codes(findings) == ["KBT-J004"]
    assert all(f.symbol.startswith("share_bad") for f in findings)
    # kernels pin f32 by contract; out of J004 scope
    assert jax_hazards.analyze([sf("kube_batch_tpu/ops/fix2.py", J004_FIXTURE)]) == []


# -- A3: registry consistency ------------------------------------------------

FAULTS_FIXTURE = (
    "POINTS = (\n"
    '    "solve.xla",\n'
    '    "bind.write",\n'
    '    "evict.write",\n'
    '    "lease.renew",\n'
    ")\n"
)

FIRER_FIXTURE = '''
from kube_batch_tpu import faults, metrics

def go(op):
    if faults.should_fire("solve.xla"):
        raise RuntimeError
    if faults.should_fire(f"{op}.write"):      # wildcard: bind./evict.write
        raise RuntimeError
    if faults.should_fire("solve.typo"):       # VIOLATION R001
        raise RuntimeError
    metrics.register_fault_injection("x")
    metrics.register_nonexistent("x")          # VIOLATION R003
'''

METRICS_FIXTURE = (
    "def register_fault_injection(point):\n"
    "    pass\n"
)


def _a3_files():
    return [
        sf("kube_batch_tpu/faults/__init__.py", FAULTS_FIXTURE),
        sf("kube_batch_tpu/metrics/__init__.py", METRICS_FIXTURE),
        sf("kube_batch_tpu/worker.py", FIRER_FIXTURE),
    ]


def test_registry_fault_points_both_directions(tmp_path):
    findings = registry_consistency.analyze(
        _a3_files(), repo=str(tmp_path), runbook="deployment/README.md"
    )
    by_code = {}
    for f in findings:
        by_code.setdefault(f.code, []).append(f)
    # the typo fires R001; lease.renew is registered but never fired (R002)
    assert [f.symbol for f in by_code["KBT-R001"]] == ["point:solve.typo"]
    assert [f.symbol for f in by_code["KBT-R002"]] == ["point:lease.renew"]
    # the f-string wildcard credited bind.write AND evict.write
    fired_r002 = {f.symbol for f in by_code["KBT-R002"]}
    assert "point:bind.write" not in fired_r002
    assert "point:evict.write" not in fired_r002
    assert [f.symbol for f in by_code["KBT-R003"]] == ["metric:register_nonexistent"]


SPANS_FIXTURE = (
    "SPAN_NAMES = (\n"
    '    "cycle",\n'
    '    "action.alpha",\n'
    '    "action.ghost",\n'
    ")\n"
)

ACTION_FACTORY_FIXTURE = '''
from kube_batch_tpu.framework.registry import register_action

def register_all_actions():
    from kube_batch_tpu.actions import alpha, beta
    register_action(alpha.new())
    register_action(beta.new())
'''

ACTION_FIXTURE = '''
class {cls}:
    @property
    def name(self):
        return "{name}"

def new():
    return {cls}()
'''

LOOP_FIXTURE = '''
from kube_batch_tpu import obs

def run_once(actions, stage):
    with obs.span("cycle"):
        for action in actions:
            with obs.span("action." + action.name):   # family: credits action.*
                pass
        with obs.span(f"stage.{stage}"):               # VIOLATION R007: matches nothing
            pass
'''


def test_registry_action_spans_both_directions(tmp_path):
    files = [
        sf("kube_batch_tpu/obs/__init__.py", SPANS_FIXTURE),
        sf("kube_batch_tpu/actions/factory.py", ACTION_FACTORY_FIXTURE),
        sf("kube_batch_tpu/actions/alpha.py", ACTION_FIXTURE.format(cls="Alpha", name="alpha")),
        sf("kube_batch_tpu/actions/beta.py", ACTION_FIXTURE.format(cls="Beta", name="beta")),
        sf("kube_batch_tpu/scheduler.py", LOOP_FIXTURE),
    ]
    findings = registry_consistency.analyze(files, repo=str(tmp_path))
    got = sorted((f.code, f.symbol, f.path) for f in findings)
    assert got == [
        # registered, but the span every cycle running it opens is undeclared
        ("KBT-R007", "span:action.beta", "kube_batch_tpu/actions/beta.py"),
        ("KBT-R007", "span:stage.*", "kube_batch_tpu/scheduler.py"),
        # declared, but names no registered action
        ("KBT-R008", "span:action.ghost", "kube_batch_tpu/obs/__init__.py"),
    ]


ENV_READER_FIXTURE = (
    "import os\n"
    'A = os.environ.get("KBT_ALPHA", "")\n'
    'B = os.environ["KBT_BETA"]\n'
    'ENV = "KBT_GAMMA"\n'
)

RUNBOOK_FIXTURE = (
    "# runbook\n\n"
    "| variable | default | meaning |\n"
    "|---|---|---|\n"
    "| `KBT_ALPHA` | off | alpha |\n"
    "| `KBT_GAMMA` | off | gamma |\n"
    "| `KBT_DEAD` | off | nobody reads me |\n"
)


def test_registry_env_table_both_directions(tmp_path):
    (tmp_path / "deployment").mkdir()
    (tmp_path / "deployment" / "README.md").write_text(RUNBOOK_FIXTURE)
    files = [sf("kube_batch_tpu/knobs.py", ENV_READER_FIXTURE)]
    findings = registry_consistency.analyze(files, repo=str(tmp_path))
    syms = {f.code: f.symbol for f in findings}
    assert syms.get("KBT-R004") == "env:KBT_BETA"  # read, undocumented
    assert syms.get("KBT-R005") == "env:KBT_DEAD"  # documented, dead
    assert len(findings) == 2  # ALPHA direct + GAMMA via ALL-CAPS const are fine


# -- A4: snapshot escape -----------------------------------------------------

A4_FIXTURE = '''
class BadAction:
    def execute(self, ssn):
        for job in ssn.jobs.values():
            for task in job.tasks.values():
                task.node_name = "n0"          # VIOLATION S001
        node = ssn.nodes.get("n0")
        node.add_task(task)                    # VIOLATION S002

class GoodAction:
    def execute(self, ssn):
        stmt = ssn.statement()
        for job in ssn.jobs.values():
            for task in job.tasks.values():
                ssn.allocate(task, "n0")       # sanctioned API
        stmt.commit()
'''


def test_snapshot_escape_fires_on_direct_mutation():
    findings = snapshot_escape.analyze([sf("kube_batch_tpu/actions/fix.py", A4_FIXTURE)])
    assert sorted(codes(findings)) == ["KBT-S001", "KBT-S002"]
    assert {f.symbol for f in findings} == {
        "BadAction.execute.node_name",
        "BadAction.execute.add_task",
    }


def test_snapshot_escape_scope_is_plugins_and_actions():
    findings = snapshot_escape.analyze([sf("kube_batch_tpu/framework/fix.py", A4_FIXTURE)])
    assert findings == []


# -- baseline ----------------------------------------------------------------

def test_baseline_requires_reasons_and_flags_stale(tmp_path):
    bl_file = tmp_path / "lint-baseline.toml"
    bl_file.write_text(
        "[[suppress]]\n"
        'code = "KBT-L001"\n'
        'path = "kube_batch_tpu/x/hub.py"\n'
        'symbol = "Hub.bad._seq"\n'
        'reason = "seeded fixture, intentionally kept"\n'
        "\n"
        "[[suppress]]\n"
        'code = "KBT-J003"\n'
        'path = "kube_batch_tpu/x/hub.py"\n'
        'reason = ""\n'          # reason-less -> KBT-B001
    )
    bl = load_baseline(str(bl_file), str(tmp_path))
    assert [e.code for e in bl.errors] == ["KBT-B001"]

    findings = lock_discipline.analyze([sf("kube_batch_tpu/x/hub.py", A1_FIXTURE)])
    kept, suppressed, stale = apply_baseline(findings, bl)
    assert kept == []
    assert len(suppressed) == 1
    # the J003 entry matched nothing -> stale (KBT-B002)
    assert [s.code for s in stale] == ["KBT-B002"]


def test_baseline_unparseable_line_is_loud(tmp_path):
    bl_file = tmp_path / "bl.toml"
    bl_file.write_text("[[suppress]]\ncode = unquoted\n")
    bl = load_baseline(str(bl_file), str(tmp_path))
    assert any("unparseable" in e.message for e in bl.errors)


# -- the stdlib lint (hack/verify.py) ---------------------------------------

def _verify_mod():
    spec = importlib.util.spec_from_file_location(
        "kbt_hack_verify", os.path.join(REPO, "hack", "verify.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize(
    "source,expect",
    [
        ("import os\n", "F401"),
        ("try:\n    pass\nexcept:\n    pass\n", "E722"),
        ("x = 1\nif x == None:\n    pass\n", "E711"),
        ("x = 1\nif None == x:\n    pass\n", "E711"),  # the left-side gap
        ("x = 1\nif None != x:\n    pass\n", "E711"),
        ("def f(a=[]):\n    return a\n", "B006"),
        ("s = f'no placeholder'\n", "F541"),
    ],
)
def test_stdlib_lint_checks_fire(source, expect, tmp_path):
    verify = _verify_mod()
    lint = verify._Lint("x.py", ast.parse(source), source)
    msgs = [m for _, m in lint.problems]
    assert any(m.startswith(expect) for m in msgs), (source, msgs)


def test_stdlib_lint_none_equality_not_double_counted():
    verify = _verify_mod()
    source = "x = 1\nif None == x == None:\n    pass\n"
    lint = verify._Lint("x.py", ast.parse(source), source)
    # two comparison ops, two problems — not four
    assert [m for _, m in lint.problems if m.startswith("E711")] != []
    assert len([m for _, m in lint.problems if m.startswith("E711")]) == 2


def test_stdlib_lint_is_none_clean():
    verify = _verify_mod()
    source = "x = 1\nif x is None:\n    pass\n"
    lint = verify._Lint("x.py", ast.parse(source), source)
    assert lint.problems == []


# -- live tree smoke ---------------------------------------------------------

def test_live_tree_is_clean_under_committed_baseline():
    findings = run_suite(REPO)
    bl = load_baseline(os.path.join(REPO, "hack", "lint-baseline.toml"), REPO)
    assert bl.errors == [], [e.message for e in bl.errors]
    kept, suppressed, stale = apply_baseline(findings, bl)
    assert kept == [], "unsuppressed findings:\n" + "\n".join(
        f.render() for f in kept
    )
    assert stale == [], "stale baseline entries:\n" + "\n".join(
        f.render() for f in stale
    )
    # the baseline is doing real work, not vacuously empty
    assert suppressed, "expected the committed baseline to cover known findings"


def test_live_tree_fault_and_env_registries_fully_covered():
    files = load_tree(REPO)
    findings = registry_consistency.analyze(files, repo=REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


# -- D codes: lock order / blocking-under-lock -------------------------------

ABBA_FIXTURE = """
import threading

class A:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()

    def ab(self):
        with self._la:
            with self._lb:
                pass

    def ba(self):
        with self._lb:
            with self._la:
                pass
"""


def test_lock_order_abba_cycle_fires():
    findings = lock_order.analyze([sf("kube_batch_tpu/x/abba.py", ABBA_FIXTURE)])
    assert codes(findings) == ["KBT-D001"]
    assert findings[0].symbol == "cycle:A._la<->A._lb"
    assert "re-nest" in findings[0].message


def test_lock_order_consistent_nesting_is_clean():
    src = ABBA_FIXTURE.replace("self._lb:\n            with self._la",
                               "self._la:\n            with self._lb")
    assert lock_order.analyze([sf("kube_batch_tpu/x/ok.py", src)]) == []


D002_FIXTURE = """
import os
import threading

class J:
    def __init__(self):
        self._lock = threading.Lock()
        self._fd = 3

    def bad(self):
        with self._lock:
            os.fsync(self._fd)

    def good(self):
        with self._lock:
            fd = self._fd
        os.fsync(fd)
"""


def test_lock_order_blocking_under_lock_fires_held_side_only():
    findings = lock_order.analyze([sf("kube_batch_tpu/x/j.py", D002_FIXTURE)])
    assert codes(findings) == ["KBT-D002"]
    assert findings[0].symbol == "J.bad.os.fsync"


def test_lock_order_condition_wait_on_held_lock_exempt():
    src = (
        "import threading\n"
        "class H:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "    def waiter(self):\n"
        "        with self._cond:\n"
        "            self._cond.wait()\n"
    )
    assert lock_order.analyze([sf("kube_batch_tpu/x/h.py", src)]) == []


def test_lock_order_interprocedural_charges_locked_caller():
    src = (
        "import threading, time\n"
        "class K:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            self._flush()\n"
        "    def _flush(self):\n"
        "        time.sleep(0.1)\n"
    )
    findings = lock_order.analyze([sf("kube_batch_tpu/x/k.py", src)])
    assert codes(findings) == ["KBT-D002"]
    assert findings[0].symbol == "K.outer.time.sleep"


def test_lock_order_crosses_collaborator_classes():
    src = (
        "import os, threading\n"
        "class Journal:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def write(self):\n"
        "        os.fsync(1)\n"
        "class Cache:\n"
        "    def __init__(self):\n"
        "        self._mutex = threading.Lock()\n"
        "        self._j = Journal()\n"
        "    def bind(self):\n"
        "        with self._mutex:\n"
        "            self._j.write()\n"
    )
    findings = lock_order.analyze([sf("kube_batch_tpu/x/c2.py", src)])
    assert codes(findings) == ["KBT-D002"]
    assert findings[0].symbol == "Cache.bind.os.fsync"
    assert "Journal.write" in findings[0].message


# -- runtime lock-order witness (dynamic half of KBT-D001) -------------------


def test_lock_order_witness_flags_abba_reversal():
    import threading

    from kube_batch_tpu.utils.locking import LockOrderWitness

    w = LockOrderWitness()
    la = w.wrap("A", threading.Lock())
    lb = w.wrap("B", threading.Lock())

    def a_then_b():
        with la:
            with lb:
                pass

    def b_then_a():
        with lb:
            with la:
                pass

    # sequential threads: both orders are observed without ever actually
    # deadlocking — exactly the latent ABBA the witness exists to catch
    for fn, name in ((a_then_b, "t-ab"), (b_then_a, "t-ba")):
        t = threading.Thread(target=fn, name=name)
        t.start()
        t.join()
    assert len(w.violations) == 1
    assert "t-ab" in w.violations[0] and "t-ba" in w.violations[0]
    with pytest.raises(AssertionError, match="reversal"):
        w.assert_clean()


def test_lock_order_witness_consistent_order_and_nonlifo_release_clean():
    import threading

    from kube_batch_tpu.utils.locking import LockOrderWitness

    w = LockOrderWitness()
    la = w.wrap("A", threading.Lock())
    lb = w.wrap("B", threading.Lock())
    for _ in range(3):
        with la:
            with lb:
                pass
    # non-LIFO release is legal for plain locks and must not corrupt the
    # held stack
    la.acquire()
    lb.acquire()
    la.release()
    lb.release()
    with la:
        with lb:
            pass
    assert w.violations == []
    w.assert_clean()


def test_lock_order_witness_reentrant_rlock_is_not_a_self_edge():
    import threading

    from kube_batch_tpu.utils.locking import LockOrderWitness

    w = LockOrderWitness()
    mu = w.wrap("M", threading.RLock())
    with mu:
        with mu:
            pass
    assert w.violations == []


@pytest.mark.chaos
def test_lock_order_witness_clean_on_live_bind_path(tmp_path):
    """Wrap the real cache/journal/store locks and drive a concurrent
    bind workload through the write pool: the dynamic acquisition graph
    must stay reversal-free (the static KBT-D001 sees the lexical graph;
    this is the dispatch-through-indirection half)."""
    import threading
    import time

    from kube_batch_tpu.cache import ClusterStore, SchedulerCache
    from kube_batch_tpu.recovery import WriteIntentJournal
    from kube_batch_tpu.testing import (
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
        build_resource_list,
    )
    from kube_batch_tpu.utils.locking import LockOrderWitness

    store = ClusterStore()
    store.create_queue(build_queue("default"))
    for i in range(4):
        store.create_node(
            build_node(f"n{i}", build_resource_list(cpu=16, memory="16Gi", pods=32))
        )
    for g in range(2):
        store.create_pod_group(build_pod_group(f"g{g}", min_member=8))
        for m in range(8):
            store.create_pod(
                build_pod(
                    name=f"g{g}-p{m}", group_name=f"g{g}",
                    req=build_resource_list(cpu=1, memory="256Mi"),
                )
            )
    journal = WriteIntentJournal(str(tmp_path / "j.wal"))
    cache = SchedulerCache(store, journal=journal)

    w = LockOrderWitness()
    cache._mutex = w.wrap("SchedulerCache._mutex", cache._mutex)
    journal._lock = w.wrap("WriteIntentJournal._lock", journal._lock)
    store._lock = w.wrap("ClusterStore._lock", store._lock)
    store._dispatch_lock = w.wrap("ClusterStore._dispatch_lock", store._dispatch_lock)

    cache.run()
    try:
        jobs = sorted(cache.jobs.values(), key=lambda j: j.name)
        assert len(jobs) == 2

        def bind_job(job, salt):
            for i, task in enumerate(sorted(job.tasks.values(), key=lambda t: t.uid)):
                cache.bind(task, f"n{(i + salt) % 4}")

        def read_side():
            for _ in range(20):
                store.list("pods")
                journal.outstanding()
                with cache._mutex:
                    len(cache.nodes)
                time.sleep(0.001)

        threads = [
            threading.Thread(target=bind_job, args=(jobs[0], 0), name="bind-0"),
            threading.Thread(target=bind_job, args=(jobs[1], 1), name="bind-1"),
            threading.Thread(target=read_side, name="reader"),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(p.node_name for p in store.list("pods")):
                break
            time.sleep(0.02)
        assert all(p.node_name for p in store.list("pods"))
    finally:
        cache.stop()
        journal.close()
    # the drive actually nested acquisitions (store event dispatch runs
    # the cache mirror handlers, so the witness saw real edges) and the
    # observed dynamic order has no reversal
    assert w._edges, "expected the bind workload to nest lock acquisitions"
    w.assert_clean()


# -- CLI ---------------------------------------------------------------------

def test_cli_json_and_exit_codes():
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--json"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert payload["suppressed"] > 0


def test_cli_explain():
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--explain", "KBT-L001"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert res.returncode == 0
    assert "guarded" in res.stdout


def test_cli_reasonless_baseline_entry_fails_the_gate(tmp_path):
    bad = tmp_path / "bl.toml"
    bad.write_text(
        "[[suppress]]\n"
        'code = "KBT-L001"\n'
        'path = "kube_batch_tpu/server.py"\n'
        'reason = ""\n'
    )
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--strict",
         "--baseline", str(bad)],
        cwd=REPO, capture_output=True, text=True,
    )
    assert res.returncode == 1
    assert "KBT-B001" in res.stdout


def test_cli_no_baseline_reports_known_intentional_findings():
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--no-baseline"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert res.returncode == 1
    assert "KBT-" in res.stdout


# -- --prune -----------------------------------------------------------------

COMMITTED_BASELINE = os.path.join(REPO, "hack", "lint-baseline.toml")


def _run_prune(bl_path, *extra):
    return subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--prune",
         "--baseline", str(bl_path), *extra],
        cwd=REPO, capture_output=True, text=True,
    )


def test_cli_prune_drops_stale_entries_preserving_the_rest(tmp_path):
    committed = open(COMMITTED_BASELINE, encoding="utf-8").read()
    bl = tmp_path / "bl.toml"
    bl.write_text(
        committed.rstrip("\n")
        + "\n\n[[suppress]]\n"
        + 'code = "KBT-L001"\n'
        + 'path = "kube_batch_tpu/does/not/exist.py"\n'
        + 'reason = "stale on purpose: the file is gone"\n'
    )
    res = _run_prune(bl)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "pruned: KBT-L001 at kube_batch_tpu/does/not/exist.py" in res.stdout
    assert "1 stale entry dropped" in res.stdout
    # live entries survive byte-for-byte: preamble, reasons, ordering
    assert bl.read_text() == committed


def test_cli_prune_noop_leaves_baseline_byte_identical(tmp_path):
    committed = open(COMMITTED_BASELINE, encoding="utf-8").read()
    bl = tmp_path / "bl.toml"
    bl.write_text(committed)
    res = _run_prune(bl)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 stale entries dropped" in res.stdout
    assert bl.read_text() == committed


def test_cli_prune_requires_a_baseline():
    res = subprocess.run(
        [sys.executable, "-m", "kube_batch_tpu.analysis", "--prune",
         "--no-baseline"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert res.returncode == 2


# -- A6: protocol lifecycles ---------------------------------------------------

C001_LEAKY = '''
from kube_batch_tpu.framework.session import close_session, open_session

def leaky(cache, tiers, args):
    ssn = open_session(cache, tiers, args)
    if not ssn.jobs:
        return None          # VIOLATION: ssn open on this exit path
    close_session(ssn)
    return True
'''

C001_CLEAN = '''
from kube_batch_tpu.framework.session import close_session, open_session

def clean(cache, tiers, args):
    ssn = open_session(cache, tiers, args)
    try:
        return len(ssn.jobs)
    finally:
        close_session(ssn)
'''

C001_STMT_LEAKY = '''
def bail_without_discard(ssn, tasks):
    stmt = ssn.statement()
    for t in tasks:
        if not t.ok:
            return False     # VIOLATION: neither commit nor discard
    stmt.commit()
    return True
'''

C001_STMT_CLEAN = '''
def settled_everywhere(ssn, tasks, helper):
    stmt = ssn.statement()
    for t in tasks:
        helper(ssn, stmt, t)  # borrow: passing by argument is not escape
        if not t.ok:
            stmt.discard()
            return False
    stmt.commit()
    return True
'''


def test_protocol_c001_session_leak_fires_and_clean_twin_does_not():
    findings = protocol.analyze([sf("kube_batch_tpu/x/leak.py", C001_LEAKY)])
    assert codes(findings) == ["KBT-C001"]
    assert "ssn" in findings[0].message
    assert protocol.analyze([sf("kube_batch_tpu/x/ok.py", C001_CLEAN)]) == []


def test_protocol_c001_statement_leak_fires_and_borrow_is_not_escape():
    findings = protocol.analyze([sf("kube_batch_tpu/x/stmt.py", C001_STMT_LEAKY)])
    assert codes(findings) == ["KBT-C001"]
    assert protocol.analyze([sf("kube_batch_tpu/x/ok.py", C001_STMT_CLEAN)]) == []


C002_DISPATCH = '''
def rogue(cache, task):
    cache.bind(task, "n1")
'''


def test_protocol_c002_dispatch_scope_is_the_statement_layer():
    findings = protocol.analyze([sf("kube_batch_tpu/plugins/rogue.py", C002_DISPATCH)])
    assert codes(findings) == ["KBT-C002"]
    # the same call inside an owning module is the implementation, not a bypass
    assert protocol.analyze(
        [sf("kube_batch_tpu/framework/statement.py", C002_DISPATCH)]
    ) == []


C002_BREAKER = '''
class Probe:
    def poke(self, breaker):
        breaker._transition("OPEN")
'''

C002_BREAKER_BAD_STATE = '''
class CircuitBreaker:
    def _step(self):
        self._transition("melted")
'''


def test_protocol_c002_breaker_transitions_stay_in_the_ladder():
    findings = protocol.analyze([sf("kube_batch_tpu/plugins/probe.py", C002_BREAKER)])
    assert codes(findings) == ["KBT-C002"]
    # inside the ladder with a declared state: fine
    ok = C002_BREAKER.replace("class Probe", "class CircuitBreaker").replace(
        '"OPEN"', '"open"'
    )
    assert protocol.analyze([sf("kube_batch_tpu/faults/ladder.py", ok)]) == []
    # inside the ladder but outside the declared alphabet: still flagged
    findings = protocol.analyze(
        [sf("kube_batch_tpu/faults/ladder.py", C002_BREAKER_BAD_STATE)]
    )
    assert codes(findings) == ["KBT-C002"]


C003_ORPHAN = '''
def orphan(journal, intents):
    journal.append_intents(intents)
    return None
'''

C003_PAIRED = '''
def paired(journal, cache, intents):
    seqs = journal.append_intents(intents)
    cache._submit_write(seqs)
    for s in seqs:
        journal.confirm(s)
'''

C003_CONFIRM_ONLY = '''
def confirm_strangers(journal, seqs):
    for s in seqs:
        journal.confirm(s)
'''


def test_protocol_c003_append_without_dispatch_or_confirm():
    findings = protocol.analyze([sf("kube_batch_tpu/x/j.py", C003_ORPHAN)])
    assert set(codes(findings)) == {"KBT-C003"}
    assert protocol.analyze([sf("kube_batch_tpu/x/ok.py", C003_PAIRED)]) == []


def test_protocol_c003_confirm_without_append_exempts_recovery():
    findings = protocol.analyze([sf("kube_batch_tpu/x/c.py", C003_CONFIRM_ONLY)])
    assert codes(findings) == ["KBT-C003"]
    # takeover legitimately confirms a dead leader's intents
    assert protocol.analyze(
        [sf("kube_batch_tpu/recovery/takeover_x.py", C003_CONFIRM_ONLY)]
    ) == []


C004_STALE_READ = '''
def stale(state, patches):
    state.invalidate("bound churn")
    state.apply_node_patches(patches)
'''

C004_REHARVESTED = '''
def reharvested(state, ssn, patches):
    state.invalidate("bound churn")
    state.adopt_full_cycle(ssn)
    state.apply_node_patches(patches)
'''


def test_protocol_c004_read_after_invalidate_needs_reharvest():
    findings = protocol.analyze([sf("kube_batch_tpu/x/s.py", C004_STALE_READ)])
    assert codes(findings) == ["KBT-C004"]
    assert protocol.analyze([sf("kube_batch_tpu/x/ok.py", C004_REHARVESTED)]) == []


C005_GAP = '''
def leaky_loop(trigger, stop, prepare, run):
    trigger.attach()
    prepare()
    try:
        run(stop)
    finally:
        trigger.detach()
'''

C005_TIGHT = '''
def tight_loop(trigger, stop, prepare, run):
    prepare()
    trigger.attach()
    try:
        run(stop)
    finally:
        trigger.detach()
'''

C005_CLASS_TEARDOWN = '''
class Consumer:
    def start(self):
        self.trigger.attach()

    def stop(self):
        self.trigger.detach()
'''


def test_protocol_c005_registration_gap_before_try_fires():
    findings = protocol.analyze([sf("kube_batch_tpu/x/loop.py", C005_GAP)])
    assert codes(findings) == ["KBT-C005"]
    assert protocol.analyze([sf("kube_batch_tpu/x/ok.py", C005_TIGHT)]) == []


def test_protocol_c005_class_teardown_pairing_is_clean():
    assert protocol.analyze(
        [sf("kube_batch_tpu/x/consumer.py", C005_CLASS_TEARDOWN)]
    ) == []

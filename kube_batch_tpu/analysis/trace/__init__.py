"""Trace-level program auditor: the KBT-P0xx code family.

The source-AST suite (``kube_batch_tpu.analysis``) sees what the code
*says*; this sibling sees what the compiler was *told*. It traces the
real solver entry points on abstract inputs — no FLOPs, no device
buffers, just jaxprs — and walks the resulting programs for the failure
modes that sink a warm scheduling loop but are invisible syntactically:

- **KBT-P001** — a host callback / transfer primitive inside a traced
  solver program (``pure_callback``, ``io_callback``, debug prints...),
  plus a runtime half: one warm cycle of the XLA twin is replayed under
  ``jax.transfer_guard("disallow")`` to catch implicit host->device
  transfers that only exist at run time (a numpy array smuggled in per
  cycle).
- **KBT-P002** — f64 avals appearing in a program whose inputs are all
  <= f32. Traced under scoped x64 (``testing.x64_enabled``) so
  the default-config dtype demotion cannot mask the leak; this is the
  trace-level closure of the syntactic KBT-J002.
- **KBT-P003** — large host constants captured into the program (the
  embedded 400k-row table footgun): any const above ``const_bytes``
  (default 1 MiB) rides every compile and lives in every executable.
- **KBT-P004** — donation declared but not honored: the arena's
  row-scatter declares ``donate_argnums`` so warm updates are in-place;
  if XLA cannot alias (shape/dtype mismatch, or a host array slipped
  in) it silently copies and device memory doubles. Detected by
  lowering+compiling the donated program and catching jax's
  "donated buffers were not usable" warning.
- **KBT-P005** — cross-tier program-signature drift: the XLA twin, the
  GSPMD sharded rung, and the blocked mesh-Pallas rung all speak the
  SolveState resume protocol; their output shapes+dtypes must be
  field-for-field identical or the action's pause/resume hybrid
  diverges structurally between tiers.

Entry points traced (mirroring ``actions/xla_allocate`` dispatch):
``ops.kernels`` fresh+resume (the XLA twin), ``parallel.sharded`` at
mesh {1,2,4,8}, ``parallel.sharded_pallas`` at mesh {1,2,4,8} (jnp
block backend — same program geometry as the mosaic one) plus its
K-deep batched-exchange variant at the largest mesh, the fused
``ops.pallas_solve`` program, and the encode-cache arena row-scatter
(donation checked for both ping-pong banks).

Findings flow through the same ``Finding``/baseline machinery as the
AST suite (own CLI: ``python -m kube_batch_tpu.analysis.trace``, own
baseline ``hack/trace-baseline.toml`` so the two gates never mark each
other's suppressions stale). The runtime sibling
(:mod:`kube_batch_tpu.analysis.trace.sentinel`) pins compile budgets
on the same entry points in tier-1.

jax is imported lazily inside functions — importing this module (e.g.
for the CLI's ``--explain``) stays cheap and device-free.
"""

from __future__ import annotations

import warnings
from typing import Optional

import numpy as np

from kube_batch_tpu.analysis import Finding

__all__ = [
    "CONST_BYTES_DEFAULT",
    "MESH_SIZES_DEFAULT",
    "build_snapshot",
    "check_callbacks",
    "check_donation",
    "check_f64",
    "check_large_consts",
    "check_signature_drift",
    "iter_eqns",
    "run_trace_audit",
    "state_signature",
]

CONST_BYTES_DEFAULT = 1 << 20  # 1 MiB of captured host data per program
MESH_SIZES_DEFAULT = (1, 2, 4, 8)

# Primitives that round-trip to the host from inside a traced program.
# Anything here inside the solve loop serializes the device pipeline on
# the python thread — the exact cost the always-warm loop exists to
# avoid.
_CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "callback",
        "outside_call",  # legacy host_callback
        "host_callback_call",
    }
)

# Entry-point anchor paths (repo-relative) for findings.
_PATHS = {
    "xla_twin": "kube_batch_tpu/ops/kernels.py",
    "sharded": "kube_batch_tpu/parallel/sharded.py",
    "mesh_pallas": "kube_batch_tpu/parallel/sharded_pallas.py",
    "mesh_pallas_batched": "kube_batch_tpu/parallel/sharded_pallas.py",
    "pallas_solve": "kube_batch_tpu/ops/pallas_solve.py",
    "arena_scatter": "kube_batch_tpu/ops/encode_cache.py",
}


# -- jaxpr plumbing ----------------------------------------------------------


def _inner_jaxprs(value):
    """Jaxpr objects hiding in one eqn param value (ClosedJaxpr, Jaxpr,
    or lists of either — cond branches, scan bodies, pjit calls)."""
    out = []
    vals = value if isinstance(value, (list, tuple)) else [value]
    for v in vals:
        inner = getattr(v, "jaxpr", v)  # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns"):
            out.append(v)
    return out


def iter_eqns(closed):
    """Every eqn in a ClosedJaxpr, recursing into sub-jaxprs (pjit
    bodies, while/cond/scan branches) — depth-first, deduplicated."""
    seen: set[int] = set()
    stack = [closed.jaxpr if hasattr(closed, "jaxpr") else closed]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            for param in eqn.params.values():
                for sub in _inner_jaxprs(param):
                    stack.append(getattr(sub, "jaxpr", sub))


def _all_consts(closed):
    """Constants captured anywhere in the program: the top ClosedJaxpr's
    consts plus every nested ClosedJaxpr's (pjit bodies carry their
    own)."""
    out = list(getattr(closed, "consts", ()))
    seen: set[int] = set()
    stack = [closed]
    while stack:
        c = stack.pop()
        j = getattr(c, "jaxpr", c)
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            for param in eqn.params.values():
                for sub in _inner_jaxprs(param):
                    if hasattr(sub, "consts"):
                        out.extend(sub.consts)
                    stack.append(sub)
    return out


def _avals_of(tree) -> list:
    import jax

    return [x for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "dtype")]


# -- the five checks (fixture tests call these directly on tiny jaxprs) ------


def check_callbacks(closed, entry: str, path: str) -> list[Finding]:
    """KBT-P001 (static half): callback primitives inside the program."""
    findings = []
    seen: set[str] = set()
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS and name not in seen:
            seen.add(name)
            findings.append(
                Finding(
                    path=path,
                    line=1,
                    code="KBT-P001",
                    message=(
                        f"traced program for {entry!r} contains host "
                        f"callback primitive '{name}' — every loop "
                        "iteration round-trips to python"
                    ),
                    symbol=f"{entry}.callback.{name}",
                )
            )
    return findings


def check_f64(closed, entry: str, path: str) -> list[Finding]:
    """KBT-P002: f64 values computed by a program whose inputs are all
    <= f32 (run on a trace taken under scoped x64, where nothing demotes
    the leak away)."""
    f64 = np.dtype(np.float64)
    for v in getattr(closed.jaxpr, "invars", ()):
        if getattr(v.aval, "dtype", None) == f64:
            return []  # deliberate f64 inputs: the whole program is f64
    for const in _all_consts(closed):
        if getattr(const, "dtype", None) == f64:
            return []
    hits: dict[str, int] = {}
    for eqn in iter_eqns(closed):
        for v in eqn.outvars:
            if getattr(v.aval, "dtype", None) == f64:
                hits[eqn.primitive.name] = hits.get(eqn.primitive.name, 0) + 1
    if not hits:
        return []
    prims = ", ".join(f"{k}×{n}" for k, n in sorted(hits.items()))
    return [
        Finding(
            path=path,
            line=1,
            code="KBT-P002",
            message=(
                f"traced program for {entry!r} upcasts to f64 with f32 "
                f"inputs ({prims}) — pin the dtype at the leak site "
                "(python float literals and default-dtype factories take "
                "the x64 default)"
            ),
            symbol=f"{entry}.f64",
        )
    ]


def check_large_consts(
    closed, entry: str, path: str, const_bytes: int = CONST_BYTES_DEFAULT
) -> list[Finding]:
    """KBT-P003: host constants baked into the program above the size
    threshold."""
    findings = []
    for const in _all_consts(closed):
        # sized from the array itself: jax's constant wrapper
        # (TypedNdArray) carries no ``nbytes`` of its own
        nbytes = np.asarray(const).nbytes
        if nbytes > const_bytes:
            shape = tuple(getattr(const, "shape", ()))
            dtype = getattr(const, "dtype", "?")
            findings.append(
                Finding(
                    path=path,
                    line=1,
                    code="KBT-P003",
                    message=(
                        f"traced program for {entry!r} captures a "
                        f"{nbytes >> 10} KiB host constant "
                        f"(shape {shape}, {dtype}) — pass it as an "
                        "argument so it is transferred once, not baked "
                        "into every compile"
                    ),
                    symbol=f"{entry}.const.{'x'.join(map(str, shape))}",
                )
            )
    return findings


def check_donation(fn, args, entry: str, path: str) -> list[Finding]:
    """KBT-P004: lower+compile a jit with declared donation and catch
    jax's 'donated buffers were not usable' warning. ``args`` are
    ShapeDtypeStructs (or concrete arrays), so nothing executes."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fn.lower(*args).compile()
    bad = [
        str(w.message)
        for w in caught
        if "donated buffers" in str(w.message).lower()
    ]
    if not bad:
        return []
    return [
        Finding(
            path=path,
            line=1,
            code="KBT-P004",
            message=(
                f"declared donation on {entry!r} is not honored "
                f"({bad[0].splitlines()[0]}) — XLA copies instead of "
                "aliasing and device memory for the buffer doubles"
            ),
            symbol=f"{entry}.donation",
        )
    ]


def state_signature(state) -> dict:
    """SolveState (of avals or arrays) -> {field: (shape, dtype)} for
    the cross-tier drift check."""
    sig = {}
    for field in state._fields:
        v = getattr(state, field)
        sig[field] = (tuple(np.shape(v)), str(np.asarray(v).dtype)
                      if not hasattr(v, "dtype") else str(v.dtype))
    return sig


def check_signature_drift(
    ref_sig: dict, sig: dict, ref_entry: str, entry: str, path: str
) -> list[Finding]:
    """KBT-P005: field-for-field shape+dtype equality of two tiers'
    SolveState outputs."""
    findings = []
    for field in sorted(set(ref_sig) | set(sig)):
        a, b = ref_sig.get(field), sig.get(field)
        if a != b:
            findings.append(
                Finding(
                    path=path,
                    line=1,
                    code="KBT-P005",
                    message=(
                        f"SolveState.{field} drifts between {ref_entry!r} "
                        f"{a} and {entry!r} {b} — the tiers no longer "
                        "speak the same resume protocol"
                    ),
                    symbol=f"{entry}.drift.{field}",
                )
            )
    return findings


# -- snapshot + entry-point registry -----------------------------------------


def build_snapshot(n_tasks: int = 64, n_nodes: int = 24) -> dict:
    """Encode a small seeded world into the exact solver input dict
    ``actions/xla_allocate`` builds: f32 arrays, nodeorder weight
    scalars folded in, host-only metadata dropped. The node bucket pads
    to 128, so every mesh size in {1,2,4,8} divides it."""
    from kube_batch_tpu import actions, plugins  # noqa: F401  (registries)
    from kube_batch_tpu.actions.xla_allocate import _nodeorder_weights
    from kube_batch_tpu.conf import parse_scheduler_conf
    from kube_batch_tpu.framework import close_session, open_session
    from kube_batch_tpu.models import multi_queue
    from kube_batch_tpu.ops.encode import encode_session
    from kube_batch_tpu.testing import FakeCache

    conf = parse_scheduler_conf(
        "tiers:\n"
        "- plugins:\n"
        "  - name: priority\n"
        "  - name: gang\n"
        "  - name: conformance\n"
        "- plugins:\n"
        "  - name: drf\n"
        "  - name: predicates\n"
        "  - name: proportion\n"
        "  - name: nodeorder\n"
    )
    ssn = open_session(FakeCache(multi_queue(n_tasks, n_nodes)), conf.tiers)
    try:
        enc = encode_session(
            ssn.jobs,
            ssn.nodes,
            ssn.queues,
            dtype=np.float32,
            drf=ssn.plugins.get("drf"),
            proportion=ssn.plugins.get("proportion"),
            session=ssn,
        )
        w_least, w_balanced, w_aff, w_podaff = _nodeorder_weights(ssn)
    finally:
        close_session(ssn)
    arrays = {k: np.asarray(v) for k, v in enc.arrays.items()}
    arrays.pop("task_created", None)  # host-only replay metadata
    arrays["w_least"] = np.float32(w_least)
    arrays["w_balanced"] = np.float32(w_balanced)
    arrays["w_aff"] = np.float32(w_aff)
    arrays["w_podaff"] = np.float32(w_podaff)
    return arrays


def _audit_capture(findings, closed, entry, path, const_bytes):
    findings += check_callbacks(closed, entry, path)
    findings += check_large_consts(closed, entry, path, const_bytes)


def run_trace_audit(
    mesh_sizes: tuple = MESH_SIZES_DEFAULT,
    const_bytes: int = CONST_BYTES_DEFAULT,
    transfer_check: bool = True,
) -> tuple[list[Finding], dict]:
    """Trace every entry point and run the P001–P005 checks.

    Returns ``(findings, info)``; ``info`` carries the audited entry
    list and per-entry jaxpr sizes for the CLI's ``--json``.
    """
    import jax

    from kube_batch_tpu.ops.kernels import _solve_fresh, _solve_resume
    from kube_batch_tpu.testing import x64_enabled

    arrays = build_snapshot()
    avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in arrays.items()}
    findings: list[Finding] = []
    sigs: dict[str, dict] = {}
    entries: dict[str, int] = {}

    def capture(entry, path, trace_fn, *t_args, x64: bool = False):
        closed = jax.make_jaxpr(trace_fn)(*t_args)
        entries[entry] = sum(1 for _ in iter_eqns(closed))
        _audit_capture(findings, closed, entry, path, const_bytes)
        if x64:
            # the sanctioned x64 flip (jax.experimental.enable_x64 is
            # deprecated; see testing.x64_enabled)
            with x64_enabled():
                closed64 = jax.make_jaxpr(trace_fn)(*t_args)
            findings.extend(check_f64(closed64, entry, path))
        return closed

    # 1. XLA twin (fresh + resume): the single-chip reference program.
    twin_fresh = lambda a: _solve_fresh(a, True, True)  # noqa: E731
    capture("xla_twin", _PATHS["xla_twin"], twin_fresh, avals, x64=True)
    st_avals = jax.eval_shape(twin_fresh, avals)
    sigs["xla_twin"] = state_signature(st_avals)
    capture(
        "xla_twin.resume",
        _PATHS["xla_twin"],
        lambda a, s: _solve_resume(a, s, True, True),
        avals,
        st_avals,
        x64=True,
    )

    # 2. GSPMD sharded rung per mesh size.
    from kube_batch_tpu.parallel.sharded import AXIS_NAME, _sharded_programs

    devices = tuple(jax.devices())
    usable = [m for m in mesh_sizes if m <= len(devices)]
    for m in usable:
        fresh, _resume = _sharded_programs(
            devices[:m], AXIS_NAME, frozenset(arrays), True, True
        )
        capture(f"sharded@{m}", _PATHS["sharded"], fresh, avals, x64=(m == usable[0]))
        sigs[f"sharded@{m}"] = state_signature(jax.eval_shape(fresh, avals))

    # 3. Blocked mesh-Pallas rung per mesh size (jnp block backend: same
    # fold geometry and output protocol as the mosaic kernel, traceable
    # off-TPU).
    from kube_batch_tpu.parallel.sharded import make_mesh
    from kube_batch_tpu.parallel.sharded_pallas import ShardedPallasSolver

    for m in usable:
        sp = ShardedPallasSolver(arrays, make_mesh(m), True, True, block_impl="jnp")
        a_call = dict(sp.a)
        a_call["_tports"] = sp._tports
        a_avals = {
            k: jax.ShapeDtypeStruct(np.asarray(v).shape, np.asarray(v).dtype)
            for k, v in a_call.items()
        }
        s_avals = {
            k: jax.ShapeDtypeStruct(np.asarray(v).shape, np.asarray(v).dtype)
            for k, v in sp._statics.items()
        }
        capture(
            f"mesh_pallas@{m}",
            _PATHS["mesh_pallas"],
            sp._fresh,
            a_avals,
            s_avals,
            x64=(m == usable[0]),
        )
        sigs[f"mesh_pallas@{m}"] = state_signature(
            jax.eval_shape(sp._fresh, a_avals, s_avals)
        )

    # 3b. The K-deep batched-exchange program (KBT_EXCHANGE_BATCH under
    # KBT_PIPELINE): same SPMD geometry, but the gang loop speculates K
    # iterations per shard and ships one [K, record] all-gather per
    # round. Audited at the largest usable mesh — the size the batching
    # exists for. The program returns (SolveState, n_batched); the
    # drift check pins the state element field-for-field against the
    # twin, so the batched rung cannot fork the resume protocol.
    mb = usable[-1]
    spb = ShardedPallasSolver(
        arrays, make_mesh(mb), True, True, block_impl="jnp", exchange_batch=4
    )
    ab_call = dict(spb.a)
    ab_call["_tports"] = spb._tports
    ab_avals = {
        k: jax.ShapeDtypeStruct(np.asarray(v).shape, np.asarray(v).dtype)
        for k, v in ab_call.items()
    }
    sb_avals = {
        k: jax.ShapeDtypeStruct(np.asarray(v).shape, np.asarray(v).dtype)
        for k, v in spb._statics.items()
    }
    capture(
        f"mesh_pallas_batched@{mb}",
        _PATHS["mesh_pallas_batched"],
        spb._fresh,
        ab_avals,
        sb_avals,
    )
    sigs[f"mesh_pallas_batched@{mb}"] = state_signature(
        jax.eval_shape(spb._fresh, ab_avals, sb_avals)[0]
    )

    # 4. Fused single-chip Pallas program (interpret build traces the
    # same jaxpr structure the mosaic build lowers).
    from kube_batch_tpu.ops.pallas_solve import PallasSolver

    ps = PallasSolver(arrays, True, True, interpret=True)
    t_args = tuple(
        jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)
        for x in ps.trace_args(None)
    )
    capture("pallas_solve", _PATHS["pallas_solve"], ps.fn, *t_args, x64=True)

    # 5. Arena row-scatter: the donated warm-update program.
    from kube_batch_tpu.ops.encode_cache import _scatter_jit

    scatter = _scatter_jit()
    buf = jax.ShapeDtypeStruct(arrays["node_idle"].shape, arrays["node_idle"].dtype)
    idx = jax.ShapeDtypeStruct((4,), np.int64)
    vals = jax.ShapeDtypeStruct((4,) + arrays["node_idle"].shape[1:],
                                arrays["node_idle"].dtype)
    capture("arena_scatter", _PATHS["arena_scatter"],
            lambda b, i, v: scatter(b, i, v), buf, idx, vals, x64=True)
    findings.extend(
        check_donation(scatter, (buf, idx, vals), "arena_scatter",
                       _PATHS["arena_scatter"])
    )
    # 5b. Pipelined mode ping-pongs the same donated scatter across two
    # live device slab sets (encode_cache bank 0/1); donation must hold
    # with a second live buffer in flight too, or double-buffering
    # silently copies and the arena's device footprint doubles per bank.
    findings.extend(
        check_donation(scatter, (buf, idx, vals), "arena_scatter.pingpong",
                       _PATHS["arena_scatter"])
    )
    entries.setdefault("arena_scatter.pingpong", entries["arena_scatter"])

    # 6. Cross-tier signature drift vs the twin.
    for entry, sig in sigs.items():
        if entry == "xla_twin":
            continue
        base = entry.split("@")[0]
        findings.extend(
            check_signature_drift(
                sigs["xla_twin"], sig, "xla_twin", entry,
                _PATHS.get(base, _PATHS["xla_twin"]),
            )
        )

    # 7. Runtime half of P001: one compiled warm cycle of the twin with
    # device-resident inputs must perform no implicit transfers.
    if transfer_check:
        dev = jax.device_put(arrays)
        jax.block_until_ready(twin_fresh(dev))  # compile + warm
        try:
            with jax.transfer_guard("disallow"):
                jax.block_until_ready(twin_fresh(dev))
        except Exception as e:  # noqa: BLE001 -- guard raises host-specific types
            findings.append(
                Finding(
                    path=_PATHS["xla_twin"],
                    line=1,
                    code="KBT-P001",
                    message=(
                        "warm cycle of the XLA twin performs an implicit "
                        f"host transfer under transfer_guard: {e}"
                    ),
                    symbol="xla_twin.transfer_guard",
                )
            )

    findings.sort(key=lambda f: (f.path, f.line, f.code, f.symbol))
    info = {
        "entries": entries,
        "mesh_sizes": usable,
        "snapshot": {
            k: (list(v.shape), str(v.dtype)) for k, v in sorted(arrays.items())
        },
    }
    return findings, info

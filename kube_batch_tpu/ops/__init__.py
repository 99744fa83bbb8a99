"""kube_batch_tpu.ops: the TPU compute path.

The reference schedules serially — per task, a 16-goroutine scan over all
nodes for predicates and priorities (reference
pkg/scheduler/util/scheduler_helper.go:34-109) inside the allocate loop
(actions/allocate/allocate.go:94-190). Here the same cycle is one XLA
program: the cluster snapshot is encoded as struct-of-arrays tensors
(`encode`), and a jitted `lax.while_loop` performs the full
queue/job/task-ordered, gang-aware assignment with every per-node scan
vectorized (`kernels`). The serial actions remain the correctness oracle;
property tests pin serial ≡ XLA assignment-for-assignment.
"""

import os as _os


# Default persistent compile cache: a fixed directory inside the
# checkout (gitignored). JAX keys its entries on the path, so the path
# must not move between runs.
_CHECKOUT_CACHE = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str | None:
    """Persistent XLA compilation cache: the solve recompiles only when a
    padding bucket changes shape, but a fresh process (server restart,
    chip smoke, failover standby taking over) pays each bucket's compile
    again without one. Returns the directory in use, or None when off.

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
      sets nothing.
    - otherwise ``<checkout>/.jax_cache/``;
    - ``KBT_JAX_CACHE=0`` turns the cache off.

    Called by the scheduler entry points (Scheduler init, chip_smoke.py,
    the graft entry) — deliberately NOT at import, so an embedding
    application that configures jax itself keeps full control no matter
    the import order; it defers to any cache dir already set."""
    env_dir = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if _os.environ.get("KBT_JAX_CACHE", "") == "0":
        return None
    import jax

    app_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    if app_dir:  # an embedding application's own configuration
        return app_dir
    try:
        _os.makedirs(_CHECKOUT_CACHE, exist_ok=True)
    except OSError as e:  # read-only checkout: run uncached, say so
        import logging

        logging.getLogger("kube_batch_tpu.ops").warning(
            "persistent jax compilation cache off: %s (set "
            "JAX_COMPILATION_CACHE_DIR to a writable directory)", e
        )
        return None
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    # persist any compile costing >= 0.5 s (the solve's bucket compiles
    # are seconds; sub-0.5s programs stay uncached — not worth the disk
    # churn) regardless of program size
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return _CHECKOUT_CACHE


from kube_batch_tpu.ops.encode import EncodedSnapshot, encode_session  # noqa: E402
from kube_batch_tpu.ops.kernels import solve_allocate  # noqa: E402

__all__ = [
    "EncodedSnapshot",
    "encode_session",
    "enable_compilation_cache",
    "solve_allocate",
]

"""Checkers: the TPU-resident analysis plane.

Layer L6 of the blueprint (SURVEY.md §1): pure functions from histories
to verdict maps. The linearizability engine (linearizable.py + wgl_jax.py)
is the knossos replacement — the framework's north star.

Re-exports resolve lazily (PEP 562): importing a host-only submodule
(wgl_oracle, wgl_native, events, models) must not drag in the jax-backed
engines — spawned bounded-pmap oracle workers and jax-free CLI paths
depend on the import chain staying clean of JAX.
"""

_EXPORTS = {
    "Checker": "jepsen_tpu.checker.core",
    "ComposeChecker": "jepsen_tpu.checker.core",
    "ConcurrencyLimitChecker": "jepsen_tpu.checker.core",
    "FnChecker": "jepsen_tpu.checker.core",
    "NoopChecker": "jepsen_tpu.checker.core",
    "UNKNOWN": "jepsen_tpu.checker.core",
    "check_safe": "jepsen_tpu.checker.core",
    "compose": "jepsen_tpu.checker.core",
    "concurrency_limit": "jepsen_tpu.checker.core",
    "merge_valid": "jepsen_tpu.checker.core",
    "CheckFuture": "jepsen_tpu.checker.dispatch",
    "DispatchPlane": "jepsen_tpu.checker.dispatch",
    "default_plane": "jepsen_tpu.checker.dispatch",
    "dispatch_stats": "jepsen_tpu.checker.dispatch",
    "reset_dispatch_stats": "jepsen_tpu.checker.dispatch",
    "LinearizableChecker": "jepsen_tpu.checker.linearizable",
    "check_events_bucketed": "jepsen_tpu.checker.linearizable",
    "linearizable": "jepsen_tpu.checker.linearizable",
    "EventStream": "jepsen_tpu.checker.events",
    "history_to_events": "jepsen_tpu.checker.events",
    "MODELS": "jepsen_tpu.checker.models",
    "Model": "jepsen_tpu.checker.models",
    "model": "jepsen_tpu.checker.models",
    "CounterChecker": "jepsen_tpu.checker.reductions",
    "QueueChecker": "jepsen_tpu.checker.reductions",
    "SetChecker": "jepsen_tpu.checker.reductions",
    "SetFullChecker": "jepsen_tpu.checker.reductions",
    "TotalQueueChecker": "jepsen_tpu.checker.reductions",
    "UniqueIdsChecker": "jepsen_tpu.checker.reductions",
    "counter": "jepsen_tpu.checker.reductions",
    "queue": "jepsen_tpu.checker.reductions",
    "set_checker": "jepsen_tpu.checker.reductions",
    "set_full": "jepsen_tpu.checker.reductions",
    "total_queue": "jepsen_tpu.checker.reductions",
    "unique_ids": "jepsen_tpu.checker.reductions",
    "TxnGraphChecker": "jepsen_tpu.checker.txn_graph",
    "fold_txn_graph": "jepsen_tpu.checker.txn_graph",
    "txn_graph_checker": "jepsen_tpu.checker.txn_graph",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        )
    import importlib

    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value  # cache: resolve once per process
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))

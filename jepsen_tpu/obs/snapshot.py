"""The ONE consolidated engine-state reader.

Before PR 12 three surfaces each hand-assembled their own view of the
engine's counters — ``cli._engine_stats`` (launch/checkpoint/streaming
only), the daemon's ``/stats`` (dispatch only), and the dryrun metric
line (raw dict reads) — and every new ``*_STATS`` dict meant three
edits, usually forgotten in at least one. ``engine_snapshot()`` is now
the single reader all three import; every section below shows up
uniformly in the CLI stats bundle, the daemon JSON, and the metric
line.

This module imports the jax-backed checker modules, so the ``obs``
package root deliberately does NOT import it (the checker modules
import ``obs.trace`` for emission — a root-level import here would
close that cycle). Consumers import ``jepsen_tpu.obs.snapshot``
explicitly.
"""

from __future__ import annotations

from jepsen_tpu.obs import trace as _trace


def engine_snapshot() -> dict:
    """Point-in-time, lock-consistent-per-section copy of every engine
    counter surface plus the flight recorder's own stats.

    Sections (each a plain JSON-able dict):

    - ``dispatch``:  coalescing-plane stats incl. derived ratios
      (``floor_amortization``, ``double_buffer_occupancy``)
    - ``launch``:    device-launch accounting (launches, host_syncs,
      escalations, donated_buffers)
    - ``mesh``:      shard_map engagement + mesh-side resilience view,
      plus the pod ``topology`` block (hosts, local vs. global
      devices, backend)
    - ``resilience``: chaos-layer retries/quarantines/breakers
    - ``checkpoint``: save/resume/replay/invalidation accounting
    - ``streaming``: incremental-tail appends and tail launches
    - ``txn_graph``: transactional dependency-graph pipeline counters
    - ``trace``:     flight-recorder meta (enabled, event counts)
    - ``perf``:      the self-tuning perf plane's disclosure — the
      resolved knob ``config_hash``, whether a persisted tuned
      profile is active, and where it was loaded from
    - ``device``:    what JAX runs on (``device_info``), so a CPU run
      is never read as a chip run
    """
    from jepsen_tpu.checker import chaos, checkpoint, dispatch, sharded
    from jepsen_tpu.checker import streaming, txn_graph
    from jepsen_tpu.checker import wgl_bitset as bs
    from jepsen_tpu.perf import knobs as perf_knobs

    return {
        "dispatch": dispatch.dispatch_stats(),
        "launch": bs.launch_stats_snapshot(),
        "mesh": sharded.mesh_stats_snapshot(),
        "resilience": chaos.resilience_snapshot(),
        "checkpoint": checkpoint.checkpoint_stats(),
        "streaming": streaming.stream_stats(),
        "txn_graph": txn_graph.txn_graph_stats(),
        "trace": _trace.trace_stats(),
        "perf": perf_knobs.perf_snapshot(),
        "device": device_info(),
    }


def device_info() -> dict:
    """The platform, ``device_kind`` and device count JAX runs on, and
    the JAX version: the device every number of this process names."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
    }


def reset_engine_stats() -> None:
    """Zero every counter surface the snapshot reads (CLI runs reset
    before each analysis so per-run numbers are per-run)."""
    from jepsen_tpu.checker import checkpoint, dispatch, sharded
    from jepsen_tpu.checker import streaming, txn_graph
    from jepsen_tpu.checker import wgl_bitset as bs
    from jepsen_tpu.checker.chaos import reset_resilience

    dispatch.reset_dispatch_stats()
    bs.reset_launch_stats()
    sharded.reset_mesh_stats()
    reset_resilience()
    checkpoint.reset_checkpoint_stats()
    streaming.reset_stream_stats()
    txn_graph.reset_txn_graph_stats()
    _trace.reset()

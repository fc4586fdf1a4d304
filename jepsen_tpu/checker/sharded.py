"""Multi-device sharded checking: the analysis-plane collective layer.

The reference parallelizes per-key sub-checks with bounded thread pools
on the control node (jepsen/src/jepsen/independent.clj:266-288,
checker.clj:90-119). Here the same independence structure maps onto the
hardware: per-key return-step tensors are stacked into [n_keys, n, W]
arrays, `vmap` batches the WGL frontier scan across keys, and
`shard_map` over a device mesh (1-D, or multi-axis like hosts x chips
for DCN x ICI layouts) splits the key axis across TPU chips
so each device checks its shard over ICI-local memory. No collectives
are needed during the scan — keys are independent by construction; the
verdict gather is implicit in shard_map's output spec.

This is the path dryrun_multichip exercises, and the engine behind
multi-key workloads (zookeeper 10k x 16 keys in BASELINE.md).
"""

from __future__ import annotations

import functools
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jepsen_tpu.checker.events import EventStream, events_to_steps
from jepsen_tpu.checker.linearizable import (
    K_LADDER,
    _bucket_events,
    _bucket_window,
    check_events_bucketed,
)
from jepsen_tpu.checker.wgl_jax import wgl_scan_steps

from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_streams(
    streams: Sequence[EventStream],
    W: int,
    n_keys: Optional[int] = None,
    model: str = "cas-register",
) -> Tuple[np.ndarray, ...]:
    """Precompile per-key event streams and stack into padded arrays:
    (occ [n_keys,n,W], f, a, b, slot [n_keys,n], live, init_state
    [n_keys]). Missing keys (n_keys > len(streams)) become all-padding
    rows — trivially valid."""
    if not streams:
        raise ValueError("no event streams")
    steps = [events_to_steps(s, W=W) for s in streams]
    n = _bucket_events(max(max(len(st) for st in steps), 1))
    steps = [st.padded(n) for st in steps]
    k = n_keys or len(steps)
    if k < len(steps):
        raise ValueError(f"n_keys {k} < {len(steps)} streams")
    while len(steps) < k:
        blank = steps[0]
        steps.append(
            type(blank)(
                occ=np.zeros_like(blank.occ),
                f=np.zeros_like(blank.f),
                a=np.zeros_like(blank.a),
                b=np.zeros_like(blank.b),
                slot=np.zeros_like(blank.slot),
                live=np.zeros_like(blank.live),
                crashed=np.zeros_like(blank.crashed),
                op_index=np.full_like(blank.op_index, -1),
                init_state=-1,
                W=W,
            )
        )
    occ = np.stack([st.occ for st in steps])
    f = np.stack([st.f for st in steps])
    a = np.stack([st.a for st in steps])
    b = np.stack([st.b for st in steps])
    slot = np.stack([st.slot for st in steps])
    live = np.stack([st.live for st in steps])
    crashed = np.stack([st.crashed for st in steps])
    op_index = np.stack([st.op_index for st in steps])
    from jepsen_tpu.checker.models import model as get_model

    kic = get_model(model).kernel_init_code
    init_state = np.asarray(
        [kic(st.init_state) for st in steps], np.int32
    )
    return occ, f, a, b, slot, live, crashed, op_index, init_state


#: number of stacked per-key arrays fed to the kernel
N_COLS = 9


def _vmap_scan(
    occ, f, a, b, slot, live, crashed, op_index, init_state, model_name, K, W
):
    """Unjitted key-axis batch of the frontier scan — the shared body of
    both the single-device vmap path and the shard_map per-shard path."""
    return jax.vmap(
        lambda o, ff, aa, bb, s, l, c, oi, i: wgl_scan_steps(
            o, ff, aa, bb, s, l, c, oi, i, model_name, K, W
        )
    )(occ, f, a, b, slot, live, crashed, op_index, init_state)


_wgl_vmap = functools.partial(
    jax.jit, static_argnames=("model_name", "K", "W")
)(_vmap_scan)


def key_spec(mesh: Mesh) -> P:
    """The one key-axis sharding: keys split across EVERY mesh axis (a
    multi-axis mesh — e.g. ("hosts", "chips") for DCN x ICI — shards
    keys over the full device product; keys are independent, so the
    layout needs no collectives either way). Both the shard_map
    in_specs and the input device_put MUST use this."""
    return P(tuple(mesh.axis_names))


#: mesh-path accounting: "sharded_launches" counts shard_map dispatches
#: (bitset or vmap tier), "last_n_devices" the device count of the most
#: recent one. dryrun_multichip and bench's one-device guard read these
#: to prove the mesh path actually engaged — MULTICHIP_r03-r05 exited 0
#: with an empty tail, so a silent fallback to one device must be loud.
#: "resilience" is the mesh's view of the chaos layer: devices ejected
#: by quarantine and launches that re-sharded onto the survivors.
MESH_STATS = {
    "sharded_launches": 0,
    "last_n_devices": 0,
    "resilience": {"quarantined_devices": [], "resharded_launches": 0},
}

_mesh_stats_lock = threading.Lock()


def note_sharded_launch(n_devices: int) -> None:
    with _mesh_stats_lock:
        MESH_STATS["sharded_launches"] += 1
        MESH_STATS["last_n_devices"] = int(n_devices)


def note_quarantine(label: str) -> None:
    """Record a device ejection in the mesh's resilience block."""
    with _mesh_stats_lock:
        q = MESH_STATS["resilience"]["quarantined_devices"]
        if label not in q:
            q.append(label)


def note_reshard() -> None:
    """Record one launch that re-sharded onto surviving devices."""
    with _mesh_stats_lock:
        MESH_STATS["resilience"]["resharded_launches"] += 1


def reset_mesh_stats() -> None:
    with _mesh_stats_lock:
        MESH_STATS["sharded_launches"] = 0
        MESH_STATS["last_n_devices"] = 0
        MESH_STATS["resilience"] = {
            "quarantined_devices": [], "resharded_launches": 0,
        }


def mesh_stats_snapshot() -> dict:
    """Locked copy of MESH_STATS (the resilience block holds a mutable
    list, so a shallow copy would alias it), plus the pod topology
    block (hosts / local vs. global devices / backend) — fetched
    OUTSIDE the lock, since it may query live jax state."""
    from jepsen_tpu.pod.topology import topology_snapshot

    topo = topology_snapshot()
    with _mesh_stats_lock:
        res = MESH_STATS["resilience"]
        return {
            "sharded_launches": MESH_STATS["sharded_launches"],
            "last_n_devices": MESH_STATS["last_n_devices"],
            "resilience": {
                "quarantined_devices": list(res["quarantined_devices"]),
                "resharded_launches": res["resharded_launches"],
            },
            "topology": topo,
        }


def mesh_size(mesh: Mesh) -> int:
    """Device count of a mesh = product over every axis (keys shard
    over the full product; see key_spec)."""
    return int(np.prod([mesh.shape[ax] for ax in mesh.axis_names]))


@functools.lru_cache(maxsize=None)
def _mesh_over(devices: tuple) -> Mesh:
    return Mesh(np.asarray(devices), axis_names=("keys",))


@functools.lru_cache(maxsize=None)
def _pod_mesh_over(rows: tuple) -> Mesh:
    """The global hosts x chips mesh: one row per host (process), one
    column per chip of that host — the DCN x ICI layout sharded
    checking has carried as a virtual axis pair since PR 3, now backed
    by real process boundaries."""
    arr = np.asarray([list(r) for r in rows], dtype=object)
    return Mesh(arr, axis_names=("hosts", "chips"))


#: the CLI's mesh-policy seam (set_mesh_policy): an explicit device
#: cap and/or backend for the ambient mesh, so mesh shape is reachable
#: from `analyze`/`daemon`/bench flags — not only the conftest
#: JEPSEN_TPU_HOST_DEVICES env seam.
_MESH_POLICY = {"devices": None, "backend": None}


def set_mesh_policy(devices: Optional[int] = None,
                    backend: Optional[str] = None) -> None:
    """Pin the ambient mesh selection: ``devices`` caps the auto mesh
    at N devices (1 forces the single-device path), ``backend``
    selects which platform's devices it spans (cpu/gpu/tpu). None
    clears the respective pin. Mesh builders are cached by device
    tuple, so changing policy mid-process is safe."""
    _MESH_POLICY["devices"] = int(devices) if devices else None
    _MESH_POLICY["backend"] = backend or None


def mesh_policy() -> dict:
    return dict(_MESH_POLICY)


def _healthy_devices() -> list:
    """Visible devices minus quarantine ejections — per-chip labels
    AND host-domain rows (a device whose owning process is quarantined
    is dead even if its own label never accumulated evidence) — under
    the CLI mesh policy's backend/device-count pins."""
    from jepsen_tpu.checker.chaos import HOST_PREFIX, is_quarantined

    backend = _MESH_POLICY["backend"]
    base = jax.devices(backend) if backend else jax.devices()
    devs = [
        d for d in base
        if not is_quarantined(str(d))
        and not is_quarantined(
            f"{HOST_PREFIX}{getattr(d, 'process_index', 0)}"
        )
    ]
    cap = _MESH_POLICY["devices"]
    if cap:
        devs = devs[:cap]
    return devs


def default_mesh() -> Optional[Mesh]:
    """The ambient execution mesh: a Mesh over every visible HEALTHY
    device when more than one is visible, else None. check_keys and
    the dispatch plane consult this when the caller passes mesh=None,
    so multi-chip hosts (and the tests' virtual 8-device CPU mesh) go
    sharded by default while a single-device host keeps the exact
    byte-identical single-device dispatch. Devices ejected by the
    resilience layer's quarantine (checker.chaos) are excluded — a
    fresh auto-mesh re-shards onto the survivors.

    In a pod (jax.process_count() > 1) the mesh generalizes to the
    global hosts x chips layout: one "hosts" row per process, chips
    within. Quarantine can leave hosts ragged (different survivor
    counts per row); the mesh then falls back to 1-D over the global
    survivors — keys shard over the full product either way
    (key_spec), so verdicts are layout-independent."""
    devs = _healthy_devices()
    if len(devs) < 2:
        return None
    by_host: dict = {}
    for d in devs:
        by_host.setdefault(
            int(getattr(d, "process_index", 0)), []
        ).append(d)
    if len(by_host) > 1:
        rows = [tuple(by_host[h]) for h in sorted(by_host)]
        if len({len(r) for r in rows}) == 1:
            return _pod_mesh_over(tuple(rows))
    return _mesh_over(tuple(devs))


def mesh_without(mesh: Optional[Mesh], labels) -> Optional[Mesh]:
    """Re-shard a mesh onto the devices NOT in ``labels`` (the
    quarantine ejection path): survivors rebuild as a 1-D mesh — the
    batch pad (launch_keys_bitset's blank rows / stack_streams'
    padding rows) absorbs the new uneven key split exactly like any
    other non-multiple batch. ``host:<i>`` labels eject that host's
    WHOLE device slice (pod.faultdomains expands them against this
    mesh — real process slices in a pod, rows of a "hosts" axis on a
    virtual one). Fewer than 2 survivors collapses to None (the
    single-device path). A mesh with nothing to eject passes through
    unchanged (same object, so lru-cached wrappers still hit)."""
    if mesh is None:
        return None
    from jepsen_tpu.pod.faultdomains import expand_host_labels

    dead = expand_host_labels(mesh, labels)
    devs = list(mesh.devices.flat)
    survivors = tuple(d for d in devs if str(d) not in dead)
    if len(survivors) == len(devs):
        return mesh
    if len(survivors) < 2:
        return None
    return _mesh_over(survivors)


def resolve_mesh(mesh) -> Optional[Mesh]:
    """The one mesh-selection rule: None -> auto (default_mesh),
    False -> force the single-device path, a Mesh passes through."""
    if mesh is None:
        return default_mesh()
    if mesh is False:
        return None
    return mesh


@functools.lru_cache(maxsize=None)
def residency_supported() -> bool:
    """Whether buffer donation actually aliases on this backend.

    The resident frontier path donates the input frontier buffer so a
    segment chain's output can reuse it in place (`donate_argnums` on
    the chain scan). XLA:CPU ignores donation and warns about every
    unused donated buffer, so on the CPU backend (tier-1, interpret
    mode) the engine keeps the non-donating twin — same chain, same one
    host sync, no warning spam. TPU and GPU honor input-output
    aliasing. Cached: the backend cannot change mid-process."""
    try:
        return jax.default_backend() in ("tpu", "gpu")
    except Exception:  # backend probe failed: stay conservative
        return False


@functools.lru_cache(maxsize=None)
def make_sharded_bitset(
    mesh: Mesh, model_name: str, S: int, W: int,
    interpret: bool, exact: bool,
):
    """Build (and cache) the shard_map wrapper around the stacked
    bitset batch (wgl_bitset._bitset_scan): a coalesced bucket of B
    keys runs B/n_devices per chip — one launch, one sync, all chips.
    Keys are independent, so the per-shard scan is collective-free;
    in/out specs both use key_spec, exactly like the vmap checker.
    The MULTICHIP_r02 crash class (element_type_p.bind under
    shard_map) is pinned by the tier-1 CPU-mesh differential."""
    from jepsen_tpu.checker import wgl_bitset as bs

    spec = key_spec(mesh)

    def per_shard(win, meta, fr0):
        return bs._bitset_scan(
            win, meta, fr0, model_name=model_name, S=S, W=W,
            interpret=interpret, exact=exact,
        )

    sharded = _shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=(spec, spec),
        check_vma=False,
    )
    return jax.jit(sharded)


@functools.lru_cache(maxsize=None)
def make_sharded_checker(mesh: Mesh, model_name: str, K: int, W: int):
    """Build (and cache) a jit'd function checking stacked key columns
    with the key axis sharded per key_spec."""
    spec = key_spec(mesh)

    def per_shard(occ, f, a, b, slot, live, crashed, op_index, init_state):
        return _vmap_scan(
            occ, f, a, b, slot, live, crashed, op_index, init_state,
            model_name, K, W,
        )

    # check_vma statically verifies collective usage; the per-shard
    # scan is collective-free, and its data-dependent while_loop carries
    # mix constants with sharded data in ways the checker can't type.
    sharded = _shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec,) * N_COLS,
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    return jax.jit(sharded)


def check_keys(
    streams: Sequence[EventStream],
    model: str = "cas-register",
    mesh=None,
    k_ladder=K_LADDER,
    interpret: bool = False,
) -> List[dict]:
    """Check many independent per-key event streams at once.

    mesh selects the execution layout: ``None`` (the default) takes a
    mesh over ALL visible devices whenever more than one is visible
    (default_mesh), ``False`` forces the single-device path, and an
    explicit ``jax.sharding.Mesh`` is used as given. With a mesh, keys
    shard across devices (padded to a multiple of the mesh size) —
    the bitset batch itself shard_maps (make_sharded_bitset), so the
    default path stays the exact bitset batch: one kernel launch, one
    host sync for ALL keys on ALL chips (the independent.clj:266-288
    role on device — zookeeper-10kx16 pays the sync floor once, not
    16 times, and B/n_devices keys scan per chip). Keys outside the
    bitset envelope ride the megakernel batch / sharded-vmap ladder.
    Keys whose False verdict is tainted by frontier overflow re-check
    individually through the escalation ladder / oracle.

    interpret runs the bitset batch in Pallas interpret mode on CPU —
    the tests' seam for pinning the one-launch contract without a TPU.
    """
    n_real = len(streams)
    if n_real == 0:
        return []
    mesh = resolve_mesh(mesh)
    from jepsen_tpu.checker.models import model as get_model

    m = get_model(model)
    if not m.jax_capable:
        in_env = (
            [m.packed_ok(s) for s in streams]
            if m.packed_variant and m.packed_ok is not None
            else [False] * n_real
        )
        if all(in_env):
            # Word-sized bounded encoding: the whole batch rides the
            # kernels under the packed variant.
            model = m.packed_variant
            m = get_model(model)
        elif any(in_env):
            # Mixed batch: in-envelope keys keep the kernel path; only
            # the offenders detour to the host oracle.
            from jepsen_tpu.checker.wgl_oracle import check_streams

            ok_idx = [i for i, e in enumerate(in_env) if e]
            bad_idx = [i for i, e in enumerate(in_env) if not e]
            kernel_res = check_keys(
                [streams[i] for i in ok_idx],
                model=m.packed_variant,
                # mesh is resolved: pass False (not None) when it
                # resolved to single-device, or auto-detection would
                # re-engage in the recursion.
                mesh=mesh if mesh is not None else False,
                k_ladder=k_ladder,
                interpret=interpret,
            )
            verdicts, meta = check_streams(
                [streams[i] for i in bad_idx], model=model
            )
            merged: List[Optional[dict]] = [None] * n_real
            for i, r in zip(ok_idx, kernel_res):
                merged[i] = r
            for i, v, rung in zip(bad_idx, verdicts, meta["rungs"]):
                merged[i] = {
                    "valid?": v, "method": f"cpu-oracle-{rung}",
                }
            return merged  # type: ignore[return-value]
        else:
            from jepsen_tpu.checker.wgl_oracle import check_streams

            verdicts, meta = check_streams(streams, model=model)
            return [
                {"valid?": v, "method": f"cpu-oracle-{rung}"}
                for v, rung in zip(verdicts, meta["rungs"])
            ]
    window = max(max(s.window for s in streams), 1)
    W = _bucket_window(window)
    if W is None:
        # Too concurrent for the kernel: oracle everything, fanned out
        # across host cores (the bounded-pmap analog).
        from jepsen_tpu.checker.wgl_oracle import check_streams

        verdicts, meta = check_streams(streams, model=model)
        return [
            {"valid?": v, "method": f"cpu-oracle-{rung}"}
            for v, rung in zip(verdicts, meta["rungs"])
        ]
    if mesh is not None:
        n_dev = mesh_size(mesh)
        n_keys = ((n_real + n_dev - 1) // n_dev) * n_dev
    else:
        n_keys = n_real
    K = k_ladder[0]

    from jepsen_tpu.checker.linearizable import _on_tpu, _pallas_ok
    from jepsen_tpu.checker.events import n_words

    if _on_tpu() or interpret:
        # Exact bitset batch first (one launch, one sync, definite
        # verdicts — no per-key escalation): all keys must fit its
        # envelope, sharing the max window/state buckets. With a mesh
        # the stacked batch itself shard_maps across devices inside
        # launch_keys_bitset — same method string, same one-launch
        # contract, B/n_devices keys per chip.
        from jepsen_tpu.checker import wgl_bitset as bs
        from jepsen_tpu.checker.models import model as get_model

        bplan = bs.plan(
            get_model(model),
            window,
            max(len(s.value_codes) for s in streams),
        )
        if bplan is not None:
            bW, S = bplan
            steps = [events_to_steps(s, W=bW) for s in streams]
            outs = bs.check_keys_bitset(
                steps, model=model, S=S, interpret=interpret,
                mesh=mesh if mesh is not None else False,
            )
            if not any(o[1] for o in outs):  # no taint ever
                res: List[dict] = []
                for o in outs:
                    r = {
                        "valid?": bool(o[0]),
                        "method": "tpu-wgl-bitset-batch",
                        "frontier_k": None,
                        "escalations": 0,
                    }
                    if not o[0]:
                        r["failed_op_index"] = int(o[2])
                    res.append(r)
                return res

    if mesh is None:
        if _on_tpu() and _pallas_ok(K, W, n_words(W)):
            # One batched megakernel launch: keys form the outer grid
            # dimension, one host sync for the whole batch.
            from jepsen_tpu.checker.wgl_pallas import check_keys_pallas

            steps = [events_to_steps(s, W=W) for s in streams]
            kic = m.kernel_init_code
            if any(
                kic(s.init_state) != st.init_state
                for s, st in zip(streams, steps)
            ):
                # Packed models re-encode the initial state; copy so
                # the memoized steps stay untouched for other models.
                import dataclasses

                steps = [
                    dataclasses.replace(
                        st, init_state=kic(s.init_state)
                    )
                    for s, st in zip(streams, steps)
                ]
            outs = check_keys_pallas(steps, model=model, K=K)
            alive = np.asarray([o[0] for o in outs])
            overflow = np.asarray([o[1] for o in outs])
            died = np.asarray([o[2] for o in outs])
            out: List[dict] = []
            for i, s in enumerate(streams):
                if alive[i] or not overflow[i]:
                    r = {
                        "valid?": bool(alive[i]),
                        "method": "tpu-wgl-pallas-batch",
                        "frontier_k": K,
                        "escalations": 0,
                    }
                    if not alive[i]:
                        r["failed_op_index"] = int(died[i])
                    out.append(r)
                else:
                    rest = k_ladder[1:]
                    if rest:
                        out.append(
                            check_events_bucketed(
                                s, model=model, k_ladder=rest
                            )
                        )
                    else:  # no bigger rung: the oracle decides
                        from jepsen_tpu.checker.wgl_oracle import (
                            check_events_fast,
                        )

                        v, st = check_events_fast(
                            s, model=model, return_stats=True
                        )
                        out.append({
                            "valid?": v,
                            "method": f"cpu-oracle-{st['oracle']}",
                        })
            return out
        cols = stack_streams(streams, W=W, n_keys=n_keys, model=model)
        args = tuple(jnp.asarray(c) for c in cols)
        alive, overflow, died = _wgl_vmap(*args, model_name=model, K=K, W=W)
    else:
        # Place inputs on the mesh explicitly: a bare jnp.asarray lands
        # on the default backend, which may not be the mesh's platform
        # (e.g. an explicit CPU mesh in a TPU process). In a pod each
        # process materializes only its addressable shards.
        from jepsen_tpu.pod.slicing import host_shard_put

        cols = stack_streams(streams, W=W, n_keys=n_keys, model=model)
        args = host_shard_put(cols, mesh)
        fn = make_sharded_checker(mesh, model, K, W)
        alive, overflow, died = fn(*args)
        note_sharded_launch(n_dev)
        # pod collect: sharded verdicts are not fully addressable
        # across processes — one replicating all-gather (no-op
        # single-process) before the funnel.
        from jepsen_tpu.pod.slicing import global_view

        alive, overflow, died = global_view(
            (alive, overflow, died), mesh
        )
    # ONE host sync for the whole stacked batch (all keys, all chips):
    # the funnel counts it toward the residency metric.
    from jepsen_tpu.checker import wgl_bitset as bs

    alive, overflow, died = bs._host_get((alive, overflow, died))
    alive = np.asarray(alive)[:n_real]
    overflow = np.asarray(overflow)[:n_real]
    died = np.asarray(died)[:n_real]

    method = "tpu-wgl-sharded" if mesh is not None else "tpu-wgl-batch"
    return vmap_verdicts(
        streams, alive, overflow, died,
        model=model, k_ladder=k_ladder, K=K, method=method,
    )


def vmap_verdicts(
    streams,
    alive,
    overflow,
    died,
    *,
    model: str,
    k_ladder,
    K: int,
    method: str = "tpu-wgl-batch",
) -> List[dict]:
    """Turn a stacked K-frontier launch's (alive, overflow, died)
    vectors back into per-stream verdict dicts: definite results map
    directly; overflow-tainted deaths escalate that stream alone up
    the remaining k_ladder rungs (check_events_bucketed). Shared by
    check_keys and the dispatch plane's vmap-tier collect."""
    out: List[dict] = []
    for i, s in enumerate(streams):
        if alive[i] or not overflow[i]:
            r = {
                "valid?": bool(alive[i]),
                "method": method,
                "frontier_k": K,
                "escalations": 0,
            }
            if not alive[i]:
                r["failed_op_index"] = int(died[i])
            out.append(r)
        else:
            # Overflow-tainted False: escalate this key alone. The
            # overflowed batch rung counts toward escalations — the
            # same tally the solo ladder's in-loop counter reports.
            r = check_events_bucketed(
                s, model=model, k_ladder=k_ladder[1:] or k_ladder
            )
            r["escalations"] = r.get("escalations", 0) + 1
            out.append(r)
    return out


# -- txn dependency-graph closure (checker/txn_graph.py) ---------------------


def row_spec(mesh: Mesh) -> P:
    """Row sharding for a single [N, N] adjacency matrix: rows split
    across every mesh axis, columns replicated — the layout of the
    oversize-component closure."""
    return P(tuple(mesh.axis_names), None)


@functools.lru_cache(maxsize=None)
def make_sharded_graph(mesh: Mesh, n_iters: int, need1: bool,
                       need2: bool,
                       packed_max: int = 32):
    """Batch-axis sharded repeated-squaring cycle kernel: [B, N, N]
    adjacency stacks split over the mesh on the batch axis (graphs are
    independent components, so the per-shard closure is collective-free
    — the same layout story as the vmap checker)."""
    spec = key_spec(mesh)

    def per_shard(wrww, allm, rw):
        from jepsen_tpu.checker.txn_graph import _graph_counts_body

        return _graph_counts_body(wrww, allm, rw, n_iters, need1,
                                  need2, packed_max)

    sharded = _shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    return jax.jit(sharded)


@functools.lru_cache(maxsize=None)
def make_sharded_graph_rows(mesh: Mesh, n_iters: int, need1: bool,
                            need2: bool):
    """Row-sharded closure for one oversize component: each device owns
    a block of rows of the [N, N] reachability matrix and squares it
    against the all_gather'd full matrix (Rblk = min(Rblk + Rblk @ R,
    1)) — log2(N) rounds of block matmul + gather, then psum'd scalar
    anomaly counts."""
    axes = tuple(mesh.axis_names)
    axis_sizes = tuple(mesh.shape[a] for a in axes)

    def per_shard(wrww, allm, rw):
        rows = wrww.shape[0]
        n = rows * int(np.prod(axis_sizes))

        def closure(blk):
            def body(_, r):
                full = jax.lax.all_gather(r, axes, axis=0, tiled=True)
                sq = jnp.dot(r, full, preferred_element_type=jnp.float32)
                return jnp.minimum(r + sq, 1.0)

            return jax.lax.fori_loop(0, n_iters, body, blk)

        idx = jnp.int32(0)
        for ax, sz in zip(axes, axis_sizes):
            idx = idx * sz + jax.lax.axis_index(ax)
        row0 = idx * rows
        z = jnp.zeros((), jnp.int32)
        rwb = rw > 0
        g1c = gs = g2 = z

        def rw_hits(c):
            cf = jax.lax.all_gather(c, axes, axis=0, tiled=True)  # [N, N]
            # this block's rows of closure.T: cf[:, row0:row0+rows].T
            ct = jax.lax.dynamic_slice(
                cf, (jnp.int32(0), row0), (n, rows)).T
            return (rwb & (ct > 0)).sum().astype(jnp.int32), cf

        if need1:
            c1 = closure(wrww)
            hits, c1f = rw_hits(c1)
            gs = hits
            diag = c1f[row0 + jnp.arange(rows), row0 + jnp.arange(rows)]
            g1c = (diag > 0).sum().astype(jnp.int32)
        if need2:
            c2 = closure(allm)
            g2, _ = rw_hits(c2)
        g1c = jax.lax.psum(g1c, axes)
        gs = jax.lax.psum(gs, axes)
        g2 = jax.lax.psum(g2, axes)
        return g1c, gs, g2

    spec = row_spec(mesh)
    sharded = _shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(spec,) * 3,
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)

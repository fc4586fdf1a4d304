"""Async coalescing check-dispatch plane: many small checks, few launches.

The problem this module exists for: every synchronous device call pays
a host<->device round trip, so small-history configs (etcd-1k,
zookeeper-10kx16) can lose to the native CPU oracle not on scan
throughput but on dispatch accounting — each check pays its own launch
+ sync. The fix is
structural, not a kernel change: accept check requests into a queue,
COALESCE requests that share a bucketed kernel shape into one stacked
launch, DISPATCH without blocking (JAX async dispatch — the host thread
returns as soon as the computation is enqueued), and SYNC once per
train at collect time. N same-shape checks then pay one launch and one
round trip instead of N of each.

Request lifecycle::

    submit(events) ──prep──▶ classify + key ──bucket──▶ coalesce
        │                                                  │ full /
        │ (async_prep: a worker thread preps and           │ aged /
        │  flushes, overlapping host prep of request       │ flush()
        │  N+1 with device execution of request N)         ▼
        │                                            stacked launch
        ▼                                                  │
    CheckFuture.result() ──────── collect train ◀──────────┘
                                  (ONE device_get for every launch up
                                   to the one the future rides on —
                                   the device executes FIFO, so the
                                   prefix is ready when the target is)

Classification mirrors ``check_events_bucketed`` exactly, so verdicts
through the plane are identical to the sequential path:

- ``bitset``: inside the exact-kernel envelope (wgl_bitset.plan) with a
  single-segment plan — coalesced by ``(model, S, W, n_bucket)`` into
  one ``launch_keys_bitset`` stacked launch. Fast-tier deaths escalate
  to the exact kernel at collect (collect_keys_bitset), and a confirmed
  death re-checks through the sequential path for its failure artifact
  (failure analysis is rare and worth the re-run — same policy as the
  checker tail).
- ``segmented``: bitset envelope but a multi-W segment plan (the north
  star's shape) — uncoalescible (the plan IS the shape), dispatched
  solo but still async: it rides the same collect train and amortizes
  the same sync.
- ``vmap``: outside the bitset envelope but kernel-capable (packed
  queue substreams, wide-window registers) — coalesced by
  ``(model, K, W, n_bucket)`` into one ``_wgl_vmap`` stacked launch,
  with per-key overflow escalation through the K-ladder at collect
  (sharded.check_keys' exact discipline).
- ``fallback``: host-only (window past every bucket, rich-state models)
  — resolved by ``check_events_bucketed`` on the collecting thread; the
  oracle pays no device round trip, so there is nothing to amortize.

Mesh execution (the per-device scheduler): when more than one device
is visible (or an explicit mesh is passed) the plane shards every
coalesced bucket across the mesh — B requests run B/n_devices per chip
through the shard_map wrappers (sharded.make_sharded_bitset /
make_sharded_checker), still ONE launch and one sync — and round-robins
non-coalescible segmented chain-scans onto per-device launch trains
(launch_steps_bitset_segmented's device commit), so independent
requests' chains execute concurrently on different chips. DEVICE_STATS
tracks the per-device launch/request counts; dispatch_stats() derives
per-device occupancy and floor_amortization from it. Keys are
independent, so no collectives ever cross chips.

The native-racer competition (linearizable._NativeRacer) stays
per-request: with ``race=True`` an eligible request's racer starts
right after its batch dispatches, a racer that finishes before the
collect wins the verdict (the device result is discarded for that
request), and a device win cross-checks against a racer that lands
within the grace window — exactly the sequential semantics.

Verdict parity note: ``method`` strings record the engine AND the batch
shape ("tpu-wgl-bitset-batch" vs the solo "tpu-wgl-bitset"), so
differential tests compare every verdict field EXCEPT method/wall —
same convention as sharded.check_keys vs the solo checker.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Any, List, Optional

import jax
import numpy as np

from jepsen_tpu.checker import chaos
from jepsen_tpu.checker import wgl_bitset as bs
from jepsen_tpu.checker.chaos import PlaneFault
from jepsen_tpu.checker.events import (
    EventStream,
    bucket,
    events_to_steps,
    memo_on,
)
from jepsen_tpu.checker.linearizable import (
    K_LADDER,
    _bucket_window,
    _decode_value,
    _native_win_verdict,
    _on_tpu,
    _race_crosscheck,
    _race_eligible,
    _NativeRacer,
    check_events_bucketed,
)
from jepsen_tpu.checker.models import model as get_model
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.perf import knobs as _perf_knobs

#: length-bucket quantum for coalescing stream tails into one stacked
#: launch (submit_stream_tail). Documented default; the plane resolves
#: the live value through the perf knob registry at construction
#: ("streaming.tail_len_bucket").
STREAM_TAIL_BUCKET = 64

#: plane-level dispatch accounting (launch-level counts live in
#: wgl_bitset.LAUNCH_STATS): "requests" = submissions accepted,
#: "batches" = coalesced stacked launches formed (occupancy >= 1),
#: "batched_requests" = requests those batches carried,
#: "solo_launches" = uncoalescible dispatches (segmented plans),
#: "fallbacks" = host-only resolutions (no launch to amortize),
#: "max_batch" = largest batch occupancy seen,
#: "coalesce_wait_us" = total microseconds batched requests spent
#: parked in a bucket waiting for partners (the latency cost of
#: coalescing), "native_wins" = racer verdicts that beat the device,
#: "worker_errors" = exceptions the async prep worker's keep-alive
#: swallowed (soaks assert zero), "pending_at_close" = futures still
#: unresolved when close() returned (resolved with a PlaneFault, never
#: dropped — nonzero means a leaked worker or an abandoned train).
DISPATCH_STATS = {
    "requests": 0,
    "batches": 0,
    "batched_requests": 0,
    "solo_launches": 0,
    "fallbacks": 0,
    "max_batch": 0,
    "coalesce_wait_us": 0.0,
    "native_wins": 0,
    "worker_errors": 0,
    "pending_at_close": 0,
    # Durable (checkpointed) routing after residency: single-segment
    # plans ride the normal coalescing buckets (durable_coalesced,
    # incl. zero-launch checkpoint replays resolved at prep), while
    # multi-segment plans run the resident checkpointed group driver
    # on the collecting thread (durable_solo).
    "durable_coalesced": 0,
    "durable_solo": 0,
    # Double-buffered collect trains: every launch registration samples
    # how many unresolved trains are in flight (train_inflight_accum /
    # train_registers = double_buffer_occupancy; 2.0 means collect of
    # train N fully overlaps launch of train N+1). Registrations past
    # max_inflight_trains collect the oldest train first
    # (backpressure_collects) — bounded device memory, pipelined syncs.
    "train_registers": 0,
    "train_inflight_accum": 0,
    "backpressure_collects": 0,
    # Txn dependency-graph bucket kind (checker/txn_graph.py):
    # adjacency-batch submissions accepted and the coalesced graph
    # launches formed from them — graph_requests / graph_batches > 1
    # means concurrent graph checks actually shared a launch.
    "graph_requests": 0,
    "graph_batches": 0,
    # Stream-tail bucket kind (checker/streaming.py): per-append tail
    # submissions accepted and the stacked tail launches formed from
    # them — stream_requests / stream_batches > 1 means concurrent
    # streams' appends actually shared a launch (each stream's
    # device-resident frontier feeds row i of the stack).
    "stream_requests": 0,
    "stream_batches": 0,
}

_stats_lock = threading.Lock()

#: "no explicit mesh" sentinel for _dispatch_resilient (None is a
#: meaningful value: the single-device placement)
_UNSET = object()

#: per-device dispatch accounting (the mesh execution plane's view):
#: device label -> {"launches": dispatches that placed work on this
#: chip, "requests": requests whose scan ran there}. A mesh-sharded
#: stacked launch counts 1 launch on EVERY chip (all execute one
#: shard) and splits its requests by the key_spec block layout; a
#: round-robin segmented chain counts on its one chip. dispatch_stats
#: derives per-device occupancy + floor_amortization from this.
DEVICE_STATS: "OrderedDict[str, dict]" = OrderedDict()


def _bump(key: str, n=1) -> None:
    with _stats_lock:
        DISPATCH_STATS[key] += n


def _bump_device(label: str, requests: int = 0, launches: int = 0) -> None:
    with _stats_lock:
        d = DEVICE_STATS.setdefault(
            label, {"launches": 0, "requests": 0}
        )
        d["launches"] += launches
        d["requests"] += requests


def reset_dispatch_stats() -> None:
    with _stats_lock:
        for k in DISPATCH_STATS:
            DISPATCH_STATS[k] = 0.0 if k == "coalesce_wait_us" else 0
        DEVICE_STATS.clear()


def snapshot() -> dict:
    """The ONE sanctioned aggregate read of the dispatch plane's
    stats surfaces (planelint JT205): DISPATCH_STATS + DEVICE_STATS
    copied under _stats_lock, launch counters copied under their own
    lock (sequentially — the two locks never nest, so no ordering
    hazard). Everything derived (ratios, occupancies) is computed by
    dispatch_stats() on top of this raw copy."""
    with _stats_lock:
        dispatch = dict(DISPATCH_STATS)
        per_device = {k: dict(v) for k, v in DEVICE_STATS.items()}
    return {
        "dispatch": dispatch,
        "per_device": per_device,
        "launch": bs.launch_stats_snapshot(),
    }


def dispatch_stats() -> dict:
    """Snapshot + derived ratios for the bench JSON / run epitaphs.

    floor_amortization: launched requests per launch actually paid —
    the factor by which coalescing divides the per-sync round trip
    (1.0 = no amortization, N = N requests rode each round trip).

    per_device: one block per device that received work — its launch
    and request counts, its own floor_amortization (requests per
    launch on THAT chip), and occupancy (its share of all launches:
    1/n_devices everywhere = perfectly balanced mesh). n_devices is
    the number of devices that actually received work — the bench's
    one-device guard trips when this reads 1 on a multi-chip host.
    """
    snap = snapshot()
    out = snap["dispatch"]
    per_dev = snap["per_device"]
    launches = out["batches"] + out["solo_launches"]
    carried = out["batched_requests"] + out["solo_launches"]
    out["mean_batch_occupancy"] = (
        out["batched_requests"] / out["batches"] if out["batches"] else 0.0
    )
    out["floor_amortization"] = carried / launches if launches else 0.0
    out["mean_coalesce_wait_us"] = (
        out["coalesce_wait_us"] / out["batched_requests"]
        if out["batched_requests"]
        else 0.0
    )
    total_dev_launches = sum(d["launches"] for d in per_dev.values())
    for d in per_dev.values():
        d["floor_amortization"] = (
            d["requests"] / d["launches"] if d["launches"] else 0.0
        )
        d["occupancy"] = (
            d["launches"] / total_dev_launches
            if total_dev_launches
            else 0.0
        )
    out["per_device"] = per_dev
    out["n_devices"] = len(per_dev)
    out["double_buffer_occupancy"] = (
        out["train_inflight_accum"] / out["train_registers"]
        if out["train_registers"]
        else 0.0
    )
    out["launch"] = snap["launch"]
    res = chaos.resilience_snapshot()
    res["worker_errors"] = out["worker_errors"]
    out["resilience"] = res
    from jepsen_tpu.checker.checkpoint import checkpoint_stats

    out["checkpoint"] = checkpoint_stats()
    return out


#: thread-local tenant attribution: the service daemon's handler
#: threads enter tenant_context(name) so every submit() on that thread
#: stamps its futures — checker entry points (check/check_async) need
#: no tenant-aware API change.
_TENANT_LOCAL = threading.local()


@contextmanager
def tenant_context(tenant: Optional[str]):
    """Attribute every submit() on this thread to ``tenant`` (the
    multi-tenant service's per-request scope). Nests; None clears."""
    prev = getattr(_TENANT_LOCAL, "tenant", None)
    _TENANT_LOCAL.tenant = tenant
    try:
        yield
    finally:
        _TENANT_LOCAL.tenant = prev


def current_tenant() -> Optional[str]:
    return getattr(_TENANT_LOCAL, "tenant", None)


def _tenant_tags(futs) -> List[str]:
    """chaos pseudo-labels for the tenants riding a launch — appended
    to the guard's device-label list so (a) a chaos plan can target one
    tenant's launches deterministically (ChaosFault(device="tenant:x"))
    and (b) attributed failures count against the TENANT label in the
    quarantine registry instead of ejecting a healthy chip: a tenant's
    fault storm trips its own breaker (chaos.quarantined_tenants),
    never the mesh."""
    seen = []
    for f in futs:
        t = getattr(f, "tenant", None)
        if t is not None:
            lbl = chaos.TENANT_PREFIX + str(t)
            if lbl not in seen:
                seen.append(lbl)
    return seen


class CheckFuture:
    """Handle for one submitted check. ``result()`` drives the owning
    plane as needed (flushing un-launched buckets, collecting the
    launch train) and returns the verdict dict — or, for raw
    steps-level submissions (run_keys), the (alive, taint, died)
    tuple check_keys_bitset callers expect."""

    def __init__(self, plane: "DispatchPlane", events, model: str):
        self.plane = plane
        self.events = events
        self.model = model  # original model name (racer + fallbacks)
        self.checkpoint = None  # durable-analysis sink (submit(...))
        self.tenant = current_tenant()  # multi-tenant attribution
        self.kind: Optional[str] = None
        self.kernel_model = model  # post packed-substitution
        self.steps = None
        self.S = 8
        self.W: Optional[int] = None
        self.key = None
        self.launch: Optional["_Launch"] = None
        self.racer = None
        self.wrap = True  # False: resolve to the raw bitset tuple
        self._bucketed_at: Optional[float] = None
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.is_set():
            self.plane._drive(self)
        if not self._done.wait(timeout):
            raise TimeoutError("check did not complete in time")
        if self._error is not None:
            raise self._error
        return self._result

    def _resolve(self, value) -> None:
        if not self._done.is_set():
            self._result = value
            self._done.set()

    def _fail(self, err: BaseException) -> None:
        if not self._done.is_set():
            self._error = err
            self._done.set()


class _Launch:
    """One dispatched device computation and the futures riding it."""

    __slots__ = ("kind", "futs", "handle", "meta", "resolved")

    def __init__(self, kind: str, futs: List[CheckFuture], meta: dict):
        self.kind = kind
        self.futs = futs
        self.meta = meta
        self.handle = None
        self.resolved = False

    def device_out(self):
        """The device arrays one host fetch must materialize — fed to a
        single jax.device_get over the whole launch-train prefix.
        Stream launches fetch VERDICTS only: the stacked fr_out stays
        device-resident (each rider's next frontier is a row slice)."""
        if self.kind in ("bitset", "stream"):
            return self.handle[0]
        if self.kind == "segmented":
            return tuple(self.handle[0])
        return self.handle  # vmap: (alive, overflow, died)


class _Bucket:
    __slots__ = ("futs", "born")

    def __init__(self):
        self.futs: List[CheckFuture] = []
        self.born = time.perf_counter()


class DispatchPlane:
    """The async coalescing dispatch plane (module docstring).

    Parameters:
      model: default model for ``submit``.
      interpret: run bitset kernels in Pallas interpret mode (the CPU
        test seam — same role as everywhere else in the checker).
      race: start the native-oracle competition racer for eligible
        requests (off by default: the plane is primarily a throughput
        surface, and the sequential default races only on real TPUs).
      max_batch: occupancy at which a bucket flushes without waiting
        (None = resolve "dispatch.max_batch" through the perf knob
        registry: the persisted per-backend profile when one is
        loaded, the registry default otherwise).
      coalesce_wait_us: how long a bucket may wait for partners before
        an age-based flush (async_prep mode; synchronous callers flush
        explicitly or at result()). None resolves
        "dispatch.coalesce_hold_s" (seconds) the same way.
      async_prep: run prep + flush on a worker thread, overlapping host
        prep of request N+1 with device execution of request N.
      mesh: the execution mesh (sharded.resolve_mesh semantics: None =
        auto over all visible devices when >1, False = force
        single-device, a Mesh = explicit). With a mesh the plane is a
        per-device scheduler: coalesced buckets shard across the mesh
        (B/n_devices keys per chip, one launch), and non-coalescible
        segmented chain-scans round-robin onto per-device launch
        trains so independent requests' chains execute concurrently
        on different chips.
      retry: chaos.RetryPolicy for the launch/collect guards (bounded
        exponential backoff on transient/deadline fault classes);
        None = chaos.DEFAULT_RETRY.
      launch_deadline_s: per-guarded-call wall budget. A hung device
        sync (the collect train's device_get, or a wedged launch)
        times out with DeadlineExceeded instead of wedging the plane:
        the call retries, then degrades — the worker stays alive and
        the future always resolves. None = no deadline (the default:
        first-compile stalls on real hardware can dwarf any static
        budget, so deadlines are opt-in).
      quarantine_after: attributed failures before a device is ejected
        and launches re-shard onto the survivors.
      worker_join_s: how long close() waits for the async prep worker
        before declaring it leaked and resolving pending futures with
        a PlaneFault.
      owner: opaque location tag for this plane's process (the fleet
        member id, "member-3"). Stamped onto any un-owned
        CheckpointSink that rides submit(), so durable state written
        through this plane records WHERE it was written — the seam
        the fleet's hand-off accounting (checkpoint.py `handoffs`)
        reads when a survivor resumes a dead member's frontier.
    """

    def __init__(
        self,
        model: str = "cas-register",
        interpret: bool = False,
        race: bool = False,
        max_batch: Optional[int] = None,
        coalesce_wait_us: Optional[float] = None,
        async_prep: bool = False,
        mesh=None,
        retry: Optional[chaos.RetryPolicy] = None,
        launch_deadline_s: Optional[float] = None,
        quarantine_after: int = 3,
        worker_join_s: float = 10.0,
        max_inflight_trains: Optional[int] = None,
        host_domain_quarantine: bool = True,
        owner: Optional[str] = None,
    ):
        from jepsen_tpu.checker.sharded import resolve_mesh

        # perf-plane consult: explicit kwargs win; unspecified knobs
        # resolve through the persisted per-backend profile (registry
        # defaults when none is loaded).
        _perf_knobs.ensure_profile()
        self.model = model
        self.interpret = interpret
        self.race = race
        self.max_batch = int(
            max_batch if max_batch is not None
            else _perf_knobs.resolve("dispatch.max_batch")
        )
        if coalesce_wait_us is None:
            coalesce_wait_us = 1e6 * float(
                _perf_knobs.resolve("dispatch.coalesce_hold_s")
            )
        self.coalesce_wait_s = coalesce_wait_us / 1e6
        #: double-buffered collect trains: at most this many unresolved
        #: launches in flight; registering one more collects the oldest
        #: first (its device->host copy started at registration, so
        #: that collect overlaps the newer train's device execution).
        self.max_inflight_trains = max(int(
            max_inflight_trains if max_inflight_trains is not None
            else _perf_knobs.resolve("dispatch.max_inflight_trains")
        ), 1)
        #: stream-tail coalescing quantum (STREAM_TAIL_BUCKET default)
        self._tail_bucket = max(int(
            _perf_knobs.resolve(
                "streaming.tail_len_bucket", STREAM_TAIL_BUCKET
            )
        ), 1)
        self.retry = retry or chaos.DEFAULT_RETRY
        self.launch_deadline_s = launch_deadline_s
        self.quarantine_after = quarantine_after
        self.worker_join_s = worker_join_s
        #: host-level failure domains (pod.faultdomains): a quarantined
        #: chip on a mesh spanning >1 host slice ejects its whole
        #: domain. Off = per-chip quarantine only.
        self.host_domain_quarantine = host_domain_quarantine
        self.owner = owner
        self.mesh = resolve_mesh(mesh)
        #: optional per-future fault attribution hook for multi-tenant
        #: embedders (the service daemon's tenant ledger): called as
        #: fault_observer(tenant, kind) with kind in
        #: {"oracle_fallback", "plane_fault"} whenever a future resolves
        #: through the degradation ladder's last rungs. Exceptions are
        #: swallowed — observers must never wedge resolution.
        self.fault_observer = None
        self._devices = (
            list(self.mesh.devices.flat)
            if self.mesh is not None
            else jax.devices()[:1]
        )
        self._rr = itertools.count()
        self._lock = threading.Lock()  # inbox + buckets + launched
        self._pump_lock = threading.Lock()  # serializes prep/flush
        self._collect_lock = threading.Lock()  # serializes resolution
        self._inbox: deque = deque()
        self._buckets: "OrderedDict[Any, _Bucket]" = OrderedDict()
        self._launched: List[_Launch] = []
        self._fallbacks: List[CheckFuture] = []
        self._worker: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._closing = threading.Event()
        if async_prep:
            self._worker = threading.Thread(
                target=self._worker_loop, daemon=True,
                name="dispatch-plane-prep",
            )
            self._worker.start()

    # -- submission ----------------------------------------------------

    def submit(self, events: EventStream, model: Optional[str] = None,
               checkpoint=None) -> CheckFuture:
        """Queue one event-stream check; returns its CheckFuture.

        checkpoint: a checkpoint.CheckpointSink makes this check
        durable. Durable checks classify like any other: a
        single-segment plan rides a normal coalesced bucket (the sink
        replays a finished verdict at prep with zero launches and
        records the verdict at resolve), while a multi-segment plan
        runs the resident checkpointed group driver — one launch and
        one host sync per `every=N` persistence boundary — on the
        collecting thread. Streams outside the bitset envelope ignore
        the sink (nothing durable to record segment-wise)."""
        fut = CheckFuture(self, events, model or self.model)
        fut.checkpoint = checkpoint
        if (checkpoint is not None and self.owner is not None
                and checkpoint.owner is None):
            # location-stamp un-owned durable state (fleet hand-off
            # accounting); explicit sink owners always win
            checkpoint.owner = self.owner
        _bump("requests")
        obs_trace.instant("submit", kind="dispatch",
                          tenant=current_tenant())
        if self._worker is not None:
            with self._lock:
                self._inbox.append(fut)
            self._wake.set()
        else:
            self._prep_and_enqueue(fut)
        return fut

    def submit_history(self, history, model: Optional[str] = None,
                       init_value=None) -> CheckFuture:
        """Encode + queue a record history (LinearizableChecker's
        entry). Window overflow routes to the oracle fallback, same as
        the sequential checker."""
        from jepsen_tpu.checker.events import (
            WindowOverflow,
            history_to_events,
        )

        name = model or self.model
        try:
            events = history_to_events(
                history, model=name, init_value=init_value
            )
        except WindowOverflow:
            events = history_to_events(
                history, model=name, init_value=init_value,
                max_window=1 << 20,
            )
        return self.submit(events, model=name)

    def submit_graph(self, wrww, allm, rw, need=(True, True)
                     ) -> CheckFuture:
        """Queue one txn dependency-graph adjacency batch (the "graph"
        bucket kind, checker/txn_graph.py): wrww/allm float32 and rw
        bool, each [B, N, N]. Batches bucket by (N, edge-class needs),
        so concurrent graph checks with same-sized components coalesce
        into one stacked closure launch exactly like bitset buckets.
        The future resolves to raw per-graph int32 count arrays
        (g1c, g_single, g2), each [B] — no verdict wrapping; the
        TxnGraphChecker builds the verdict host-side."""
        wrww = np.asarray(wrww, np.float32)
        allm = np.asarray(allm, np.float32)
        rw = np.asarray(rw, bool)
        if wrww.ndim != 3 or wrww.shape != allm.shape or \
                wrww.shape != rw.shape:
            raise ValueError(
                f"graph stacks must share one [B, N, N] shape, got "
                f"{wrww.shape}/{allm.shape}/{rw.shape}"
            )
        fut = CheckFuture(self, None, "txn-graph")
        fut.kind = "graph"
        fut.wrap = False
        fut.graph = (wrww, allm, rw)
        fut.key = ("graph", int(wrww.shape[-1]), bool(need[0]),
                   bool(need[1]))
        _bump("requests")
        _bump("graph_requests")
        full = None
        with self._lock:
            b = self._buckets.get(fut.key)
            if b is None:
                b = self._buckets[fut.key] = _Bucket()
            b.futs.append(fut)
            fut._bucketed_at = time.perf_counter()
            if len(b.futs) >= self.max_batch:
                full = fut.key
        if full is not None:
            self._flush_bucket(full)
        elif self._worker is not None:
            self._wake.set()
        return fut

    def submit_stream_tail(
        self,
        steps,
        frontier,
        model: Optional[str] = None,
        S: int = 8,
        exact: bool = False,
    ) -> CheckFuture:
        """Queue one stream's unchecked TAIL (the "stream" bucket
        kind, checker/streaming.py): ``steps`` is a single-W
        ReturnSteps slice and ``frontier`` the stream's boundary
        frontier — None for a fresh stream, a host array, or (the
        steady state) the device-resident row a previous stacked tail
        launch left behind. Concurrent streams sharing a kernel shape
        (model, S, W, length bucket, tier) coalesce into ONE stacked
        bitset launch (wgl_bitset.launch_tails_bitset); the future
        resolves to the raw ``(alive, taint, died, fr_row)`` tuple
        where fr_row is the stream's NEXT frontier as a device-side
        slice — frontiers never cross to the host between appends.
        Escalation/death semantics stay with the StreamingCheck (fast
        deaths are provisional; the handle re-runs sticky-exact)."""
        name = model or self.model
        name = name if isinstance(name, str) else name.name
        fut = CheckFuture(self, None, name)
        fut.kind = "stream"
        fut.wrap = False
        fut.steps = steps
        fut.frontier = frontier
        fut.S = S
        fut.W = steps.W
        n = bucket(max(len(steps), 1), self._tail_bucket)
        fut.key = (
            "stream", name, S, steps.W, n, self.interpret, bool(exact)
        )
        _bump("requests")
        _bump("stream_requests")
        obs_trace.instant("submit_stream", kind="dispatch",
                          tenant=current_tenant())
        full = None
        with self._lock:
            b = self._buckets.get(fut.key)
            if b is None:
                b = self._buckets[fut.key] = _Bucket()
            b.futs.append(fut)
            fut._bucketed_at = time.perf_counter()
            if len(b.futs) >= self.max_batch:
                full = fut.key
        if full is not None:
            self._flush_bucket(full)
        elif self._worker is not None:
            self._wake.set()
        return fut

    def flush(self) -> None:
        """Prep everything queued and dispatch every pending bucket
        (returns once dispatched — collection still happens at
        result()/drain())."""
        self._pump(flush_all=True)

    def flush_for(self, futs) -> None:
        """Targeted flush: dispatch only the buckets holding these
        futures (the inbox preps first so queued submissions have
        bucket keys). Unlike flush(), other submitters' partially
        filled buckets keep coalescing — the entry for callers that
        batch their own submissions on a shared plane
        (check_queue_by_value's per-value substreams)."""
        self._pump(flush_futs=tuple(futs))

    def drain(self) -> None:
        """Flush, then collect the whole launch train (one device_get)
        and resolve every outstanding future, fallbacks included."""
        self._pump(flush_all=True)
        with self._lock:
            pending = [L for L in self._launched if not L.resolved]
        if pending:
            self._collect_upto(pending[-1])
        self._resolve_fallbacks()

    def close(self) -> None:
        """Shut the plane down with every future accounted for: join
        the prep worker (bounded), drain the train, and resolve ANY
        still-pending future with a structured PlaneFault — close()
        always returns, and no rider is ever silently dropped. A
        worker that outlives its join budget is a leak: it may hold
        _pump_lock, so the drain is skipped (it could wedge behind the
        leak) and pending futures fail over immediately."""
        self._closing.set()
        self._wake.set()
        leaked = None
        if self._worker is not None:
            w = self._worker
            w.join(timeout=self.worker_join_s)
            if w.is_alive():
                leaked = w
            self._worker = None
        if leaked is not None:
            import logging

            logging.getLogger("jepsen_tpu.checker").error(
                "dispatch plane prep worker %r failed to join within "
                "%.1fs (leaked thread); resolving pending futures with "
                "PlaneFault", leaked.name, self.worker_join_s,
            )
            self._fail_pending(PlaneFault(
                site="close", kind="worker-leak", attempts=0,
            ))
            return
        try:
            self.drain()
        finally:
            self._fail_pending(PlaneFault(
                site="close", kind="abandoned", attempts=0,
            ))

    def _fail_pending(self, pf: PlaneFault) -> int:
        """Resolve every future the plane still holds with ``pf`` and
        report the count (DISPATCH_STATS['pending_at_close']). Zero on
        a clean close — drain() resolved the world."""
        with self._lock:
            futs = list(self._inbox)
            self._inbox.clear()
            for b in self._buckets.values():
                futs.extend(b.futs)
            self._buckets.clear()
            futs.extend(self._fallbacks)
            self._fallbacks = []
            for L in self._launched:
                futs.extend(L.futs)
            self._launched = []
        n = 0
        for f in futs:
            if not f.done():
                f._fail(pf)
                n += 1
        if n:
            import logging

            _bump("pending_at_close", n)
            chaos.note_plane_fault(n)
            logging.getLogger("jepsen_tpu.checker").warning(
                "dispatch plane closed with %d pending future(s); "
                "resolved with %s", n, pf,
            )
        return n

    def __enter__(self) -> "DispatchPlane":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- prep + classification ----------------------------------------

    def _worker_loop(self) -> None:
        while not self._closing.is_set():
            self._wake.wait(timeout=self.coalesce_wait_s)
            self._wake.clear()
            try:
                self._pump()
            except Exception:  # keep the loop alive, but never silently
                import logging

                _bump("worker_errors")
                logging.getLogger("jepsen_tpu.checker").exception(
                    "dispatch plane prep worker error "
                    "(DISPATCH_STATS['worker_errors'] counts these; "
                    "soaks assert zero)"
                )

    def _pump(self, flush_all: bool = False, flush_futs=()) -> None:
        """Prep the inbox, bucket/dispatch each request, and flush
        aged buckets — plus the buckets holding ``flush_futs`` (the
        targeted flush), or every bucket with ``flush_all``. Callable
        from the worker thread and from any caller needing progress —
        _pump_lock makes it single-file."""
        with self._pump_lock:
            while True:
                with self._lock:
                    if not self._inbox:
                        break
                    fut = self._inbox.popleft()
                # planelint: disable=JT402,JT403 reason=_pump_lock is the pump-phase serializer by design ("makes it single-file" above): dispatch/collect work reached from here IS the serialized phase, and every wait inside it rides the deadline-bounded guard ladder
                self._prep_and_enqueue(fut)
            # Bucket keys are assigned during prep, so the targets are
            # read only after the inbox drains.
            targets = {f.key for f in flush_futs if f.key is not None}
            now = time.perf_counter()
            with self._lock:
                keys = [
                    k for k, b in self._buckets.items()
                    if flush_all or k in targets
                    or now - b.born >= self.coalesce_wait_s
                ]
            for k in keys:
                # planelint: disable=JT402,JT403 reason=_pump_lock is the pump-phase serializer by design; bucket flushes (and anything they collect) are the work it serializes, deadline-bounded by the guard ladder
                self._flush_bucket(k)

    def _prep_and_enqueue(self, fut: CheckFuture) -> None:
        try:
            self._prep_one(fut)
        except BaseException as e:  # noqa: BLE001 - delivered at result()
            fut._fail(e)
            return
        if fut.kind == "done":
            return  # resolved at prep (checkpoint replay)
        # planelint: disable=JT502 reason=request-kind branch keys on replicated request data (prep classifies identically on every pod member), so all members take the same arm
        if fut.kind == "segmented":
            self._dispatch_segmented(fut)
        elif fut.kind in ("fallback", "durable"):
            _bump("fallbacks" if fut.kind == "fallback" else "durable_solo")
            with self._lock:
                self._fallbacks.append(fut)
        else:
            full = None
            with self._lock:
                b = self._buckets.get(fut.key)
                if b is None:
                    b = self._buckets[fut.key] = _Bucket()
                b.futs.append(fut)
                fut._bucketed_at = time.perf_counter()
                if len(b.futs) >= self.max_batch:
                    full = fut.key
            if full is not None:
                self._flush_bucket(full)

    def _prep_one(self, fut: CheckFuture) -> None:
        """Classify one request, mirroring check_events_bucketed's
        tier order exactly (bitset plan on the ORIGINAL model, then
        packed substitution, then the K-ladder envelope)."""
        ev = fut.events
        m = get_model(fut.model)
        device_ok = _on_tpu() or self.interpret
        plan = (
            bs.plan(m, ev.window, len(ev.value_codes))
            if device_ok
            else None
        )
        if plan is not None:
            bW, S = plan
            steps = events_to_steps(ev, W=bW)
            fut.steps = steps
            fut.S = S
            fut.W = bW
            if fut.checkpoint is not None:
                # Durable checks plan with the SINK's segment floor so
                # the content hash matches the sequential checkpointed
                # driver (replay/resume interchange across both paths).
                segs = bs._plan_for(steps, fut.checkpoint.seg_min_len)
                if len(segs) > 1:
                    # Multi-segment durable plan: the resident group
                    # driver is its own launch loop (a durable boundary
                    # per `every` segments) — resolved on the
                    # collecting thread, not a shared bucket.
                    fut.kind = "durable"
                    return
                # Single-segment durable plan: ride a normal coalesced
                # bucket. A finished checkpoint replays right here with
                # zero launches; otherwise the sink records the verdict
                # when the bucket resolves (_checkpoint_finish).
                _bump("durable_coalesced")
                if self._checkpoint_replay(fut, steps, m.name, S, segs):
                    return
            else:
                segs = bs._plan_for(steps, None)
                if len(segs) > 1:
                    fut.kind = "segmented"
                    return
            fut.kind = "bitset"
            n = bucket(max(len(steps), 1), 64)
            fut.key = (
                "bitset", m.name, S, bW, n, self.interpret, False
            )
            return
        W = _bucket_window(max(ev.window, 1))
        if (
            W is not None
            and not m.jax_capable
            and m.packed_variant
            and m.packed_ok is not None
            and m.packed_ok(ev)
        ):
            m = get_model(m.packed_variant)
        if W is None or not m.jax_capable:
            fut.kind = "fallback"
            return
        fut.kind = "vmap"
        fut.kernel_model = m.name
        fut.W = W
        steps = events_to_steps(ev, W=W)
        from jepsen_tpu.checker.linearizable import (
            _bucket_events,
            _jax_ok,
            _pallas_ok,
        )

        # Mirror the solo K-ladder's crash-skip heuristic: crash-heavy
        # histories start at the >=256 rungs (when runnable), so the
        # plane's starting rung — and therefore its verdict's
        # frontier_k — matches the sequential path exactly. The ladder
        # is part of the bucket key: a batch shares one rung schedule.
        NW = steps.NW
        n_crashed = (
            int(np.unpackbits(steps.crashed[-1].view(np.uint8)).sum())
            if len(steps)
            else 0
        )
        on_tpu_now = _on_tpu()

        def _runnable(K):
            return (on_tpu_now and _pallas_ok(K, W, NW)) or _jax_ok(
                K, W, NW
            )

        ladder = K_LADDER
        if n_crashed >= 6:
            bigger = tuple(
                K for K in ladder if K >= 256 and _runnable(K)
            )
            if bigger:
                ladder = bigger
        if not _runnable(ladder[0]):
            fut.kind = "fallback"  # first rung infeasible: oracle
            return
        fut.key = (
            "vmap", m.name, W,
            _bucket_events(max(len(steps), 1)), ladder,
        )

    # -- dispatch ------------------------------------------------------

    def _start_racer(self, fut: CheckFuture) -> None:
        """Competition racer, started AFTER the dispatch (sequential
        discipline: host prep is done, the core idles through the
        device scan / host sync)."""
        if not (self.race and fut.wrap and fut.events is not None):
            return
        if _race_eligible(fut.events, get_model(fut.model)):
            fut.racer = _NativeRacer(fut.events, fut.model)

    def _register_launch(self, launch: _Launch) -> None:
        """Register one in-flight train, double-buffered. The
        device->host copy of this train's outputs starts NOW
        (copy_to_host_async), so it overlaps the next train's host prep
        and device work; the later collect's device_get then mostly
        finds bytes already landed. At most ``max_inflight_trains``
        stay unresolved — registering past the cap collects the oldest
        train on THIS thread, which is exactly the backpressure that
        keeps an unbounded submit burst from queueing device memory."""
        try:
            for leaf in jax.tree_util.tree_leaves(launch.device_out()):
                leaf.copy_to_host_async()
        except Exception:  # noqa: BLE001 - overlap is best-effort
            pass
        with self._lock:
            self._launched.append(launch)
            pending = [L for L in self._launched if not L.resolved]
        _bump("train_registers")
        _bump("train_inflight_accum", len(pending))
        # inflight mirrors train_inflight_accum's bump, so occupancy is
        # recomputable from the trace alone (bench cross-check)
        obs_trace.instant("train_register", kind="dispatch",
                          inflight=len(pending))
        for f in launch.futs:
            f.launch = launch
        for f in launch.futs:
            self._start_racer(f)
        excess = len(pending) - self.max_inflight_trains
        if excess > 0:
            _bump("backpressure_collects", excess)
            self._collect_upto(pending[excess - 1])

    def _note_launch(self, n_requests: int, mesh=None) -> None:
        """Per-device accounting for one dispatch. A mesh-sharded
        stacked launch runs one shard on EVERY chip (1 launch each);
        its real requests split by the key_spec block layout (device i
        holds rows [i*k, (i+1)*k) of the padded batch). A solo/no-mesh
        dispatch lands whole on one device."""
        if mesh is None:
            _bump_device(
                str(self._devices[0]), requests=n_requests, launches=1
            )
            return
        devs = list(mesh.devices.flat)
        per = (n_requests + len(devs) - 1) // len(devs)
        for i, d in enumerate(devs):
            got = min(max(n_requests - i * per, 0), per)
            _bump_device(str(d), requests=got, launches=1)

    # -- resilience: guards + the degradation ladder -------------------

    def _labels(self, mesh) -> List[str]:
        """Device labels a guarded call may place work on — the chaos
        seam's match set and the classifier's attribution domain."""
        if mesh is not None:
            return [str(d) for d in mesh.devices.flat]
        return [str(d) for d in jax.devices()[:1]]

    def _guard(self, site: str, thunk, devices) -> Any:
        """Run one launch/collect callable through the chaos seam with
        this plane's retry policy and per-call deadline. Raises a
        structured PlaneFault when the budget is spent."""
        return chaos.resilient_call(
            thunk, site=site, devices=devices, policy=self.retry,
            deadline_s=self.launch_deadline_s, on_fault=self._on_fault,
        )

    def _on_fault(self, kind: str, device: Optional[str],
                  exc: BaseException) -> None:
        """Per-attempt failure accounting: attributed failures count
        against their device; crossing quarantine_after ejects it (the
        ladder then re-shards onto the survivors)."""
        if device is None:
            return
        if chaos.note_device_failure(device, self.quarantine_after):
            import logging

            if chaos.is_tenant_label(device):
                # A tenant breaker trip, not a chip ejection: the mesh
                # is untouched; the service's admission door sheds the
                # tenant (chaos.quarantined_tenants).
                logging.getLogger("jepsen_tpu.checker").warning(
                    "%s quarantined after %d attributed failures "
                    "(%s: %s); its submissions shed at admission",
                    device, self.quarantine_after,
                    type(exc).__name__, exc,
                )
                return
            from jepsen_tpu.checker.sharded import note_quarantine

            note_quarantine(device)
            logging.getLogger("jepsen_tpu.checker").warning(
                "device %s quarantined after %d attributed failures "
                "(%s: %s); launches re-shard onto the survivors",
                device, self.quarantine_after, type(exc).__name__, exc,
            )
            if self.host_domain_quarantine:
                # Host-level failure domain: on a mesh spanning >1
                # host slice, a dead chip condemns its WHOLE domain
                # (from across DCN a dead chip and a dead host are
                # indistinguishable, and a half-dead slice wedges pod
                # collectives). The ladder then ejects the slice in
                # one reshard instead of bleeding through it chip by
                # chip.
                from jepsen_tpu.pod import faultdomains

                h = faultdomains.escalate_device_to_host(
                    device, self.mesh
                )
                if h is not None:
                    logging.getLogger("jepsen_tpu.checker").warning(
                        "host domain %s quarantined with %s; its "
                        "whole slice ejects at the next reshard",
                        h, device,
                    )

    def _after_fault(self, mesh):
        """One degradation-ladder step after a guarded dispatch spent
        its retry budget: (1) a quarantine ejection re-shards the mesh
        onto the survivors (the blank-row pad absorbs the new uneven
        split; ``host:<i>`` ledger rows eject whole slices); (2) a
        multi-host mesh that failed WITHOUT ejection evidence retreats
        to this process's local host mesh (cross-host collectives no
        longer trusted, local chips still good); (3) no survivors
        worth sharding drops to the single-device dispatch; (4) a
        single-device failure exhausts the device rungs (the caller
        falls back to the host oracle). Returns (next_mesh, exhausted).
        Quarantine-driven shrinks of the PLANE's own mesh are sticky —
        future dispatches skip the dead chip without re-failing."""
        if mesh is None:
            chaos.note_degradation()
            return None, True
        from jepsen_tpu.checker.sharded import mesh_without, note_reshard
        from jepsen_tpu.pod import faultdomains

        healthy = mesh_without(mesh, chaos.mesh_ejection_labels())
        if healthy is not mesh and healthy is not None:
            note_reshard()
            if mesh is self.mesh:
                self.mesh = healthy
                self._devices = list(healthy.devices.flat)
            return healthy, False
        if healthy is mesh and len(faultdomains.host_domains(mesh)) > 1:
            local = faultdomains.local_host_mesh()
            if local is not None and local is not mesh:
                chaos.note_degradation()
                if mesh is self.mesh:
                    self.mesh = local
                    self._devices = list(local.devices.flat)
                return local, False
        chaos.note_degradation()
        if healthy is None and mesh is self.mesh:
            # quarantine left <2 survivors: the plane goes single-device
            self.mesh = None
            self._devices = jax.devices()[:1]
        return None, False

    def _dispatch_resilient(self, launch_with, mesh=_UNSET, tags=()):
        """Drive ``launch_with(mesh)`` down the degradation ladder:
        full mesh -> quarantine-resharded mesh -> single device.
        Returns (handle, mesh_used, None) on success or
        (None, None, PlaneFault) when every device rung failed — the
        caller resolves the riders from the host oracle. ``tags`` are
        the riders' tenant pseudo-labels (_tenant_tags): they join the
        guard's label list so faults can match and attribute by
        tenant without ever naming a real chip."""
        mesh = self.mesh if mesh is _UNSET else mesh
        while True:
            try:
                handle = self._guard(
                    "launch", lambda: launch_with(mesh),
                    self._labels(mesh) + list(tags),
                )
                return handle, mesh, None
            except PlaneFault as pf:
                mesh, exhausted = self._after_fault(mesh)
                if exhausted:
                    return None, None, pf

    def _observe(self, fut: CheckFuture, kind: str) -> None:
        cb = self.fault_observer
        if cb is None or fut.tenant is None:
            return
        try:
            cb(fut.tenant, kind)
        except Exception:  # noqa: BLE001 - observers never wedge
            pass

    def _oracle_resolve(self, futs, pf: PlaneFault) -> None:
        """The ladder's last rung: resolve each rider from the host
        oracle (_oracle_decide — pure host, no device dispatch), whose
        verdict is identical to the kernel path's by construction.
        Raw steps-level futures (run_keys) carry no events to
        re-decide, so they resolve with the structured PlaneFault
        itself — the raw device exception never crosses result()."""
        from jepsen_tpu.checker.linearizable import (
            _oracle_decide,
            _oracle_verdict,
        )

        for f in futs:
            if f.done():
                continue
            if f.events is None:
                chaos.note_plane_fault()
                self._observe(f, "plane_fault")
                f._fail(pf)
                continue
            chaos.note_oracle_fallback()
            self._observe(f, "oracle_fallback")
            try:
                out = _oracle_verdict(*_oracle_decide(f.events, f.model))
            except Exception as e:  # noqa: BLE001 - structured envelope
                chaos.note_plane_fault()
                self._observe(f, "plane_fault")
                f._fail(PlaneFault(
                    site="oracle", kind="fatal", attempts=1, cause=e,
                ))
                continue
            out["degraded"] = pf.describe()
            self._finish(f, out)

    def _flush_bucket(self, key) -> None:
        with self._lock:
            b = self._buckets.pop(key, None)
        if b is None:
            return
        now = time.perf_counter()
        wait_us = sum(
            (now - f._bucketed_at) * 1e6
            for f in b.futs
            if f._bucketed_at is not None
        )
        _bump("batches")
        _bump("batched_requests", len(b.futs))
        _bump("coalesce_wait_us", wait_us)
        with _stats_lock:
            DISPATCH_STATS["max_batch"] = max(
                DISPATCH_STATS["max_batch"], len(b.futs)
            )
        obs_trace.instant("dispatch_batch", kind="dispatch",
                          riders=len(b.futs), wait_us=wait_us,
                          bucket=key[0])
        try:
            with obs_trace.span("dispatch", kind="dispatch",
                                bucket=key[0], riders=len(b.futs)):
                # planelint: disable=JT502 reason=bucket-kind branch keys on replicated request data, so every pod member takes the same arm and meets the same collectives
                if key[0] == "bitset":
                    self._dispatch_bitset_batch(b.futs, key)
                # planelint: disable=JT502 reason=same data-uniform bucket-kind key as the branch above
                elif key[0] == "graph":
                    self._dispatch_graph_batch(b.futs, key)
                # planelint: disable=JT502 reason=same data-uniform bucket-kind key as the branches above
                elif key[0] == "stream":
                    self._dispatch_stream_batch(b.futs, key)
                else:
                    self._dispatch_vmap_batch(b.futs, key)
        except BaseException as e:  # noqa: BLE001
            for f in b.futs:
                f._fail(e)

    def _dispatch_bitset_batch(self, futs, key) -> None:
        _, name, S, _W, _n, interpret, exact = key

        def launch_with(mesh):
            return bs.launch_keys_bitset(
                [f.steps for f in futs], model=name, S=S,
                interpret=interpret, exact=exact, mesh=mesh,
            )

        handle, mesh_used, pf = self._dispatch_resilient(
            launch_with, tags=_tenant_tags(futs)
        )
        if handle is None:
            self._oracle_resolve(futs, pf)
            return
        launch = _Launch("bitset", futs, {
            "model": name, "S": S, "interpret": interpret,
            "exact": exact,
        })
        launch.handle = handle
        self._note_launch(len(futs), mesh_used)
        self._register_launch(launch)

    def _dispatch_stream_batch(self, futs, key) -> None:
        """Stack same-shape stream tails + their resident frontiers
        into one bitset launch. A ladder-exhausted dispatch fails the
        riders with the PlaneFault: the StreamingCheck catches it and
        falls back to its direct (solo) tail chain, so a degraded
        plane costs coalescing, never verdicts."""
        _, name, S, _W, _n, interpret, exact = key

        def launch_with(mesh):
            return bs.launch_tails_bitset(
                [f.steps for f in futs],
                [f.frontier for f in futs],
                model=name, S=S, interpret=interpret, exact=exact,
                mesh=mesh,
            )

        handle, mesh_used, pf = self._dispatch_resilient(
            launch_with, tags=_tenant_tags(futs)
        )
        if handle is None:
            # No oracle arm here: the frontier chain is the stream
            # handle's state, so degradation belongs to streaming.py
            # (it retries the tail solo and owns escalation).
            for f in futs:
                chaos.note_plane_fault()
                self._observe(f, "plane_fault")
                f._fail(pf)
            return
        _bump("stream_batches")
        launch = _Launch("stream", futs, {})
        launch.handle = handle
        self._note_launch(len(futs), mesh_used)
        self._register_launch(launch)

    #: coalesced graph launch memory cap, in elements per adjacency
    #: stack (3 stacks + 2 closures ride each launch)
    GRAPH_LAUNCH_ELEMS = 1 << 24

    def _dispatch_graph_batch(self, futs, key) -> None:
        """Concatenate same-shaped adjacency stacks into coalesced
        closure launches. Groups are bounded by GRAPH_LAUNCH_ELEMS so a
        max_batch pile-up of big stacks cannot blow device memory — an
        over-cap single future still launches (alone)."""
        _, n, need1, need2 = key
        per_graph = n * n
        group: list = []
        elems = 0
        for f in futs:
            b = int(f.graph[0].shape[0])
            if group and elems + b * per_graph > self.GRAPH_LAUNCH_ELEMS:
                self._launch_graph_group(group, need1, need2)
                group, elems = [], 0
            group.append(f)
            elems += b * per_graph
        if group:
            self._launch_graph_group(group, need1, need2)

    def _launch_graph_group(self, futs, need1: bool, need2: bool) -> None:
        from jepsen_tpu.checker import txn_graph as tg

        sizes = [int(f.graph[0].shape[0]) for f in futs]
        if len(futs) == 1:
            stacks = futs[0].graph
        else:
            stacks = tuple(
                np.concatenate([f.graph[i] for f in futs], axis=0)
                for i in range(3)
            )

        def launch_with(mesh):
            return tg.launch_graph_batch(
                *stacks, need1=need1, need2=need2, mesh=mesh,
            )

        handle, mesh_used, pf = self._dispatch_resilient(
            launch_with, tags=_tenant_tags(futs)
        )
        if handle is None:
            # no events to re-decide host-side: the checker catches the
            # PlaneFault at result() and runs its own census fallback
            for f in futs:
                chaos.note_plane_fault()
                self._observe(f, "plane_fault")
                f._fail(pf)
            return
        _bump("graph_batches")
        launch = _Launch("graph", futs, {"sizes": sizes})
        launch.handle = handle
        self._note_launch(len(futs), mesh_used)
        self._register_launch(launch)
        for f in futs:
            f.graph = None  # host stacks are dead weight once launched

    def _dispatch_vmap_batch(self, futs, key) -> None:
        import jax.numpy as jnp

        from jepsen_tpu.checker.sharded import _wgl_vmap, stack_streams

        _, name, W, _n, ladder = key
        K = ladder[0]

        def launch_with(mesh):
            if mesh is not None:
                from jax.sharding import NamedSharding

                from jepsen_tpu.checker.sharded import (
                    key_spec,
                    make_sharded_checker,
                    mesh_size,
                    note_sharded_launch,
                )

                n_dev = mesh_size(mesh)
                n_keys = ((len(futs) + n_dev - 1) // n_dev) * n_dev
                cols = stack_streams(
                    [f.events for f in futs], W=W, n_keys=n_keys,
                    model=name,
                )
                sharding = NamedSharding(mesh, key_spec(mesh))
                args = tuple(
                    jax.device_put(np.asarray(c), sharding)
                    for c in cols
                )
                fn = make_sharded_checker(mesh, name, K, W)
                out = fn(*args)
                note_sharded_launch(n_dev)
                return out
            cols = stack_streams(
                [f.events for f in futs], W=W, model=name
            )
            args = tuple(jnp.asarray(c) for c in cols)
            return _wgl_vmap(*args, model_name=name, K=K, W=W)

        handle, mesh_used, pf = self._dispatch_resilient(
            launch_with, tags=_tenant_tags(futs)
        )
        if handle is None:
            self._oracle_resolve(futs, pf)
            return
        launch = _Launch("vmap", futs, {
            "model": name, "K": K, "W": W, "k_ladder": ladder,
            "method": (
                "tpu-wgl-sharded" if mesh_used is not None
                else "tpu-wgl-batch"
            ),
        })
        launch.handle = handle
        self._note_launch(len(futs), mesh_used)
        self._register_launch(launch)

    def _dispatch_segmented(self, fut: CheckFuture) -> None:
        _bump("solo_launches")
        obs_trace.instant("dispatch_solo", kind="dispatch",
                          tenant=fut.tenant)
        # Round-robin segmented chains across the mesh: independent
        # requests' chains execute concurrently on different chips,
        # each on its own per-device launch train (jit follows the
        # committed args — see launch_steps_bitset_segmented). The
        # ladder here degrades by PLACEMENT: a failing chip's chain
        # re-places on the resharded mesh's pick, then the default
        # device, then the host oracle.
        mesh = self.mesh
        handle = dev = pf = None
        while handle is None:
            dev = None
            if mesh is not None:
                devs = list(mesh.devices.flat)
                dev = devs[next(self._rr) % len(devs)]
            labels = (
                [str(dev)] if dev is not None else self._labels(None)
            ) + _tenant_tags([fut])
            try:
                handle = self._guard(
                    "launch",
                    lambda: bs.launch_steps_bitset_segmented(
                        fut.steps, model=fut.model, S=fut.S,
                        interpret=self.interpret, device=dev,
                    ),
                    labels,
                )
            except PlaneFault as e:
                pf = e
                mesh, exhausted = self._after_fault(mesh)
                if exhausted:
                    self._oracle_resolve([fut], pf)
                    return
        launch = _Launch("segmented", [fut], {})
        launch.handle = handle
        _bump_device(
            str(dev if dev is not None else self._devices[0]),
            requests=1, launches=1,
        )
        self._register_launch(launch)

    # -- collection ----------------------------------------------------

    def _drive(self, fut: CheckFuture) -> None:
        """Make enough progress to resolve one future: prep the inbox,
        flush the bucket THIS future rides (other submitters' buckets
        keep coalescing — a result() call must not force-dispatch the
        whole plane), then collect its launch's prefix of the train."""
        self._pump(flush_futs=(fut,))
        if fut.done():
            return
        if fut.kind in ("fallback", "durable"):
            self._resolve_fallbacks()
            return
        while not fut.done():
            launch = fut.launch
            if launch is not None:
                self._collect_upto(launch)
                return
            # A concurrent flush (bucket-full trigger on a submitting
            # thread) popped the bucket but hasn't registered the
            # launch yet: it either registers or fails the futures.
            time.sleep(0.0005)

    def _collect_upto(self, target: _Launch) -> None:
        """ONE device_get over every unresolved launch up to (and
        including) the target, then resolve their futures. The device
        executes launches FIFO, so once the target's outputs are ready
        the prefix costs nothing extra to fetch — the whole train pays
        a single sync.

        Resolved launches leave the train immediately and drop their
        handle/future references: a launch pins its device output
        arrays and every rider's events/steps, so an append-only train
        on a long-lived plane (the process-wide default_plane()
        especially) would grow host+device memory for the life of the
        run — and degrade this method's index()/prefix scan — without
        bound."""
        with self._collect_lock:
            if target.resolved:
                return
            with self._lock:
                idx = self._launched.index(target)
                prefix = [
                    L for L in self._launched[: idx + 1]
                    if not L.resolved
                ]
            # Per-request competition: a racer that already finished
            # beats the device — its future resolves native and skips
            # the device verdict (discarded harmlessly), exactly the
            # sequential _race_decide outcome.
            for L in prefix:
                for f in L.futs:
                    if f.racer is not None and f.racer.done():
                        out = _native_win_verdict(
                            f.events, f.racer, f.model
                        )
                        if out is not None:
                            _bump("native_wins")
                            f.racer = None
                            f._resolve(out)
            try:
                # The train's one sync runs guarded: a transient fetch
                # failure retries, a hung sync times out against
                # launch_deadline_s (the wedged-plane class this layer
                # exists for) and retries, and an exhausted budget
                # degrades every rider below — the collecting thread
                # and the prep worker always come back.
                # One host sync for the whole train prefix (the
                # residency metric counts it; _register_launch started
                # the device->host copies, so by now the transfer has
                # mostly overlapped newer launches' device work).
                bs._bump_launch("host_syncs")
                # planelint: disable=JT302 reason=the collect span MUST wrap the guarded device_get, and collectors are serialized under _collect_lock by design (single collector per train prefix); ring append is lock-free so no cross-lock coupling
                with obs_trace.span("collect", kind="collect",
                                    trains=len(prefix)):
                    # planelint: disable=JT403 reason=the guarded device_get IS the collect phase _collect_lock exists to serialize; its retry backoff sleep is the resilient-call ladder, deadline-bounded
                    host = self._guard(
                        "collect",
                        lambda: jax.device_get(
                            tuple(L.device_out() for L in prefix)
                        ),
                        self._labels(self.mesh) + _tenant_tags(
                            [f for L in prefix for f in L.futs]
                        ),
                    )
            except PlaneFault as pf:
                try:
                    for L in prefix:
                        # planelint: disable=JT403 reason=_collect_lock is the collect-phase serializer by design; degrading the train to the oracle is part of the serialized phase and its crosscheck join is deadline-bounded
                        self._oracle_resolve(L.futs, pf)
                        L.resolved = True
                        for f in L.futs:
                            f.launch = None
                            f.steps = None
                        L.futs = []
                        L.handle = None
                finally:
                    with self._lock:
                        self._launched = [
                            L for L in self._launched if not L.resolved
                        ]
                return
            try:
                for L, h in zip(prefix, host):
                    try:
                        # planelint: disable=JT402,JT403 reason=_collect_lock is the collect-phase serializer by design: resolution (incl. the bitset collect's one global_view and the bounded crosscheck join) IS the serialized phase, not bookkeeping under it
                        self._resolve_launch(L, h)
                    except PlaneFault as pf:
                        # A collect-time escalation re-run exhausted
                        # its guard: this launch's riders degrade to
                        # the oracle; the rest of the train resolves
                        # normally.
                        # planelint: disable=JT403 reason=_collect_lock is the collect-phase serializer (one collector per train prefix by design, see PR 7); the oracle crosscheck join it reaches is deadline-bounded
                        self._oracle_resolve(L.futs, pf)
                    except BaseException as e:  # noqa: BLE001
                        # A half-resolved launch must not strand
                        # siblings in result() forever: fail the rest,
                        # re-raise.
                        for f in L.futs:
                            f._fail(e)
                        raise
                    finally:
                        L.resolved = True
                        for f in L.futs:
                            f.launch = None
                            f.steps = None
                        L.futs = []
                        L.handle = None
            finally:
                with self._lock:
                    self._launched = [
                        L for L in self._launched if not L.resolved
                    ]

    def _resolve_launch(self, launch: _Launch, host) -> None:
        if launch.kind == "bitset":
            self._resolve_bitset(launch, host)
        elif launch.kind == "segmented":
            self._resolve_segmented(launch, host)
        elif launch.kind == "graph":
            self._resolve_graph(launch, host)
        elif launch.kind == "stream":
            self._resolve_stream(launch, host)
        else:
            self._resolve_vmap(launch, host)

    def _resolve_stream(self, launch: _Launch, host) -> None:
        """Hand each stream rider its raw fast verdict plus its NEXT
        frontier as a device-side row slice of the stacked fr_out —
        the one fetch this train already paid covered the verdict
        array only, so frontiers stay resident for the next append's
        stacked launch. No escalation here: a provisional fast death
        is the StreamingCheck's to re-run sticky-exact."""
        fr_out = launch.handle[1][0]
        n_real = launch.handle[1][-1]
        verdicts = bs._out_to_verdicts(np.asarray(host))[:n_real]
        for i, (f, v) in enumerate(zip(launch.futs, verdicts)):
            if not f.done():
                alive, taint, died = v
                f._resolve((alive, taint, died, fr_out[i]))

    def _resolve_graph(self, launch: _Launch, host) -> None:
        """Slice the stacked per-graph count arrays back out to each
        rider: future i gets (g1c, g_single, g2), each [B_i]. Mesh
        padding rows live past the riders' total and are never read."""
        arrs = [np.asarray(a) for a in host]
        off = 0
        for f, b in zip(launch.futs, launch.meta["sizes"]):
            if not f.done():
                f._resolve(tuple(a[off:off + b] for a in arrs))
            off += b

    def _finish(self, fut: CheckFuture, out: dict) -> None:
        """Deliver a device-side verdict, running the racer crosscheck
        first (free differential coverage, sequential discipline). It
        is never deferred: the collecting thread may be one caller
        resolving other callers' futures."""
        if fut.racer is not None:
            _race_crosscheck(fut.racer, out["valid?"], defer=False)
            fut.racer = None
        if fut.checkpoint is not None and "checkpoint" not in out:
            self._checkpoint_finish(fut, out)
        fut._resolve(out)

    def _checkpoint_replay(self, fut, steps, name, S, segs) -> bool:
        """Bind a durable single-segment check to its sink at prep and
        replay a finished verdict with ZERO launches (fut.kind="done").
        Binding here computes the same content hash the sequential
        checkpointed driver would, so replay/resume interchange freely
        between the plane and `analyze --resume`. Returns True when the
        future resolved from the checkpoint."""
        from jepsen_tpu.checker import checkpoint as _cp

        sink = fut.checkpoint
        chash = _cp.steps_content_hash(steps, name, S, segs)
        state = sink.begin(chash, segs, name, S)
        v = state.get("verdict")
        if v is None:
            return False
        alive, died = bool(v["alive"]), int(v["died"])
        fr = sink.death_frontier_array()
        if fr is not None:
            steps._death_frontier = fr
        out = {
            "valid?": alive,
            "method": "tpu-wgl-bitset",
            "frontier_k": None,
            "escalations": 0,
            "checkpoint": sink.summary(),
        }
        if not alive:
            out["failed_op_index"] = died
            if fr is not None:
                out["failure"] = bs.decode_frontier(
                    fr, steps, died, fut.model,
                    decode_value=_decode_value(fut.events),
                )
        fut.kind = "done"
        fut._resolve(out)
        return True

    def _checkpoint_finish(self, fut: CheckFuture, out: dict) -> None:
        """Record a durable coalesced check's verdict in its sink: for
        single-segment durable plans begin() ran at prep and the
        verdict just resolved off a shared bucket, so finish() makes it
        replayable. Sinks that never began (streams outside the bitset
        envelope) have nothing to record. Durability must never wedge
        resolution: persistence failures leave the verdict intact."""
        sink = fut.checkpoint
        if getattr(sink, "_state", None) is None:
            return
        try:
            fr = None
            if out.get("valid?") is False and fut.steps is not None:
                fr = getattr(fut.steps, "_death_frontier", None)
            sink.finish(
                alive=bool(out.get("valid?")),
                taint=False,
                died=int(out.get("failed_op_index", -1)),
                death_frontier=fr,
            )
            out["checkpoint"] = sink.summary()
        except Exception:  # noqa: BLE001 - verdict delivery wins
            pass

    def _sequential_recheck(self, fut: CheckFuture) -> dict:
        """Full sequential re-check for a request whose batched verdict
        needs the solo path's artifacts (death reports) or tiers
        (K-ladder escalation). Rare by construction. Durable futures
        hand their sink through so the definite verdict (and death
        frontier) lands in the checkpoint."""
        return check_events_bucketed(
            fut.events, model=fut.kernel_model, race=False,
            interpret=self.interpret, checkpoint=fut.checkpoint,
        )

    def _resolve_bitset(self, launch: _Launch, host) -> None:
        verdicts = bs.collect_keys_bitset(
            launch.handle, out_host=np.asarray(host)
        )
        for f, v in zip(launch.futs, verdicts):
            if f.done():
                continue  # native racer already won
            if not f.wrap:
                f._resolve(v)
                continue
            alive, taint, died = v
            if taint or not alive:
                # Death/taint: the solo path supplies the definite
                # verdict + failure artifact (decode_frontier needs the
                # per-stream death frontier the stacked launch doesn't
                # keep). Deaths are rare; reports are worth the re-run.
                self._finish(f, self._sequential_recheck(f))
                continue
            self._finish(f, {
                "valid?": True,
                "method": "tpu-wgl-bitset-batch",
                "frontier_k": None,
                "escalations": 0,
            })

    def _resolve_segmented(self, launch: _Launch, host) -> None:
        fut = launch.futs[0]
        if fut.done():
            return
        alive, taint, died = bs.collect_steps_bitset_segmented(
            fut.steps, launch.handle, outs_host=host
        )
        if taint:  # impossible by construction; ladder decides
            self._finish(fut, self._sequential_recheck(fut))
            return
        out = {
            "valid?": alive,
            "method": "tpu-wgl-bitset",
            "frontier_k": None,
            "escalations": 0,
        }
        if not alive:
            out["failed_op_index"] = died
            fr = getattr(fut.steps, "_death_frontier", None)
            if fr is not None:
                out["failure"] = bs.decode_frontier(
                    fr, fut.steps, died, fut.model,
                    decode_value=_decode_value(fut.events),
                )
        self._finish(fut, out)

    def _resolve_vmap(self, launch: _Launch, host) -> None:
        from jepsen_tpu.checker.sharded import vmap_verdicts

        alive, overflow, died = (np.asarray(a) for a in host)
        live = [f for f in launch.futs if not f.done()]
        idx = [i for i, f in enumerate(launch.futs) if not f.done()]
        results = vmap_verdicts(
            [f.events for f in live],
            alive[idx], overflow[idx], died[idx],
            model=launch.meta["model"],
            k_ladder=launch.meta["k_ladder"],
            K=launch.meta["K"],
            method=launch.meta.get("method", "tpu-wgl-batch"),
        )
        for f, r in zip(live, results):
            self._finish(f, r)

    def _resolve_fallbacks(self) -> None:
        with self._lock:
            futs, self._fallbacks = self._fallbacks, []
        for f in futs:
            if f.done():
                continue
            try:
                # Durable solos inherit the plane's race policy (race=
                # None defers to eligibility): the sequential driver
                # runs its own racer crosscheck after the device
                # verdict. Plain fallbacks stay race=False — they are
                # the oracle rung, there is nothing to crosscheck.
                out = check_events_bucketed(
                    f.events, model=f.model,
                    race=(None if (self.race and f.checkpoint is not None)
                          else False),
                    interpret=self.interpret,
                    checkpoint=f.checkpoint,
                )
            except BaseException as e:  # noqa: BLE001
                f._fail(e)
            else:
                self._finish(f, out)

    # -- steps-level entry (check_keys_bitset's engine) ----------------

    def run_keys(
        self,
        steps_list,
        model: str = "cas-register",
        S: int = 8,
        interpret: bool = False,
        exact: bool = False,
        mesh=None,
    ) -> List[tuple]:
        """The check_keys_bitset engine, routed through the plane's
        launch/collect machinery: the caller's pre-stacked batch
        dispatches as ONE launch (launch accounting unchanged — tests
        pin launches==1; a mesh-sharded batch is still one launch),
        rides the shared launch train, and collects with the train's
        single sync. Returns raw (alive, taint, died) tuples.

        mesh: None defers to the plane's mesh; False forces the
        single-device dispatch; a Mesh shards the batch explicitly."""
        name = model if isinstance(model, str) else model.name
        use_mesh = self.mesh if mesh is None else (mesh or None)
        futs = []
        for st in steps_list:
            f = CheckFuture(self, None, name)
            f.kind = "bitset"
            f.steps = st
            f.wrap = False
            futs.append(f)
        _bump("requests", len(futs))
        _bump("batches")
        _bump("batched_requests", len(futs))
        with _stats_lock:
            DISPATCH_STATS["max_batch"] = max(
                DISPATCH_STATS["max_batch"], len(futs)
            )
        obs_trace.instant("dispatch_batch", kind="dispatch",
                          riders=len(futs), wait_us=0.0,
                          bucket="bitset")

        def launch_with(m):
            return bs.launch_keys_bitset(
                steps_list, model=name, S=S, interpret=interpret,
                exact=exact, mesh=m,
            )

        handle, mesh_used, pf = self._dispatch_resilient(
            launch_with, mesh=use_mesh, tags=_tenant_tags(futs)
        )
        if handle is None:
            # Raw steps carry no events to re-decide on the host: the
            # structured PlaneFault is the resolution (result() raises
            # it — never the raw device exception). Every injected
            # fault class resolves on an earlier rung.
            self._oracle_resolve(futs, pf)
            return [f.result() for f in futs]
        launch = _Launch("bitset", futs, {
            "model": name, "S": S, "interpret": interpret,
            "exact": exact,
        })
        launch.handle = handle
        self._note_launch(len(futs), mesh_used)
        self._register_launch(launch)
        self._collect_upto(launch)
        return [f.result() for f in futs]


#: process-wide default plane: check_keys_bitset and other synchronous
#: entry points route through it so their launches join one train (and
#: one stats surface) with any concurrent async submitters.
_DEFAULT_PLANE: Optional[DispatchPlane] = None
_default_lock = threading.Lock()


def default_plane(**kw) -> DispatchPlane:
    """The process-wide plane, built lazily. Keyword arguments shape
    the plane ONLY on first construction (the service daemon owns the
    process and configures interpret/deadline/retry up front); later
    callers get the existing plane unchanged — call
    reset_default_plane() first to reconfigure. Construction consults
    the persisted perf profile (perf.knobs.ensure_profile) for every
    knob not pinned by a kwarg."""
    global _DEFAULT_PLANE
    with _default_lock:
        if _DEFAULT_PLANE is None:
            kw.setdefault("async_prep", False)
            _DEFAULT_PLANE = DispatchPlane(**kw)
        return _DEFAULT_PLANE


def drain_default_plane() -> None:
    """Collect the process-wide plane's outstanding launch train
    (no-op when no plane exists). A native-racer win resolves its
    rider without forcing the train's collect (_drive returns on
    fut.done() before _collect_upto), so an end-of-run accounting
    snapshot taken right after the last verdict can otherwise miss
    the train's host sync — and leave its device buffers pinned.
    End-of-run reporters (cli results.json / analyze --trace) call
    this before reading stats so the ledger is complete."""
    with _default_lock:
        plane = _DEFAULT_PLANE
    if plane is not None:
        plane.drain()


def reset_default_plane() -> None:
    """Close and discard the process-wide plane (the next
    default_plane() builds a fresh one over the currently-healthy
    mesh). The seam chaos tests use to undo a sticky quarantine
    shrink; operators can use it to re-admit a repaired chip after
    chaos.reset_resilience()."""
    global _DEFAULT_PLANE
    with _default_lock:
        plane, _DEFAULT_PLANE = _DEFAULT_PLANE, None
    if plane is not None:
        plane.close()

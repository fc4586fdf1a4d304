"""Linearizability checker: host driver around the TPU WGL kernel.

Replaces the reference's knossos delegation
(jepsen/src/jepsen/checker.clj:127-158). The pipeline:

  History ──history_to_events──▶ EventStream ──bucket/pad──▶ TPU kernel
                                      │                          │
                                      └────── CPU oracle ◀─ escalation
                                               fallback

Shape discipline (XLA compiles one program per distinct shape):
- event count pads up to the next power-of-two bucket with NOP events;
- the slot window W rounds up to {4, 8, 16, 32, 64, 128} (multi-word
  masks — 32 slots per int32 word);
- the frontier capacity K escalates 64 → 256 → 1024 only when a False
  verdict is tainted by frontier overflow (a True verdict is a witness
  and never needs escalation — wgl_jax.py docstring). Dominance pruning
  keeps pruned frontiers small, so escalation is rare even on
  crash-heavy histories.

If the largest K still overflows, or concurrency exceeds the 128-slot
mask, the unbounded CPU oracle decides. Verdicts therefore always come
back definite (True/False), with `method` recording who produced them,
and a False verdict carries `failed_op_index` — the history index of
the completion whose RETURN filter emptied the frontier (the analog of
the reference's failing-op report, checker.clj:146-154).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Optional

import numpy as np

from jepsen_tpu.checker.events import (
    EventStream,
    WindowOverflow,
    events_to_steps,
    history_to_events,
)
from jepsen_tpu.checker.wgl_oracle import check_events_fast as oracle_check_fast
from jepsen_tpu.checker.wgl_jax import check_steps_jax
from jepsen_tpu.obs import trace as obs_trace

#: K escalation ladder: frontier capacities tried in order. Starts at
#: 128: measured closure-width distributions on register workloads put
#: p99 well under 128 (mean ~11), so the first rung almost always
#: decides, and dominance pruning keeps crash-heavy histories inside it.
K_LADDER = (128, 256, 1024)

#: VMEM budget for the Pallas megakernel's [K, W, K] intermediates
#: (v5e scoped vmem is 16 MiB; ~2.2 such buffers live at peak).
_PALLAS_VMEM_ELEMS = 1_500_000

#: HBM budget for the pure-JAX kernel's [N, N] canonicalize matrices,
#: N = K*(1+W): beyond this the rung would allocate multi-GB
#: intermediates per closure round, so the ladder skips it (the oracle
#: decides instead — verdicts stay definite either way). Sized so the
#: K=128 rung covers windows up to 64 (two mask words).
_JAX_MATRIX_ELEMS = 160_000_000


def _pallas_ok(K: int, W: int, NW: int) -> bool:
    return NW == 1 and K * K * W <= _PALLAS_VMEM_ELEMS


def _jax_ok(K: int, W: int, NW: int) -> bool:
    n = K * (1 + W)
    return n * n * NW <= _JAX_MATRIX_ELEMS


#: W buckets: slot-window sizes the kernel is compiled for.
W_BUCKETS = (4, 8, 16, 32, 64, 128)


def _on_tpu() -> bool:
    """True when the default JAX backend is a real TPU (where the
    Pallas megakernel can compile)."""
    import jax

    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend init failure
        return False


def interpret_off_chip(who: str) -> bool:
    """Pallas mode for a driver that needs the kernels: compiled on a
    TPU, interpreted only when the CPU was chosen on purpose
    (``JAX_PLATFORMS=cpu``). Any other backend is an error naming
    what JAX found: finding no chip is never a reason to interpret."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return True
    raise RuntimeError(
        f"{who}: no TPU found (JAX backend {platform!r}, devices "
        f"{jax.devices()}); set JAX_PLATFORMS=cpu to run the kernels "
        "in interpret mode on purpose"
    )


def _bucket_window(window: int) -> Optional[int]:
    for w in W_BUCKETS:
        if window <= w:
            return w
    return None


def _bucket_events(n: int) -> int:
    from jepsen_tpu.checker.events import bucket

    return bucket(n, 64)


def _bitset_plan(events: EventStream, m) -> Optional[tuple]:
    """(W, S) for the exact bitset kernel, or None when the stream is
    outside its envelope (window, state rows, or model shape)."""
    from jepsen_tpu.checker import wgl_bitset as bs

    return bs.plan(m, events.window, len(events.value_codes))


def _decode_value(events: EventStream):
    """code -> original value decoder for failure reports (intern keys
    are ("int", 2)-style tuples)."""
    rev = {c: k for k, c in events.value_codes.items()}

    def dec(c):
        if c < 0:
            return None
        k = rev.get(c)
        if isinstance(k, tuple) and len(k) == 2:
            return k[1]
        return k

    return dec


def oracle_failure_report(events: EventStream, stats: dict, model):
    """Build the decode_frontier-shaped failure report from the Python
    oracle's death material, so invalid verdicts carry the same
    linear.svg-role artifact on every engine path (checker.clj:146-154).
    Returns None when the stats carry no death configs (valid verdict,
    or the native rung decided — callers re-run the Python oracle for
    the report in that case: failure analysis is rare and worth it,
    the reference budgets hours for report writing)."""
    if "death_configs" not in stats:
        return None
    from jepsen_tpu.checker.models import model as get_model

    m = get_model(model)
    f_names: dict = {}
    for name, code in m.f_names.items():
        f_names.setdefault(code, str(name))
    dec = _decode_value(events)
    open_ops = stats["death_open_ops"]

    def op_desc(slot: int) -> dict:
        f, a, b = open_ops[slot]
        name = f_names.get(f, "?")
        d = {"slot": slot, "f": name, "value": dec(a)}
        if name in ("cas", "compare-and-set"):
            d["value"] = [dec(a), dec(b)]
        return d

    configs = []
    for state, mask in stats["death_configs"]:
        configs.append({
            "state": m.state_repr(state, dec),
            "linearized": [
                op_desc(s) for s in sorted(open_ops)
                if (mask >> s) & 1
            ],
            "pending": [
                op_desc(s) for s in sorted(open_ops)
                if not (mask >> s) & 1
            ],
        })
    return {
        "failed_op": op_desc(stats["death_slot"]),
        "configs": configs,
    }


def _oracle_verdict(valid, stats, failure, **extra) -> dict:
    """The one place a cpu-oracle verdict dict is assembled."""
    out = {
        "valid?": valid,
        "method": f"cpu-oracle-{stats['oracle']}",
        **extra,
    }
    if not valid:
        out["failed_op_index"] = stats["failed_op_index"]
        if failure is not None:
            out["failure"] = failure
    return out


def _harvest_failure(events: EventStream, out: dict, model) -> None:
    """Attach the failure report to an invalid verdict that arrived
    index-only (K-frontier rungs, the native oracle, the dispatch
    plane's batched tiers): re-run the Python oracle and decode its
    death material in place. Rare and worth the re-run (the reference
    budgets hours for report writing, checker.clj:155-158). No-op for
    valid verdicts or ones already carrying a report — every invalid
    verdict path (check, check_async, queue-by-value) funnels here so
    _render_failure always has its artifact."""
    if out.get("valid?") is not False or "failure" in out:
        return
    from jepsen_tpu.checker.wgl_oracle import check_events

    with obs_trace.span("verdict.harvest", kind="verdict"):
        _, py_stats = check_events(events, model=model, return_stats=True)
        failure = oracle_failure_report(events, py_stats, model)
    if failure is not None:
        out["failure"] = failure


def _decode_death(frontier, bsteps, died: int, model, events):
    """The failure report of a bitset-kernel death, decoded from the
    dying frontier the kernel already returned."""
    from jepsen_tpu.checker.wgl_bitset import decode_frontier

    with obs_trace.span("verdict.harvest", kind="verdict"):
        return decode_frontier(
            frontier, bsteps, died, model,
            decode_value=_decode_value(events),
        )


def _oracle_decide(events: EventStream, model):
    """Oracle verdict + (on invalid) the failure report, re-running the
    Python rung when the native one decided (it carries no frontier)."""
    valid, stats = oracle_check_fast(
        events, model=model, return_stats=True
    )
    failure = None
    if not valid:
        if "death_configs" not in stats:
            from jepsen_tpu.checker.wgl_oracle import check_events

            _, py_stats = check_events(
                events, model=model, return_stats=True
            )
            py_stats["oracle"] = stats["oracle"]
            stats = py_stats
        failure = oracle_failure_report(events, stats, model)
    return valid, stats, failure


#: largest stream the decision race will hand to the native-oracle
#: thread: above this the TPU always wins and the loser thread would
#: burn the host core long after the verdict (no cancellation seam in
#: a blocking ctypes call).
RACE_MAX_OPS = 20_000


#: how long a cross-check waits for a racer that lost to the device:
#: one that lands within it is compared, a slower one goes unchecked.
RACE_GRACE_S = 0.05


class _NativeRacer:
    """Background native-oracle run for the competition race
    (knossos's `competition` role, checker.clj:128-144): the TPU
    kernel and the C++ oracle start together, the first definite
    verdict wins, and when both land the verdicts cross-check —
    production differential coverage for free.

    The ctypes call releases the GIL, so the oracle genuinely overlaps
    the device round trip; on a busy single-core host callers start
    the racer AFTER host-side prep so the threads don't contend. When
    the device wins for one key of a keyed history (a
    deferred_crosschecks scope is open), the racer keeps running while
    the caller preps the next key, and its cross-check settles later
    (_Crosschecks); the verdict never depends on it."""

    def __init__(self, events: EventStream, model):
        import threading

        self.result: Optional[tuple] = None
        self.error: Optional[BaseException] = None
        ev, mdl = events, model
        # the caller's span (the key's check): the racer's span hangs
        # under it across the thread boundary
        parent = obs_trace.current()

        def run():
            try:
                from jepsen_tpu.checker.wgl_native import (
                    check_events_native,
                )

                with obs_trace.span("racer.native", kind="racer",
                                    parent=parent):
                    self.result = check_events_native(
                        ev, model=mdl, return_stats=True
                    )
            except BaseException as e:  # noqa: BLE001 - report later
                self.error = e

        self._thread = threading.Thread(
            target=run, daemon=True, name="wgl-native-race"
        )
        self._thread.start()

    def done(self) -> bool:
        return not self._thread.is_alive()

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)


def _race_eligible(events: EventStream, m) -> bool:
    from jepsen_tpu.checker import wgl_native

    return (
        events.n_ops <= RACE_MAX_OPS
        and events.window <= 64
        and m.name in wgl_native._MODEL_IDS
        and wgl_native.available()
    )


#: cumulative race outcomes for observability (bench engine_stats and
#: run epitaphs read this; reset_race_stats() for tests). Updated via
#: _bump_race: races now finish on the dispatch plane's collecting
#: threads as well as the caller's, and unlocked += drops counts under
#: that interleaving.
RACE_STATS = {
    "tpu_wins": 0,
    "native_wins": 0,
    "crosschecked": 0,
    "mismatches": 0,
    # cross-checks settled after their key's check returned
    # (deferred_crosschecks)
    "deferred": 0,
}

_race_stats_lock = threading.Lock()


def _bump_race(key: str, n: int = 1) -> None:
    with _race_stats_lock:
        RACE_STATS[key] += n


def reset_race_stats() -> None:
    with _race_stats_lock:
        for k in RACE_STATS:
            RACE_STATS[k] = 0


def _tpu_handle_ready(handle) -> bool:
    outs = handle[0]
    try:
        return all(o.is_ready() for o in outs)
    except AttributeError:  # pragma: no cover - very old jax
        return True


def _native_win_verdict(events, racer, model, escalations=0):
    """Assemble the verdict dict for a native race win, or None if the
    racer crashed/declined (its envelope check returned None)."""
    if racer.error is not None or racer.result is None:
        return None
    valid, stats = racer.result
    _bump_race("native_wins")
    out = {
        "valid?": valid,
        "method": "cpu-oracle-native",
        "race_winner": "native",
        "frontier_k": None,
        "escalations": escalations,
    }
    if not valid:
        out["failed_op_index"] = stats.get("failed_op_index")
        # The native oracle carries no death-config material;
        # failure analysis is rare and worth a Python re-run
        # (the reference budgets hours for report writing).
        with obs_trace.span("verdict.harvest", kind="verdict"):
            _, py_stats, failure = _oracle_decide(events, model)
        if failure is not None:
            out["failure"] = failure
    return out


def _race_decide(events, bsteps, handle, racer, model):
    """Poll until either engine produces a verdict. Returns the
    assembled verdict dict when the NATIVE side wins, or None when the
    TPU result is ready first (the caller collects it normally). A
    native win leaves the device work to finish harmlessly in the
    background; a TPU win leaves the oracle thread to run out (bounded
    by the RACE_MAX_OPS gate)."""
    import time as _time

    with obs_trace.span("device_wait", kind="sync"):
        while not _tpu_handle_ready(handle) and not racer.done():
            _time.sleep(0.001)
    if _tpu_handle_ready(handle):
        return None
    # native win, or None when the oracle crashed/declined: TPU decides
    return _native_win_verdict(events, racer, model)


def _settle_crosscheck(racer, tpu_alive: bool) -> bool:
    """Compare a racer's verdict with the device's, if it has landed
    one. A mismatch means an engine bug; it is logged loudly and
    counted (the differential soaks treat any mismatch as a failure).
    False when the racer is still running, crashed or declined."""
    if not racer.done() or racer.error or racer.result is None:
        return False
    _bump_race("crosschecked")
    native_valid = racer.result[0]
    if bool(native_valid) != bool(tpu_alive):
        _bump_race("mismatches")
        import logging

        logging.getLogger("jepsen_tpu.checker").critical(
            "RACE MISMATCH: tpu-wgl-bitset=%s cpu-oracle-native=%s — "
            "engine bug; file with the stream's seed/material",
            tpu_alive, native_valid,
        )
    return True


class _Crosschecks:
    """The racers that lost to the device on one thread, waiting for
    their cross-check (deferred_crosschecks). At most `cap` wait: a
    TPU win past the cap first joins the oldest. Every join gives its
    racer at least RACE_GRACE_S from the moment the caller blocks, so
    nothing the immediate cross-check would have compared goes
    unchecked; each wait is a `racer.wait` span."""

    def __init__(self, cap: int):
        self.cap = cap
        self.pending: collections.deque = collections.deque()

    def _settle(self, racer, tpu_alive: bool) -> None:
        if _settle_crosscheck(racer, tpu_alive):
            _bump_race("deferred")

    def add(self, racer, tpu_alive: bool) -> None:
        if len(self.pending) >= self.cap:
            self.settle_finished()
        while len(self.pending) >= self.cap:
            oldest, alive = self.pending.popleft()
            with obs_trace.span("racer.wait", kind="racer"):
                oldest.join(RACE_GRACE_S)
            self._settle(oldest, alive)
        self.pending.append((racer, tpu_alive))

    def settle_finished(self) -> None:
        """Settle the racers that have already landed; no wait."""
        running: collections.deque = collections.deque()
        for racer, alive in self.pending:
            if racer.done():
                self._settle(racer, alive)
            else:
                running.append((racer, alive))
        self.pending = running

    def drain(self) -> None:
        if not self.pending:
            return
        deadline = time.perf_counter() + RACE_GRACE_S
        with obs_trace.span("racer.wait", kind="racer"):
            for racer, _ in self.pending:
                racer.join(max(0.0, deadline - time.perf_counter()))
        while self.pending:
            self._settle(*self.pending.popleft())


_scope = threading.local()


def _crosscheck_cap() -> int:
    """Racers that may wait at once: one per core the caller leaves
    free, so 0 — settle at once — on a one-core host."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - no affinity API
        cores = os.cpu_count() or 1
    return cores - 1


@contextlib.contextmanager
def deferred_crosschecks():
    """Defer the cross-checks of TPU wins on this thread until the
    scope closes. For a caller that checks many short histories in a
    row (the keys of one keyed history): the native racer of a key the
    device already decided finishes while the next key is prepped,
    rather than the caller waiting on it. The scope yields its
    _Crosschecks, whose settle_finished() the caller may call between
    checks; on exit every pending racer is joined and compared, so the
    counters are whole once the scope returns. Re-entrant: a nested
    scope joins the open one, which drains when the outermost exits.
    Thread-local: other threads keep the immediate cross-check."""
    scope = getattr(_scope, "crosschecks", None)
    if scope is not None:
        yield scope
        return
    scope = _scope.crosschecks = _Crosschecks(_crosscheck_cap())
    try:
        yield scope
    finally:
        _scope.crosschecks = None
        scope.drain()


def _race_crosscheck(racer, tpu_alive: bool, defer: bool = True) -> None:
    """TPU won the race: cross-check the verdicts if the oracle lands
    within RACE_GRACE_S — free production differential coverage. The
    verdict never depends on it. Inside a deferred_crosschecks scope
    (and with defer) the racer is left running and settles after its
    key's check has returned; callers whose cross-check must follow
    the verdict at once (the checkpointed driver, the dispatch plane's
    shared collecting path) pass defer=False."""
    _bump_race("tpu_wins")
    scope = getattr(_scope, "crosschecks", None) if defer else None
    if scope is not None and scope.cap > 0:
        scope.add(racer, tpu_alive)
        return
    with obs_trace.span("racer.wait", kind="racer"):
        racer.join(RACE_GRACE_S)
    _settle_crosscheck(racer, tpu_alive)


def check_events_bucketed(
    events: EventStream,
    model: str = "cas-register",
    k_ladder=K_LADDER,
    race: Optional[bool] = None,
    interpret: bool = False,
    checkpoint=None,
) -> dict:
    """Definite linearizability verdict for an event stream.

    Returns {"valid?": bool, "method": "tpu-wgl-bitset"|"tpu-wgl"|
             "cpu-oracle-native"|"cpu-oracle-python", "frontier_k": K or None, "escalations": int}.

    race: run the native C++ oracle concurrently with the TPU kernel
    and take the first verdict (knossos competition, checker.clj:
    128-144). Default: on for streams the native envelope covers and
    small enough that the losing thread's overrun is bounded
    (RACE_MAX_OPS). Pass False for pure-TPU measurement runs. When the
    device wins, the racer's verdict is only cross-checked (counted in
    RACE_STATS), never used: at once within RACE_GRACE_S, or, inside a
    deferred_crosschecks scope (the keys of a keyed history), settled
    after this call returns while the next key runs.

    interpret: run the bitset kernel in Pallas interpret mode on CPU —
    the tests' seam for exercising the device branch (race logic,
    launch accounting, escalation) without a TPU.

    checkpoint: a checkpoint.CheckpointSink routes the bitset tier
    through the durable resident group driver (one launch and one host
    sync per `every=N` persistence boundary, crash-safe resume — see
    wgl_bitset.check_steps_bitset_segmented_checkpointed). The racer
    runs as a post-verdict crosscheck for checkpointed checks: the
    device verdict lands in the durable trail first, then the native
    oracle must agree — a native "win" never races past persistence.
    Only the bitset envelope checkpoints; out-of-envelope streams
    ignore the sink and run their usual path.
    """
    from jepsen_tpu.checker.models import model as get_model

    # Exact bitset kernel first: for windows <= 16 and small state
    # spaces it holds the ENTIRE config space, so its verdict is always
    # definite — no escalation ladder, no oracle fallback (wgl_bitset
    # module docstring). taint is impossible by construction; if it ever
    # fires, fall through to the capacity-ladder paths below.
    racer = None  # one native racer serves bitset AND ladder tiers
    with obs_trace.span("prep.steps", kind="prep"):
        W = _bucket_window(max(events.window, 1))
        m = get_model(model)
        plan = (
            _bitset_plan(events, m)
            if (_on_tpu() or interpret)
            else None
        )
        if plan is not None:
            bW, S = plan
            bsteps = events_to_steps(events, W=bW)  # memoized per stream
    if plan is not None:
        from jepsen_tpu.checker.wgl_bitset import (
            collect_steps_bitset_segmented,
            launch_steps_bitset_segmented,
        )

        if checkpoint is not None:
            from jepsen_tpu.checker.wgl_bitset import (
                check_steps_bitset_segmented,
            )

            if race is None:
                race = _race_eligible(events, m)
            if race:
                # Crosscheck, not competition: the racer starts before
                # the (long) durable driver so the native scan overlaps
                # device work, but its verdict is only COMPARED after
                # the device verdict is durably recorded.
                racer = _NativeRacer(events, model)
            alive, taint, died = check_steps_bitset_segmented(
                bsteps, model=model, S=S, interpret=interpret,
                checkpoint=checkpoint,
            )
            if not taint:
                if racer is not None:
                    _race_crosscheck(racer, alive, defer=False)
                    racer = None
                out = {
                    "valid?": alive,
                    "method": "tpu-wgl-bitset",
                    "frontier_k": None,
                    "escalations": 0,
                    "checkpoint": checkpoint.summary(),
                }
                if not alive:
                    out["failed_op_index"] = died
                    fr = getattr(bsteps, "_death_frontier", None)
                    if fr is not None:
                        out["failure"] = _decode_death(
                            fr, bsteps, died, model, events
                        )
                return out
        # Segment-aware: the prefix before crashes widen the window
        # runs on the narrow (16x cheaper) kernel; padding/bucketing
        # happens per segment inside.
        with obs_trace.span("launch", kind="launch"):
            handle = launch_steps_bitset_segmented(
                bsteps, model=model, S=S, interpret=interpret
            )
        if race is None:
            race = _race_eligible(events, m)
        if race:
            # Start AFTER the dispatch: host prep is done, the core is
            # otherwise idle while the device scans / the host syncs.
            # (A tainted checkpointed run falls through with its racer
            # already live — reuse it rather than spawning a second.)
            if racer is None:
                racer = _NativeRacer(events, model)
            verdict = _race_decide(
                events, bsteps, handle, racer, model
            )
            if verdict is not None:
                return verdict
        alive, taint, died = collect_steps_bitset_segmented(
            bsteps, handle
        )
        if racer is not None:
            _race_crosscheck(racer, alive)
            # The crosscheck consumed this racer's verdict (counted a
            # tpu_win): drop it so the taint fall-through below can't
            # hand the same finish to the K-ladder and double-count.
            racer = None
        if not taint:
            out = {
                "valid?": alive,
                "method": "tpu-wgl-bitset",
                "frontier_k": None,
                "escalations": 0,
            }
            if not alive:
                out["failed_op_index"] = died
                fr = getattr(bsteps, "_death_frontier", None)
                if fr is not None:
                    out["failure"] = _decode_death(
                        fr, bsteps, died, model, events
                    )
            return out
    if (
        W is not None
        and not m.jax_capable
        and m.packed_variant
        and m.packed_ok is not None
        and m.packed_ok(events)
    ):
        # Rich-state model whose bounded encoding fits a machine word
        # (packed queue count-vectors): substitute the packed variant
        # so the history rides the K-frontier kernels instead of
        # detouring to the host oracle.
        m = get_model(m.packed_variant)
        model = m.name
    if W is None or not m.jax_capable:
        # Too concurrent for the masks, or the model's state doesn't
        # fit a machine word (out-of-envelope queue multisets): the
        # oracle decides.
        reason = (
            f"window {events.window} exceeds {W_BUCKETS[-1]} slots"
            if W is None
            else f"model {m.name} is host-only (rich state)"
        )
        valid, stats, failure = _oracle_decide(events, model)
        return _oracle_verdict(
            valid, stats, failure,
            frontier_k=None, escalations=0, reason=reason,
        )

    steps = events_to_steps(events, W=W)
    ki = m.kernel_init_code(events.init_state)
    if ki != steps.init_state:
        # Packed models re-encode the initial state (e.g. empty
        # multiset = 0, not the NIL code). Copy rather than mutate:
        # the memoized steps object may serve other models.
        import dataclasses

        steps = dataclasses.replace(steps, init_state=ki)
    # Crash-heavy histories blow past the first rung almost surely (the
    # pruned frontier still grows with the crashed-op antichain), so
    # skip rungs that measured frontier statistics say are doomed: with
    # c crashed slots the pruned width commonly reaches ~2^min(c,8)+.
    # (Counted BEFORE padding — pad rows have all-zero crash masks.)
    n_crashed = (
        int(np.unpackbits(steps.crashed[-1].view(np.uint8)).sum())
        if len(steps)
        else 0
    )
    steps = steps.padded(_bucket_events(max(len(steps), 1)))
    on_tpu_now = _on_tpu()
    if n_crashed >= 6:
        # Only skip ahead if a bigger rung is actually runnable at this
        # (W, NW) — otherwise keep the small rungs (a wide window with
        # many crashed ops can still have a tiny pruned frontier).
        bigger = tuple(
            K for K in k_ladder
            if K >= 256
            and (
                (on_tpu_now and _pallas_ok(K, W, steps.NW))
                or _jax_ok(K, W, steps.NW)
            )
        )
        if bigger:
            k_ladder = bigger
    # On a real TPU with single-word masks, the Pallas megakernel runs
    # the whole scan in one fused kernel (~10x the pure-JAX scan, which
    # pays per-op dispatch for every return step). The pure-JAX path
    # remains the fallback for wide windows, big-K rungs that exceed the
    # kernel's VMEM budget, CPU meshes, and shard_map.
    on_tpu = on_tpu_now
    # The K-ladder is where escalation-heavy histories burn time, so
    # the competition race matters most here (checker.clj:128-144):
    # the native oracle runs through every rung, and its verdict is
    # taken at the next rung boundary if it lands first.
    if race is None:
        race = on_tpu_now and _race_eligible(events, m)
    if race and racer is None:
        # (an already-running racer from the bitset branch's taint
        # fall-through is reused, not duplicated)
        racer = _NativeRacer(events, model)
    elif not race:
        racer = None
    escalations = 0
    for K in k_ladder:
        if racer is not None and racer.done():
            out = _native_win_verdict(
                events, racer, model, escalations
            )
            if out is not None:
                return out
            racer = None  # oracle crashed/declined: ladder decides
        if on_tpu and _pallas_ok(K, W, steps.NW):
            from jepsen_tpu.checker.wgl_pallas import check_steps_pallas

            alive, overflow, died = check_steps_pallas(
                steps, model=model, K=K
            )
            method = "tpu-wgl-pallas"
        elif _jax_ok(K, W, steps.NW):
            alive, overflow, died = check_steps_jax(steps, model=model, K=K)
            method = "tpu-wgl"
        else:
            # Rung infeasible at this (K, W): the matrices would blow
            # the memory budget. Fall through to the oracle.
            break
        if alive or not overflow:
            out = {
                "valid?": alive,
                "method": method,
                "frontier_k": K,
                "escalations": escalations,
            }
            if not alive:
                out["failed_op_index"] = died
            if racer is not None:
                _race_crosscheck(racer, alive)
            return out
        escalations += 1
    if racer is not None:
        # Every rung overflowed and the racer is already computing
        # exactly the oracle verdict we need: wait for it rather than
        # starting a second native run.
        racer.join(3600.0)
        out = _native_win_verdict(events, racer, model, escalations)
        if out is not None:
            return out
    valid, stats, failure = _oracle_decide(events, model)
    return _oracle_verdict(
        valid, stats, failure,
        frontier_k=None, escalations=escalations,
        reason=f"frontier overflowed at K={k_ladder[-1]}",
    )


def split_queue_history_by_value(history):
    """Per-value subhistories of an unordered-queue history, or None
    when the history doesn't decompose (non-enq/deq ops, or a
    pathological ok-dequeue/enqueue of nil).

    Soundness: the unordered queue's state factorizes by value —
    enqueue is always enabled, dequeue(v) is gated only by v's own
    count, and transitions of distinct values commute — so this is
    Herlihy-Wing locality with each value as its own object: H is
    linearizable iff every per-value subhistory is. (Pick
    linearization points per subhistory witness; the ops are disjoint,
    so the pointwise merge is a global witness.) Crashed dequeues with
    unknown value can never linearize (the model's NIL rule — the
    value taken can't be named), so they are vacuous and dropped, same
    as the joint model treats them.

    The payoff is the device envelope: each subhistory has ONE value
    (interning to code 0) and a tiny window, so any queue history
    whose per-value enqueue count fits a nibble rides the packed
    kernels — the value-domain bound disappears entirely
    (models.PACKED_QUEUE_MAX_CODES no longer limits whole histories).

    Substreams are rebuilt in ONE pass over the original history
    order: every invoke and completion lands at its own real-time
    position. (An earlier version appended each completion right after
    its invoke, which serialized the substream in invocation order —
    an overlapping enq/deq pair lost its concurrency and a valid
    history could report a false violation.) Drain-expansion synthetic
    dequeues invoke at the drain's invoke position and complete at the
    drain's completion position — the exact interval the batch
    occupied. Each synthetic pair gets a UNIQUE INTEGER process:
    History.pairs matches invoke->completion by process, so two
    expansion pairs sharing the drain's process would corrupt pairing,
    and the encoder (history_to_events) drops any op whose process is
    not an int (is_client_op), so non-int synthetics would silently
    vanish from the check. Fresh processes are drawn counting DOWN
    from below the smallest real integer process, so they can never
    collide with a live client.
    """
    import itertools
    from collections import defaultdict

    from jepsen_tpu.checker.models import F_DEQ, F_ENQ, QUEUE_F_NAMES
    from jepsen_tpu.history.history import History

    subs = defaultdict(list)
    synth = itertools.count(len(history))
    synth_proc = itertools.count(
        min(
            (op.process for op in history
             if isinstance(op.process, int)),
            default=0,
        ) - 1,
        -1,
    )
    #: drain completion index -> [(value, synthetic ok), ...] queued
    #: for emission when the walk reaches the completion's position
    drain_oks: dict = {}
    for op in history:
        if op.is_invoke:
            comp = history.completion(op)
            if op.f == "drain":
                # Drain = a batch of dequeues in one interval.
                # Expansion into per-value dequeue pairs is EXACT for
                # the unordered queue (the total-queue expansion
                # discipline, checker.clj:570-629): removals only
                # shrink enabledness, so any witness using a mid-drain
                # state has an equivalent one using the pre-drain
                # state — atomicity of the batch constrains nothing
                # observable. A crashed drain's values are unknown and
                # removal-only: vacuous, dropped.
                if comp is not None and comp.type == "ok":
                    for v in comp.value or ():
                        if v is None:
                            return None
                        proc = next(synth_proc)
                        subs[v].append(op.with_(
                            f="dequeue", value=None,
                            index=next(synth), process=proc,
                        ))
                        drain_oks.setdefault(comp.index, []).append((
                            v,
                            comp.with_(
                                f="dequeue", value=v,
                                index=next(synth), process=proc,
                            ),
                        ))
                continue
            fcode = QUEUE_F_NAMES.get(op.f)
            if fcode is None:
                return None  # not a pure enqueue/dequeue history
            if fcode == F_ENQ:
                v = op.value
            else:
                v = (
                    comp.value
                    if comp is not None and comp.type == "ok"
                    else None
                )
            if v is None:
                if fcode == F_DEQ:
                    continue  # NIL dequeue: vacuous (docstring)
                return None  # enqueue of nil: keep the joint path
            subs[v].append(op)
        else:
            if op.f == "drain":
                for v, ok_op in drain_oks.pop(op.index, ()):
                    subs[v].append(ok_op)
                continue
            fcode = QUEUE_F_NAMES.get(op.f)
            if fcode is None:
                return None
            inv = history.invocation(op)
            if inv is None:
                continue  # stray completion: nothing to pair with
            if fcode == F_ENQ:
                v = inv.value
                if v is None:
                    return None
            else:
                # dequeue: only ok completions name a value; a
                # fail/info dequeue's invoke was dropped as vacuous,
                # so its completion drops with it.
                v = op.value if op.type == "ok" else None
                if v is None:
                    continue
            subs[v].append(op)
    return {
        v: History(ops, indexed=True) for v, ops in subs.items()
    }


def check_queue_by_value(history, model: str, init_value=None,
                         plane=None, mesh=None, validate=True,
                         strict=False):
    """Batched per-value queue check (split_queue_history_by_value),
    or None when the history doesn't decompose / a subhistory blows
    the window. Verdict merge: valid iff every value is; the first
    invalid value re-checks through the joint single-stream machinery
    for its failure report.

    plane: a dispatch.DispatchPlane — the per-value substreams submit
    as individual requests and coalesce with whatever else the plane
    holds (other keys, other checkers) instead of forming their own
    private batch; verdict-identical to the check_keys path.

    mesh: execution layout for the batched (non-plane) path, with
    sharded.resolve_mesh semantics — None auto-shards over every
    visible device when more than one is visible, False pins one
    device, a Mesh is explicit. A plane carries its own mesh, so
    mesh is ignored when plane is given.

    validate: run the history sentry first (history/sentry.py) —
    clean histories pass through untouched; repaired ones carry a
    history_report in the verdict. LinearizableChecker.check already
    validated and passes False. strict: raise HistorySentryError
    instead of repairing."""
    hreport = None
    if validate:
        from jepsen_tpu.history.sentry import validate_history

        history, hreport = validate_history(history, strict=strict)
    subs = split_queue_history_by_value(history)
    if subs is None or not subs:
        return None
    try:
        streams = {
            v: history_to_events(sub, model=model, init_value=init_value)
            for v, sub in subs.items()
        }
    except WindowOverflow:
        return None
    if plane is not None:
        futs = [
            plane.submit(s, model=model) for s in streams.values()
        ]
        # Targeted: dispatch only our substreams' buckets — a plane-
        # wide flush would force out other submitters' partially
        # filled buckets and undercut the coalescing they're parked
        # for.
        plane.flush_for(futs)
        results = [f.result() for f in futs]
    else:
        from jepsen_tpu.checker.sharded import check_keys

        results = check_keys(
            list(streams.values()), model=model, mesh=mesh
        )
    methods: dict = {}
    for r in results:
        methods[r["method"]] = methods.get(r["method"], 0) + 1
    out = {
        "valid?": True,
        "method": "per-value:" + ",".join(
            f"{m}x{n}" for m, n in sorted(methods.items())
        ),
        "n_values": len(subs),
        "frontier_k": None,
        "escalations": sum(r.get("escalations", 0) for r in results),
    }
    if hreport is not None and not hreport.get("clean"):
        out["history_report"] = hreport
    for v, r in zip(streams, results):
        if r["valid?"] is False:
            detail = check_events_bucketed(streams[v], model=model)
            out["valid?"] = False
            out["failed_value"] = v
            out["failed_op_index"] = detail.get("failed_op_index")
            if "failure" in detail:
                out["failure"] = detail["failure"]
            else:
                # index-only engine decided (K-frontier rung): harvest
                # the report on the one failing substream.
                _harvest_failure(streams[v], out, model)
            break
    return out


class LinearizableChecker:
    """Checker-protocol adapter for the WGL engine.

    check() accepts a record History (jepsen_tpu.history.History) or any
    iterable of op dicts; keyed/independent histories should be split by
    jepsen_tpu.independent before reaching here, exactly as the reference
    splits per key (jepsen/src/jepsen/independent.clj:247-298).
    """

    def __init__(
        self,
        model: str = "cas-register",
        init_value: Any = None,
        use_tpu: bool = True,
        plane=None,
        mesh=None,
        interpret: bool = False,
        sentry: bool = True,
        strict_history: bool = False,
    ):
        # perf-plane consult: load the persisted per-backend profile
        # (once per process) so plan-time knob resolution — the bitset
        # W rung ladder, the rows-bucket quantum — sees it. No-op on
        # the common no-profile path.
        from jepsen_tpu.perf import knobs as _perf_knobs

        _perf_knobs.ensure_profile()
        self.model = model
        self.init_value = init_value
        self.use_tpu = use_tpu
        # Optional dispatch.DispatchPlane: checks submitted through it
        # coalesce with concurrent requests (other keys, other checker
        # instances) into shared device launches instead of paying the
        # sync floor each. Verdicts are identical either way.
        self.plane = plane
        # Execution layout for batched non-plane paths (queue-by-value
        # substreams), sharded.resolve_mesh semantics: None auto-shards
        # over every visible device when >1 is visible, False pins one
        # device, a Mesh is explicit. A configured plane already
        # carries its own mesh and ignores this.
        self.mesh = mesh
        # Pallas interpret mode: the device branch (bitset tier,
        # checkpointed driver included) on CPU — the analyze seam's
        # test hook and the checkpoint/resume path's CPU fallback.
        self.interpret = interpret
        # History sentry (history/sentry.py): validate/repair the
        # history before encoding. Clean histories pass through
        # zero-copy; repaired ones attach a history_report to the
        # verdict. strict_history raises HistorySentryError instead
        # of repairing (analyze --strict-history, exit code 3).
        self.sentry = sentry
        self.strict_history = strict_history

    def _sentry(self, history):
        """(validated history, report-or-None) per the sentry flags."""
        if not self.sentry:
            return history, None
        from jepsen_tpu.history.sentry import validate_history

        return validate_history(history, strict=self.strict_history)

    @staticmethod
    def _attach_report(out: dict, hreport) -> None:
        if hreport is not None and not hreport.get("clean"):
            out["history_report"] = hreport

    def check_async(self, test, history, opts=None):
        """Submit this history to the configured dispatch plane and
        return a zero-arg resolver; calling it blocks on the coalesced
        launch and yields the same dict check() would. Requires plane.
        Submitting many keys before resolving any lets them share
        device dispatches (the whole point of the plane)."""
        if self.plane is None:
            raise ValueError("check_async requires a dispatch plane")
        from jepsen_tpu.history.history import History

        if not isinstance(history, History):
            history = History(history)
        t0 = time.perf_counter()
        history, hreport = self._sentry(history)
        fut = self.plane.submit_history(
            history, model=self.model, init_value=self.init_value
        )

        def resolve() -> dict:
            out = self._plane_result(fut)
            if fut.events is not None:
                out.setdefault("n_ops", fut.events.n_ops)
                out.setdefault("window", fut.events.window)
                # Same tail as check(): an invalid verdict from an
                # index-only engine gets its failure report harvested
                # before the SVG render, so the async path yields the
                # same dict (and artifact) the synchronous one would.
                _harvest_failure(fut.events, out, self.model)
            self._attach_report(out, hreport)
            out["wall_s"] = time.perf_counter() - t0
            self._render_failure(test, out, opts)
            return out

        return resolve

    def _plane_result(self, fut) -> dict:
        """Resolve a plane future with the checker-level safety net:
        the plane's own degradation ladder already absorbs injected
        fault classes, but an unrecoverable PlaneFault (every rung
        failed, plane closed mid-flight) still yields the host
        oracle's verdict here instead of an exception — check() and
        check_async() NEVER surface a device fault to the caller when
        the events are on hand to re-decide."""
        from jepsen_tpu.checker.chaos import PlaneFault

        try:
            return fut.result()
        except PlaneFault as pf:
            if fut.events is None:
                raise
            out = _oracle_verdict(
                *_oracle_decide(fut.events, self.model)
            )
            out["degraded"] = pf.describe()
            return out

    def check(self, test, history, opts=None, checkpoint=None) -> dict:
        """checkpoint: a checkpoint.CheckpointSink makes the bitset
        tier durable — every verified segment boundary persists
        atomically, and re-running the same check (same history,
        model, plan) resumes at the last durable frontier instead of
        starting over (the `analyze --resume` engine). Ignored by
        tiers that don't segment (K-ladder, oracle, queue-by-value).
        """
        with obs_trace.span("check", kind="check"):
            return self._check(test, history, opts, checkpoint)

    def _check(self, test, history, opts, checkpoint) -> dict:
        from jepsen_tpu.history.history import History

        t0 = time.perf_counter()
        with obs_trace.span("prep.sentry", kind="prep"):
            if not isinstance(history, History):
                history = History(history)
            history, hreport = self._sentry(history)
        if self.model == "unordered-queue" and self.use_tpu:
            # Queue histories decompose by value (locality — see
            # split_queue_history_by_value): one batched kernel pass
            # over per-value substreams instead of a joint scan whose
            # packed envelope real value domains immediately exceed.
            out = check_queue_by_value(
                history, self.model, init_value=self.init_value,
                plane=self.plane, mesh=self.mesh, validate=False,
            )
            if out is not None:
                out["n_ops"] = len(history)
                self._attach_report(out, hreport)
                out["wall_s"] = time.perf_counter() - t0
                self._render_failure(test, out, opts)
                return out
        try:
            with obs_trace.span("prep.encode", kind="prep"):
                events = history_to_events(
                    history, model=self.model, init_value=self.init_value
                )
        except WindowOverflow:
            # Too concurrent for int32 masks: unbounded oracle decides
            # (and flows into the shared tail below — overflow runs get
            # the same failure artifact and fields as every other path).
            events = history_to_events(
                history,
                model=self.model,
                init_value=self.init_value,
                max_window=1 << 20,
            )
            out = _oracle_verdict(*_oracle_decide(events, self.model))
        else:
            if self.use_tpu:
                if self.plane is not None:
                    out = self._plane_result(
                        self.plane.submit(
                            events, model=self.model,
                            checkpoint=checkpoint,
                        )
                    )
                else:
                    out = check_events_bucketed(
                        events, model=self.model,
                        interpret=self.interpret,
                        checkpoint=checkpoint,
                    )
            else:
                out = _oracle_verdict(
                    *_oracle_decide(events, self.model)
                )
        out["n_ops"] = events.n_ops
        out["window"] = events.window
        # Every invalid verdict carries a failure report: engines that
        # return only the failing index (K-frontier rungs, the native
        # oracle) get theirs harvested from the Python oracle.
        _harvest_failure(events, out, self.model)
        self._attach_report(out, hreport)
        out["wall_s"] = time.perf_counter() - t0
        self._render_failure(test, out, opts)
        return out

    def check_streaming(self, path: Optional[str] = None):
        """A streaming.StreamingCheck handle bound to this checker's
        model/init_value/interpret config: append(ops) checks only the
        new tail of the history (device-resident frontier), result()
        yields the definite verdict. path persists the stream frontier
        so a restarted process resumes instead of re-checking the
        prefix — the `analyze --follow` and `POST /check/stream`
        engine."""
        from jepsen_tpu.checker.streaming import StreamingCheck

        return StreamingCheck(
            model=self.model,
            init_value=self.init_value,
            interpret=self.interpret,
            path=path,
        )

    @staticmethod
    def _render_failure(test, out, opts) -> None:
        """Render the death report (the reference's linear.svg,
        checker.clj:146-154) next to results.json when a run dir is
        in play; per-key checks land in their key subdirectory."""
        run_dir = (opts or {}).get("subdirectory") or (
            test.get("run_dir") if isinstance(test, dict) else None
        )
        if out["valid?"] is False and "failure" in out and run_dir:
            from jepsen_tpu.checker.failure_viz import write_failure_svg

            try:
                out["failure_svg"] = write_failure_svg(
                    out["failure"], run_dir,
                    failed_op_index=out.get("failed_op_index"),
                )
            except OSError:
                pass


def linearizable(model: str = "cas-register", **kw) -> LinearizableChecker:
    return LinearizableChecker(model=model, **kw)

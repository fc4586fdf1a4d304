"""The flight recorder: a process-wide, per-thread ring-buffer span
recorder for plane crossings.

Design constraints, in order:

1. **Disabled is free.** The recorder ships enabled=False; every
   emission helper's first action is one attribute check on the
   module singleton and an immediate return. No ring is ever
   allocated, no clock is read — the instrumentation is safe to leave
   on the dispatch plane's hot paths permanently (the bench guard
   pins < 1% wall regression with tracing off).
2. **No cross-thread locking on the hot path.** Each thread appends
   to its OWN ring (a plain list); under the GIL a single-owner
   append is atomic, so emission takes no lock. The registry of
   rings takes a lock only on a thread's FIRST emission (ring
   creation) and in snapshot readers. Rings are registered per
   thread INSTANCE (a process-unique sequence number, not the
   reusable ``thread.ident``), so short-lived threads — one native
   racer per checked key — never overwrite each other's rings;
   ``reset()`` forgets the rings of threads that have exited.
3. **Bounded memory.** Rings trim themselves (owner-side ``del``)
   back to ``capacity`` once they reach twice it; trimmed events
   count in ``dropped`` so a truncated trace is detectable.
4. **Monotonic clock.** Timestamps are ``time.perf_counter_ns()`` —
   spans measure real elapsed wall on one host, immune to wall-clock
   steps (the nemesis bends wall clocks on purpose).
5. **One timeline with the device.** While the recorder is on and
   jax is already imported, every span also holds a
   ``jax.profiler.TraceAnnotation`` of its name for its duration, so
   under a profiler capture the span sits on the capture's host
   plane, on the device trace's clock. Importing this module never
   imports jax.

Event records are plain dicts (the export layer's wire shape)::

    {"name", "kind", "ph": "X"|"i", "ts": ns, "dur": ns (X only),
     "tid", "tname", "args": {...}}

Spans (``ph == "X"``) also carry their place in the span tree:
``id`` (process-unique), ``parent`` (the innermost span open on the
same thread when it opened, or an explicit ``parent=`` id — how a
worker thread's span names the caller's span it works for, see
``current()``; None at a root) and ``root`` (the parent's root, or
the span's own id).

Emission discipline (enforced by planelint Family C, JT301-303):
``span(...)`` is ALWAYS used as a context manager, never while
holding a plane lock, and never from code reachable under jax
tracing — a traced emission would record trace-time, not run-time,
and its clock read would bake into the jit cache.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

#: default ring capacity per thread (events kept after a trim)
DEFAULT_CAPACITY = 1 << 16

#: process-unique span ids and ring sequence numbers (``next`` on a
#: count is atomic under the GIL)
_span_ids = itertools.count(1)
_ring_seqs = itertools.count(1)


def _annotation(name: str):
    """A profiler annotation for a span, or None when jax has not been
    imported (the recorder never imports it)."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    return prof.TraceAnnotation(name)


class _NoopSpan:
    """The disabled-mode span: a process-wide singleton whose enter/
    exit/set do nothing and allocate nothing (``__slots__ = ()``)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self


_NOOP = _NoopSpan()


class _Span:
    """A live duration span; records itself into the owner thread's
    ring at ``__exit__`` (one complete event — no separate begin/end
    records to pair up)."""

    __slots__ = ("_tracer", "name", "kind", "args", "id", "parent",
                 "root", "_ent", "_ann", "_t0")

    def __init__(self, tracer: "Tracer", name: str, kind: str,
                 args: Dict[str, Any], parent: Optional[int]):
        self._tracer = tracer
        self.name = name
        self.kind = kind
        self.args = args
        self.parent = parent

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (admission verdicts,
        response status) to the eventual record."""
        self.args.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        ent = self._ent = tracer._ent()
        stack = ent["open"]
        self.id = next(_span_ids)
        if self.parent is not None:
            # a parent on another thread: its root if it is still open
            self.root = tracer._open_roots.get(self.parent, self.parent)
        elif stack:
            self.parent = stack[-1].id
            self.root = stack[-1].root
        else:
            self.root = self.id
        stack.append(self)
        tracer._open_roots[self.id] = self.root
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ent["open"].remove(self)
        self._tracer._open_roots.pop(self.id, None)
        self._tracer._emit(self._ent, {
            "name": self.name,
            "kind": self.kind,
            "ph": "X",
            "ts": self._t0,
            "dur": t1 - self._t0,
            "id": self.id,
            "parent": self.parent,
            "root": self.root,
            "args": self.args,
        })
        return False


class Tracer:
    """The process-wide recorder. One instance (``TRACER``) lives for
    the process; ``enable()``/``disable()`` flip it."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self.capacity = capacity
        #: ring seq -> {"ring": list, "tid", "tname", "thread", "open"};
        #: created lazily on a thread's first emission, under
        #: _rings_lock
        self._rings: Dict[int, dict] = {}
        self._rings_lock = threading.Lock()
        self._local = threading.local()
        #: open span id -> its root, for parents named across threads
        self._open_roots: Dict[int, int] = {}
        self._dropped = 0

    # -- lifecycle -----------------------------------------------------

    def enable(self, capacity: Optional[int] = None) -> None:
        if capacity is not None:
            self.capacity = int(capacity)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        """Drop every recorded event, and the rings of threads that
        have exited (live threads still hold references to theirs)."""
        with self._rings_lock:
            for seq, ent in list(self._rings.items()):
                if ent["thread"].is_alive():
                    del ent["ring"][:]
                else:
                    del self._rings[seq]
            self._dropped = 0

    def clear(self) -> None:
        """Forget rings entirely (test teardown)."""
        with self._rings_lock:
            self._rings.clear()
            self._dropped = 0
        self._open_roots.clear()
        self._local = threading.local()

    # -- emission (hot path) -------------------------------------------

    def _ent(self) -> dict:
        ent = getattr(self._local, "ent", None)
        if ent is None:
            t = threading.current_thread()
            ent = {
                "ring": [], "tid": t.ident, "tname": t.name,
                "thread": t, "open": [],
            }
            with self._rings_lock:
                self._rings[next(_ring_seqs)] = ent
            self._local.ent = ent
        return ent

    def _emit(self, ent: dict, rec: dict) -> None:
        ring = ent["ring"]
        ring.append(rec)
        # owner-side trim: only this thread ever mutates its ring, so
        # the del cannot race another writer; snapshot readers copy
        # under the GIL and tolerate a concurrent trim (they slice)
        if len(ring) >= 2 * self.capacity:
            drop = len(ring) - self.capacity
            del ring[:drop]
            self._dropped += drop

    # -- snapshot readers ----------------------------------------------

    def spans(self) -> List[dict]:
        """Point-in-time copy of every ring, stamped with tid/tname,
        sorted by start timestamp."""
        with self._rings_lock:
            ents = [(e["tid"], e["tname"], e["ring"][:])
                    for e in self._rings.values()]
        out: List[dict] = []
        for tid, tname, ring in ents:
            for rec in ring:
                r = dict(rec)
                r["tid"] = tid
                r["tname"] = tname
                out.append(r)
        out.sort(key=lambda r: r["ts"])
        return out

    def trace_stats(self) -> dict:
        """Counter view for the engine snapshot / metric lines:
        event totals by phase and per-kind counts."""
        evs = self.spans()
        by_kind: Dict[str, int] = {}
        n_spans = n_instants = 0
        for r in evs:
            by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
            if r["ph"] == "X":
                n_spans += 1
            else:
                n_instants += 1
        return {
            "enabled": self.enabled,
            "events": len(evs),
            "spans": n_spans,
            "instants": n_instants,
            "dropped": self._dropped,
            "by_kind": by_kind,
        }


#: THE process-wide recorder; module helpers below are the hot-path
#: entry points (one attribute check when disabled)
TRACER = Tracer()


def enable(capacity: Optional[int] = None) -> None:
    TRACER.enable(capacity)


def disable() -> None:
    TRACER.disable()


def reset() -> None:
    TRACER.reset()


def span(name: str, kind: str = "span", parent: Optional[int] = None,
         **attrs):
    """Open a duration span (ALWAYS ``with span(...):`` — planelint
    JT301). ``parent`` names a span open on another thread (an id from
    ``current()``; should that span have closed already, the new span
    takes the parent's id as its root); by default the parent is the
    innermost span open on this thread. Disabled mode returns the
    no-op singleton (no clock read, no record)."""
    if not TRACER.enabled:
        return _NOOP
    return _Span(TRACER, name, kind, attrs, parent)


def current() -> Optional[int]:
    """Id of the innermost span open on the calling thread — what a
    worker thread passes as ``span(..., parent=...)`` to hang its span
    under the caller's — or None when the recorder is off or no span
    is open."""
    if not TRACER.enabled:
        return None
    stack = TRACER._ent()["open"]
    return stack[-1].id if stack else None


def instant(name: str, kind: str = "instant", **attrs) -> None:
    """Record a zero-duration event (stat bumps, retries, ejections)."""
    if not TRACER.enabled:
        return
    TRACER._emit(TRACER._ent(), {
        "name": name,
        "kind": kind,
        "ph": "i",
        "ts": time.perf_counter_ns(),
        "args": attrs,
    })


def spans() -> List[dict]:
    return TRACER.spans()


def trace_stats() -> dict:
    return TRACER.trace_stats()

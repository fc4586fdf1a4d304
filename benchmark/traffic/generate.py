"""The benchmark's one traffic generator: register histories from a seed.

Pure standard library, and independent of the program under test, so a
change to the program cannot move the yardstick. Histories are lists
of plain op dicts (``type``, ``f``, ``value``, ``process``), the shape
Jepsen writes and the checker daemon accepts on the wire.

The register simulation is a copy of the program's
``jepsen_tpu.sim.gen_register_history`` (a linearizable CAS register
whose ops linearize at invocation or at completion, so histories are
valid by construction), with one change: crashes are an exact count
per history, split over read/write/cas as the configuration states,
instead of a coin per completion. Every seed then gives the same
amount of work (the same concurrency window, so the same kernel
shapes), in another order.

An invalid history is made by one lost write: an ok read, invoked
after a write had completed, is changed to observe the empty register
(``None``). No op ever writes ``None``, so that read cannot be
linearized: the history is invalid by construction, and the reference
checker confirms it.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

FS = ("read", "write", "cas")


def rng_for(seed: int, *parts: int) -> random.Random:
    """One independent stream per (seed, parts): integer arithmetic,
    so the same seed gives the same stream on every Python."""
    x = int(seed)
    for p in parts:
        x = x * 1_000_003 + int(p) + 1
    return random.Random(x)


def register_history(
    rng: random.Random,
    n_ops: int,
    n_procs: int,
    n_values: int,
    crashes: Dict[str, int],
    p_early: float = 0.5,
) -> Tuple[List[dict], List[int]]:
    """One key's history: ``n_ops`` invocations by ``n_procs``
    concurrent processes over values ``0..n_values-1``. ``crashes``
    gives, per f, how many invocations end ``:info`` (a crashed
    process retires and a fresh one takes its place).

    Returns the ops and the order in which they took effect: the
    positions of their invocations, a witness that the reference
    checks rather than trusts."""
    fs = [rng.choice(FS) for _ in range(n_ops)]
    values: List = []
    for f in fs:
        if f == "read":
            values.append(None)
        elif f == "write":
            values.append(rng.randrange(n_values))
        else:
            values.append([rng.randrange(n_values), rng.randrange(n_values)])
    # one crash in each of equal strata of the invocations, so that the
    # window widens at the same pace in every history
    crash_fs = [f for f, k in sorted(crashes.items()) for _ in range(int(k))]
    rng.shuffle(crash_fs)
    crash_at = set()
    for s, f in enumerate(crash_fs):
        lo = s * n_ops // len(crash_fs)
        hi = (s + 1) * n_ops // len(crash_fs)
        pool = [i for i in range(lo, hi) if fs[i] == f]
        if pool:
            crash_at.add(rng.choice(pool))

    state = None
    ops: List[dict] = []
    order: List[int] = []
    pending: dict = {}  # process -> (op number, position, applied?, result)
    procs = list(range(n_procs))
    next_proc = n_procs
    emitted = 0

    def apply(f, v, pos):
        nonlocal state
        if f == "read":
            order.append(pos)
            return True, state
        if f == "write":
            state = v
            order.append(pos)
            return True, v
        if state == v[0]:
            state = v[1]
            order.append(pos)
            return True, v
        return False, v

    while emitted < n_ops or pending:
        p = rng.choice(procs)
        if p in pending:
            i, pos, applied, res = pending.pop(p)
            f, v = fs[i], values[i]
            if i in crash_at:
                ops.append({"type": "info", "f": f, "value": v, "process": p})
                procs.remove(p)
                procs.append(next_proc)
                next_proc += 1
                continue
            if not applied:
                okp, res = apply(f, v, pos)
            else:
                okp = res is not False
            if f == "read":
                ops.append({"type": "ok", "f": f, "value": res, "process": p})
            elif f == "write" or okp:
                ops.append({"type": "ok", "f": f, "value": v, "process": p})
            else:
                ops.append({"type": "fail", "f": f, "value": v, "process": p})
        elif emitted < n_ops:
            i = emitted
            f, v = fs[i], values[i]
            applied, res = False, None
            if rng.random() < p_early:
                okp, res = apply(f, v, len(ops))
                applied = True
                if f == "cas" and not okp:
                    res = False
            ops.append({"type": "invoke", "f": f, "value": v, "process": p})
            pending[p] = (i, len(ops) - 1, applied, res)
            emitted += 1
    return ops, order


def lose_write(
    ops: List[dict], rng: random.Random, at: Sequence[float] = (0.0, 1.0)
) -> Tuple[List[dict], int]:
    """A copy of ``ops`` with one ok read, drawn from the span ``at``
    (fractions of the history) among reads invoked after some write
    had completed, changed to observe ``None``: invalid by
    construction. Where the span holds no such read, the first one
    after it is taken. Returns the copy and the read's position."""
    written = False
    open_at: dict = {}
    eligible = []
    lo, hi = int(at[0] * len(ops)), int(math.ceil(at[1] * len(ops)))
    for j, o in enumerate(ops):
        if o["type"] == "invoke":
            open_at[o["process"]] = written
            continue
        after_write = open_at.pop(o["process"], False)
        if o["type"] != "ok":
            continue
        if o["f"] == "read" and after_write:
            eligible.append(j)
        if o["f"] in ("write", "cas"):
            written = True
    cands = [j for j in eligible if lo <= j < hi]
    if not cands:
        cands = [j for j in eligible if j >= lo][:1] or eligible[-1:]
    if not cands:
        raise ValueError("no ok read after a completed write to corrupt")
    j = rng.choice(cands)
    out = list(ops)
    out[j] = dict(ops[j], value=None)
    return out, j


def key_history(cfg: dict, rng: random.Random) -> Tuple[List[dict], List[int]]:
    return register_history(
        rng,
        n_ops=cfg["ops_per_key"],
        n_procs=cfg["processes_per_key"],
        n_values=cfg["values"],
        crashes=cfg["crashes_per_key"],
        p_early=cfg.get("p_early", 0.5),
    )


def history(
    cfg: dict, seed: int, h: int, corrupt_key: Optional[int] = None,
    corrupt_at: Sequence[float] = (0.0, 1.0),
) -> Dict[int, dict]:
    """History ``h`` of the pool drawn from ``seed``: per key
    (``0..keys-1``), ``{"ops": [...], "order": [...]}``, or for
    ``corrupt_key``, which loses one write, ``{"ops": [...],
    "lost_read": j}``."""
    return {
        k: request(cfg, seed, h, k, k == corrupt_key, corrupt_at)
        for k in range(cfg["keys"])
    }


def request(
    cfg: dict, seed: int, h: int, k: int, corrupt: bool,
    corrupt_at: Sequence[float] = (0.0, 1.0),
) -> dict:
    """Key ``k`` of history ``h``: ops with the certificate of its
    verdict (see ``history``)."""
    ops, order = key_history(cfg, rng_for(seed, h, k))
    if not corrupt:
        return {"ops": ops, "order": order}
    ops, j = lose_write(ops, rng_for(seed, h, k, 1), corrupt_at)
    return {"ops": ops, "lost_read": j}


def interleave(
    cfg: dict, per_key: Dict[int, List[dict]], rng: random.Random
) -> List[tuple]:
    """The order in which a multi-key test records its ops: groups of
    ``processes_per_key`` threads work concurrently, each on one key
    at a time, keys taken in turn (Jepsen's independent concurrent
    generator). Returns ``[(key, op), ...]``; each key's ops keep
    their own order, and each key's processes are made unique by
    ``key * 1000``."""
    keys = sorted(per_key)
    groups = max(1, cfg.get("concurrent_keys", 1))
    queues = [keys[g::groups] for g in range(groups)]
    cursors = [[q[0], 0] if q else None for q in queues]
    for q in queues:
        if q:
            q.pop(0)
    out = []
    live = [g for g in range(groups) if cursors[g] is not None]
    while live:
        g = rng.choice(live)
        k, i = cursors[g]
        o = per_key[k][i]
        out.append((k, dict(o, process=k * 1000 + o["process"])))
        i += 1
        if i < len(per_key[k]):
            cursors[g][1] = i
        elif queues[g]:
            cursors[g] = [queues[g].pop(0), 0]
        else:
            cursors[g] = None
            live.remove(g)
    return out

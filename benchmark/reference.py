"""The plain reference: linearizability of one CAS register's history.

A straightforward just-in-time linearization search (Wing & Gong, with
Lowe's refinement), written from the definition and independent of
the program under test: it imports nothing of it.

Semantics, as Jepsen and Knossos give them for a ``cas-register``
starting empty (``None``):

- an ``:ok`` op took effect exactly once, between its invocation and
  its completion; an ok read returned the register's value then;
- a ``:fail`` op took no effect and is dropped;
- an ``:info`` op (or one never completed) may have taken effect once,
  at any time after its invocation, or never. A crashed read changes
  nothing and is dropped.

The search keeps every configuration the history could be in: the
register's value, which pending ok ops have already taken effect, and
how many crashed ops of each kind (f and value) have been used. At
each ok completion it explores the orders in which pending ops could
have taken effect up to that point, and keeps the configurations in
which the completing op did. Crashed ops of one kind are
interchangeable once invoked, so they are counted, not named; a
configuration that used fewer of them can do all that one that used
more can, so only the least counts are kept. No configuration left
means the history is not linearizable at that completion.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

_FAIL = object()


def _step(state, f, v):
    """Register transition: the next value, or _FAIL."""
    if f == "write":
        return v
    if f == "read":
        return state if v == state else _FAIL
    if f == "cas":
        return v[1] if state == v[0] else _FAIL
    raise ValueError(f"unknown f {f!r}")


def _norm(v):
    return tuple(v) if isinstance(v, list) else v


def _dominated(antichain: List[tuple], u: tuple) -> bool:
    return any(all(a <= b for a, b in zip(w, u)) for w in antichain)


def _insert(table: Dict, key, u: tuple) -> bool:
    """Add use-count vector ``u`` under ``key`` unless one already
    there uses no more of every kind; drop those ``u`` improves on."""
    chain = table.get(key)
    if chain is None:
        table[key] = [u]
        return True
    if _dominated(chain, u):
        return False
    chain[:] = [w for w in chain if not all(a <= b for a, b in zip(u, w))]
    chain.append(u)
    return True


def check(ops: List[dict], init=None) -> Tuple[bool, Optional[int]]:
    """(valid, index of the completion at which no configuration was
    left, or None)."""
    open_: Dict = {}
    completion: Dict[int, int] = {}
    for j, o in enumerate(ops):
        p = o["process"]
        if o["type"] == "invoke":
            open_[p] = j
        else:
            i = open_.pop(p, None)
            if i is not None:
                completion[i] = j

    kinds: Dict = {}
    starts: Dict[int, tuple] = {}  # invoke index -> ("ok", id) | ("crash", kind)
    returns: Dict[int, int] = {}  # completion index -> ok id
    ok_ops: Dict[int, tuple] = {}
    for i, o in enumerate(ops):
        if o["type"] != "invoke":
            continue
        j = completion.get(i)
        end = ops[j]["type"] if j is not None else "info"
        if end == "fail":
            continue
        if end == "ok":
            f = o["f"]
            v = _norm(ops[j]["value"] if f == "read" else o["value"])
            oid = len(ok_ops)
            ok_ops[oid] = (f, v)
            starts[i] = ("ok", oid)
            returns[j] = oid
        elif o["f"] != "read":
            kind = (o["f"], _norm(o["value"]))
            kinds.setdefault(kind, len(kinds))
            starts[i] = ("crash", kinds[kind])
    kind_list = sorted(kinds, key=kinds.get)
    n_kinds = len(kind_list)
    avail = [0] * n_kinds

    configs: Dict = {(init, frozenset()): [(0,) * n_kinds]}
    pending: Dict[int, tuple] = {}
    for j in range(len(ops)):
        s = starts.get(j)
        if s is not None:
            if s[0] == "ok":
                pending[s[1]] = ok_ops[s[1]]
            else:
                avail[s[1]] += 1
            continue
        oid = returns.get(j)
        if oid is None:
            continue
        out: Dict = {}
        seen: Dict = {}
        stack = []
        for (st, lin), chain in configs.items():
            for u in chain:
                _insert(seen, (st, lin), u)
                stack.append((st, lin, u))
        while stack:
            st, lin, u = stack.pop()
            if oid in lin:
                _insert(out, (st, lin - {oid}), u)
                continue
            for p, (f, v) in pending.items():
                if p in lin:
                    continue
                st2 = _step(st, f, v)
                if st2 is _FAIL:
                    continue
                if p == oid:
                    _insert(out, (st2, lin), u)
                    continue
                lin2 = lin | {p}
                if _insert(seen, (st2, lin2), u):
                    stack.append((st2, lin2, u))
            for k in range(n_kinds):
                if u[k] >= avail[k]:
                    continue
                f, v = kind_list[k]
                st2 = _step(st, f, v)
                if st2 is _FAIL:
                    continue
                u2 = u[:k] + (u[k] + 1,) + u[k + 1:]
                if _insert(seen, (st2, lin), u2):
                    stack.append((st2, lin, u2))
        del pending[oid]
        if not out:
            return False, j
        configs = out
    return True, None


def _pairs(ops: List[dict]) -> Dict[int, int]:
    open_: Dict = {}
    completion: Dict[int, int] = {}
    for j, o in enumerate(ops):
        if o["type"] == "invoke":
            open_[o["process"]] = j
        else:
            i = open_.pop(o["process"], None)
            if i is not None:
                completion[i] = j
    return completion


def proves_valid(ops: List[dict], order: List[int], init=None) -> bool:
    """True when ``order`` (positions of invocations, in the order
    their ops took effect) is a linearization of ``ops``: every ok op
    in it once, no failed op, crashed ops at most once; each op
    placed between its invocation and its completion, in that order;
    and each op's result is what a register run in that order gives.
    A witness that proves nothing returns False."""
    completion = _pairs(ops)
    placed = set()
    t = -1
    state = init
    for i in order:
        if i in placed or not 0 <= i < len(ops):
            return False
        o = ops[i]
        if o["type"] != "invoke":
            return False
        placed.add(i)
        j = completion.get(i)
        end = ops[j] if j is not None else None
        if end is not None and end["type"] == "fail":
            return False
        t = max(t, i)
        if end is not None and end["type"] != "info" and t >= j:
            return False
        f = o["f"]
        crashed = end is None or end["type"] == "info"
        if f == "read":
            if not crashed and _norm(end["value"]) != state:
                return False
        elif f == "write":
            state = _norm(o["value"])
        elif f == "cas":
            a, b = _norm(o["value"])
            if state != a:
                return False
            state = b
        else:
            return False
    return all(
        i in placed
        for i, j in completion.items()
        if ops[j]["type"] == "ok"
    )


def proves_lost_write(ops: List[dict], j: int, init=None) -> bool:
    """True when the ok read at ``j`` observed ``init`` although it was
    invoked after a write or cas had completed, and no op in the
    history ever writes ``init``: that read cannot take effect
    anywhere, so the history is not linearizable."""
    if not 0 <= j < len(ops):
        return False
    o = ops[j]
    if o["type"] != "ok" or o["f"] != "read" or o["value"] != init:
        return False
    for x in ops:
        if x["f"] == "write" and x["value"] == init:
            return False
        if x["f"] == "cas" and _norm(x["value"])[1] == init:
            return False
    completion = _pairs(ops)
    inv = next((i for i, c in completion.items() if c == j), None)
    if inv is None:
        return False
    return any(
        ops[c]["type"] == "ok" and ops[c]["f"] in ("write", "cas")
        for c in completion.values()
        if c < inv
    )


def decide(item: dict, init=None) -> bool:
    """The verdict for one key: from the certificate the generator
    left (a linearization, or a lost write), checked here; from the
    search where no certificate proves anything."""
    ops = item["ops"]
    if "order" in item and proves_valid(ops, item["order"], init):
        return True
    if "lost_read" in item and proves_lost_write(ops, item["lost_read"], init):
        return False
    return check(ops, init)[0]

"""Spans inside the per-key register check: a keyed history checked
through ``independent_checker(LinearizableChecker)`` with the bitset
kernel in interpret mode (CPU) and the native racer on records one
``independent.check`` tree per history — ``prep.split``, then per key
a ``check`` holding the prep, launch-and-sync and verdict leaves, then
the drain's ``racer.wait`` — and each racer thread's ``racer.native``
hangs under its key's ``check``. A key the split declines is checked
through its own History: ``prep.history`` before its ``check``, and
``prep.sentry`` inside it."""

import random
import threading

import pytest

from jepsen_tpu import obs
from jepsen_tpu.checker.linearizable import (
    LinearizableChecker,
    _crosscheck_cap,
)
from jepsen_tpu.checker.wgl_native import available as native_available
from jepsen_tpu.history.history import History
from jepsen_tpu.history.ops import invoke_op, ok_op
from jepsen_tpu.independent import independent_checker, tuple_
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.sim import gen_register_history

pytestmark = [
    pytest.mark.obs,
    pytest.mark.skipif(not native_available(), reason="no C++ toolchain"),
]

#: the leaves every key's check emits on the caller's thread
KEY_LEAVES = {"prep.encode", "prep.steps", "launch", "device_wait"}
#: keys in the history: all valid but the last
N_KEYS = 3


def _keyed_history():
    """N_KEYS - 1 simulated (valid) keys, then one whose read sees a
    value never written."""
    ops = []
    for k in range(N_KEYS - 1):
        h = gen_register_history(random.Random(k), n_ops=24, n_procs=3)
        ops += [o.with_(process=o.process + 10 * k, value=tuple_(k, o.value))
                for o in h.ops]
    bad = N_KEYS - 1
    p = 10 * bad
    ops += [invoke_op(p, "write", tuple_(bad, 1)), ok_op(p, "write", tuple_(bad, 1)),
            invoke_op(p + 1, "read", tuple_(bad, None)),
            ok_op(p + 1, "read", tuple_(bad, 2))]
    return History(ops)


def _declined_history():
    """_keyed_history with an orphan completion in key 0: the split
    declines that key, and the sentry repairs it."""
    ops = _keyed_history().ops
    return History(ops[:3] + [ok_op(99, "read", tuple_(0, 1))] + ops[3:])


def _join_racers():
    for t in threading.enumerate():
        if t.name == "wgl-native-race":
            t.join(30.0)


def _trace(history):
    """(verdict, spans, trace stats) of one traced keyed check, after
    an untraced warm-up check compiled its kernels."""
    checker = independent_checker(LinearizableChecker(interpret=True))
    checker.check({}, history)
    _join_racers()
    obs.disable()
    obs_trace.TRACER.clear()
    obs.enable()
    try:
        out = checker.check({}, history)
        _join_racers()
        spans = [s for s in obs.spans() if s["ph"] == "X"]
        stats = obs.trace_stats()
    finally:
        obs.disable()
        obs_trace.TRACER.clear()
    return out, spans, stats


@pytest.fixture(scope="module")
def traced():
    return _trace(_keyed_history())


@pytest.fixture(scope="module")
def traced_declined():
    return _trace(_declined_history())


def test_racer_spans_hang_under_their_key_check(traced):
    out, spans, stats = traced
    by_id = {s["id"]: s for s in spans}
    racers = [s for s in spans if s["name"] == "racer.native"]
    assert len(racers) == N_KEYS
    main = threading.get_ident()
    for r in racers:
        assert r["tname"] == "wgl-native-race"
        parent = by_id[r["parent"]]
        assert parent["name"] == "check" and parent["tid"] == main
        assert r["root"] == parent["root"]
    # one racer per key: each check span has exactly one racer child
    assert len({r["parent"] for r in racers}) == N_KEYS
    assert stats["dropped"] == 0


def test_keyed_check_emits_split_and_per_key_leaf_spans(traced):
    out, spans, _ = traced
    assert out["valid?"] is False and out["key_count"] == N_KEYS
    (root,) = [s for s in spans if s["name"] == "independent.check"]
    assert root["parent"] is None
    assert all(s["root"] == root["id"] for s in spans)
    kids = [s for s in spans if s["parent"] == root["id"]]
    assert [s["name"] for s in kids].count("prep.split") == 1
    # every key is split in one pass: no History, no sentry of its own
    assert out["prep"] == {"one_pass_keys": N_KEYS, "declined_keys": 0}
    assert "prep.history" not in [s["name"] for s in kids]
    assert not [s for s in spans if s["name"] == "prep.sentry"]
    checks = [s for s in kids if s["name"] == "check"]
    assert len(checks) == N_KEYS
    by_key = dict(zip(sorted(out["results"], key=str), checks))
    for k, chk in by_key.items():
        names = [s["name"] for s in spans
                 if s["parent"] == chk["id"] and s["tid"] == chk["tid"]]
        assert KEY_LEAVES <= set(names), (k, names)
        assert all(names.count(n) == 1 for n in KEY_LEAVES - {"launch"}), \
            (k, names)
        r = out["results"][k]
        if r.get("race_winner") != "native":
            # the device decided: its fetch (the racer's cross-check is
            # deferred past the key); a death on the fast kernel
            # re-launches on the exact one
            assert "host_sync" in names, (k, names)
            launches = 2 if r["valid?"] is False else 1
        else:
            launches = 1
        assert names.count("launch") == launches, (k, names)
    # host prep runs one step at a time on the caller's thread, and
    # every host-prep span carries the prefix its metric reads
    prep = sorted((s for s in spans if s["kind"] == "prep"),
                  key=lambda s: s["ts"])
    assert all(s["name"].startswith("prep.") for s in prep)
    for a, b in zip(prep, prep[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a["name"], b["name"])


def test_declined_key_keeps_its_history_and_sentry_spans(traced_declined):
    out, spans, _ = traced_declined
    assert out["prep"] == {"one_pass_keys": N_KEYS - 1, "declined_keys": 1}
    assert out["results"][0]["history_report"]["detected"] == {
        "orphan_completion": 1}
    (root,) = [s for s in spans if s["name"] == "independent.check"]
    kids = sorted((s for s in spans if s["parent"] == root["id"]),
                  key=lambda s: s["ts"])
    names = [s["name"] for s in kids]
    assert names.count("prep.split") == 1
    # the declined key (0, first in key order) builds its History
    # just before its check; the sentry runs inside that check
    assert names.count("prep.history") == 1
    hist = names.index("prep.history")
    assert names[hist + 1] == "check"
    checks = [s for s in kids if s["name"] == "check"]
    for i, chk in enumerate(checks):
        leaves = [s["name"] for s in spans if s["parent"] == chk["id"]
                  and s["tid"] == chk["tid"]]
        assert KEY_LEAVES <= set(leaves), (i, leaves)
        assert leaves.count("prep.sentry") == (1 if chk is kids[hist + 1]
                                               else 0), (i, leaves)
    assert all(s["name"].startswith("prep.")
               for s in spans if s["kind"] == "prep")


def test_racer_waits_are_the_drain_and_cap_joins(traced):
    """A device-decided key does not wait on its racer: the caller
    blocks on racers at the drain, under ``independent.check`` once the
    last key is done, or at a cap join under a later key's check."""
    out, spans, _ = traced
    (root,) = [s for s in spans if s["name"] == "independent.check"]
    checks = sorted((s for s in spans if s["name"] == "check"),
                    key=lambda s: s["ts"])
    waits = [s for s in spans if s["name"] == "racer.wait"]
    if _crosscheck_cap() == 0:
        # one core: every TPU win settles in its own check, as ever
        assert all(w["parent"] in {c["id"] for c in checks} for w in waits)
        return
    drains = [w for w in waits if w["parent"] == root["id"]]
    # a cap join waits on an earlier key's racer, so never in the first
    assert all(w["parent"] in {c["id"] for c in checks[1:]}
               for w in waits if w not in drains)
    last = out["results"][max(out["results"], key=str)]
    if last.get("race_winner") != "native":
        # the last key's racer is pending when its check returns
        (drain,) = drains
        end = checks[-1]["ts"] + checks[-1]["dur"]
        assert drain["ts"] >= end and drain["tid"] == root["tid"]
    else:
        assert len(drains) <= 1


def test_invalid_key_emits_verdict_harvest(traced):
    out, spans, _ = traced
    by_id = {s["id"]: s for s in spans}
    (bad,) = [k for k, r in out["results"].items() if r["valid?"] is False]
    assert bad == N_KEYS - 1
    assert "failure" in out["results"][bad]
    harvests = [s for s in spans if s["name"] == "verdict.harvest"]
    assert len(harvests) == 1
    # under the last key's check (keys are checked in sorted order)
    chk = by_id[harvests[0]["parent"]]
    assert chk["name"] == "check"
    last = max((s for s in spans if s["name"] == "check"),
               key=lambda s: s["ts"])
    assert chk["id"] == last["id"]

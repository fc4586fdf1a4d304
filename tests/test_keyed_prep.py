"""The keyed split (``events.split_keyed``) against the per-key History
path it stands in for.

A keyed history reaches each key's event stream in one pass: the split
groups and pairs every key's ops, ``records_to_events`` encodes them,
and ``IndependentChecker`` hands each key as split (a ``KeyOps``) to
``LinearizableChecker.check``, which encodes its records. Today's path
— a History per key, the sentry, ``history_to_events`` — stays for
every key the split declines and for every other sub-checker. These tests hold the two to
the same streams, the same sentry verdicts and the same results:

- differential: every key's stream equals
  ``history_to_events(History(key_ops))`` field by field, on the
  benchmark's traffic generator, the simulator and a hand-built
  history of edge cases;
- sentry parity: each corruption class planted in one key declines
  that key alone, which then reads exactly as before (report, strict
  refusal);
- checker parity: per-key results and artifacts equal today's path.
"""

import importlib
import importlib.util
import json
import os
import random
import sys
import threading

import numpy as np
import pytest

from jepsen_tpu.checker.events import (
    WindowOverflow,
    history_to_events,
    records_to_events,
    split_keyed,
)
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.history.history import History
from jepsen_tpu.history.ops import fail_op, info_op, invoke_op, ok_op
from jepsen_tpu.history.sentry import CORRUPTION_CLASSES, HistorySentryError
from jepsen_tpu.independent import (
    KV,
    PREP_STATS,
    independent_checker,
    tuple_,
)
from jepsen_tpu.sim import gen_register_history

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the EventStream fields a key's two encodes must share
ARRAYS = ("kind", "slot", "f", "a", "b", "op_index")
SCALARS = ("window", "n_ops", "init_state", "value_codes")


def _traffic():
    """benchmark/traffic/generate.py, the benchmark's history source."""
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_generate",
        os.path.join(ROOT, "benchmark", "traffic", "generate.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _plain_split(history):
    """Today's split: each KV-valued op, value unwrapped, under its key
    in the order keys first appear."""
    out = {}
    for o in history.ops:
        if isinstance(o.value, KV):
            out.setdefault(o.value.key, []).append(
                o.with_(value=o.value.value)
            )
    return out


# -- keyed histories ----------------------------------------------------


def _generated(seed, corrupt_key=None):
    """The benchmark's etcd-keyed shape at a small size: 6 keys of 40
    ops, 3 processes a key, 2 keys in flight, one crash per f a key."""
    gen = _traffic()
    cfg = {"keys": 6, "ops_per_key": 40, "processes_per_key": 3,
           "values": 5, "crashes_per_key": {"read": 1, "write": 1, "cas": 1},
           "p_early": 0.5, "concurrent_keys": 2}
    per_key = gen.history(cfg, seed, 0, corrupt_key, (0.0, 1.0))
    order = gen.interleave(
        cfg, {k: v["ops"] for k, v in per_key.items()}, gen.rng_for(seed, 99)
    )
    return History([dict(o, value=tuple_(k, o["value"])) for k, o in order])


def _simulated(seed):
    """Simulator keys (crashes, failed cas) merged at random, each key
    keeping its order, with the SAME process ids on every key and
    increasing times: one process works several keys."""
    rng = random.Random(seed)
    per_key = {
        k: list(gen_register_history(
            random.Random(seed * 31 + i), n_ops=30, n_procs=3, p_crash=0.1
        ).ops)
        for i, k in enumerate(("x", "y", 7))
    }
    ops, t = [], 0
    while per_key:
        k = rng.choice(sorted(per_key, key=str))
        o = per_key[k].pop(0)
        if not per_key[k]:
            del per_key[k]
        t += 1
        ops.append(o.with_(value=tuple_(k, o.value), time=t))
    return History(ops)


def _edges():
    """Keys 1 and "1" together; None, bool and int values; crashed
    reads, writes and cas; a failed cas; an op outside the model; an
    invocation never completed, and one left open by its process's next
    invocation; nemesis and unkeyed client ops in between; process 0 on
    three keys."""
    K = tuple_
    ops = [
        invoke_op("nemesis", "start", None),
        invoke_op(0, "write", K(1, True)),
        invoke_op(1, "read", K(1, None)),
        ok_op("nemesis", "start", "partitioned"),
        ok_op(0, "write", K(1, True)),
        ok_op(1, "read", K(1, True)),
        invoke_op(0, "write", K("1", 1)),
        invoke_op(2, "cas", K("1", [1, False])),
        invoke_op(3, "read", K("1", None)),
        ok_op(0, "write", K("1", 1)),
        fail_op(2, "cas", K("1", [1, False])),
        info_op(3, "read", K("1", None)),
        invoke_op(4, "write", "unkeyed"),
        ok_op(4, "write", "unkeyed"),
        invoke_op(0, "cas", K(1, [True, None])),
        invoke_op(5, "frob", K(1, 3)),
        ok_op(5, "frob", K(1, 3)),
        info_op(0, "cas", K(1, [True, None])),
        invoke_op(6, "write", K(1, 0)),
        invoke_op(7, "read", K(1, None)),
        ok_op(7, "read", K(1, 0)),
        invoke_op(8, "write", K(None, False)),
        ok_op(8, "write", K(None, False)),
        invoke_op(0, "read", K(None, None)),
        ok_op(0, "read", K(None, False)),
        invoke_op("nemesis", "stop", None),
        ok_op("nemesis", "stop", None),
        invoke_op(9, "write", K("1", None)),
        invoke_op(6, "read", K(1, None)),
        ok_op(6, "read", K(1, 0)),
    ]
    return History([o.with_(time=i) for i, o in enumerate(ops)])


HISTORIES = {
    "generated": lambda: _generated(11),
    "generated-invalid": lambda: _generated(12, corrupt_key=3),
    "simulated": lambda: _simulated(5),
    "edges": _edges,
}


def _assert_same_stream(new, old, key):
    for f in ARRAYS:
        assert np.array_equal(getattr(new, f), getattr(old, f)), (key, f)
    for f in SCALARS:
        assert getattr(new, f) == getattr(old, f), (key, f)


# -- differential -------------------------------------------------------


@pytest.mark.parametrize("model,init", [
    ("cas-register", None), ("cas-register", 0), ("register", None),
])
@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_each_key_encodes_as_its_history(name, model, init):
    h = HISTORIES[name]()
    keys = split_keyed(h.ops)
    plain = _plain_split(h)
    assert list(keys) == list(plain)
    assert all(g.records is not None for g in keys.values())
    for k, g in keys.items():
        assert g.sub_ops() == plain[k]
        _assert_same_stream(
            records_to_events(g.records, model, init),
            history_to_events(History(plain[k]), model, init),
            k,
        )


def test_edge_history_keeps_keys_1_and_str_1_apart():
    keys = split_keyed(_edges().ops)
    assert list(keys) == [1, "1", None]
    ev = records_to_events(keys["1"].records)
    # the failed cas never happened and the crashed read is dropped:
    # the write returns, the last write stays open
    assert ev.kind.tolist() == [0, 1, 0]
    assert ev.op_index.tolist() == [0, 3, 6]


def test_malformed_cas_raises_as_today():
    h = History([
        invoke_op(0, "write", tuple_("k", 1)), ok_op(0, "write", tuple_("k", 1)),
        invoke_op(1, "cas", tuple_("k", 5)), ok_op(1, "cas", tuple_("k", 5)),
    ])
    g = split_keyed(h.ops)["k"]
    with pytest.raises(ValueError) as new:
        records_to_events(g.records)
    with pytest.raises(ValueError) as old:
        history_to_events(History(g.sub_ops()))
    assert str(new.value) == str(old.value)
    assert "at history index 2" in str(new.value)


def _wide():
    """Key "wide" opens 130 writes at once (past MAX_WINDOW's 128), all
    of which crash, so its verdict needs no search; key "narrow" is an
    ordinary pair."""
    n = 130
    ops = [invoke_op(p, "write", tuple_("wide", p % 3)) for p in range(n)]
    ops += [info_op(p, "write", tuple_("wide", p % 3)) for p in range(n)]
    ops += [invoke_op(n, "write", tuple_("narrow", 1)),
            ok_op(n, "write", tuple_("narrow", 1))]
    return History(ops)


def test_over_wide_key_overflows_and_reaches_the_oracle():
    h = _wide()
    g = split_keyed(h.ops)["wide"]
    with pytest.raises(WindowOverflow):
        records_to_events(g.records)
    with pytest.raises(WindowOverflow):
        history_to_events(History(g.sub_ops()))
    _assert_same_stream(
        records_to_events(g.records, max_window=1 << 20),
        history_to_events(History(g.sub_ops()), max_window=1 << 20),
        "wide",
    )
    lin = LinearizableChecker(interpret=True)
    new = independent_checker(lin).check({}, h)
    assert new["prep"] == {"one_pass_keys": 2, "declined_keys": 0}
    today = lin.check({}, History(g.sub_ops()))
    r = new["results"]["wide"]
    assert r["valid?"] is True and r["window"] == 130
    assert _strip(r) == _strip(today)
    assert r["method"] == today["method"]
    assert not r["method"].startswith("tpu-")


# -- sentry parity ------------------------------------------------------


def _key_ops(k, p, t0):
    """test_sentry's clean register history on key k, processes p and
    p + 1, times from t0."""
    K = lambda v: tuple_(k, v)  # noqa: E731
    ops = [
        invoke_op(p, "write", K(1)), invoke_op(p + 1, "read", K(None)),
        ok_op(p, "write", K(1)), ok_op(p + 1, "read", K(1)),
        invoke_op(p, "cas", K([1, 2])), ok_op(p, "cas", K([1, 2])),
        invoke_op(p + 1, "read", K(None)), ok_op(p + 1, "read", K(2)),
    ]
    return [o.with_(time=t0 + i) for i, o in enumerate(ops)]


def _plant(cls, ops, k, p, t0):
    """ops (key k's, from _key_ops) with cls planted, as test_sentry
    plants it in a single register."""
    K = lambda v: tuple_(k, v)  # noqa: E731
    bad = list(ops)
    if cls == "orphan_completion":
        bad.insert(4, ok_op(p + 7, "read", K(9)).with_(time=t0 + 3.5))
    elif cls == "double_completion":
        bad.insert(4, ok_op(p + 1, "read", K(1)).with_(time=t0 + 3.5))
    elif cls == "inversion":
        bad[4], bad[5] = bad[5], bad[4]
    elif cls == "unpaired_info":
        bad.append(info_op(p + 3, "write", K(5)).with_(time=t0 + 9))
    elif cls == "non_monotone_time":
        bad[5] = bad[5].with_(time=t0 + 0.5)
    elif cls == "nemesis_interleaved":
        bad.insert(0, invoke_op("nemesis", "start", K(None)).with_(time=t0))
        bad.insert(1, ok_op("nemesis", "start", K(None)).with_(time=t0))
        bad.insert(4, invoke_op(p + 5, "start", K(None)).with_(time=t0 + 2.5))
    return bad


#: classes of the whole history's indices: a key's History indexes its
#: ops afresh, so neither can reach a key's sentry, on either path
WHOLE_HISTORY_ONLY = ("duplicate_index", "missing_index")


def _planted(cls):
    """Keys "a" and "c" clean, "b" carrying cls, interleaved op by op."""
    per_key = {
        k: _key_ops(k, 10 * i, 100.0 * i)
        for i, k in enumerate(("a", "b", "c"))
    }
    per_key["b"] = _plant(cls, per_key["b"], "b", 10, 100.0)
    ops = []
    for i in range(max(map(len, per_key.values()))):
        ops += [v[i] for v in per_key.values() if i < len(v)]
    h = History(ops)
    if cls == "duplicate_index":
        h.ops[3] = h.ops[3].with_(index=h.ops[2].index)
    elif cls == "missing_index":
        h.ops[5] = h.ops[5].with_(index=-1)
    return h


class _Delegate:
    """Not a LinearizableChecker: IndependentChecker checks every key
    through its own History, today's path."""

    def __init__(self, inner):
        self.inner = inner

    def check(self, test, history, opts=None):
        return self.inner.check(test, history, opts)


def _strip(r):
    return {k: v for k, v in r.items() if k != "wall_s"}


@pytest.mark.parametrize("cls", CORRUPTION_CLASSES)
def test_planted_class_declines_its_key_alone(cls):
    h = _planted(cls)
    lin = LinearizableChecker(use_tpu=False)
    before = dict(PREP_STATS)
    new = independent_checker(lin).check({}, h)
    declined = 0 if cls in WHOLE_HISTORY_ONLY else 1
    want = {"one_pass_keys": 3 - declined, "declined_keys": declined}
    assert new["prep"] == want
    assert {k: PREP_STATS[k] - before[k] for k in want} == want
    assert (split_keyed(h.ops)["b"].records is None) == bool(declined)
    old = independent_checker(_Delegate(lin)).check({}, h)
    assert old["prep"] == {"one_pass_keys": 0, "declined_keys": 0}
    assert list(new["results"]) == list(old["results"]) == ["a", "b", "c"]
    for k in new["results"]:
        assert _strip(new["results"][k]) == _strip(old["results"][k]), k
    report = new["results"]["b"].get("history_report")
    if declined:
        assert cls in report["detected"]
    else:
        assert report is None


@pytest.mark.parametrize(
    "cls", [c for c in CORRUPTION_CLASSES if c not in WHOLE_HISTORY_ONLY]
)
def test_planted_class_strict_refusal_as_today(cls):
    h = _planted(cls)
    lin = LinearizableChecker(use_tpu=False, strict_history=True)
    with pytest.raises(HistorySentryError) as new:
        independent_checker(lin).check({}, h)
    with pytest.raises(HistorySentryError) as old:
        independent_checker(_Delegate(lin)).check({}, h)
    assert new.value.classes == old.value.classes
    assert cls in new.value.classes


def test_prep_counts_add_up_across_threads():
    """Keyed checks on more threads than cores, switching often, lose
    no count of PREP_STATS."""
    h = _generated(11)
    lin = LinearizableChecker(use_tpu=False)
    n_threads, n_checks = 2 * (os.cpu_count() or 1) + 2, 4
    before = dict(PREP_STATS)
    errors = []

    def work():
        try:
            for _ in range(n_checks):
                independent_checker(lin).check({}, h)
        except Exception as e:  # surfaced by the assert below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert PREP_STATS["one_pass_keys"] - before["one_pass_keys"] == (
        6 * n_threads * n_checks)
    assert PREP_STATS["declined_keys"] == before["declined_keys"]


@pytest.mark.parametrize("extra", [
    {"type": "bogus"}, {"fails": True}, {"crashed": True},
])
def test_op_the_encoder_cannot_read_declines_its_key(extra):
    """An op of another type, or one already carrying a flag that
    complete() sets, sends its key through its own History."""
    h = _generated(11)
    j = next(i for i, o in enumerate(h.ops)
             if o.value.key == 2 and o.type == "invoke")
    h.ops[j] = h.ops[j].with_(**extra)
    keys = split_keyed(h.ops)
    assert [k for k, g in keys.items() if g.records is None] == [2]
    lin = LinearizableChecker(use_tpu=False)
    new = independent_checker(lin).check({}, h)
    assert new["prep"] == {"one_pass_keys": 5, "declined_keys": 1}
    old = independent_checker(_Delegate(lin)).check({}, h)
    for k in new["results"]:
        assert _strip(new["results"][k]) == _strip(old["results"][k]), k


# -- checker parity -----------------------------------------------------


@pytest.fixture
def no_race(monkeypatch):
    """The device (interpreted) decides every key, so both paths'
    methods are fixed rather than raced."""
    monkeypatch.setattr(
        importlib.import_module("jepsen_tpu.checker.linearizable"),
        "_race_eligible", lambda ev, m: False,
    )


CHECKERS = {
    "oracle": lambda: LinearizableChecker(use_tpu=False),
    "interpret": lambda: LinearizableChecker(interpret=True),
}


@pytest.mark.parametrize("kind", sorted(CHECKERS))
@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_keyed_results_equal_todays_path(name, kind, no_race):
    h = HISTORIES[name]()
    lin = CHECKERS[kind]()
    new = independent_checker(lin).check({}, h)
    old = independent_checker(_Delegate(lin)).check({}, h)
    n = new["key_count"]
    assert new["prep"] == {"one_pass_keys": n, "declined_keys": 0}
    assert list(new["results"]) == list(old["results"])
    assert new["valid?"] == old["valid?"]
    assert new["valid?"] is (name != "generated-invalid")
    for k, r in new["results"].items():
        assert _strip(r) == _strip(old["results"][k]), k
        # and the key's own History, checked alone
        alone = lin.check({}, History(_plain_split(h)[k]))
        for f in ("valid?", "failure", "failed_op_index", "window",
                  "n_ops", "method"):
            assert r.get(f) == alone.get(f), (k, f)
    assert new["engine_stats"] == old["engine_stats"]


def _tree(d):
    out = {}
    for root, _, files in os.walk(d):
        for f in files:
            p = os.path.join(root, f)
            out[os.path.relpath(p, d)] = open(p, "rb").read()
    return out


@pytest.mark.parametrize("name", ["generated-invalid", "edges"])
def test_keyed_artifacts_equal_todays_path(name, tmp_path, no_race):
    h = HISTORIES[name]()
    lin = LinearizableChecker(interpret=True)
    independent_checker(lin).check({"run_dir": str(tmp_path / "new")}, h)
    independent_checker(_Delegate(lin)).check(
        {"run_dir": str(tmp_path / "old")}, h
    )
    new, old = _tree(tmp_path / "new"), _tree(tmp_path / "old")
    assert sorted(new) == sorted(old)
    assert any(p.endswith("history.jsonl") for p in new)
    for p, data in new.items():
        if not p.endswith("results.json"):
            assert data == old[p], p
            continue
        a, b = json.loads(data), json.loads(old[p])
        # wall time, and the death report's path under each run dir
        for r in (a, b):
            r.pop("wall_s")
            if "failure_svg" in r:
                r["failure_svg"] = os.path.basename(r["failure_svg"])
        assert a == b, p


@pytest.mark.parametrize("cls", [None, "orphan_completion"])
def test_key_as_split_reads_as_its_ops(cls):
    """A KeyOps is the key's op sequence to any reader, and check()
    of one gives what check() of the key's History gives, kept or
    declined alike."""
    h = _planted(cls) if cls else _generated(12, corrupt_key=3)
    plain = _plain_split(h)
    lin = LinearizableChecker(use_tpu=False)
    for k, g in split_keyed(h.ops).items():
        assert History(g).ops == History(plain[k]).ops
        assert _strip(lin.check({}, g)) == _strip(
            lin.check({}, History(plain[k]))
        ), k


def test_replaced_check_still_sees_every_key(monkeypatch):
    """A check put in the register checker's place (as the benchmark's
    control is) is what IndependentChecker calls for every key, and it
    reads each key's ops."""
    seen = []

    def check(self, test, history, opts=None, checkpoint=None):
        seen.append(History(history).ops)
        return {"valid?": True}

    monkeypatch.setattr(LinearizableChecker, "check", check)
    h = _generated(11)
    out = independent_checker(LinearizableChecker()).check({}, h)
    assert out["valid?"] is True
    plain = _plain_split(h)
    assert seen == [
        History(plain[k]).ops for k in sorted(plain, key=str)
    ]


def test_other_sub_checkers_take_todays_path():
    seen = []

    class Recorder:
        def check(self, test, history, opts=None):
            seen.append(history)
            return {"valid?": True}

    h = _generated(11)
    out = independent_checker(Recorder()).check({}, h)
    assert out["prep"] == {"one_pass_keys": 0, "declined_keys": 0}
    plain = _plain_split(h)
    assert [x.ops for x in seen] == [
        History(plain[k]).ops for k in sorted(plain, key=str)
    ]
    queue = LinearizableChecker(model="unordered-queue")
    assert not queue.encodes_whole
    q = History([
        invoke_op(0, "enqueue", tuple_("q", 1)),
        ok_op(0, "enqueue", tuple_("q", 1)),
        invoke_op(1, "dequeue", tuple_("q", None)),
        ok_op(1, "dequeue", tuple_("q", 1)),
    ])
    out = independent_checker(queue).check({}, q)
    assert out["prep"] == {"one_pass_keys": 0, "declined_keys": 0}
    assert out["results"]["q"]["valid?"] is True
    assert LinearizableChecker(model="unordered-queue",
                               use_tpu=False).encodes_whole

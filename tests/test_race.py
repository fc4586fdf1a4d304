"""Competition-race mechanics (knossos `competition` analog,
jepsen/src/jepsen/checker.clj:128-144): the native C++ oracle races
the TPU kernel, first definite verdict wins, and verdicts cross-check
when both land. The TPU side is faked here (no accelerator on the test
host); the native thread, winner selection, cross-check accounting and
the eligibility gate are all real."""

import random
import threading
import time

import pytest

import jepsen_tpu.checker.linearizable as lin
from jepsen_tpu.checker.events import history_to_events
from jepsen_tpu.checker.models import model as get_model
from jepsen_tpu.checker.wgl_native import available as native_available
from jepsen_tpu.sim import gen_register_history

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain"
)


class FakeOut:
    def __init__(self, ready_at):
        self.ready_at = ready_at

    def is_ready(self):
        return time.perf_counter() >= self.ready_at


def _stream(n_ops=200, seed=5):
    h = gen_register_history(
        random.Random(seed), n_ops=n_ops, n_procs=4, p_crash=0.01
    )
    return history_to_events(h)


def _handle(ready_in):
    return ([FakeOut(time.perf_counter() + ready_in)], None, None)


def test_native_wins_when_tpu_slow():
    lin.reset_race_stats()
    ev = _stream()
    racer = lin._NativeRacer(ev, "cas-register")
    # TPU "ready" far in the future: the oracle must win.
    out = lin._race_decide(ev, None, _handle(30.0), racer, "cas-register")
    assert out is not None
    assert out["valid?"] is True
    assert out["method"] == "cpu-oracle-native"
    assert out["race_winner"] == "native"
    assert lin.RACE_STATS["native_wins"] == 1


def test_tpu_wins_when_ready_first():
    lin.reset_race_stats()
    ev = _stream()
    racer = lin._NativeRacer(ev, "cas-register")
    out = lin._race_decide(ev, None, _handle(0.0), racer, "cas-register")
    assert out is None  # caller collects the TPU verdict
    lin._race_crosscheck(racer, True)
    assert lin.RACE_STATS["tpu_wins"] == 1
    # the oracle on a 200-op stream lands within the grace window
    assert lin.RACE_STATS["crosschecked"] == 1
    assert lin.RACE_STATS["mismatches"] == 0


def test_crosscheck_counts_mismatch():
    lin.reset_race_stats()
    ev = _stream()
    racer = lin._NativeRacer(ev, "cas-register")
    racer.join(10.0)
    # Claim the TPU said invalid while the oracle says valid: the
    # mismatch must be counted (and logged), not raised.
    lin._race_crosscheck(racer, False)
    assert lin.RACE_STATS["mismatches"] == 1


def test_native_win_invalid_carries_failure_report():
    lin.reset_race_stats()
    # Non-linearizable literal history: read sees a never-written value.
    from jepsen_tpu.history.history import History
    from jepsen_tpu.history.ops import invoke_op, ok_op

    h = History([
        invoke_op(0, "write", 1),
        ok_op(0, "write", 1),
        invoke_op(1, "read"),
        ok_op(1, "read", 2),
    ])
    ev = history_to_events(h)
    racer = lin._NativeRacer(ev, "cas-register")
    out = lin._race_decide(ev, None, _handle(30.0), racer, "cas-register")
    assert out is not None
    assert out["valid?"] is False
    assert out["failed_op_index"] is not None
    assert "failure" in out and out["failure"]["configs"]


def test_eligibility_gate():
    ev = _stream(n_ops=100)
    m = get_model("cas-register")
    assert lin._race_eligible(ev, m)
    big = _stream(n_ops=100)
    big.n_ops = lin.RACE_MAX_OPS + 1  # size gate
    assert not lin._race_eligible(big, m)


def test_bitset_crosscheck_consumes_racer_no_double_count(monkeypatch):
    """Regression: after the bitset tier cross-checks its racer, the
    racer must be DROPPED before the taint fall-through hands control
    to the K-ladder. The old code kept it, so one native computation
    was counted twice — a tpu_win at the crosscheck AND a native_win
    when the ladder saw the already-finished racer. Invariant: every
    racer decides exactly one race, so tpu_wins + native_wins must
    equal the number of racers created."""
    import jepsen_tpu.checker.wgl_bitset as bs

    lin.reset_race_stats()
    ev = _stream(n_ops=60, seed=11)

    created = []
    real_racer = lin._NativeRacer

    class CountingRacer(real_racer):
        def __init__(self, *a, **kw):
            created.append(self)
            super().__init__(*a, **kw)

    monkeypatch.setattr(lin, "_NativeRacer", CountingRacer)
    # Deterministic ordering: the TPU side always wins the decide, so
    # the bitset tier reaches its crosscheck.
    monkeypatch.setattr(lin, "_race_decide", lambda *a, **kw: None)
    # Force the impossible-by-construction taint so the bitset branch
    # falls through to the K-ladder after cross-checking.
    monkeypatch.setattr(
        bs, "collect_steps_bitset_segmented",
        lambda steps, handle: (True, True, -1),
    )

    out = lin.check_events_bucketed(ev, race=True, interpret=True)
    assert out["valid?"] is True, out
    assert lin.RACE_STATS["crosschecked"] >= 1
    wins = lin.RACE_STATS["tpu_wins"] + lin.RACE_STATS["native_wins"]
    assert wins == len(created), (dict(lin.RACE_STATS), len(created))
    assert len(created) == 2  # bitset racer dropped; ladder made its own


# -- deferred cross-checks (deferred_crosschecks) ----------------------


class FakeRacer:
    """A native racer whose verdict lands when the test says: at once
    (landed=True), after `land_in` seconds, or when it is joined
    (lands_on_join: a slow racer the caller must wait for). Records
    the timeout of every join."""

    def __init__(self, valid=True, landed=False, lands_on_join=False):
        self.result = (valid, {})
        self.error = None
        self.joins = []
        self._landed = threading.Event()
        self._lands_on_join = lands_on_join
        if landed:
            self._landed.set()

    def land_in(self, seconds):
        t = threading.Timer(seconds, self._landed.set)
        t.daemon = True
        t.start()

    def done(self):
        return self._landed.is_set()

    def join(self, timeout):
        self.joins.append(timeout)
        if self._lands_on_join:
            self._landed.set()
        self._landed.wait(timeout)


@pytest.fixture
def stats():
    lin.reset_race_stats()
    yield lin.RACE_STATS
    lin.reset_race_stats()


@pytest.fixture
def cap(monkeypatch):
    """Set the number of racers a scope keeps waiting."""
    def set_cap(n):
        monkeypatch.setattr(lin, "_crosscheck_cap", lambda: n)
    return set_cap


def _racer_waits(fn):
    """Run fn with the span recorder on; the `racer.wait` spans."""
    from jepsen_tpu import obs
    from jepsen_tpu.obs import trace as obs_trace

    obs.disable()
    obs_trace.TRACER.clear()
    obs.enable()
    try:
        fn()
        return [s for s in obs.spans()
                if s["ph"] == "X" and s["name"] == "racer.wait"]
    finally:
        obs.disable()
        obs_trace.TRACER.clear()


def _keyed_history(n_keys):
    from jepsen_tpu.history.history import History
    from jepsen_tpu.independent import tuple_

    ops = []
    for k in range(n_keys):
        h = gen_register_history(random.Random(k), n_ops=20, n_procs=3)
        ops += [o.with_(process=o.process + 10 * k,
                        value=tuple_(k, o.value)) for o in h.ops]
    return History(ops)


def test_scope_tpu_win_does_not_join(stats, cap):
    cap(4)
    racer = FakeRacer()
    with lin.deferred_crosschecks() as scope:
        lin._race_crosscheck(racer, True)
        assert racer.joins == []
        assert len(scope.pending) == 1
        assert stats["tpu_wins"] == 1 and stats["crosschecked"] == 0
        racer.land_in(0.0)
    assert stats["crosschecked"] == 1 and stats["deferred"] == 1


def test_scope_exit_crosschecks_every_tpu_win_and_counts_mismatch(
        stats, cap):
    cap(8)
    racers = [FakeRacer(landed=True) for _ in range(3)]
    racers += [FakeRacer(lands_on_join=True) for _ in range(2)]
    racers.append(FakeRacer(valid=False, lands_on_join=True))  # planted
    with lin.deferred_crosschecks() as scope:
        for r in racers:
            lin._race_crosscheck(r, True)
        scope.settle_finished()  # the three landed racers, no wait
        assert stats["crosschecked"] == 3 and len(scope.pending) == 3
        assert all(r.joins == [] for r in racers)
    assert stats["tpu_wins"] == len(racers)
    assert stats["crosschecked"] == len(racers)
    assert stats["deferred"] == len(racers)
    assert stats["mismatches"] == 1


def test_scope_pending_never_exceeds_cap_and_cap_join_is_racer_wait(
        stats, cap):
    cap(2)
    racers = [FakeRacer(lands_on_join=True) for _ in range(5)]
    sizes = []

    def run():
        with lin.deferred_crosschecks() as scope:
            for r in racers:
                lin._race_crosscheck(r, True)
                sizes.append(len(scope.pending))

    waits = _racer_waits(run)
    assert max(sizes) == 2
    # the three racers past the cap were joined oldest first, with the
    # whole grace, each under a racer.wait; the drain is the fourth
    assert [r.joins for r in racers[:3]] == [[lin.RACE_GRACE_S]] * 3
    assert len(waits) == 4
    assert stats["crosschecked"] == stats["deferred"] == 5


def test_scope_drains_when_a_key_check_raises(stats, cap):
    from jepsen_tpu.independent import independent_checker

    cap(8)

    class RacingChecker:
        """The device decides every key and its racer lands late; the
        third key's check raises."""

        def __init__(self):
            self.racers = []

        def check(self, test, history, opts=None):
            if len(self.racers) == 2:
                raise RuntimeError("key check failed")
            r = FakeRacer(lands_on_join=True)
            self.racers.append(r)
            lin._race_crosscheck(r, True)
            return {"valid?": True}

    checker = RacingChecker()
    with pytest.raises(RuntimeError):
        independent_checker(checker).check({}, _keyed_history(3))
    assert all(r.joins for r in checker.racers)
    assert stats["tpu_wins"] == stats["crosschecked"] == 2
    assert getattr(lin._scope, "crosschecks", None) is None


def test_drain_gives_each_racer_the_grace_from_the_drain(
        stats, cap, monkeypatch):
    cap(8)
    # a grace wide enough that a loaded host's timers land inside it
    monkeypatch.setattr(lin, "RACE_GRACE_S", 0.3)
    late = [FakeRacer(), FakeRacer()]
    never = FakeRacer()
    with lin.deferred_crosschecks():
        for r in late + [never]:
            lin._race_crosscheck(r, True)
        # past the grace counted from the TPU wins
        time.sleep(2 * lin.RACE_GRACE_S)
        late[0].land_in(0.3 * lin.RACE_GRACE_S)
        late[1].land_in(0.6 * lin.RACE_GRACE_S)
        t0 = time.perf_counter()
    assert time.perf_counter() - t0 < 10 * lin.RACE_GRACE_S
    assert stats["tpu_wins"] == 3
    assert stats["crosschecked"] == stats["deferred"] == 2


def test_scope_is_reentrant_and_drains_once_at_the_outermost(
        stats, cap):
    cap(8)
    racer = FakeRacer(lands_on_join=True)
    with lin.deferred_crosschecks() as outer:
        with lin.deferred_crosschecks() as inner:
            assert inner is outer
            lin._race_crosscheck(racer, True)
        assert racer.joins == [] and len(outer.pending) == 1
    assert stats["crosschecked"] == stats["deferred"] == 1


def test_no_scope_keeps_the_grace_join(stats):
    racer = FakeRacer(landed=True)
    waits = _racer_waits(lambda: lin._race_crosscheck(racer, True))
    assert racer.joins == [lin.RACE_GRACE_S] and len(waits) == 1
    assert stats["crosschecked"] == 1 and stats["deferred"] == 0


def test_other_thread_inside_an_open_scope_keeps_the_grace_join(
        stats, cap):
    cap(8)
    racer = FakeRacer(landed=True)
    with lin.deferred_crosschecks():
        t = threading.Thread(
            target=lin._race_crosscheck, args=(racer, False))
        t.start()
        t.join(10.0)
        assert racer.joins == [lin.RACE_GRACE_S]
        assert stats["crosschecked"] == stats["mismatches"] == 1
    assert stats["deferred"] == 0


def test_cap_zero_settles_at_once(stats, cap):
    """A one-core host leaves no core for a waiting racer."""
    cap(0)
    racer = FakeRacer(landed=True)
    with lin.deferred_crosschecks() as scope:
        lin._race_crosscheck(racer, True)
        assert racer.joins == [lin.RACE_GRACE_S] and not scope.pending
        assert stats["crosschecked"] == 1
    assert stats["deferred"] == 0


def _fake_device(monkeypatch, racers):
    """The bitset tier with the device always first and valid: no
    kernel runs, and every native racer is a landed FakeRacer."""
    import jepsen_tpu.checker.wgl_bitset as bs

    def make_racer(events, model):
        racers.append(FakeRacer(landed=True))
        return racers[-1]

    monkeypatch.setattr(lin, "_NativeRacer", make_racer)
    monkeypatch.setattr(lin, "_race_decide", lambda *a, **kw: None)
    monkeypatch.setattr(bs, "launch_steps_bitset_segmented",
                        lambda *a, **kw: None)
    monkeypatch.setattr(bs, "collect_steps_bitset_segmented",
                        lambda steps, handle: (True, False, -1))
    monkeypatch.setattr(bs, "check_steps_bitset_segmented",
                        lambda *a, **kw: (True, False, -1))


def test_single_history_check_keeps_the_grace_join(stats, monkeypatch):
    racers = []
    _fake_device(monkeypatch, racers)
    h = gen_register_history(random.Random(3), n_ops=40, n_procs=3)
    out = lin.LinearizableChecker(interpret=True).check({}, h)
    assert out["valid?"] is True and out["method"] == "tpu-wgl-bitset"
    assert [r.joins for r in racers] == [[lin.RACE_GRACE_S]]
    assert stats["crosschecked"] == 1 and stats["deferred"] == 0


def test_keyed_check_defers_every_key_crosscheck(stats, monkeypatch, cap):
    from jepsen_tpu.independent import independent_checker

    cap(8)
    racers = []
    _fake_device(monkeypatch, racers)
    out = independent_checker(
        lin.LinearizableChecker(interpret=True)).check(
            {}, _keyed_history(4))
    assert out["valid?"] is True and len(racers) == 4
    # landed racers settle at the next key's start with no join; only
    # the last key's is still pending at the drain
    assert all(r.joins == [] for r in racers[:-1])
    assert stats["tpu_wins"] == stats["crosschecked"] == 4
    assert stats["deferred"] == 4


def test_keyed_check_real_racers_all_crosschecked(stats, monkeypatch, cap):
    """Real native racers on a keyed history the device always wins:
    by the time the check returns, every TPU win is cross-checked."""
    from jepsen_tpu.independent import independent_checker

    cap(8)
    import jepsen_tpu.checker.wgl_bitset as bs

    monkeypatch.setattr(lin, "_race_decide", lambda *a, **kw: None)
    monkeypatch.setattr(bs, "launch_steps_bitset_segmented",
                        lambda *a, **kw: None)
    monkeypatch.setattr(bs, "collect_steps_bitset_segmented",
                        lambda steps, handle: (True, False, -1))
    out = independent_checker(
        lin.LinearizableChecker(interpret=True)).check(
            {}, _keyed_history(5))
    assert out["valid?"] is True
    assert stats["tpu_wins"] == stats["crosschecked"] == 5
    assert stats["deferred"] == 5 and stats["mismatches"] == 0


def test_checkpointed_crosscheck_follows_the_verdict_in_a_scope(
        stats, monkeypatch, cap):
    from types import SimpleNamespace

    cap(8)
    racers = []
    _fake_device(monkeypatch, racers)
    sink = SimpleNamespace(summary=lambda: {})
    with lin.deferred_crosschecks() as scope:
        out = lin.check_events_bucketed(
            _stream(n_ops=40), race=True, interpret=True, checkpoint=sink)
        assert out["valid?"] is True and "checkpoint" in out
        assert [r.joins for r in racers] == [[lin.RACE_GRACE_S]]
        assert not scope.pending and stats["crosschecked"] == 1
    assert stats["deferred"] == 0


def test_dispatch_plane_crosscheck_is_never_deferred(stats, cap):
    from types import SimpleNamespace

    from jepsen_tpu.checker.dispatch import DispatchPlane

    cap(8)
    plane = DispatchPlane(mesh=False)
    racer = FakeRacer(landed=True)
    resolved = []
    fut = SimpleNamespace(racer=racer, checkpoint=None,
                          _resolve=resolved.append)
    with lin.deferred_crosschecks() as scope:
        plane._finish(fut, {"valid?": True})
        assert racer.joins == [lin.RACE_GRACE_S] and not scope.pending
        assert stats["crosschecked"] == 1 and resolved
    assert stats["deferred"] == 0

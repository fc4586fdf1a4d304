"""A run with the timed path broken underneath must come out not
correct. Each test skips the harness's look for a chip and drives the
rest of a run (``run.measure``) on the CPU at a small size, once sound
and once with one fault the cell can have planted in the program."""

import argparse

import control
import jax
import pytest
import run

from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.independent import IndependentChecker

#: the cell at a size the CPU holds: many keys, and one register alone
SMALL = {
    "keyed": {"keys": 12, "ops_per_key": 60},
    "one-register": {"keys": 1, "ops_per_key": 2000, "processes_per_key": 5,
                     "crashes_per_key": {"read": 1, "write": 2, "cas": 1}},
}


def measure(size):
    spec, cell, cfg, traffic = run.resolve("etcd-keyed.analyze")
    args = argparse.Namespace(workload=cell["name"], seed=2**31 + 99, seconds=1.0,
                              trace=0)
    return run.measure(args, spec, cell, dict(cfg, **SMALL[size]), traffic,
                       jax.devices()[:1])


def flip_check(monkeypatch):
    """An answer altered where it is produced: every register verdict
    comes back negated."""
    orig = LinearizableChecker.check

    def check(self, *a, **kw):
        out = orig(self, *a, **kw)
        out["valid?"] = not out["valid?"]
        return out

    monkeypatch.setattr(LinearizableChecker, "check", check)


def half_the_keys(monkeypatch):
    """Half of the batch left out: the multi-key check checks every
    other key and merges only those."""
    orig = IndependentChecker.check

    def check(self, test, history, opts=None):
        from jepsen_tpu.history.history import History
        keep = {k for k in {o.value.key for o in history.ops} if k % 2 == 0}
        return orig(self, test, History(
            [o for o in history.ops if o.value.key in keep], indexed=True), opts)

    monkeypatch.setattr(IndependentChecker, "check", check)


@pytest.mark.parametrize("size", sorted(SMALL))
def test_sound_run_is_correct(size):
    out = measure(size)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["verdict_mismatches"]["value"] == 0


@pytest.mark.parametrize("size,fault", [
    ("one-register", flip_check),
    ("keyed", flip_check),
    ("keyed", half_the_keys),
])
def test_fault_is_not_correct(monkeypatch, size, fault):
    fault(monkeypatch)
    out = measure(size)
    assert out["correct"] is False
    assert out["checks"]["verdict_mismatches"]["value"] > 0


def host_oracle_verdicts(monkeypatch):
    """The plane's last rung taken on every check: a plane fault
    reaches the future and the host oracle's verdict comes back tagged
    ``degraded`` (``LinearizableChecker._plane_result``)."""
    from jepsen_tpu.checker import chaos

    orig = LinearizableChecker.check

    def check(self, *a, **kw):
        out = orig(self, *a, **kw)
        chaos.note_plane_fault()
        chaos.note_oracle_fallback()
        return dict(out, method="cpu-oracle-python", race_winner=None,
                    degraded={"kind": "planted"})

    monkeypatch.setattr(LinearizableChecker, "check", check)


@pytest.mark.parametrize("size", sorted(SMALL))
def test_host_oracle_verdict_is_not_correct(monkeypatch, size):
    """Right verdicts from the host oracle instead of the device are
    caught by the audit, not by the verdicts."""
    host_oracle_verdicts(monkeypatch)
    out = measure(size)
    assert out["correct"] is False
    assert out["checks"]["verdict_mismatches"]["value"] == 0
    for k in ("host_resolved_verdicts", "degraded_verdicts",
              "oracle_fallbacks", "plane_faults"):
        assert out["checks"][k]["value"] > 0, k


@pytest.mark.parametrize("size", sorted(SMALL))
def test_control_in_place_is_not_correct(monkeypatch, size):
    """The control (control.py), in the register checker's place,
    through the whole run: it passes the lost write."""
    control.put_in_place(monkeypatch.setattr)
    out = measure(size)
    assert out["correct"] is False
    assert out["checks"]["verdict_mismatches"]["value"] > 0

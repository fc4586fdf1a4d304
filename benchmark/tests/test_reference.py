"""The reference's search, its certificates, and the control.

The search decides any register history; a certificate is checked,
never trusted. Both agree on random small histories, valid and
invalid, and a wrong witness proves nothing. The control (control.py)
drops real-time order and must disagree with the reference."""

import random

import control
import pytest
import reference
from traffic import generate as gen


def small(seed):
    rng = random.Random(seed)
    cfg = {"ops_per_key": rng.randrange(5, 60), "processes_per_key": rng.randrange(1, 7),
           "values": rng.randrange(2, 6),
           "crashes_per_key": {f: rng.randrange(3) for f in ("read", "write", "cas")}}
    return gen.request(cfg, seed, 0, 0, seed % 2 == 1, (0.0, 1.0))


@pytest.mark.parametrize("block", range(4))
def test_certificates_agree_with_search(block):
    for seed in range(block * 100, block * 100 + 100):
        try:
            item = small(seed)
        except ValueError:  # no read after a write to lose
            continue
        ops = item["ops"]
        want = reference.check(ops)[0]
        assert reference.decide(item) is want
        if "order" in item:
            assert reference.proves_valid(ops, item["order"]) is want
            if len(item["order"]) > 2 and not want:
                bad = list(item["order"])
                bad[0], bad[-1] = bad[-1], bad[0]
                assert not reference.proves_valid(ops, bad)
        else:
            assert want is False
            assert reference.proves_lost_write(ops, item["lost_read"])


def w(p, f, v, t="invoke"):
    return {"type": t, "f": f, "value": v, "process": p}


def test_known_histories():
    # a read of 1 completed before the write of 1 was invoked
    stale = [w(0, "read", None), w(0, "read", 1, "ok"), w(1, "write", 1), w(1, "write", 1, "ok")]
    assert reference.check(stale) == (False, 1)
    # concurrent: the read may take effect after the write
    conc = [w(0, "read", None), w(1, "write", 1), w(0, "read", 1, "ok"), w(1, "write", 1, "ok")]
    assert reference.check(conc) == (True, None)
    # a crashed write may take effect any time later, once
    crash = [w(1, "write", 2), w(1, "write", 2, "info"), w(0, "read", None), w(0, "read", 2, "ok"),
             w(2, "write", 3), w(2, "write", 3, "ok"), w(0, "read", None), w(0, "read", 2, "ok")]
    assert reference.check(crash)[0] is False
    assert reference.check(crash[:6])[0] is True
    # a failed cas took no effect
    failed = [w(0, "cas", [None, 4]), w(0, "cas", [None, 4], "fail"), w(1, "read", None),
              w(1, "read", 4, "ok")]
    assert reference.check(failed)[0] is False


def test_control_disagrees_with_reference():
    """The control passes a lost write: it reads as an initial value."""
    items = []
    for s in range(1, 80, 2):
        try:
            items.append(small(s))
        except ValueError:  # no read after a write to lose
            pass
    assert any(control.weak_check(i["ops"]) is not reference.decide(i) for i in items)
    assert all(control.weak_check(i["ops"]) for i in items)

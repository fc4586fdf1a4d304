"""The trace reduction: interval arithmetic on hand-made traces, and
the whole reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: three 300-op register checks)."""

import os

import pytest
import trace_reduce as tr

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "data", "v5e_three_checks.xplane.pb.gz")


def test_union_clip_gaps():
    iv = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert iv == [(0, 3), (5, 8)]
    assert tr.clip(iv, 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps(tr.clip(iv, 2, 10), 2, 10) == [(3, 5), (8, 10)]


def fake(dev, host):
    return tr.Trace({"/device:TPU:0": dev}, host, {})


def test_reduce_busy_kernels_and_named_gaps():
    t = fake(
        [("_bitset_scan.1", 10, 30), ("fusion", 25, 40), ("copy", 70, 80)],
        [("bench.window", 0, 100), ("bench.check", 0, 50), ("bench.check", 55, 100)],
    )
    r = tr.reduce(t, 1, spans=[("host_sync", 42, 48)])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_pct"] == pytest.approx(60.0)
    assert r["op_s"] == pytest.approx(
        {"_bitset_scan.1": 20e-9, "fusion": 15e-9, "copy": 10e-9})
    gaps = dict((n, s) for n, s in r["idle_gaps"])
    assert r["idle_gaps"][0] == ["bench.check", pytest.approx(30e-9)]  # 40-70
    assert "unattributed" not in gaps  # every gap lies in some span
    assert r["n_gaps"] == 3


def test_no_device_work_is_an_error():
    with pytest.raises(tr.TraceError):
        tr.reduce(fake([], [("bench.window", 0, 10)]), 1)
    with pytest.raises(tr.TraceError):
        tr.reduce(tr.Trace({}, [("bench.window", 0, 10)], {}), 1)
    with pytest.raises(tr.TraceError):
        fake([("x", 1, 2)], []).window()


def test_recorded_v5e_trace():
    t = tr.Trace.load(SAMPLE)
    assert "/device:TPU:0" in t.devices
    r = tr.reduce(t, 1)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_pct"] < 100
    assert any("bitset" in n and s > 0 for n, s in r["op_s"].items())
    assert r["device_ops"] and r["idle_gaps"]
    assert all(s > 0 for _, s in r["idle_gaps"])

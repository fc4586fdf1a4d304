"""The span readers on hand-made span lists: each sums its spans'
durations over the register checks, and reads nothing where the
program recorded none of its spans (as a program without them does)."""

import os

import pytest
import run

READERS = {
    name: run.load_module(os.path.join(run.BENCH, "metrics", name + ".py"),
                          "bench_metric_" + name)
    for name in ("host_prep_ms_per_check", "launch_sync_ms_per_check",
                 "racer_wait_ms_per_check")
}


def span(name, dur_ms, ph="X"):
    return {"name": name, "ph": ph, "ts": 0, "dur": int(dur_ms * 1e6)}


SPANS = [
    span("independent.check", 100.0), span("prep.split", 10.0),
    span("prep.history", 1.0), span("check", 40.0),
    span("prep.sentry", 0.5), span("prep.encode", 2.0), span("prep.steps", 0.5),
    span("launch", 1.5), span("device_wait", 2.0), span("host_sync", 0.5),
    span("racer.wait", 3.0), span("racer.native", 6.0),
    span("verdict.harvest", 4.0), span("launches", 0, ph="i"),
]


@pytest.mark.parametrize("name,want", [
    ("host_prep_ms_per_check", (10.0 + 1.0 + 0.5 + 2.0 + 0.5) / 2),
    ("launch_sync_ms_per_check", (1.5 + 2.0 + 0.5) / 2),
    ("racer_wait_ms_per_check", 3.0 / 2),
])
def test_reader_sums_its_spans_per_check(name, want):
    assert READERS[name].read({"checks": 2, "spans": SPANS}) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_its_spans(name):
    other = [s for s in SPANS if s["name"] in ("independent.check", "check",
                                              "racer.native", "launches")]
    assert READERS[name].read({"checks": 2, "spans": other}) is None
    assert READERS[name].read({"checks": 2, "spans": []}) is None
    assert READERS[name].read({"checks": 2}) is None
    assert READERS[name].read({"checks": 0, "spans": SPANS}) is None

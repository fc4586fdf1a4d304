"""Flight-recorder tests (jepsen_tpu/obs): span/instant semantics,
the disabled-mode free-ness guarantee, ring bounding, the launch-
accounting parity pin (trace instants == LAUNCH_STATS on a mesh run),
Chrome-trace schema, Prometheus exposition, the consolidated engine
snapshot, and the analyze --trace / trace-summary CLI surfaces."""

import json
import re
import sys
import threading

import pytest

from jepsen_tpu import obs
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.obs.export import chrome_trace, validate_chrome_trace

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _quiet_tracer():
    """Every test starts and ends with the recorder off and empty —
    the tracer is process-wide state, like the stats planes."""
    obs.disable()
    obs_trace.TRACER.clear()
    yield
    obs.disable()
    obs_trace.TRACER.clear()


# -- span / instant semantics -----------------------------------------


def test_span_records_complete_event_with_set_attrs():
    obs.enable()
    with obs.span("check", kind="service", tenant="t0") as sp:
        sp.set(status=200)
    (ev,) = obs.spans()
    assert ev["name"] == "check" and ev["kind"] == "service"
    assert ev["ph"] == "X" and ev["dur"] >= 0
    assert ev["args"] == {"tenant": "t0", "status": 200}
    assert ev["tid"] == threading.get_ident()


def test_nested_spans_and_instants_order_by_start():
    obs.enable()
    with obs.span("outer"):
        obs.instant("mark", kind="launch_stat", n=1)
        with obs.span("inner"):
            pass
    names = [e["name"] for e in obs.spans()]
    # sorted by start ts: outer opened first, then the instant, then
    # inner — completion order (inner closes first) must not leak in
    assert names == ["outer", "mark", "inner"]
    st = obs.trace_stats()
    assert st["spans"] == 2 and st["instants"] == 1
    assert st["by_kind"]["launch_stat"] == 1


def test_disabled_mode_is_noop_singleton():
    # one attribute check, one shared object, zero allocations
    assert obs.span("a") is obs.span("b")
    assert obs.span("a").__enter__().set(x=1).__exit__() is False
    assert obs.instant("a", n=1) is None
    assert obs_trace.TRACER._rings == {}
    assert obs.trace_stats()["events"] == 0


def test_disabled_mode_full_check_allocates_no_rings():
    """The overhead guard's structural half: a full instrumented check
    with the tracer off must never touch a ring (the bench pins the
    < 1% wall half on hardware)."""
    from jepsen_tpu.checker.events import history_to_events
    from jepsen_tpu.checker.sharded import check_keys
    from jepsen_tpu.sim import gen_register_history
    import random

    streams = [
        history_to_events(gen_register_history(
            random.Random(s), n_ops=16, n_procs=2))
        for s in range(3)
    ]
    res = check_keys(streams, mesh=False)
    assert len(res) == 3
    assert obs_trace.TRACER._rings == {}
    assert obs.trace_stats() == {
        "enabled": False, "events": 0, "spans": 0, "instants": 0,
        "dropped": 0, "by_kind": {},
    }


def test_ring_bounds_memory_and_counts_drops():
    obs.enable(capacity=16)
    for i in range(100):
        obs.instant("tick", kind="soak", i=i)
    st = obs.trace_stats()
    assert st["events"] < 32  # never holds 2x capacity after a trim
    assert st["dropped"] > 0
    assert st["events"] + st["dropped"] == 100
    # the survivors are the newest events (owner-side front trim)
    assert obs.spans()[-1]["args"]["i"] == 99
    obs_trace.TRACER.capacity = obs_trace.DEFAULT_CAPACITY


def test_per_thread_rings_stamp_tid_and_tname():
    obs.enable()

    def emit():
        obs.instant("from_worker", kind="test")

    t = threading.Thread(target=emit, name="worker-0")
    t.start()
    t.join()
    obs.instant("from_main", kind="test")
    by_name = {e["name"]: e for e in obs.spans()}
    assert by_name["from_worker"]["tname"] == "worker-0"
    assert by_name["from_worker"]["tid"] != by_name["from_main"]["tid"]


# -- span tree, thread-instance rings, profiler annotations ------------


def test_nested_spans_carry_id_parent_and_root():
    obs.enable()
    with obs.span("outer"):
        with obs.span("mid"):
            with obs.span("leaf"):
                pass
        with obs.span("sibling"):
            pass
    with obs.span("second_root"):
        pass
    by = {e["name"]: e for e in obs.spans()}
    outer = by["outer"]
    assert outer["parent"] is None and outer["root"] == outer["id"]
    assert by["mid"]["parent"] == outer["id"]
    assert by["leaf"]["parent"] == by["mid"]["id"]
    assert by["sibling"]["parent"] == outer["id"]
    assert {by[n]["root"] for n in ("mid", "leaf", "sibling")} == {outer["id"]}
    second = by["second_root"]
    assert second["parent"] is None and second["root"] == second["id"]
    ids = [e["id"] for e in by.values()]
    assert len(set(ids)) == len(ids)


def test_explicit_parent_crosses_threads():
    assert obs.current() is None  # recorder off
    obs.enable()
    assert obs.current() is None  # no span open
    seen = []

    def work(pid):
        # a fresh thread has no open span of its own
        seen.append(obs.current())
        with obs.span("worker", parent=pid):
            pass

    with obs.span("caller") as caller:
        with obs.span("inner") as inner:
            assert obs.current() == inner.id
            t = threading.Thread(target=work, args=(obs.current(),))
            t.start()
            t.join()
        assert obs.current() == caller.id
    assert seen == [None]
    by = {e["name"]: e for e in obs.spans()}
    assert by["worker"]["parent"] == by["inner"]["id"]
    assert by["worker"]["root"] == by["caller"]["id"]
    assert by["worker"]["tid"] != by["caller"]["tid"]


def test_short_lived_threads_keep_every_span():
    """Thread idents are reused as soon as a thread exits; a ring per
    thread INSTANCE keeps every one of their spans."""
    obs.enable()

    def work(i):
        with obs.span("short", i=i):
            pass

    for i in range(200):
        t = threading.Thread(target=work, args=(i,))
        t.start()
        t.join()
    st = obs.trace_stats()
    assert st["spans"] == 200 and st["dropped"] == 0
    assert sorted(e["args"]["i"] for e in obs.spans()) == list(range(200))


def test_reset_forgets_rings_of_exited_threads():
    obs.enable()
    ts = [threading.Thread(target=lambda: obs.instant("x")) for _ in range(20)]
    for t in ts:
        t.start()
        t.join()
    obs.instant("main")
    assert len(obs_trace.TRACER._rings) == 21
    obs.reset()
    # only the live (main) thread's ring survives, emptied
    assert len(obs_trace.TRACER._rings) == 1
    assert obs.spans() == []
    obs.instant("again")
    assert [e["name"] for e in obs.spans()] == ["again"]


def test_span_lands_on_profiler_host_plane(tmp_path):
    """With jax imported and the recorder on, a span holds a profiler
    annotation of its name: under a capture it appears by name on a
    ``/host:`` plane of the .xplane.pb, on the device trace's clock."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("probe.annotated"):
            with obs.span("probe.inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("probe."):
                    found[e.name] = plane.name
    assert set(found) == {"probe.annotated", "probe.inner"}
    assert all(p.startswith("/host:") for p in found.values())


def test_importing_obs_leaves_jax_unimported():
    import subprocess

    code = ("import sys; import jepsen_tpu.obs as o; o.enable(); "
            "s = o.span('a'); s.__enter__(); s.__exit__(); "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert o.spans()[0]['name'] == 'a'")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# -- launch-accounting parity (the differential pin) ------------------


@pytest.mark.mesh
def test_trace_instants_equal_launch_stats_on_mesh_run():
    """THE parity pin: every _bump_launch mirrors one launch_stat
    instant, so summing instants per name from the trace reproduces
    LAUNCH_STATS exactly — the timeline and the counters are two views
    of the same accounting, never two accountings."""
    import random

    from jepsen_tpu.checker.events import history_to_events
    from jepsen_tpu.checker.sharded import check_keys
    from jepsen_tpu.checker.wgl_bitset import launch_stats_snapshot
    from jepsen_tpu.obs.snapshot import reset_engine_stats
    from jepsen_tpu.sim import corrupt_history, gen_register_history

    streams = []
    for seed in range(6):
        rng = random.Random(seed)
        h = gen_register_history(rng, n_ops=20, n_procs=3)
        if seed % 2:
            h = corrupt_history(h, rng)
        streams.append(history_to_events(h))
    # warm the jit caches untraced so compile-time launches don't
    # differ between the two views' observation windows
    check_keys(streams, interpret=True)
    reset_engine_stats()
    obs.enable()
    check_keys(streams, interpret=True)
    obs.disable()
    ls = launch_stats_snapshot()
    counted = {}
    for e in obs.spans():
        if e["kind"] == "launch_stat":
            counted[e["name"]] = (
                counted.get(e["name"], 0) + e["args"]["n"]
            )
    assert ls["launches"] > 0 and ls["host_syncs"] > 0
    for key, val in ls.items():
        assert counted.get(key, 0) == val, (key, counted, ls)


# -- export schema ----------------------------------------------------


def test_chrome_trace_schema_golden(tmp_path):
    obs.enable()
    with obs.span("launch", kind="launch"):
        obs.instant("launches", kind="launch_stat", n=1)
    events = obs.spans()
    obj = chrome_trace(events)
    assert validate_chrome_trace(obj) == []
    # structure Perfetto's legacy importer needs, pinned exactly
    metas = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    inst = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert len(metas) == 1 and metas[0]["name"] == "thread_name"
    assert len(xs) == 1 and xs[0]["cat"] == "launch"
    assert inst[0]["s"] == "t"
    # ts rebased to the earliest event and lowered ns -> us
    assert min(e["ts"] for e in xs + inst) == 0.0
    # survives a disk roundtrip
    p = tmp_path / "t.json"
    obs.write_chrome_trace(str(p), events)
    assert validate_chrome_trace(json.loads(p.read_text())) == []


def test_chrome_trace_validator_rejects_torn_events():
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0},  # no dur
        {"name": "y", "ph": "i", "pid": 1, "tid": 1, "ts": 0},  # no s
        {"name": "", "ph": "Q", "pid": 1, "tid": 1, "ts": 0},   # bad ph
    ]}
    errors = validate_chrome_trace(bad)
    assert len(errors) == 3
    assert validate_chrome_trace({"events": []}) != []


# -- the consolidated snapshot + Prometheus ---------------------------


def test_engine_snapshot_is_the_one_reader():
    from jepsen_tpu.obs.snapshot import engine_snapshot

    snap = engine_snapshot()
    assert set(snap) == {
        "dispatch", "launch", "mesh", "resilience", "checkpoint",
        "streaming", "txn_graph", "trace", "perf", "device",
    }
    # every surface names the device it ran on (CPU under tier-1)
    assert snap["device"]["platform"] == "cpu"
    assert snap["device"]["count"] == (
        snap["mesh"]["topology"]["global_devices"]
    )
    # sections carry their planes' own snapshot shapes
    assert "launches" in snap["launch"]
    assert "enabled" in snap["trace"]
    assert isinstance(snap["txn_graph"], dict)
    # the perf plane discloses the knob config every number ran under
    assert "config_hash" in snap["perf"]
    assert "tuned" in snap["perf"]


def test_reset_engine_stats_resets_every_plane():
    from jepsen_tpu.checker.wgl_bitset import (
        _bump_launch,
        launch_stats_snapshot,
    )
    from jepsen_tpu.obs.snapshot import reset_engine_stats

    obs.enable()
    _bump_launch("launches")
    assert launch_stats_snapshot()["launches"] >= 1
    assert obs.trace_stats()["events"] == 1
    reset_engine_stats()
    assert launch_stats_snapshot()["launches"] == 0
    assert obs.trace_stats()["events"] == 0


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|NaN)$"
)


def test_prometheus_exposition_format():
    from jepsen_tpu.obs.prom import prometheus_text

    obs.enable()
    with obs.span("check", kind="service"):
        pass
    text = prometheus_text()
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, "empty exposition"
    for ln in lines:
        if ln.startswith("#"):
            assert ln.startswith(("# HELP ", "# TYPE "))
        else:
            assert _PROM_LINE.match(ln), ln
    # the engine gauges and the trace-derived histogram both fold in
    assert any(ln.startswith("jepsen_tpu_launch_launches ")
               for ln in lines)
    hist = [ln for ln in lines if "span_duration_seconds_bucket" in ln
            and 'kind="service"' in ln]
    assert hist and any('le="+Inf"' in ln for ln in hist)
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in hist]
    assert counts == sorted(counts)  # cumulative buckets


# -- CLI surfaces -----------------------------------------------------


def test_cli_analyze_trace_and_summary(tmp_path, capsys, monkeypatch):
    """analyze --trace writes a Perfetto-loadable trace whose
    launch_stat instants equal the engine's LAUNCH_STATS, and
    trace-summary renders the attribution table from it."""
    from jepsen_tpu.checker.wgl_bitset import launch_stats_snapshot
    from jepsen_tpu.cli import EXIT_VALID, main

    # Pallas interpret mode: the seam that takes the device branch
    # (and therefore pays counted launches/syncs) on a CPU-only host
    monkeypatch.setenv("JEPSEN_TPU_INTERPRET", "1")
    # no native racer: a racer win leaves the launch uncollected (zero
    # host syncs), which made this parity pin depend on compile timing
    monkeypatch.setattr(
        sys.modules["jepsen_tpu.checker.linearizable"], "RACE_MAX_OPS", 0
    )
    store_root = str(tmp_path / "store")
    assert main([
        "test", "--workload", "register", "--ops", "40",
        "--store", store_root, "--name", "obs-run", "--seed", "7",
    ]) in (0, 1)
    trace_path = str(tmp_path / "trace.json")
    code = main([
        "analyze", "obs-run", "--workload", "register",
        "--store", store_root, "--trace", trace_path,
    ])
    assert code in (0, 1)
    obj = json.loads(open(trace_path).read())
    assert validate_chrome_trace(obj) == []
    # parity through the CLI surface: the trace's launch accounting
    # is the engine's launch accounting
    ls = launch_stats_snapshot()
    counted = {}
    for e in obj["traceEvents"]:
        if e.get("cat") == "launch_stat":
            counted[e["name"]] = (
                counted.get(e["name"], 0) + e["args"]["n"]
            )
    assert counted.get("launches", 0) == ls["launches"] > 0
    assert counted.get("host_syncs", 0) == ls["host_syncs"] > 0
    # the wrapper printed the export line and disabled the tracer
    assert not obs_trace.TRACER.enabled
    capsys.readouterr()
    assert main(["trace-summary", trace_path]) == EXIT_VALID
    out = capsys.readouterr().out
    assert "wall" in out and "launch_stat" in out


def test_cli_trace_summary_rejects_bad_schema(tmp_path, capsys):
    from jepsen_tpu.cli import EXIT_UNKNOWN, main

    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"traceEvents": [{"ph": "Z"}]}))
    assert main(["trace-summary", str(p)]) == EXIT_UNKNOWN
    assert "schema" in capsys.readouterr().out


# -- pod-wide flight recorder (obs/podtrace) ---------------------------


def _member_events(base_ns, tid=1, tname="MainThread"):
    """A tiny synthetic member ring: one span + one instant, raw ns."""
    return [
        {"name": "check", "kind": "service", "ph": "X",
         "ts": base_ns, "dur": 5_000_000, "tid": tid, "tname": tname,
         "args": {"tenant": "t0"}},
        {"name": "launches", "kind": "launch_stat", "ph": "i",
         "ts": base_ns + 1_000_000, "dur": 0, "tid": tid,
         "tname": tname, "args": {"n": 1}},
    ]


def test_podtrace_persist_load_roundtrip(tmp_path):
    from jepsen_tpu.obs import podtrace

    path = podtrace.persist_member_trace(
        str(tmp_path), process_index=1, n_hosts=2,
        events=_member_events(10_000),
        clock={"offset_ns": 500, "skew_bound_ns": 50},
    )
    assert path.endswith("member-001.trace.json")
    obj = podtrace.load_member_trace(path)
    assert obj["schema"] == podtrace.SCHEMA_VERSION
    assert obj["process_index"] == 1 and obj["n_hosts"] == 2
    assert len(obj["events"]) == 2


def test_podtrace_load_rejects_wrong_schema(tmp_path):
    from jepsen_tpu.obs import podtrace

    p = tmp_path / "member-000.trace.json"
    p.write_text(json.dumps({"schema": 999, "events": []}))
    with pytest.raises(ValueError, match="schema"):
        podtrace.load_member_trace(str(p))


def test_podtrace_merge_rebases_onto_member0_clock(tmp_path):
    from jepsen_tpu.obs import podtrace

    # Member 1's clock reads 1 ms ahead of member 0's: the SAME
    # physical instant carries different raw timestamps, and the
    # handshake's recorded offset brings them back together.
    podtrace.persist_member_trace(
        str(tmp_path), process_index=0, n_hosts=2,
        events=_member_events(1_000_000),
        clock={"offset_ns": 0, "skew_bound_ns": 20_000},
    )
    podtrace.persist_member_trace(
        str(tmp_path), process_index=1, n_hosts=2,
        events=_member_events(1_000_000 + 1_000_000),
        clock={"offset_ns": 1_000_000, "skew_bound_ns": 40_000},
    )
    out = str(tmp_path / "pod_trace.json")
    merged = podtrace.merge_pod_trace(
        str(tmp_path), out, expect_members=2
    )
    assert validate_chrome_trace(merged) == []
    # one Perfetto process per member, named and sort-indexed
    names = {e["pid"]: e["args"]["name"]
             for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {1: "pod-member-0", 2: "pod-member-1"}
    sorts = {e["pid"]: e["args"]["sort_index"]
             for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_sort_index"}
    assert sorts == {1: 0, 2: 1}
    # rebased: the same physical instant lands at the same merged ts
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    by_pid = {e["pid"]: e["ts"] for e in spans}
    assert by_pid[1] == by_pid[2] == 0.0
    # skew bound disclosed: the worst member window
    meta = merged["metadata"]
    assert meta["clock_skew_bound_ns"] == 40_000
    assert [m["process_index"] for m in meta["members"]] == [0, 1]
    assert all(m["events"] == 2 for m in meta["members"])
    # the merged trace persisted atomically to out_path
    disk = json.loads(open(out).read())
    assert disk == merged


def test_podtrace_merge_times_out_loudly_on_missing_member(tmp_path):
    from jepsen_tpu.obs import podtrace

    podtrace.persist_member_trace(
        str(tmp_path), process_index=0, n_hosts=2,
        events=_member_events(0),
        clock={"offset_ns": 0, "skew_bound_ns": 0},
    )
    with pytest.raises(RuntimeError, match="expected 2"):
        podtrace.merge_pod_trace(
            str(tmp_path), expect_members=2, timeout_s=0.2
        )


def test_podtrace_merge_without_clock_degrades_unaligned(tmp_path):
    # A member whose handshake couldn't run (clock None) still merges
    # — unaligned (offset 0), never a crash.
    from jepsen_tpu.obs import podtrace

    p = tmp_path / "member-000.trace.json"
    p.write_text(json.dumps({
        "schema": podtrace.SCHEMA_VERSION, "process_index": 0,
        "n_hosts": 1, "clock": None, "events": _member_events(5_000),
    }))
    merged = podtrace.merge_pod_trace(str(tmp_path))
    assert validate_chrome_trace(merged) == []
    assert merged["metadata"]["members"][0]["offset_ns"] == 0


def test_cli_trace_summary_by_process(tmp_path, capsys):
    """Per-member attribution from the merged file alone — no live
    pod needed."""
    from jepsen_tpu.cli import EXIT_VALID, main
    from jepsen_tpu.obs import podtrace

    for pidx in (0, 1):
        podtrace.persist_member_trace(
            str(tmp_path), process_index=pidx, n_hosts=2,
            events=_member_events(1_000_000 * (pidx + 1)),
            clock={"offset_ns": 1_000_000 * pidx,
                   "skew_bound_ns": 30_000},
        )
    out = tmp_path / "pod_trace.json"
    podtrace.merge_pod_trace(str(tmp_path), str(out),
                             expect_members=2)
    assert main(["trace-summary", str(out), "--by-process"]) \
        == EXIT_VALID
    txt = capsys.readouterr().out
    assert "pod-member-0" in txt and "pod-member-1" in txt
    assert "clock_skew_bound" in txt and "2 members" in txt
    assert "2 process(es)" in txt


# -- xla trace unification (obs/xla absorbed utils/profiling) ----------


def test_xla_trace_raises_when_a_capture_is_running(tmp_path):
    """A profiler that cannot start fails loudly: an operator who asked
    for a trace never silently gets none. The capture turns the span
    recorder on for its duration, and back off after."""
    from jepsen_tpu.obs.xla import xla_trace

    with xla_trace(str(tmp_path / "outer")):
        assert obs_trace.TRACER.enabled
        with pytest.raises(RuntimeError):
            with xla_trace(str(tmp_path / "inner")):
                pass
        assert obs_trace.TRACER.enabled
    assert not obs_trace.TRACER.enabled
    # the outer capture stopped cleanly: a new one starts
    with xla_trace(str(tmp_path / "again")):
        pass


def test_utils_profiling_is_gone():
    # one tracing stack, not two: the old duplicate module must not
    # quietly come back
    import importlib

    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("jepsen_tpu.utils.profiling")


# -- bench trend ledger / cli perf-trend -------------------------------


def test_cli_perf_trend_exit_code_contract(tmp_path, capsys):
    from jepsen_tpu.cli import (
        EXIT_INVALID,
        EXIT_UNKNOWN,
        EXIT_VALID,
        main,
    )

    ledger = tmp_path / "trend.jsonl"
    # no ledger -> unknown (exit 2)
    assert main(["perf-trend", "--ledger", str(ledger)]) \
        == EXIT_UNKNOWN
    capsys.readouterr()

    row = {"ts": "2026-08-06T00:00:00+00:00", "ops_per_sec": 1000.0,
           "vs_baseline": 2.0, "vs_python_oracle": 30.0,
           "syncs_per_check": 1.0, "sync_floor_ms": 94.0,
           "double_buffer_occupancy": 2.0, "trace_overhead_pct": 0.4,
           "smoke": False}
    ledger.write_text(json.dumps(row) + "\n")
    assert main(["perf-trend", "--ledger", str(ledger)]) == EXIT_VALID
    assert "nothing to compare" in capsys.readouterr().out

    # two consecutive runs render both rows and pass the gate
    row2 = dict(row, ts="2026-08-07T00:00:00+00:00", vs_baseline=2.1)
    ledger.write_text(
        json.dumps(row) + "\n" + json.dumps(row2) + "\n"
    )
    assert main(["perf-trend", "--ledger", str(ledger)]) == EXIT_VALID
    out = capsys.readouterr().out
    assert "2026-08-06" in out and "2026-08-07" in out
    assert "ok" in out

    # synthetic regressed run: > 10% vs_baseline drop trips exit 1
    row3 = dict(row, ts="2026-08-08T00:00:00+00:00", vs_baseline=1.0)
    ledger.write_text(
        "".join(json.dumps(r) + "\n" for r in (row, row2, row3))
    )
    assert main(["perf-trend", "--ledger", str(ledger)]) \
        == EXIT_INVALID
    assert "REGRESSION" in capsys.readouterr().out

    # a tightened budget flags what the default forgives
    ledger.write_text(
        json.dumps(row2) + "\n" + json.dumps(row) + "\n"
    )  # 2.1 -> 2.0 is a ~4.8% drop
    assert main(["perf-trend", "--ledger", str(ledger)]) == EXIT_VALID
    capsys.readouterr()
    assert main([
        "perf-trend", "--ledger", str(ledger),
        "--max-regression", "0.01",
    ]) == EXIT_INVALID
    capsys.readouterr()


def test_perf_trend_gates_each_mode_against_its_own_history(
    tmp_path, capsys
):
    """The round-11 gate fix: smoke rows (CPU flow validations) and
    hardware rows (real measurements) are separate trajectories — a
    low smoke geomean after a high hardware one is a category error,
    not a regression, and a real smoke regression must trip the gate
    even when the hardware trajectory is healthy."""
    from jepsen_tpu.cli import EXIT_INVALID, EXIT_VALID, main
    from jepsen_tpu.obs.trend import gate_trend, trend_mode

    base = {"ops_per_sec": 1000.0, "vs_python_oracle": 30.0,
            "syncs_per_check": 1.0}
    hw = [dict(base, ts=f"2026-08-0{d}T00:00:00+00:00",
               vs_baseline=v, mode="hardware", smoke=False)
          for d, v in ((1, 11.0), (2, 11.2))]
    # a smoke run landing AFTER the hardware rows: 11.2 -> 2.5 across
    # modes must NOT read as a drop
    smoke = [dict(base, ts=f"2026-08-0{d}T01:00:00+00:00",
                  vs_baseline=v, mode="smoke", smoke=True)
             for d, v in ((3, 2.5), (4, 2.6))]
    ledger = tmp_path / "trend.jsonl"
    ledger.write_text(
        "".join(json.dumps(r) + "\n" for r in hw + smoke[:1])
    )
    assert main(["perf-trend", "--ledger", str(ledger)]) == EXIT_VALID
    capsys.readouterr()

    # both trajectories healthy -> valid
    ledger.write_text(
        "".join(json.dumps(r) + "\n" for r in hw + smoke)
    )
    assert main(["perf-trend", "--ledger", str(ledger)]) == EXIT_VALID
    capsys.readouterr()

    # a regressed SMOKE run trips the gate even though the hardware
    # trajectory is fine (and vice versa stays caught)
    bad_smoke = dict(smoke[-1], ts="2026-08-05T01:00:00+00:00",
                     vs_baseline=1.0)
    ledger.write_text(
        "".join(json.dumps(r) + "\n" for r in hw + smoke + [bad_smoke])
    )
    assert main(["perf-trend", "--ledger", str(ledger)]) \
        == EXIT_INVALID
    out = capsys.readouterr().out
    assert "smoke: REGRESSION" in out
    assert "hardware: ok" in out

    # pre-mode legacy rows infer their trajectory from the smoke bool
    legacy = dict(base, vs_baseline=2.4, smoke=True)
    legacy.pop("mode", None)
    assert trend_mode(legacy) == "smoke"
    assert trend_mode(dict(base, vs_baseline=11.0)) == "hardware"
    # legacy row joins the smoke trajectory: 2.6 -> 2.4 is a ~7.7%
    # drop — inside the default 10% budget, outside a tightened 5%
    ok, _ = gate_trend(hw + smoke + [legacy], 0.1)
    assert ok
    ok, msgs = gate_trend(hw + smoke + [legacy], 0.05)
    assert not ok
    assert any("smoke: REGRESSION" in m for m in msgs)


def test_bench_trend_row_shape_and_append(tmp_path):
    """bench.trend_row_from_record pulls exactly the columns
    perf-trend renders; append_trend_row survives repeated appends
    and a pre-existing unterminated file."""
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    import bench

    record = {
        "value": 1234.5, "vs_baseline": 2.5, "vs_python_oracle": 31.0,
        "sync_floor_ms": 94.2, "trace_overhead_pct": 0.7,
        "residency": {"syncs_per_check": 1.0,
                      "double_buffer_occupancy": 2.0},
    }
    row = bench.trend_row_from_record(
        record, ts="2026-08-06T01:02:03+00:00", smoke=True
    )
    assert row["ops_per_sec"] == 1234.5
    assert row["vs_baseline"] == 2.5
    assert row["syncs_per_check"] == 1.0
    assert row["double_buffer_occupancy"] == 2.0
    assert row["trace_overhead_pct"] == 0.7
    assert row["smoke"] is True

    ledger = str(tmp_path / "trend.jsonl")
    bench.append_trend_row(row, ledger)
    bench.append_trend_row(dict(row, vs_baseline=2.6), ledger)
    rows = [json.loads(ln) for ln in open(ledger) if ln.strip()]
    assert len(rows) == 2
    assert rows[0]["vs_baseline"] == 2.5
    assert rows[1]["vs_baseline"] == 2.6
    # a torn last line (no newline) is repaired, not corrupted
    with open(ledger, "a") as f:
        f.write(json.dumps(row))
    bench.append_trend_row(dict(row, vs_baseline=2.7), ledger)
    rows = [json.loads(ln) for ln in open(ledger) if ln.strip()]
    assert rows[-1]["vs_baseline"] == 2.7 and len(rows) == 4

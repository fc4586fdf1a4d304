"""Command-line interface: test / analyze / serve.

Reference: jepsen/src/jepsen/cli.clj — shared option spec (:54-92),
"3n" concurrency parsing (:130-145), subcommand dispatch with exit
codes (:229-304: 0 valid, 1 invalid, 2 unknown, 254 crash, 255 usage),
single-test-cmd's paired `test` + `analyze` commands (:323-397 — the
decoupled analyze seam is exactly where the TPU checker plugs in), and
serve-cmd (:306-321).

    python -m jepsen_tpu.cli test --workload bank --time-limit 10
    python -m jepsen_tpu.cli analyze store/bank/latest --workload bank
    python -m jepsen_tpu.cli serve --port 8080
"""

from __future__ import annotations

import argparse
import random
import sys
import traceback
from typing import Any, Dict, List, Optional

EXIT_VALID = 0
EXIT_INVALID = 1
EXIT_UNKNOWN = 2
#: the stored history itself failed strict sentry validation — a
#: distinct failure from an invalid VERDICT (the history was readable
#: and the checker found a consistency violation) and from unknown
#: (the checker could not decide). See history/sentry.py.
EXIT_HOSTILE_HISTORY = 3
#: `lint` found non-baselined planelint findings (distinct from every
#: verdict code so CI can tell "dirty tree" from "invalid history")
EXIT_LINT_DIRTY = 5
#: `fleet-drill` / `bench --fleet-chaos` invariant gate failed: the
#: chaos gauntlet ran, but the invariant monitor found a violation
#: (lost accepted check, divergent verdicts, gray member never
#: evicted, fleet not restored within budget)
EXIT_DRILL = 8
EXIT_CRASH = 254
EXIT_USAGE = 255

WORKLOADS = (
    "register", "register-keyed", "bank", "long-fork", "g2",
    "txn-graph", "set", "counter", "monotonic", "dirty-reads",
)


def parse_concurrency(spec: str, n_nodes: int) -> int:
    """Parse "5" or "3n" (n = node count) — cli.clj:130-145."""
    spec = str(spec).strip()
    if spec.endswith("n"):
        return int(spec[:-1] or 1) * n_nodes
    return int(spec)


def parse_nodes(args) -> List[str]:
    if args.nodes_file:
        with open(args.nodes_file) as f:
            return [ln.strip() for ln in f if ln.strip()]
    return [n.strip() for n in args.nodes.split(",") if n.strip()]


def _workload_spec(args, rng: random.Random) -> Dict[str, Any]:
    from jepsen_tpu.workloads import adya, bank, long_fork, register

    name = args.workload
    if name == "register":
        return register.workload(n_ops=args.ops, rng=rng)
    if name == "register-keyed":
        return register.keyed_workload(
            keys=range(args.keys), per_key_ops=max(args.ops // args.keys, 1),
            rng=rng,
        )
    if name == "bank":
        return bank.workload(n_ops=args.ops, rng=rng)
    if name == "long-fork":
        return long_fork.workload(n_ops=args.ops, rng=rng)
    if name == "g2":
        return adya.workload(n_keys=max(args.ops // 2, 1))
    if name == "txn-graph":
        from jepsen_tpu.workloads import txn_graph as txn_graph_wl

        return txn_graph_wl.workload(n_ops=args.ops, rng=rng)
    if name == "set":
        from jepsen_tpu.workloads import set as set_wl

        return set_wl.workload(n_adds=args.ops, rng=rng)
    if name == "counter":
        from jepsen_tpu.workloads import counter

        return counter.workload(n_ops=args.ops, rng=rng)
    if name == "monotonic":
        from jepsen_tpu.workloads import monotonic

        return monotonic.workload(n_ops=args.ops, rng=rng)
    if name == "dirty-reads":
        from jepsen_tpu.workloads import dirty_reads

        return dirty_reads.workload(n_ops=args.ops, rng=rng)
    raise ValueError(f"unknown workload {name!r}")


def _checker_for(workload: str):
    import os

    from jepsen_tpu import independent
    from jepsen_tpu.checker.adya import G2Checker
    from jepsen_tpu.checker.bank import BankChecker
    from jepsen_tpu.checker.divergence import DirtyReadsChecker
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.checker.longfork import LongForkChecker
    from jepsen_tpu.checker.monotonic import MonotonicChecker
    from jepsen_tpu.checker.reductions import CounterChecker, SetFullChecker
    from jepsen_tpu.checker.txn_graph import TxnGraphChecker
    from jepsen_tpu.workloads.adya import _KVG2Checker

    # Pallas interpret mode for the linearizable tiers: the seam that
    # exercises the device branch (segmented scan, checkpoint/resume)
    # on a CPU-only host — the kill-restart nemesis test runs
    # `analyze --resume` subprocesses under this.
    interp = os.environ.get("JEPSEN_TPU_INTERPRET", "") not in ("", "0")
    return {
        "set": SetFullChecker(),
        "register": LinearizableChecker(interpret=interp),
        "register-keyed": independent.independent_checker(
            LinearizableChecker(interpret=interp)
        ),
        "bank": BankChecker(),
        "long-fork": LongForkChecker(2),
        "g2": _KVG2Checker(),
        "txn-graph": TxnGraphChecker(),
        "counter": CounterChecker(),
        "monotonic": MonotonicChecker(),
        "dirty-reads": DirtyReadsChecker(),
    }[workload]


def _exit_code(results: Optional[dict]) -> int:
    if results is None:
        return EXIT_UNKNOWN
    v = results.get("valid?")
    if v is True:
        return EXIT_VALID
    if v is False:
        return EXIT_INVALID
    return EXIT_UNKNOWN  # "unknown" verdicts (cli.clj:272-283)


def _reset_engine_state() -> None:
    """Clean resilience slate at command entry: a quarantine ledger or
    a sticky-shrunk default plane left by a prior in-process run (or
    an embedding test harness) must not shadow THIS run's mesh; stats
    reset so the engine_stats this command reports are its own."""
    from jepsen_tpu.checker import dispatch
    from jepsen_tpu.obs.snapshot import reset_engine_stats

    # one consolidated reset for every counter surface the snapshot
    # reads (chaos/launch/dispatch/mesh/checkpoint/streaming/txn-graph
    # plus the flight recorder's rings), then the plane itself
    reset_engine_stats()
    dispatch.reset_default_plane()


def _apply_mesh_args(args) -> None:
    """Thread the --devices/--backend/--pod-* seam into the engine:
    pod flags (or the JEPSEN_TPU_POD_* env they override) join the
    pod FIRST (jax.distributed must initialize before the first device
    query), then the mesh policy pins what sharded.resolve_mesh's
    ambient default_mesh may span. Then the stderr banner names the
    platform, device_kind and count the run is on."""
    from jepsen_tpu.checker import sharded
    from jepsen_tpu.pod import topology

    cfg = None
    coord = getattr(args, "pod_coordinator", None)
    if coord:
        cfg = topology.PodConfig(
            coordinator=coord,
            num_processes=int(getattr(args, "pod_processes") or 1),
            process_id=int(getattr(args, "pod_index") or 0),
        )
    topology.init_pod(cfg)
    sharded.set_mesh_policy(
        devices=getattr(args, "devices", None),
        backend=getattr(args, "backend", None),
    )
    from jepsen_tpu.obs.snapshot import device_info

    d = device_info()
    print(f"device: platform={d['platform']} kind={d['kind']!r} "
          f"count={d['count']} jax={d['jax']}", file=sys.stderr)


def cmd_test(args) -> int:
    from jepsen_tpu import store as storelib
    from jepsen_tpu.generator import pure as gen
    from jepsen_tpu.runtime import run

    _reset_engine_state()
    rng = random.Random(args.seed)
    nodes = parse_nodes(args)
    worst = EXIT_VALID
    for i in range(args.test_count):
        spec = _workload_spec(args, rng)
        if args.time_limit:
            g = spec["generator"]
            spec["generator"] = gen.time_limit(args.time_limit, g)
        concurrency = parse_concurrency(args.concurrency, len(nodes))
        if args.workload == "register-keyed":
            # concurrent_generator needs a thread-group multiple.
            concurrency += (-concurrency) % 2
        test = {
            **spec,
            "name": args.name or args.workload,
            "nodes": nodes,
            "store": args.store,
            "concurrency": concurrency,
        }
        test = run(test)
        d = test["run_dir"]
        results = test["results"]
        print(f"run {i + 1}/{args.test_count}: "
              f"valid?={results.get('valid?')}  ({d})")
        worst = max(worst, _exit_code(results))
        if worst != EXIT_VALID and args.until_failure:
            break
    print(_epitaph(worst))
    return worst


def _resolve_run_dir(path: str, store_root: str) -> str:
    import os

    if os.path.isdir(path) and os.path.exists(
        os.path.join(path, "history.jsonl")
    ):
        return path
    # maybe a test name: use its latest run
    from jepsen_tpu.store import Store

    latest = Store(store_root).latest(path if path else None)
    if latest is None:
        raise FileNotFoundError(f"no stored run at {path!r}")
    return latest


def _perf_setup(args) -> None:
    """Perf-plane session setup shared by the single-process entry
    points (analyze, daemon): turn on the persistent XLA compile cache
    (pod children already inherit it via launcher.pod_env) and honor an
    explicit ``--profile PATH``. The explicit path is strict-ish: a
    profile the user NAMED that fails to load gets a warning (silent
    fallback is only for the ambient auto-discovered store)."""
    from jepsen_tpu.perf import autotune

    autotune.enable_persistent_compile_cache()
    prof = getattr(args, "profile", None)
    if prof:
        import os

        os.environ[autotune.PROFILE_ENV] = prof
        if autotune.load_active_profile() is None:
            print(
                f"perf: profile {prof} is invalid, foreign, or stale; "
                "using defaults",
                file=sys.stderr,
            )


def cmd_analyze(args) -> int:
    """`analyze`, with the flight recorder wrapped around it when
    --trace PATH is given: the tracer enables before any launch,
    records every plane crossing the re-check makes, and exports a
    Perfetto-loadable Chrome-trace JSON to PATH on the way out
    (whatever the verdict — a crashed analysis still leaves its
    trace). Inside a pod every member persists its ring into the
    shared trace dir and process 0 merges ONE clock-aligned trace;
    single-process runs export directly. --xla-trace DIR additionally
    wraps the run in a jax.profiler capture with the recorder on, so
    the obs spans sit in the XLA profile's host plane, on the device
    timeline's clock; a profiler that cannot start fails the command.
    Feed the file to ui.perfetto.dev or `jepsen_tpu trace-summary`."""
    trace_path = getattr(args, "trace", None)
    xla_dir = getattr(args, "xla_trace", None)
    if not trace_path and not xla_dir:
        return _cmd_analyze(args)
    from contextlib import ExitStack

    from jepsen_tpu import obs

    with ExitStack() as stack:
        if xla_dir:
            from jepsen_tpu.obs.xla import xla_trace

            stack.enter_context(xla_trace(xla_dir))
            print(f"xla-trace: capturing to {xla_dir}")
        if trace_path:
            obs.enable()
        try:
            return _cmd_analyze(args)
        finally:
            if trace_path:
                try:
                    _export_trace(trace_path)
                finally:
                    obs.disable()


def _export_trace(trace_path: str) -> None:
    """Export the live ring to ``trace_path`` — pod-aware.

    Single process: the PR 12 path, one chrome trace straight from the
    ring. Inside an initialized pod: every member persists its raw
    ring (plus the init_pod clock record) into the shared trace dir
    (the JEPSEN_TPU_TRACE_DIR seam, defaulting to trace_path's
    directory, which all members must share), and process 0 waits for
    all member files and merges them into ONE clock-aligned Perfetto
    trace at trace_path."""
    import os

    from jepsen_tpu import obs
    from jepsen_tpu.obs import podtrace
    from jepsen_tpu.pod import topology

    if not topology.is_multiprocess():
        events = obs.spans()
        obs.write_chrome_trace(trace_path, events)
        print(f"trace: {len(events)} events -> {trace_path}")
        return
    import jax

    pidx = int(jax.process_index())
    n_procs = int(jax.process_count())
    trace_dir = (
        os.environ.get(podtrace.ENV_TRACE_DIR)
        or os.path.dirname(os.path.abspath(trace_path))
    )
    member_path = podtrace.persist_member_trace(trace_dir)
    if pidx != 0:
        print(f"trace: member {pidx} ring -> {member_path}")
        return
    merged = podtrace.merge_pod_trace(
        trace_dir, trace_path, expect_members=n_procs, timeout_s=30.0
    )
    print(
        f"trace: {len(merged['traceEvents'])} events from "
        f"{n_procs} members -> {trace_path}"
    )


def _cmd_analyze(args) -> int:
    """Re-check a stored history — the checkpoint/resume seam for the
    analysis phase (cli.clj:366-397).

    --strict-history: refuse (exit code 3, distinct message) instead
    of repairing when the stored history fails sentry validation.

    --resume: run the check durably — verified segment boundaries
    persist atomically into <run_dir>/checkpoint.json, and a re-run
    after a crash re-enters at the last durable frontier (stale or
    tampered checkpoints are rejected and the check runs cold).
    engine_stats in results.json carries the launch + checkpoint
    accounting so a resumed run's strictly-fewer launches are
    auditable.

    --follow: tail a GROWING history.jsonl with the streaming checker
    instead of loading it once — each poll appends the newly written
    ops and launches only that tail (checker/streaming.py). Combine
    with --resume to persist the stream frontier into
    <run_dir>/stream.json so a restarted --follow skips the already-
    checked prefix."""
    import os

    from jepsen_tpu.history.sentry import (
        HistorySentryError,
        validate_history,
    )
    from jepsen_tpu.store import Store

    _perf_setup(args)
    _reset_engine_state()
    _apply_mesh_args(args)
    run_dir = _resolve_run_dir(args.path, args.store)
    if args.follow:
        return _analyze_follow(args, run_dir)
    st = Store(args.store)
    history = st.load_history(run_dir)
    test = st.load_test(run_dir)
    # Resolve BEFORE checking: test.json may carry a stale absolute
    # run_dir (runs relocated via zip export), and artifact-writing
    # checkers (linear.svg, timeline) target test["run_dir"].
    test["run_dir"] = run_dir
    # Sentry gate ahead of EVERY checker (linearizable runs its own
    # pass too, but bank/set/etc. get validated history only here).
    try:
        history, hreport = validate_history(
            history, strict=args.strict_history
        )
    except HistorySentryError as e:
        print(f"analyzed {run_dir}: hostile history — {e}")
        print(_epitaph(EXIT_HOSTILE_HISTORY))
        return EXIT_HOSTILE_HISTORY
    checker = _checker_for(args.workload)
    checkpoint = None
    if args.resume:
        from jepsen_tpu.checker.checkpoint import CheckpointSink

        seg_env = os.environ.get("JEPSEN_TPU_SEG_MIN_LEN")
        checkpoint = CheckpointSink(
            run_dir,
            seg_min_len=int(seg_env) if seg_env else None,
        )
    import inspect

    kw = {}
    if (
        checkpoint is not None
        and "checkpoint" in inspect.signature(checker.check).parameters
    ):
        kw["checkpoint"] = checkpoint
    results = checker.check(test, history, {}, **kw)
    if hreport is not None and not hreport.get("clean"):
        results.setdefault("history_report", hreport)
    results["engine_stats"] = _engine_stats()
    test["results"] = results
    st.save_2(test)
    if args.stats_json:
        _dump_stats_json(args.stats_json)
    print(f"analyzed {run_dir}: valid?={results.get('valid?')}")
    print(_epitaph(_exit_code(results)))
    return _exit_code(results)


def _analyze_follow(args, run_dir: str) -> int:
    """`analyze --follow`: tail <run_dir>/history.jsonl with a
    StreamingCheck. Each poll reads the complete lines written since
    the last one, appends them, and checks only that tail; the follow
    ends after --follow-idle seconds without growth, or immediately at
    an invalid verdict (terminal — linearizability is prefix-closed).
    The sentry gate is skipped while following (a live history always
    has unpaired tails); run a plain `analyze` afterwards for the
    sentry report. Register (linearizable) workloads only."""
    import json as _json
    import os
    import time as _time

    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.store import op_from_json

    if args.workload not in (None, "register"):
        print(f"--follow supports only the register (linearizable) "
              f"workload, not {args.workload!r}")
        return EXIT_USAGE
    interp = os.environ.get("JEPSEN_TPU_INTERPRET", "") not in ("", "0")
    checker = LinearizableChecker(interpret=interp)
    sc = checker.check_streaming(
        path=os.path.join(run_dir, "stream.json") if args.resume else None
    )
    hist = os.path.join(run_dir, "history.jsonl")
    pos = 0
    idle_s = max(float(args.follow_idle), 0.0)
    last_growth = _time.monotonic()
    while True:
        batch = []
        try:
            with open(hist, "rb") as f:
                f.seek(pos)
                for raw in f:
                    if not raw.endswith(b"\n"):
                        break  # torn tail write: retry next poll
                    pos += len(raw)
                    line = raw.decode().strip()
                    if line:
                        batch.append(op_from_json(_json.loads(line)))
        except FileNotFoundError:
            pass  # appears on the writer's first atomic rename
        if batch:
            status = sc.append(batch)
            last_growth = _time.monotonic()
            print(f"followed +{len(batch)} ops "
                  f"(checked_steps={status.get('checked_steps')}, "
                  f"valid?={status.get('valid?')})")
            if status.get("valid?") is False:
                break
        elif _time.monotonic() - last_growth >= idle_s:
            break
        else:
            _time.sleep(min(0.2, idle_s) if idle_s else 0.2)
    results = sc.result()
    results["engine_stats"] = _engine_stats()
    if args.stats_json:
        _dump_stats_json(args.stats_json)
    print(f"analyzed {run_dir} (followed): "
          f"valid?={results.get('valid?')}")
    print(_epitaph(_exit_code(results)))
    return _exit_code(results)


def _dump_stats_json(path: str) -> None:
    """Write the full engine-stats bundle — the same shape the daemon's
    /stats endpoint serves — to `path` ("-" = stdout). Scripts that
    scrape launches/resumes get one machine-readable artifact instead
    of parsing results.json out of the run dir."""
    import json

    bundle = _engine_stats()
    if path == "-":
        print(json.dumps(bundle, indent=2, default=str))
    else:
        from jepsen_tpu.store import atomic_write_text

        atomic_write_text(
            path, json.dumps(bundle, indent=2, default=str)
        )


def _engine_stats() -> dict:
    """The consolidated engine snapshot for results.json — the cross-
    process audit trail the kill-restart differential reads (a
    resumed run shows strictly fewer launches than the cold one).
    Same shape the daemon's /stats serves and the dryrun metric line
    summarizes: obs.snapshot.engine_snapshot() is the one reader.
    Drains the default plane first: a native-racer win can leave the
    launch train uncollected (its host sync unpaid and uncounted), and
    this snapshot is the run's final ledger."""
    from jepsen_tpu.checker.dispatch import drain_default_plane
    from jepsen_tpu.obs.snapshot import engine_snapshot

    drain_default_plane()
    return engine_snapshot()


def cmd_trace_summary(args) -> int:
    """Attribution table from a Chrome-trace file (`analyze --trace`
    output): where the wall went, by span kind and name — launch vs.
    host-sync floor vs. coalesce holds — plus the two derived ratios
    the dispatch plane reports (floor amortization from dispatch_batch/
    dispatch_solo instants, double-buffer occupancy from train_register
    instants), recomputed purely from the trace."""
    import json

    from jepsen_tpu.obs.export import validate_chrome_trace

    with open(args.path) as f:
        obj = json.load(f)
    errors = validate_chrome_trace(obj)
    if errors:
        for e in errors[:10]:
            print(f"trace-summary: schema: {e}")
        return EXIT_UNKNOWN
    evs = [e for e in obj["traceEvents"] if e["ph"] in ("X", "i")]
    wall_ms = 0.0
    if evs:
        wall_ms = (max(e["ts"] + e.get("dur", 0) for e in evs)
                   - min(e["ts"] for e in evs)) / 1e3
    if getattr(args, "by_process", False):
        return _trace_summary_by_process(obj, evs, wall_ms)
    rows = {}
    for e in evs:
        key = (e.get("cat", "?"), e["name"])
        cnt, tot = rows.get(key, (0, 0.0))
        rows[key] = (cnt + 1, tot + e.get("dur", 0) / 1e3)
    print(f"{'kind':<12} {'name':<24} {'count':>8} {'total_ms':>10} "
          f"{'mean_ms':>9} {'%wall':>6}")
    for (kind, name), (cnt, tot) in sorted(
            rows.items(), key=lambda kv: -kv[1][1]):
        pct = 100.0 * tot / wall_ms if wall_ms else 0.0
        print(f"{kind:<12} {name:<24} {cnt:>8} {tot:>10.3f} "
              f"{tot / cnt:>9.3f} {pct:>6.1f}")
    batches = sum(1 for e in evs if e["name"] == "dispatch_batch")
    solos = sum(1 for e in evs if e["name"] == "dispatch_solo")
    riders = sum(e["args"].get("riders", 0) for e in evs
                 if e["name"] == "dispatch_batch")
    regs = [e["args"].get("inflight", 0) for e in evs
            if e["name"] == "train_register"]
    launches = batches + solos
    if launches:
        print(f"floor_amortization    "
              f"{(riders + solos) / launches:.3f}  "
              f"({riders + solos} requests / {launches} launches)")
    if regs:
        print(f"double_buffer_occupancy {sum(regs) / len(regs):.3f}  "
              f"(over {len(regs)} trains)")
    print(f"wall {wall_ms:.3f} ms, {len(evs)} events")
    return EXIT_VALID


def _trace_summary_by_process(obj, evs, wall_ms: float) -> int:
    """Per-member attribution from a merged pod trace: wall and span
    totals by Perfetto pid, named from the trace's own process_name
    metadata rows — everything comes from the file, no live pod
    needed. Also discloses the recorded clock skew bound so readers
    know the alignment error bar on cross-member comparisons."""
    names = {}
    for e in obj["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e.get("pid", 1)] = str(
                (e.get("args") or {}).get("name", "?")
            )
    rows = {}
    for e in evs:
        pid = e.get("pid", 1)
        cnt, tot = rows.get(pid, (0, 0.0))
        rows[pid] = (cnt + 1, tot + e.get("dur", 0) / 1e3)
    print(f"{'process':<20} {'pid':>4} {'events':>8} {'total_ms':>10} "
          f"{'%wall':>6}")
    for pid in sorted(rows):
        cnt, tot = rows[pid]
        pct = 100.0 * tot / wall_ms if wall_ms else 0.0
        print(f"{names.get(pid, '?'):<20} {pid:>4} {cnt:>8} "
              f"{tot:>10.3f} {pct:>6.1f}")
    meta = obj.get("metadata") or {}
    skew = meta.get("clock_skew_bound_ns")
    if skew is not None:
        print(f"clock_skew_bound {int(skew) / 1e3:.1f} us "
              f"({len(meta.get('members', []))} members)")
    print(f"wall {wall_ms:.3f} ms, {len(evs)} events, "
          f"{len(rows)} process(es)")
    return EXIT_VALID


def cmd_perf_trend(args) -> int:
    """Render the bench trend ledger (bench_runs/trend.jsonl — one
    compact row per bench run) and gate on regressions PER MODE: smoke
    rows (CPU flow validations) and hardware rows (real measurements)
    form separate trajectories, and each mode's latest row is gated
    against ITS OWN predecessor — a CPU smoke geomean is never
    compared against a TPU hardware one. Fleet rows (`--fleet N`
    bench runs, fleet_size stamped) segregate the same way: each
    "mode/fleetN" trajectory gates against its own history, never
    against solo rows. Exit 1 when any trajectory's
    vs_baseline geomean dropped more than --max-regression
    (fractional) below its previous row's, exit 2 when there is no
    ledger to judge. The perf story stays observable ACROSS runs, not
    just within one."""
    import os

    from jepsen_tpu.obs.trend import (
        gate_trend,
        load_trend_rows,
        trend_fleet,
        trend_mode,
    )

    path = args.ledger
    if not os.path.exists(path):
        print(f"perf-trend: no trend ledger at {path}")
        return EXIT_UNKNOWN
    rows = load_trend_rows(path)
    if not rows:
        print(f"perf-trend: empty trend ledger at {path}")
        return EXIT_UNKNOWN

    def _num(row, key):
        v = row.get(key)
        return f"{v:.3f}" if isinstance(v, (int, float)) else "-"

    def _cfg(row):
        """Short knob-config identity: rows before the schema gained
        config_hash render '-'; a '*' marks a persisted tuned profile
        (vs. registry defaults)."""
        h = row.get("config_hash")
        if not isinstance(h, str) or not h:
            return "-"
        return h[:8] + ("*" if row.get("tuned") else "")

    print(f"{'ts':<20} {'mode':<8} {'fleet':>5} {'cfg':<9} "
          f"{'vs_base':>8} "
          f"{'vs_py':>10} {'syncs':>6} {'floor_ms':>9} {'occup':>6} "
          f"{'trace_ov%':>9} {'ops/s':>10}")
    for r in rows:
        ts = str(r.get("ts", "?"))[:19]
        print(f"{ts:<20} {trend_mode(r):<8} "
              f"{trend_fleet(r):>5} "
              f"{_cfg(r):<9} "
              f"{_num(r, 'vs_baseline'):>8} "
              f"{_num(r, 'vs_python_oracle'):>10} "
              f"{_num(r, 'syncs_per_check'):>6} "
              f"{_num(r, 'sync_floor_ms'):>9} "
              f"{_num(r, 'double_buffer_occupancy'):>6} "
              f"{_num(r, 'trace_overhead_pct'):>9} "
              f"{_num(r, 'ops_per_sec'):>10}")
    ok, msgs = gate_trend(rows, args.max_regression)
    for m in msgs:
        print(f"perf-trend: {m}")
    return EXIT_VALID if ok else EXIT_INVALID


def cmd_tune(args) -> int:
    """`tune`: sweep the perf-knob registry on THIS backend and
    persist the winning overrides as a per-(backend, device-count,
    jax-version) profile beside the compile cache. Every candidate
    rung must reproduce the baseline probe verdict (verdict parity) or
    it is rejected regardless of speed; sweep evidence lands in a
    sibling .evidence.json. Exit 0 when a profile was written (or
    --dry-run completed), 1 when nothing persistable came out of the
    budget, 255 on an unknown --knobs name."""
    from jepsen_tpu.perf import autotune

    autotune.enable_persistent_compile_cache()
    only = None
    if args.knobs:
        only = [k.strip() for k in args.knobs.split(",") if k.strip()]
    try:
        return autotune.run_tune(
            budget_s=args.budget_s, only=only, dry_run=args.dry_run
        )
    except ValueError as e:
        print(f"tune: {e}", file=sys.stderr)
        return EXIT_USAGE


def cmd_lint(args) -> int:
    """Run planelint (jepsen_tpu/analysis) over the package tree.

    Exit 0 when every finding is inline-suppressed or baselined, 5
    when non-baselined findings remain. --update-baseline rewrites
    planelint_baseline.json with the current findings (grandfathering
    them, and pruning entries whose file::symbol no longer exists);
    --changed-only scopes findings to the files git considers changed
    (the call graph still spans the whole package); --sarif writes
    the new findings as SARIF 2.1.0 for CI annotation; --json emits
    the machine-readable report (findings, per-rule descriptions,
    suppression census) the CI preflight parses. Stdlib-ast only: no
    jax import, so it runs anywhere."""
    import json

    from jepsen_tpu import analysis

    root = args.root or analysis.package_root()
    baseline_path = args.baseline or analysis.default_baseline_path()
    only = None
    if args.changed_only:
        only = analysis.changed_files(root)
        if not args.json:
            print(
                f"planelint: --changed-only scope: "
                f"{len(only)} file(s)"
            )
    findings = analysis.run_lint(root, only=only)
    baseline = analysis.load_baseline(baseline_path)
    stale = analysis.stale_baseline_entries(baseline, root)
    for key in stale:
        print(
            f"planelint: warning: stale baseline entry {key} "
            "(file or symbol no longer exists)",
            file=sys.stderr,
        )
    if args.update_baseline:
        analysis.save_baseline(baseline_path, findings)
        print(
            f"planelint: baselined {len(findings)} finding(s) into "
            f"{baseline_path}"
            + (f" (pruned {len(stale)} stale entries)" if stale else "")
        )
        return EXIT_VALID
    new, matched = analysis.apply_baseline(findings, baseline)
    if args.sarif:
        doc = analysis.to_sarif(new, analysis.RULES)
        errors = analysis.validate_sarif(doc)
        if errors:  # never ship a SARIF a CI ingester would drop
            for e in errors:
                print(f"planelint: sarif: {e}", file=sys.stderr)
            return EXIT_CRASH
        with open(args.sarif, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        if not args.json:
            print(
                f"planelint: wrote {len(new)} finding(s) to "
                f"{args.sarif}"
            )
    if args.json:
        print(json.dumps({
            "findings": [f.to_dict() for f in new],
            "baselined": sum(matched.values()),
            "total": len(findings),
            "clean": not new,
            "rules_total": analysis.rules_total(),
            "rules": {
                rid: {"title": title, "invariant": invariant}
                for rid, (title, invariant) in sorted(
                    analysis.RULES.items()
                )
            },
            "suppressions": analysis.suppression_census(
                root, only=only
            ),
            "stale_baseline": stale,
        }, indent=2))
    else:
        for f in new:
            print(f.render())
        print(
            f"planelint: {len(new)} finding(s) "
            f"({sum(matched.values())} baselined, "
            f"{len(findings)} total, "
            f"{analysis.rules_total()} rules)"
        )
    return EXIT_LINT_DIRTY if new else EXIT_VALID


def cmd_serve(args) -> int:
    from jepsen_tpu.web import serve

    serve(root=args.store, port=args.port)
    return EXIT_VALID


def cmd_daemon(args) -> int:
    """Run the checker-as-a-service daemon (service/server.py): one
    warm plane serving history checks for many tenants, with admission
    control at the door and a SIGTERM-triggered graceful drain.
    In-flight durable checks that outlive --drain-seconds are safe:
    their verified frontier is already checkpointed, and a restarted
    daemon resumes them on resubmission."""
    from jepsen_tpu.service.drain import install_signal_drain
    from jepsen_tpu.service.server import CheckerDaemon

    _perf_setup(args)
    _reset_engine_state()
    _apply_mesh_args(args)
    if args.trace:
        from jepsen_tpu import obs

        obs.enable()
    daemon = CheckerDaemon(
        root=args.store,
        host=args.host,
        port=args.port,
        interpret=None,  # honor JEPSEN_TPU_INTERPRET like analyze
        max_inflight=args.max_inflight,
        per_tenant_inflight=args.tenant_inflight,
        max_payload_bytes=args.max_payload_mb << 20,
        strict_default=args.strict_history,
        coalesce_hold_s=args.coalesce_hold,
        launch_deadline_s=args.launch_deadline,
        drain_s=args.drain_seconds,
        audit_path=args.audit_path,
        audit_max_bytes=args.audit_max_mb << 20,
        fleet_dir=args.fleet_dir,
        member_id=args.member_id,
        member_epoch=args.member_epoch,
    )
    handle = install_signal_drain(daemon.drain)
    member = (
        f" member={daemon.member_id}" if args.fleet_dir else ""
    )
    print(f"checker daemon serving on {daemon.url} "
          f"(store={args.store}){member}")
    try:
        daemon.serve_forever()
    except KeyboardInterrupt:
        daemon.drain()
    finally:
        handle.restore()
        daemon.close()
    print("checker daemon drained. (code 0)")
    return EXIT_VALID


def cmd_fleet(args) -> int:
    """Run an N-member checker fleet behind one front door.

    Spawns N `daemon` subprocesses on ephemeral ports (each announces
    its bound URL into the shared fleet dir and heartbeats), waits for
    the full fleet to come alive, then serves the front door
    (service/frontdoor.py) in the foreground: consistent-hash tenant
    routing, admission-shed stealing, and durable hand-off of a dead
    member's in-flight checks to survivors. SIGTERM drains the fleet:
    members get SIGTERM first (each drains its own in-flight checks
    and retires its membership), then the door stops."""
    import os
    import time

    from jepsen_tpu.pod import launcher
    from jepsen_tpu.service.drain import install_signal_drain
    from jepsen_tpu.service.frontdoor import FleetFrontDoor

    fleet_dir = args.fleet_dir or os.path.join(
        args.store, ".fleet"
    )
    os.makedirs(fleet_dir, exist_ok=True)
    extra = [
        "--max-inflight", str(args.max_inflight),
        "--tenant-inflight", str(args.tenant_inflight),
        "--coalesce-hold", str(args.coalesce_hold),
        "--drain-seconds", str(args.drain_seconds),
    ]
    procs = [
        launcher.spawn_fleet_member(
            i, fleet_dir, args.store,
            n_local_devices=args.member_devices,
            extra_args=extra,
            log_path=os.path.join(fleet_dir, f"member-{i:03d}.log"),
        )
        for i in range(args.members)
    ]
    try:
        launcher.wait_fleet(
            fleet_dir, args.members, timeout_s=args.spawn_timeout
        )
    except TimeoutError as e:
        print(f"fleet: {e}", file=sys.stderr)
        for p in procs:
            p.kill()
        return EXIT_CRASH
    door = FleetFrontDoor(
        fleet_dir, host=args.host, port=args.port, mode=args.mode
    )
    recovered = door.recover_intents()
    if recovered:
        print(f"fleet: recovered {len(recovered)} orphaned "
              f"intent(s) from a previous door")

    def _drain(signum=None):
        for p in procs:
            if p.poll() is None:
                p.terminate()  # member drains + retires itself
        deadline = time.time() + args.drain_seconds + 5.0
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.time(), 0.1))
            except Exception:  # noqa: BLE001 - escalate past drain
                p.kill()
        door.shutdown()

    handle = install_signal_drain(_drain)
    print(f"fleet front door ({args.mode}) on {door.url} — "
          f"{args.members} members over {fleet_dir}")
    try:
        door.serve_forever()
    except KeyboardInterrupt:
        _drain()
    finally:
        handle.restore()
        door.close()
    print("fleet drained. (code 0)")
    return EXIT_VALID


def cmd_fleet_drill(args) -> int:
    """Run the fleet chaos gauntlet (service/nemesis.run_fleet_drill):
    spawn a real subprocess fleet, inject the seeded fault schedule
    (SIGKILL, SIGSTOP gray periods, torn registry writes, clock skew,
    checkpoint corruption) while live multi-tenant traffic flows, and
    gate on the invariant monitor: zero accepted-check loss,
    at-most-once verdicts per check_id, verdict parity against a solo
    oracle, gray-member eviction within budget, and supervised fleet
    restoration. Exit 8 on any violation."""
    import json
    import os

    from jepsen_tpu.service.nemesis import run_fleet_drill

    fleet_dir = args.fleet_dir or os.path.join(
        args.store, ".fleet-drill"
    )
    classes = (
        [c.strip() for c in args.classes.split(",") if c.strip()]
        if args.classes else None
    )
    report = run_fleet_drill(
        args.store, fleet_dir,
        members=args.members,
        duration_s=args.duration,
        seed=args.seed,
        gray_s=args.gray_seconds,
        restart_budget=args.restart_budget,
        member_devices=args.member_devices,
        spawn_timeout_s=args.spawn_timeout,
        classes=classes,
        log_dir=fleet_dir,
        parity=not args.no_parity,
    )
    out = json.dumps(report, indent=2, sort_keys=True, default=str)
    if args.report:
        with open(args.report, "w") as f:
            f.write(out + "\n")
    print(out)
    if report.get("clean"):
        print(f"fleet drill clean: {report['checks']['unique']} "
              f"unique checks under fire, 0 lost. (code 0)")
        return EXIT_VALID
    kinds = sorted({v["invariant"] for v in report["violations"]})
    print(f"fleet drill FAILED: {len(report['violations'])} "
          f"violation(s) ({', '.join(kinds)}). (code {EXIT_DRILL})",
          file=sys.stderr)
    return EXIT_DRILL


def _epitaph(code: int) -> str:
    """Results one-liner (core.clj:453-465's celebratory/despair)."""
    if code == EXIT_VALID:
        return "Everything looks good! (code 0)"
    if code == EXIT_INVALID:
        return "Analysis invalid! (code 1)"
    if code == EXIT_HOSTILE_HISTORY:
        return (
            "Stored history failed validation; no verdict issued. "
            "(code 3)"
        )
    return "Errors occurred during analysis; verdict unknown. (code 2)"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="jepsen_tpu",
        description="TPU-native distributed-systems correctness testing",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    def shared(sp):
        sp.add_argument("--nodes", default="n1,n2,n3,n4,n5",
                        help="comma-separated node names")
        sp.add_argument("--nodes-file", default=None)
        sp.add_argument("--store", default="store",
                        help="store root directory")
        sp.add_argument("--workload", choices=WORKLOADS,
                        default="register")

    def mesh_args(sp):
        """The explicit mesh/pod seam (analyze, daemon; bench.py adds
        the same flags): mesh shape by flag, not only the conftest
        JEPSEN_TPU_HOST_DEVICES env seam."""
        sp.add_argument("--devices", type=int, default=None,
                        help="cap the ambient mesh at N devices "
                             "(1 forces the single-device path)")
        sp.add_argument("--backend", default=None,
                        help="jax platform the mesh spans "
                             "(cpu/gpu/tpu; default: ambient)")
        sp.add_argument("--pod-coordinator", default=None,
                        metavar="HOST:PORT",
                        help="join a multi-process pod via this "
                             "coordinator (jax.distributed; overrides "
                             "JEPSEN_TPU_POD_COORDINATOR)")
        sp.add_argument("--pod-processes", type=int, default=None,
                        help="total pod process count")
        sp.add_argument("--pod-index", type=int, default=None,
                        help="this process's pod index (0-based)")

    t = sub.add_parser("test", help="run a test and analyze it")
    shared(t)
    t.add_argument("--name", default=None)
    t.add_argument("--concurrency", default="1n",
                   help="worker count; '3n' = 3 per node")
    t.add_argument("--time-limit", type=float, default=None,
                   help="seconds of op generation")
    t.add_argument("--ops", type=int, default=500,
                   help="op budget for the workload generator")
    t.add_argument("--keys", type=int, default=8)
    t.add_argument("--test-count", type=int, default=1)
    t.add_argument("--until-failure", action="store_true")
    t.add_argument("--seed", type=int, default=None)
    t.set_defaults(fn=cmd_test)

    a = sub.add_parser(
        "analyze", help="re-check a stored history (no cluster needed)"
    )
    shared(a)
    mesh_args(a)
    a.add_argument("path", nargs="?", default="",
                   help="run directory or test name (default: latest)")
    a.add_argument("--resume", action="store_true",
                   help="durable check: persist segment checkpoints "
                        "into the run dir and resume a killed "
                        "analysis at its last verified frontier")
    a.add_argument("--follow", action="store_true",
                   help="tail a growing history.jsonl and check "
                        "incrementally (streaming checker; register "
                        "workload only — combine with --resume to "
                        "persist the stream frontier)")
    a.add_argument("--follow-idle", type=float, default=2.0,
                   metavar="SECONDS",
                   help="stop following after this long with no new "
                        "ops (default 2.0)")
    a.add_argument("--strict-history", action="store_true",
                   help="refuse (exit 3) instead of repairing when "
                        "the stored history fails sentry validation")
    a.add_argument("--stats-json", default=None, metavar="PATH",
                   help="also write the engine-stats bundle (launch/"
                        "resilience/checkpoint, the /stats shape) as "
                        "JSON to PATH ('-' = stdout)")
    a.add_argument("--trace", default=None, metavar="PATH",
                   help="record every plane crossing with the flight "
                        "recorder and export a Perfetto-loadable "
                        "Chrome-trace JSON to PATH (pod runs merge "
                        "all members into one aligned trace)")
    a.add_argument("--xla-trace", default=None, metavar="DIR",
                   help="also capture a jax.profiler XLA trace into "
                        "DIR, with the flight recorder's spans on its "
                        "host plane (fails if the profiler cannot "
                        "start)")
    a.add_argument("--profile", default=None, metavar="PATH",
                   help="load this tuned perf profile instead of the "
                        "auto-discovered per-backend one (invalid/"
                        "foreign/stale profiles warn and fall back to "
                        "registry defaults)")
    a.set_defaults(fn=cmd_analyze)

    ts = sub.add_parser(
        "trace-summary",
        help="attribution table (floor/occupancy, %%wall by span) "
             "from an `analyze --trace` Chrome-trace file",
    )
    ts.add_argument("path", help="Chrome-trace JSON file")
    ts.add_argument("--by-process", action="store_true",
                    help="attribute wall per pod member (merged pod "
                         "traces; reads process_name metadata rows "
                         "and the recorded clock skew bound)")
    ts.set_defaults(fn=cmd_trace_summary)

    pt = sub.add_parser(
        "perf-trend",
        help="render the bench trend ledger and gate on geomean "
             "regressions vs the previous run",
    )
    pt.add_argument("--ledger", default="bench_runs/trend.jsonl",
                    metavar="PATH",
                    help="trend ledger written by bench.py "
                         "(default: bench_runs/trend.jsonl)")
    pt.add_argument("--max-regression", type=float, default=0.10,
                    metavar="FRACTION",
                    help="fail (exit 1) when vs_baseline drops more "
                         "than this fraction below the previous row "
                         "(default 0.10)")
    pt.set_defaults(fn=cmd_perf_trend)

    tu = sub.add_parser(
        "tune",
        help="sweep the perf-knob registry on this backend and "
             "persist the verdict-parity-checked winners as a "
             "per-backend profile",
    )
    tu.add_argument("--budget-s", type=float, default=60.0,
                    metavar="SECONDS",
                    help="wall-clock sweep budget; rungs past it are "
                         "skipped and recorded as such (default 60)")
    tu.add_argument("--knobs", default=None, metavar="NAMES",
                    help="comma-separated knob subset to sweep "
                         "(default: every registered knob)")
    tu.add_argument("--dry-run", action="store_true",
                    help="sweep and report winners without writing "
                         "the profile")
    tu.set_defaults(fn=cmd_tune)

    ln = sub.add_parser(
        "lint",
        help="planelint: static hot-path/lock-discipline analysis "
             "over the package (exit 0 clean, 5 findings)",
    )
    ln.add_argument("--root", default=None,
                    help="package tree to lint (default: the "
                         "installed jepsen_tpu package)")
    ln.add_argument("--baseline", default=None, metavar="PATH",
                    help="baseline file (default: "
                         "planelint_baseline.json at the repo root)")
    ln.add_argument("--json", action="store_true",
                    help="machine-readable findings report")
    ln.add_argument("--update-baseline", action="store_true",
                    help="grandfather the current findings into the "
                         "baseline instead of failing on them "
                         "(prunes stale entries)")
    ln.add_argument("--sarif", default=None, metavar="PATH",
                    help="write new findings as SARIF 2.1.0 (for CI "
                         "annotation)")
    ln.add_argument("--changed-only", action="store_true",
                    help="scope findings to the files git considers "
                         "changed vs HEAD (graph still spans the "
                         "package)")
    ln.set_defaults(fn=cmd_lint)

    s = sub.add_parser("serve", help="web dashboard over the store")
    shared(s)
    s.add_argument("--port", type=int, default=8080)
    s.set_defaults(fn=cmd_serve)

    d = sub.add_parser(
        "daemon",
        help="checker-as-a-service: a long-lived multi-tenant "
             "analysis daemon over one warm dispatch plane",
    )
    shared(d)
    mesh_args(d)
    d.add_argument("--host", default="127.0.0.1")
    d.add_argument("--port", type=int, default=8008)
    d.add_argument("--max-inflight", type=int, default=64,
                   help="global in-flight check bound (429 past it)")
    d.add_argument("--tenant-inflight", type=int, default=16,
                   help="per-tenant in-flight cap (fairness floor)")
    d.add_argument("--max-payload-mb", type=int, default=32,
                   help="413 payloads above this many MiB")
    d.add_argument("--strict-history", action="store_true",
                   help="default tenant policy: refuse hostile "
                        "histories (422) instead of repairing")
    d.add_argument("--coalesce-hold", type=float, default=0.005,
                   metavar="S",
                   help="hold window between submit and resolve so "
                        "concurrent tenants coalesce into one launch")
    d.add_argument("--launch-deadline", type=float, default=None,
                   metavar="S",
                   help="per-launch deadline inherited by the plane")
    d.add_argument("--drain-seconds", type=float, default=10.0,
                   help="SIGTERM drain budget for in-flight checks")
    d.add_argument("--audit-path", default=None, metavar="PATH",
                   help="request audit log (JSONL; default "
                        "<store>/.service/audit.jsonl)")
    d.add_argument("--audit-max-mb", type=int, default=4,
                   help="rotate the audit log past this many MiB")
    d.add_argument("--trace", action="store_true",
                   help="enable the flight recorder for the daemon's "
                        "life; GET /trace drains the ring")
    d.add_argument("--fleet-dir", default=None, metavar="DIR",
                   help="join a checker fleet: announce + heartbeat "
                        "this daemon's URL into DIR (the front "
                        "door's membership registry)")
    d.add_argument("--member-id", type=int, default=None,
                   help="this daemon's fleet member id (with "
                        "--fleet-dir; default 0)")
    d.add_argument("--member-epoch", type=int, default=None,
                   help="this member's supervision epoch (set by the "
                        "fleet supervisor on respawn; an older "
                        "incarnation of the same member id fences "
                        "itself instead of double-owning checks)")
    d.set_defaults(fn=cmd_daemon)

    fl = sub.add_parser(
        "fleet",
        help="N-member checker fleet behind one front door: "
             "consistent-hash tenant routing, work-stealing, "
             "zero-loss member hand-off",
    )
    shared(fl)
    fl.add_argument("--members", type=int, default=2,
                    help="checker-daemon member count (default 2)")
    fl.add_argument("--host", default="127.0.0.1")
    fl.add_argument("--port", type=int, default=8010,
                    help="front-door port (members use ephemeral "
                         "ports; default 8010)")
    fl.add_argument("--mode", choices=("proxy", "redirect"),
                    default="proxy",
                    help="proxy = relay + journal + steal/hand-off; "
                         "redirect = 307 to the owning member")
    fl.add_argument("--fleet-dir", default=None, metavar="DIR",
                    help="membership registry dir (default "
                         "<store>/.fleet)")
    fl.add_argument("--member-devices", type=int, default=4,
                    help="virtual CPU devices per member (default 4)")
    fl.add_argument("--max-inflight", type=int, default=64,
                    help="per-member global in-flight bound")
    fl.add_argument("--tenant-inflight", type=int, default=16,
                    help="per-member per-tenant in-flight cap")
    fl.add_argument("--coalesce-hold", type=float, default=0.005,
                    metavar="S",
                    help="per-member coalescing hold window")
    fl.add_argument("--drain-seconds", type=float, default=10.0,
                    help="per-member SIGTERM drain budget")
    fl.add_argument("--spawn-timeout", type=float, default=120.0,
                    metavar="S",
                    help="budget for all members to come alive "
                         "(first launch pays JAX import + compile)")
    fl.set_defaults(fn=cmd_fleet)

    fd = sub.add_parser(
        "fleet-drill",
        help="continuously-verified chaos drill: a live fleet under "
             "the seeded fault gauntlet, gated on the invariant "
             "monitor (exit 8 on violation)",
    )
    shared(fd)
    fd.add_argument("--members", type=int, default=2,
                    help="fleet size under drill (min 2; default 2)")
    fd.add_argument("--duration", type=float, default=30.0,
                    metavar="S",
                    help="traffic-under-fire window (default 30s; "
                         "settle/restore time is extra)")
    fd.add_argument("--seed", type=int, default=0,
                    help="fault-schedule seed (same seed = same "
                         "drill, byte for byte)")
    fd.add_argument("--classes", default=None, metavar="K1,K2,...",
                    help="restrict the gauntlet to these fault "
                         "classes (kill,stall,delay,drop,torn_write,"
                         "clock_skew,checkpoint_corrupt); default all")
    fd.add_argument("--gray-seconds", type=float, default=12.0,
                    metavar="S",
                    help="SIGSTOP gray-failure period length")
    fd.add_argument("--restart-budget", type=int, default=3,
                    help="supervisor respawns per member")
    fd.add_argument("--member-devices", type=int, default=2,
                    help="virtual CPU devices per member (default 2)")
    fd.add_argument("--fleet-dir", default=None, metavar="DIR",
                    help="registry dir (default <store>/.fleet-drill)")
    fd.add_argument("--spawn-timeout", type=float, default=180.0,
                    metavar="S",
                    help="budget for the initial fleet to come alive")
    fd.add_argument("--report", default=None, metavar="PATH",
                    help="also write the invariant report JSON here")
    fd.add_argument("--no-parity", action="store_true",
                    help="skip the solo-oracle verdict-parity pass "
                         "(faster; weakens the gate)")
    fd.set_defaults(fn=cmd_fleet_drill)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_parser()
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        return EXIT_USAGE if e.code not in (0, None) else 0
    try:
        return args.fn(args)
    except Exception:
        traceback.print_exc()
        return EXIT_CRASH


if __name__ == "__main__":
    sys.exit(main())

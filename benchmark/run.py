#!/usr/bin/env python3
"""Benchmark harness: one cell of BENCHMARK.json, one run, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip(s). It finds the cell's configuration
(``configs[].file``), traffic mix (``benchmark/traffic/<traffic>.json``)
and that mix's mode (``benchmark/modes/<mode>.py``) by the names in
BENCHMARK.json; builds the inputs from ``--seed``; warms up every
shape the window uses (set-up); measures for ``--seconds``; then checks
every answer of the window against the plain reference
(``benchmark/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` runs
the same window under the JAX profiler and reports the cell's
per-layer metrics, each read by ``benchmark/metrics/<metric>.py``.

The last stdout line is the result object; the last stderr lines are
each compared number beside its limit. Off a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result. A set-up that
would pass its budget stops: the run exits 3, prints no result, and its
last stderr line names the budget.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Iterator, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: a directory of its own under the program's default one. JAX, when it
#: bounds the cache's size, reads an access-time file beside every entry
#: in its directory and fails every write when one lacks it, as entries
#: written without a bound (other tools', on another machine) do.
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "benchmark")
#: seconds from process start that set-up may take, the same for every
#: cell: the 360 s a run may take, less the longest window (51 s) and
#: ~40 s for the reference, the reduction and exit
SETUP_BUDGET_S = 270
#: the same for a cell's first run in a checkout, which compiles and may
#: take 1,200 s
FIRST_RUN_SETUP_BUDGET_S = 1110
#: one empty file per cell that has started a run in this checkout
STARTED_DIR = os.path.join(ROOT, ".jax_cache", "benchmark-started")
#: per-run detail files (set-up split, compile log, check walls, trace
#: summary), one per run
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot measure here; exit 2, print no result."""


class OverBudget(Exception):
    """Set-up would pass its budget; exit 3, print no result."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def for_cell(entries: List[dict], cell: str) -> List[dict]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


class CompileLog:
    """Counts compile requests, persistent-cache hits and misses, and
    backend compile seconds, split at the window's start and end."""

    def __init__(self):
        import jax.monitoring as mon

        self.phase = "setup"
        self.counts: Dict[str, Dict[str, float]] = {}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _bump(self, key: str, by: float = 1) -> None:
        d = self.counts.setdefault(self.phase, {})
        d[key] = d.get(key, 0) + by

    def _event(self, name: str, **_kw) -> None:
        if name.startswith("/jax/compilation_cache/"):
            self._bump(name.rsplit("/", 1)[1])

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self._bump("backend_compiles")
            self._bump("backend_compile_s", secs)

    def in_phase(self, phase: str, key: str) -> float:
        return self.counts.get(phase, {}).get(key, 0)


class Ctx:
    """What a mode is given: the cell, its configuration and traffic,
    the run's arguments, and the window helper."""

    def __init__(self, args, cell, cfg, traffic, devices, compiles, start,
                 budget_s):
        self.args = args
        self.cell = cell
        self.cfg = cfg
        self.traffic = traffic
        self.devices = devices
        self.compiles = compiles
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.setup_split: Dict[str, float] = {}
        self.start = start
        self.budget_s = budget_s
        self._mark = start
        self.window_s: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.trace_summary: Optional[dict] = None
        self.program_spans: List[dict] = []
        self._perf_at_window: Optional[int] = None
        self.warmed: list = []
        self.warm_walls: List[float] = []
        self.warm_compiles: List[dict] = []
        self._warmed = (0, 0)  # warm checks done, of at most how many

    def memory_peak(self) -> Optional[int]:
        """Peak bytes in use on the fullest chip of the cell."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None

    def split(self, name: str) -> None:
        """Close one part of the set-up, named for what it did."""
        now = time.perf_counter()
        self.setup_split[name] = now - self._mark
        self._mark = now

    def budget(self, next_s: float = 0.0) -> None:
        """Stop set-up (``OverBudget``) where it has passed its budget,
        or would pass it with ``next_s`` seconds more."""
        used = time.perf_counter() - self.start
        if used + next_s > self.budget_s:
            done, most = self._warmed
            nxt = f", the next expected to take {next_s:.1f} s" if next_s else ""
            raise OverBudget(
                f"set-up stopped at its budget of {self.budget_s} s: {done} "
                f"of at most {most} warm checks done, {used:.1f} s used{nxt}; "
                "no window was measured")

    def warm(self, first: list, rest: list = ()) -> Iterator:
        """Set-up's warm checks: yields each history for the mode to
        check, in its own frame (called from a frame of the harness, the
        first check traced its kernels 6-9 s slower on a TPU v5e): each of
        ``first``, then each of ``rest`` in turn while the check before
        it needed a program (a compile request, from the persistent
        cache or not); a history that needed none shows the pool's
        programs are made. Keeps the histories warmed (``warmed``), each
        one's wall (``warm_walls``) and what each added to the compile
        log's counts (``warm_compiles``). Before each, stops set-up where
        that check, if it took as long as the longest so far, would end
        past the budget. A first check that alone passes the budget
        cannot be foreseen, and runs to its end."""
        items = list(first) + list(rest)
        for n, i in enumerate(items):
            if n >= len(first) and self.warm_compiles and not (
                    self.warm_compiles[-1].get("backend_compiles")
                    or self.warm_compiles[-1].get("cache_hits")):
                break
            self._warmed = (n, len(items))
            self.budget(max(self.warm_walls, default=0.0))
            before = dict(self.compiles.counts.get("setup", {}))
            t = time.perf_counter()
            yield i
            self.warm_walls.append(time.perf_counter() - t)
            after = self.compiles.counts.get("setup", {})
            self.warm_compiles.append(
                {k: v - before.get(k, 0) for k, v in after.items()})
            self.warmed.append(i)
        self._warmed = (len(self.warmed), len(self.warmed))

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends here. Under ``--trace 1``
        the profiler (Python tracer off) and the program's span
        recorder run for exactly this block."""
        self.budget()
        import jax

        from jepsen_tpu.obs import trace as obs_trace

        log_dir = None
        if self.trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            obs_trace.TRACER.reset()
            obs_trace.enable()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        self.split("to_window")
        self.setup_s = time.perf_counter() - self.start
        self.compiles.phase = "window"
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self._perf_at_window = time.perf_counter_ns()
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles.phase = "after"
            if self.trace:
                jax.profiler.stop_trace()
                self.program_spans = obs_trace.spans()
                obs_trace.disable()
        if self.trace:
            self.trace_summary = self._reduce(log_dir)

    def _reduce(self, log_dir: str) -> dict:
        import shutil

        import trace_reduce as tr

        try:
            trace = tr.Trace.load(tr.newest_xplane(log_dir))
            lo, _ = trace.window()
            # the program's spans are on perf_counter_ns: shift them to
            # the trace's clock by the window annotation's start
            shift = lo - self._perf_at_window
            spans = [
                (s["name"], s["ts"] + shift, s["ts"] + s["dur"] + shift)
                for s in self.program_spans if s.get("ph") == "X"
            ]
            return tr.reduce(trace, self.cell["chips"], spans)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refused as e:
        log(f"REFUSED: {e}")
        return 2
    except OverBudget as e:
        log(f"STOPPED: {e}")
        return 3


def resolve(name: str):
    """(spec, cell, configuration, traffic) for a cell, found by the
    names in BENCHMARK.json."""
    spec_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(spec_path):
        raise Refused(f"no {spec_path}")
    with open(spec_path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} (cells: {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, cfg, traffic


def chips(cell) -> list:
    """The cell's TPU devices, or Refused naming what JAX found."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no backend: {e}")
    d0 = devices[0]
    if d0.platform != "tpu":
        raise Refused(
            f"no TPU: JAX found platform {d0.platform!r} "
            f"({d0.device_kind!r}) x{len(devices)}")
    if len(devices) < cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chips, JAX found "
                      f"{len(devices)}")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if d0.device_kind not in peaks["devices"]:
        raise Refused(f"device kind {d0.device_kind!r} is not in "
                      "benchmark/peaks.json")
    return devices[: cell["chips"]]


def run(args) -> int:
    spec, cell, cfg, traffic = resolve(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    devices = chips(cell)
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    os.makedirs(STARTED_DIR, exist_ok=True)
    started = os.path.join(STARTED_DIR, cell["name"])
    budget_s = SETUP_BUDGET_S if os.path.exists(started) else FIRST_RUN_SETUP_BUDGET_S
    open(started, "a").close()
    out = measure(args, spec, cell, cfg, traffic, devices, start=T0,
                  budget_s=budget_s)
    print(json.dumps(out), flush=True)
    return 0


def measure(args, spec, cell, cfg, traffic, devices, start=None,
            budget_s=None) -> dict:
    """Set-up, window, reference comparison and metrics of one run on
    ``devices``; returns the result object. Set-up counts from ``start``
    (by default, now) and may take ``budget_s`` (by default,
    ``SETUP_BUDGET_S``)."""
    start = time.perf_counter() if start is None else start
    budget_s = SETUP_BUDGET_S if budget_s is None else budget_s
    compiles = CompileLog()
    ctx = Ctx(args, cell, cfg, traffic, devices, compiles, start, budget_s)
    ctx.split("import_and_backend")
    mode = load_module(os.path.join(BENCH, "modes", traffic["mode"] + ".py"),
                       "bench_mode_" + traffic["mode"])
    res = mode.run(ctx)

    metrics = {}
    if not args.trace:
        values = dict(res["e2e"], setup_s=ctx.setup_s)
        for m in for_cell(spec["end_to_end"], cell["name"]):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        obs = dict(res["obs"], trace=ctx.trace_summary,
                   spans=ctx.program_spans)
        for m in for_cell(spec["per_layer"], cell["name"]):
            reader = load_module(
                os.path.join(BENCH, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    in_window = compiles.in_phase("window", "compile_requests_use_cache")
    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": res["memory_peak_bytes"],
    }
    checks = res["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    out = {
        "correct": correct,
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        t = ctx.trace_summary
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}

    os.makedirs(OUT_DIR, exist_ok=True)
    detail = {
        "workload": cell["name"], "seed": args.seed, "trace": args.trace,
        "seconds": args.seconds, "setup_s": ctx.setup_s,
        "setup_budget_s": ctx.budget_s,
        "window_s": ctx.window_s, "setup_split": ctx.setup_split,
        "compiles": compiles.counts, "result": out,
        "detail": res.get("detail"), "trace_summary": ctx.trace_summary,
    }
    # one file per run: a seed run twice keeps both
    stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    path = os.path.join(
        OUT_DIR,
        f"{cell['name']}.s{args.seed}.t{args.trace}.{stamp}.p{os.getpid()}.json")
    with open(path, "w") as f:
        json.dump(detail, f, indent=1, default=str)
    log(f"setup split {json.dumps(ctx.setup_split)}")
    log(f"compiles {json.dumps(compiles.counts)}")
    log(f"compile requests inside the window: {in_window:.0f}")
    log(f"detail {os.path.relpath(path, ROOT)}")
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v} (limit {lim})")
    return out


if __name__ == "__main__":
    sys.exit(main())

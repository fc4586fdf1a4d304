"""Set-up warms one valid and every invalid history, then the pool's
others while the last warm check needed a program, and stops where it
would pass the harness's budget: no window, no result, exit 3. The
runs drive ``run.measure`` on the CPU at ``test_faults``' size of one
register."""

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

import control
import jax
import numpy as np
import pytest
import run
from test_faults import SMALL

from jepsen_tpu.checker.linearizable import LinearizableChecker

#: constants no other program of the process compiles with
FRESH = itertools.count(1)


def compile_fresh():
    """Compile a program that no check before has compiled."""
    c = float(next(FRESH)) + 0.125
    jax.jit(lambda v: v * c + 0.5).lower(np.arange(5.0)).compile()


RESOLVE = run.resolve


def small(name):
    spec, cell, cfg, traffic = RESOLVE(name)
    return spec, cell, dict(cfg, **SMALL["one-register"]), traffic


def measure(monkeypatch, tmp_path, seed=2**31 + 7):
    """The result and the detail of one run of the cell at one
    register."""
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    spec, cell, cfg, tr = small("etcd-keyed.analyze")
    args = argparse.Namespace(workload=cell["name"], seed=seed, seconds=1.0, trace=0)
    out = run.measure(args, spec, cell, cfg, tr, jax.devices()[:1])
    (path,) = tmp_path.iterdir()
    with open(path) as f:
        return out, json.load(f)


def wrap_checks(monkeypatch, also=None) -> list:
    """Every register check, in order, as a fingerprint of its history;
    ``also()`` runs after each."""
    orig = LinearizableChecker.check
    seen = []

    def check(self, test, history, *a, **kw):
        seen.append(tuple((o.type, o.f, str(o.value)) for o in history.ops[:40]))
        out = orig(self, test, history, *a, **kw)
        if also is not None:
            also()
        return out

    monkeypatch.setattr(LinearizableChecker, "check", check)
    return seen


def first_set(order):
    invalid = run.resolve("etcd-keyed.analyze")[3]["invalid"]
    return [i for i in order if i not in invalid][:1] + sorted(invalid)


def needed_a_program(counts) -> bool:
    return bool(counts.get("backend_compiles") or counts.get("cache_hits"))


@pytest.mark.parametrize("every_check_compiles", [False, True],
                         ids=["as-the-program-compiles", "every-check-compiles"])
def test_warm_set_grows_while_checks_need_programs(monkeypatch, tmp_path,
                                                   every_check_compiles):
    seen = wrap_checks(monkeypatch, compile_fresh if every_check_compiles else None)
    out, run_detail = measure(monkeypatch, tmp_path)
    assert out["correct"] is True, out["checks"]
    detail = run_detail["detail"]
    order, warm, compiled = detail["order"], detail["warm"], detail["warm_compiles"]
    first = first_set(order)
    assert warm[: len(first)] == first
    assert warm[len(first):] == [i for i in order if i not in first][: len(warm) - len(first)]
    if every_check_compiles:
        assert sorted(warm) == sorted(order)
    else:
        # past the first set, a history is warmed only after a check
        # that needed a program, and the warm-up ends on one that needed
        # none or with the pool
        for n in range(len(first), len(warm)):
            assert needed_a_program(compiled[n - 1])
        assert len(warm) == len(order) or not needed_a_program(compiled[-1])
        assert run_detail["compiles"].get("window", {}).get("backend_compiles", 0) == 0
    assert len(detail["warm_walls_s"]) == len(compiled) == len(warm)
    # each warm check is of its history, once, before the window
    assert len(seen) == len(warm) + len(detail["checked"])
    in_window = {i: fp for i, fp in zip(detail["checked"], seen[len(warm):])}
    assert [in_window[i] for i in warm] == seen[: len(warm)]
    assert len(set(in_window.values())) == len(order)


@pytest.mark.parametrize("compiling,warmed", [
    ({0}, [0, 1]),
    ({0, 1, 2}, [0, 1, 2, 3]),
    ({0, 1, 2, 3, 4}, [0, 1, 2, 3, 4]),
], ids=["first-set", "until-one-needs-none", "every-history"])
def test_warm_records_what_each_check_compiled(compiling, warmed):
    spec, cell, cfg, traffic = run.resolve("etcd-keyed.analyze")
    args = argparse.Namespace(workload=cell["name"], seed=1, seconds=1.0, trace=0)
    ctx = run.Ctx(args, cell, cfg, traffic, jax.devices()[:1], run.CompileLog(),
                  time.perf_counter(), run.SETUP_BUDGET_S)

    for i in ctx.warm([0, 1], [2, 3, 4]):
        if i in compiling:
            compile_fresh()
    assert ctx.warmed == warmed
    assert len(ctx.warm_walls) == len(warmed)
    assert [c.get("backend_compiles", 0) for c in ctx.warm_compiles] == [
        int(i in compiling) for i in warmed]


class FakeClock:
    """The harness's ``time``, its clock moved on by hand."""

    def __init__(self):
        self.ahead = 0.0

    def perf_counter(self):
        return time.perf_counter() + self.ahead

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.mark.parametrize("budget,stops_after", [(150, 1), (450, 2), (950, 4)],
                         ids=["before-the-second-check", "before-the-third-check",
                              "at-the-window"])
def test_setup_stops_at_its_budget(monkeypatch, tmp_path, budget, stops_after):
    """A fake slow check: the k-th takes 100·k s on the harness's clock,
    and each compiles a program of its own, so the warm-up would take
    the pool of 4. Before each warm check the harness adds the longest
    so far to the time used: 100 + 100 before the second, 300 + 200
    before the third, 600 + 300 before the fourth; set-up ends at 1,000."""
    clock = FakeClock()
    monkeypatch.setattr(run, "time", clock)
    seen = []

    def slow():
        clock.ahead += 100.0 * len(seen)
        compile_fresh()

    seen = wrap_checks(monkeypatch, slow)
    monkeypatch.setattr(run, "SETUP_BUDGET_S", budget)
    with pytest.raises(run.OverBudget,
                       match=f"budget of {budget} s: {stops_after} of at most 4 "
                             "warm checks done"):
        measure(monkeypatch, tmp_path)
    assert len(seen) == stops_after


def test_control_runs_every_seed_in_one_process(monkeypatch, tmp_path, capsys):
    """``control.py`` measures seed after seed in one process: each run's
    set-up counts from that run's start, not the process's, so a budget
    that one run's set-up keeps to holds for every seed."""
    monkeypatch.setattr(run, "T0", time.perf_counter() - 1000.0)
    monkeypatch.setattr(run, "SETUP_BUDGET_S", 120)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(run, "resolve", small)
    monkeypatch.setattr(run, "chips", lambda cell: jax.devices()[:1])
    put_in_place = control.put_in_place
    monkeypatch.setattr(control, "put_in_place",
                        lambda: put_in_place(monkeypatch.setattr))
    seeds = [2**31 + 11, 2**31 + 12, 2**31 + 13]
    argv = ["--workload", "etcd-keyed.analyze", "--seconds", "1"]
    assert control.main(argv + [a for s in seeds for a in ("--seed", str(s))]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == seeds
    assert all(x["control_correct"] is False for x in lines)


def stopped_run(tmp_path, started: bool) -> subprocess.CompletedProcess:
    """The whole run in a process of its own, its look for a chip
    skipped, with set-up budgets of 0 s (0.001 s for a cell's first run
    in the checkout): set-up has passed either before its first warm
    check."""
    code = f"""
import sys
sys.path[:0] = [{run.ROOT!r}, {run.BENCH!r}]
import run
import jax
resolve = run.resolve
def small(name):
    spec, cell, cfg, traffic = resolve(name)
    return spec, cell, dict(cfg, **{SMALL["one-register"]!r}), traffic
run.resolve = small
run.chips = lambda cell: jax.devices()[:1]
run.SETUP_BUDGET_S = 0
run.FIRST_RUN_SETUP_BUDGET_S = 0.001
run.CACHE_DIR = {str(tmp_path / "cache")!r}
run.STARTED_DIR = {str(tmp_path / "started")!r}
run.OUT_DIR = {str(tmp_path / "out")!r}
sys.exit(run.main(["--workload", "etcd-keyed.analyze", "--seed", "1",
                   "--seconds", "1", "--trace", "0"]))
"""
    assert (tmp_path / "started" / "etcd-keyed.analyze").exists() is started
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=300)


def test_a_run_stopped_at_its_budget_exits_3_with_no_result(tmp_path):
    """The cell's first run in the checkout has the first run's budget,
    every later run the other."""
    for started, budget in ((False, "0.001"), (True, "0")):
        p = stopped_run(tmp_path, started)
        assert p.returncode == 3, p.stderr[-2000:]
        assert p.stdout == ""
        last = p.stderr.strip().splitlines()[-1]
        assert f"set-up stopped at its budget of {budget} s" in last
        assert "0 of at most 4 warm checks done" in last
        assert not (tmp_path / "out").exists()

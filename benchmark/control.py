#!/usr/bin/env python3
"""The control of ``correct``: a checker with one guarantee broken, put
in the program's place, driven through a whole run of the harness.

    python3 benchmark/control.py --workload <cell> --seconds <s> --seed <n> [--seed <m> ...]

The control checker keeps per-read validity and drops real-time order:
an ok read passes when it returns the empty register or a value some
write or cas in the history wrote, whenever that happened. That is
what a checker that reorders ops freely across processes would accept.
It replaces ``LinearizableChecker.check``, so each key's check of the
harness's own run (``run.measure``: the loaded history, the
interleave, the ``independent`` split, the window and the comparison)
answers by the control. For each seed it prints the run's ``correct``
and its compared numbers; the control is caught when ``correct`` is
false. The benchmark's own runs never call it. It needs the cell's
chips, as a run does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def weak_check(ops, init=None) -> bool:
    """Every ok read observed the initial value or a written one.
    ``ops`` are dicts or the program's Op records."""
    written = {init}
    for o in ops:
        if o.get("type") in ("invoke", "ok", "info"):
            if o.get("f") == "write":
                written.add(o.get("value"))
            elif o.get("f") == "cas" and o.get("value") is not None:
                written.add(o.get("value")[1])
    return all(o.get("value") in written for o in ops
               if o.get("type") == "ok" and o.get("f") == "read")


def put_in_place(setattr_=setattr):
    """Replace the register checker's check with the control; returns
    the original. ``setattr_`` may be a test's ``monkeypatch.setattr``."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.history.history import History

    orig = LinearizableChecker.check

    def check(self, test, history, opts=None, checkpoint=None):
        if not isinstance(history, History):
            history = History(history)
        return {"valid?": weak_check(history.ops), "method": "control-weak"}

    setattr_(LinearizableChecker, "check", check)
    return orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    spec, cell, cfg, traffic = run.resolve(args.workload)
    devices = run.chips(cell)
    put_in_place()
    for seed in args.seed:
        a = argparse.Namespace(workload=cell["name"], seed=seed,
                               seconds=args.seconds, trace=0)
        out = run.measure(a, spec, cell, cfg, traffic, devices)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control_correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

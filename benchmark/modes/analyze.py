"""Mode ``analyze``: one caller checks whole histories, closed loop.

The entry is the checker ``cli analyze`` runs once a history is
loaded: ``LinearizableChecker().check`` for a single register, wrapped
in ``independent_checker`` for a multi-key history (the
``register-keyed`` path, which checks key by key). The test map has no
``run_dir``, so nothing is written.

Set-up builds the pool (``traffic["pool"]`` distinct histories from
the seed; those listed in ``traffic["invalid"]`` lose one write in one
key) and checks the warm set, which compiles or loads every program
the window runs: first one valid history (the first in the window's
order) and every invalid one, then the pool's other histories in the
window's order for as long as the check before needed a program
(``run.Ctx.warm`` yields each for the mode to check). Where every
history compiles to the same programs as the others of its verdict,
as where every key's window stays in one bucket, it ends one history
past the first set at most; where each history compiles programs of
its own, it is the whole pool. The harness stops set-up before a warm
check that would end past its budget (the run exits 3).

The window checks the pool's histories one after another, in the same
order drawn from the seed and round again, and ends with the check
that ends past ``--seconds``; the rate is the invoked ops of every
history checked over the window's length.

After the window each history's verdict, and each key's, is compared
with the reference's, and every verdict is audited for where it came
from: a device engine (a ``tpu-`` method) or the native racer that is
part of the checker. A verdict the plane's degradation ladder handed
to the host oracle (``degraded``, ``oracle_fallbacks``,
``plane_faults``), any other host method, or a racer that disagreed
with the device makes the run not correct.
"""

from __future__ import annotations

import sys
import time

import reference
from traffic import generate as gen


def build_pool(ctx):
    cfg, tr = ctx.cfg, ctx.traffic
    pool = []
    for h in range(tr["pool"]):
        corrupt_key = None
        if h in tr["invalid"]:
            corrupt_key = gen.rng_for(ctx.seed, h, 3).randrange(cfg["keys"])
        pool.append(gen.history(cfg, ctx.seed, h, corrupt_key, tr["corrupt_at"]))
    return pool


def program_ops(ctx, per_key):
    """The history as the program loads it: Op records, multi-key
    values as ``independent`` tuples in the order a test records them."""
    from jepsen_tpu.history.history import History
    from jepsen_tpu.independent import tuple_

    if ctx.cfg["keys"] == 1:
        return History(per_key[0]["ops"]).ops
    order = gen.interleave(
        ctx.cfg, {k: v["ops"] for k, v in per_key.items()},
        gen.rng_for(ctx.seed, 99),
    )
    return History([dict(o, value=tuple_(k, o["value"])) for k, o in order]).ops


def register_results(ctx, out) -> dict:
    """{key: the register checker's result} of one check."""
    return {0: out} if ctx.cfg["keys"] == 1 else dict(out["results"])


def has_degraded(obj) -> bool:
    if isinstance(obj, dict):
        return "degraded" in obj or any(has_degraded(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return any(has_degraded(v) for v in obj)
    return False


def from_device_or_racer(r) -> bool:
    return (str(r.get("method", "")).startswith("tpu-")
            or r.get("race_winner") == "native")


def counters():
    """The program's counters the window is audited and read by."""
    from jepsen_tpu.checker import chaos, txn_graph, wgl_bitset
    from jepsen_tpu.checker.linearizable import RACE_STATS

    res = chaos.resilience_snapshot()
    return {
        "launch": wgl_bitset.launch_stats_snapshot(),
        "race": dict(RACE_STATS),
        "resilience": {k: res[k] for k in ("oracle_fallbacks", "plane_faults")},
        "txn_graph": {"host_fallback_components":
                      txn_graph.txn_graph_stats()["host_fallback_components"]},
    }


def delta(a: dict, b: dict) -> dict:
    return {s: {k: b[s][k] - a[s].get(k, 0) for k in b[s]} for s in b}


def run(ctx) -> dict:
    from jepsen_tpu.checker.linearizable import (
        LinearizableChecker,
        interpret_off_chip,
    )
    from jepsen_tpu.history.history import History
    from jepsen_tpu.independent import independent_checker

    import jax

    pool = build_pool(ctx)
    ctx.split("generate")
    prog = [program_ops(ctx, per_key) for per_key in pool]
    invoked = [sum(1 for o in ops if o.type == "invoke") for ops in prog]
    ctx.split("load")
    # compiled kernels on the chip; interpreted only in the CPU tests
    checker = LinearizableChecker(
        model=ctx.cfg["model"], interpret=interpret_off_chip("benchmark"))
    if ctx.cfg["keys"] > 1:
        checker = independent_checker(checker)
    test = {"name": ctx.cell["name"]}
    order = list(range(len(prog)))
    gen.rng_for(ctx.seed, 98).shuffle(order)

    def check(i):
        with jax.profiler.TraceAnnotation("bench.check"):
            return checker.check(test, History(prog[i], indexed=True))

    invalid = set(ctx.traffic["invalid"])
    first = [i for i in order if i not in invalid][:1] + sorted(invalid)
    for i in ctx.warm(first, [i for i in order if i not in first]):
        check(i)
    ctx.split("warm_up")

    records = []
    walls = []
    failed = 0
    c0 = counters()
    with ctx.window():
        t0 = time.perf_counter()
        n = 0
        while True:
            i = order[n % len(order)]
            n += 1
            t = time.perf_counter()
            try:
                out = check(i)
            except Exception as e:  # noqa: BLE001 - counted, reported
                failed += 1
                records.append((i, None))
                print(f"bench: check of history {i} raised {e!r}",
                      file=sys.stderr)
            else:
                walls.append(time.perf_counter() - t)
                records.append((i, out))
            if time.perf_counter() - t0 >= ctx.seconds:
                break
    c1 = counters()
    mem = ctx.memory_peak()

    want = []
    for per_key in pool:
        v = {k: reference.decide(item) for k, item in per_key.items()}
        v["all"] = all(v.values())
        want.append(v)
    mismatches = host_resolved = degraded = 0
    for i, out in records:
        if out is None:
            continue
        results = register_results(ctx, out)
        got = {k: r.get("valid?") for k, r in results.items()}
        got["all"] = out.get("valid?")
        mismatches += sum(1 for k, w in want[i].items() if got.get(k) is not w)
        host_resolved += sum(1 for r in results.values()
                             if not from_device_or_racer(r))
        degraded += sum(1 for r in results.values() if has_degraded(r))
    d = delta(c0, c1)
    n_checks = len(records)
    ops = sum(invoked[i] for i, _ in records)
    return {
        "e2e": {"ops_verified_per_s": ops / ctx.window_s},
        "attempted": n_checks,
        "failed": failed,
        "memory_peak_bytes": mem,
        "checks": {
            "verdict_mismatches": (mismatches, 0),
            "checks_unanswered": (failed, 0),
            "host_resolved_verdicts": (host_resolved, 0),
            "degraded_verdicts": (degraded, 0),
            "oracle_fallbacks": (d["resilience"]["oracle_fallbacks"], 0),
            "plane_faults": (d["resilience"]["plane_faults"], 0),
            "host_fallback_components":
                (d["txn_graph"]["host_fallback_components"], 0),
            "racer_mismatches": (d["race"]["mismatches"], 0),
        },
        "obs": {
            "checks": n_checks * ctx.cfg["keys"],
            "launch": d["launch"],
            "race": d["race"],
        },
        "detail": {
            "histories": n_checks, "ops": ops, "order": order, "warm": ctx.warmed,
            "warm_walls_s": ctx.warm_walls, "warm_compiles": ctx.warm_compiles,
            "invalid_in_pool": [i for i, w in enumerate(want) if not w["all"]],
            "checked": [i for i, _ in records],
            "check_walls_s": walls,
            "counters": d,
        },
    }

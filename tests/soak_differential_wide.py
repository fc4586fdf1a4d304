"""Randomized differential soak — NOT collected by pytest (no test_
prefix): run directly (`python tests/soak_differential_wide.py`) from the repo
root. Exit 0 = no divergences. COVERAGE.md's differential-confidence
section records the last results."""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import random

from jepsen_tpu.checker.events import history_to_events
from jepsen_tpu.checker.linearizable import check_events_bucketed
from jepsen_tpu.checker.wgl_oracle import check_events
from jepsen_tpu.checker import wgl_native
from jepsen_tpu.history.history import History
from jepsen_tpu.history.ops import info_op, invoke_op, ok_op
from jepsen_tpu.sim import corrupt_history, gen_register_history

t0 = time.time(); fails = 0; n = 0

# Phase A: mutex differential (random acquire/release interleavings).
def gen_mutex(rng, n_ops, n_procs):
    ops = []
    held = [False]
    free = list(range(n_procs))
    open_by = {}
    emitted = 0
    while emitted < n_ops or open_by:
        if emitted < n_ops and free and (not open_by or rng.random() < 0.6):
            p = free.pop(rng.randrange(len(free)))
            if held[0] and rng.random() < 0.5:
                op = invoke_op(p, "release"); held[0] = False
            elif not held[0]:
                op = invoke_op(p, "acquire"); held[0] = True
            elif rng.random() < 0.4:
                # Doomed double-acquire: emitted anyway — invalid if it
                # completes :ok while the first holder never released.
                op = invoke_op(p, "acquire")
            else:
                free.append(p); continue
            ops.append(op); open_by[p] = op; emitted += 1
        else:
            p = rng.choice(list(open_by)); op = open_by.pop(p)
            if rng.random() < 0.08:
                ops.append(info_op(p, op.f)); free.append(p + n_procs)
            else:
                ops.append(ok_op(p, op.f)); free.append(p)
    return History(ops)

for seed in range(800):
    rng = random.Random(300000 + seed)
    h = gen_mutex(rng, rng.choice((8, 16, 30)), rng.choice((2, 3)))
    ev = history_to_events(h, model="mutex")
    want = check_events(ev, model="mutex")
    got_n = wgl_native.check_events_native(ev, model="mutex")
    if got_n is not None and got_n != want:
        print(f"MUTEX NATIVE DIV seed={seed}", flush=True); fails += 1
    if seed % 3 == 0:
        got_k = check_events_bucketed(ev, model="mutex")
        if got_k["valid?"] != want:
            print(f"MUTEX KERNEL DIV seed={seed} {got_k}", flush=True); fails += 1
    n += 1

print(f"phaseA done ({time.time()-t0:.0f}s)", flush=True)

# Phase B: wide windows 17-40 via seeded crashed writes.
for seed in range(600):
    rng = random.Random(400000 + seed)
    pre = []
    for i in range(rng.choice((17, 22, 30, 38))):
        pre.append(invoke_op(700 + i, "write", i % 6))
        pre.append(info_op(700 + i, "write", i % 6))
    body = gen_register_history(rng, n_ops=rng.choice((20, 50)), n_procs=4, p_crash=0.03)
    h = History(pre + list(body.ops))
    if seed % 2:
        h = corrupt_history(h, rng)
    ev = history_to_events(h)
    want = check_events(ev)
    got_n = wgl_native.check_events_native(ev)
    if got_n is not None and got_n != want:
        print(f"WIDE NATIVE DIV seed={seed} W={ev.window}", flush=True); fails += 1
    if seed % 5 == 0:
        got_k = check_events_bucketed(ev)
        if got_k["valid?"] != want:
            print(f"WIDE KERNEL DIV seed={seed} W={ev.window} {got_k}", flush=True); fails += 1
    n += 1
    if seed % 100 == 0:
        print(f"phaseB {seed} ({time.time()-t0:.0f}s)", flush=True)

# Phase C: larger histories, native vs python only (fast engines).
for seed in range(300):
    rng = random.Random(500000 + seed)
    h = gen_register_history(rng, n_ops=rng.choice((500, 1500)), n_procs=5,
                             p_crash=rng.choice((0.002, 0.01)))
    if seed % 2:
        h = corrupt_history(h, rng)
    ev = history_to_events(h)
    want = check_events(ev)
    got = wgl_native.check_events_native(ev)
    if got is not None and got != want:
        print(f"BIG NATIVE DIV seed={seed}", flush=True); fails += 1
    n += 1
    if seed % 100 == 0:
        print(f"phaseC {seed} ({time.time()-t0:.0f}s)", flush=True)

print(f"SOAK2 DONE: {n} cases, {fails} divergences, {time.time()-t0:.0f}s", flush=True)
sys.exit(1 if fails else 0)

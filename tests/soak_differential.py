"""Randomized differential soak — NOT collected by pytest (no test_
prefix): run directly (`python tests/soak_differential.py`) from the repo
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
from jepsen_tpu.sim import corrupt_history, gen_register_history

t0 = time.time()
fails = 0
n = 0
# Phase 1: register family, jax kernel + native + python, varied shapes.
for seed in range(4000):
    rng = random.Random(100000 + seed)
    n_ops = rng.choice((12, 30, 60, 120))
    n_procs = rng.choice((3, 4, 5, 6))
    p_crash = rng.choice((0.0, 0.02, 0.1, 0.25))
    h = gen_register_history(rng, n_ops=n_ops, n_procs=n_procs, p_crash=p_crash)
    if seed % 2:
        h = corrupt_history(h, rng)
    model = rng.choice(("cas-register", "register"))
    ev = history_to_events(h, model=model)
    want = check_events(ev, model=model)
    got_n = wgl_native.check_events_native(ev, model=model)
    if got_n is not None and got_n != want:
        print(f"NATIVE DIVERGENCE seed={seed} model={model}", flush=True)
        fails += 1
    if seed % 4 == 0:  # kernel path is slower; sample
        # every other kernel sample runs with the competition race ON
        # (native oracle vs kernel, either may win) — the verdict must
        # not depend on who wins or on the crosscheck accounting
        race = True if (seed % 8 == 0 and wgl_native.available()) else None
        got_k = check_events_bucketed(ev, model=model, race=race)
        if got_k["valid?"] != want:
            print(f"KERNEL DIVERGENCE seed={seed} model={model} race={race} {got_k}", flush=True)
            fails += 1
    n += 1
    if seed % 500 == 0:
        print(f"phase1 {seed} ({time.time()-t0:.0f}s)", flush=True)

# Phase 2: queue model (tuple vs packed python vs packed native vs kernel).
from test_queue_device import _corrupt, gen_queue_history
for seed in range(1500):
    rng = random.Random(200000 + seed)
    h = gen_queue_history(rng, n_ops=rng.choice((10, 20, 35)),
                          n_procs=rng.choice((2, 3, 4)),
                          n_values=rng.choice((2, 3, 5)),
                          p_crash=rng.choice((0.0, 0.08, 0.2)))
    if seed % 2:
        h = _corrupt(h, rng)
    ev = history_to_events(h, model="unordered-queue")
    want = check_events(ev, model="unordered-queue")
    got_p = check_events(ev, model="unordered-queue-packed")
    if got_p != want:
        print(f"PACKED DIVERGENCE seed={seed}", flush=True)
        fails += 1
    got_n = wgl_native.check_events_native(ev, model="unordered-queue-packed")
    if got_n is not None and got_n != want:
        print(f"NATIVE-Q DIVERGENCE seed={seed}", flush=True)
        fails += 1
    if seed % 3 == 0:
        got_k = check_events_bucketed(ev, model="unordered-queue")
        if got_k["valid?"] != want:
            print(f"KERNEL-Q DIVERGENCE seed={seed} {got_k}", flush=True)
            fails += 1
    n += 1
    if seed % 300 == 0:
        print(f"phase2 {seed} ({time.time()-t0:.0f}s)", flush=True)

print(f"SOAK DONE: {n} cases, {fails} divergences, {time.time()-t0:.0f}s", flush=True)
sys.exit(1 if fails else 0)

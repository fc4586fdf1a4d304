"""Long-running randomized soak (run directly; Ctrl-C when done).
Not pytest-collected."""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import random

from jepsen_tpu.checker.events import history_to_events
from jepsen_tpu.checker.linearizable import check_events_bucketed
from jepsen_tpu.checker.wgl_oracle import check_events
from jepsen_tpu.checker import wgl_native
from jepsen_tpu.sim import corrupt_history, gen_register_history
from test_queue_device import _corrupt, gen_queue_history

t0 = time.time(); fails = 0; n = 0
for seed in range(1_000_000):
    rng = random.Random(900000 + seed)
    if seed % 4 == 3:
        h = gen_queue_history(rng, n_ops=rng.randrange(8, 40),
                              n_procs=rng.randrange(2, 5),
                              n_values=rng.randrange(2, 6),
                              p_crash=rng.choice((0.0, 0.05, 0.15)))
        if seed % 2:
            h = _corrupt(h, rng)
        ev = history_to_events(h, model="unordered-queue")
        want = check_events(ev, model="unordered-queue")
        pair = [
            ("packed-py", check_events(ev, model="unordered-queue-packed")),
            ("packed-cc", wgl_native.check_events_native(ev, model="unordered-queue-packed")),
        ]
        if seed % 12 == 3:
            pair.append(("kernel", check_events_bucketed(ev, model="unordered-queue")["valid?"]))
    else:
        n_ops = rng.randrange(10, 200)
        # Keep windows out of the CPU-hostile giant-matrix regime: the
        # K-frontier jax rung at W=64 on 1 CPU core takes minutes per
        # history (fine on TPU, not in a soak).
        p_crash = rng.choice((0.0, 0.01, 0.05, 0.2))
        if n_ops * p_crash > 5:
            p_crash = 5.0 / n_ops
        h = gen_register_history(rng, n_ops=n_ops,
                                 n_procs=rng.randrange(2, 7),
                                 p_crash=p_crash)
        if seed % 2:
            h = corrupt_history(h, rng)
        model = ("cas-register", "register")[seed % 2]
        ev = history_to_events(h, model=model)
        want = check_events(ev, model=model)
        pair = [("native", wgl_native.check_events_native(ev, model=model))]
        if seed % 8 == 0 and ev.window <= 16 and len(ev) <= 300:
            pair.append(("kernel", check_events_bucketed(ev, model=model)["valid?"]))
    for name, got in pair:
        if got is not None and got != want:
            print(f"DIVERGENCE {name} seed={seed}", flush=True)
            fails += 1
    n += 1
    if n % 2000 == 0:
        print(f"{n} cases, {fails} divergences ({time.time()-t0:.0f}s)", flush=True)

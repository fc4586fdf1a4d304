"""Checker perf-regression smoke (tier 3, perf_test.clj's role): every
checker family runs over a fixed LARGISH history in one go — not
timing assertions (flaky in CI), but the at-scale code paths the tiny
unit histories never touch (blocked set-full reductions, device-path
thresholds, long single-key WGL streams on the CPU oracle)."""

import random

from jepsen_tpu.checker.adya import G2Checker
from jepsen_tpu.checker.bank import BankChecker
from jepsen_tpu.checker.divergence import DirtyReadsChecker
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.checker.longfork import LongForkChecker
from jepsen_tpu.checker.reductions import (
    counter,
    set_full,
    total_queue,
    unique_ids,
)
from jepsen_tpu.runtime import run
from jepsen_tpu.sim import (
    gen_bank_history,
    gen_g2_history,
    gen_long_fork_history,
    gen_register_history,
)


def test_linearizable_5k_ops_cpu():
    h = gen_register_history(
        random.Random(1), n_ops=5000, n_procs=5, p_crash=0.002
    )
    r = LinearizableChecker().check({}, h)
    assert r["valid?"] is True, r
    assert r["n_ops"] > 3000


def test_bank_20k_ops():
    test = {"accounts": list(range(8)), "total_amount": 100}
    h = gen_bank_history(random.Random(2), n_ops=20_000)
    r = BankChecker().check(test, h)
    assert r["valid?"] is True and r["read_count"] > 5000


def test_g2_20k_keys():
    h = gen_g2_history(random.Random(3), n_keys=20_000)
    r = G2Checker().check({}, h)
    assert r["valid?"] is True and r["key_count"] == 20_000


def test_long_fork_64_groups():
    h = gen_long_fork_history(
        random.Random(4), n_groups=64, ops_per_group=128, n=2
    )
    r = LongForkChecker(2).check({}, h)
    assert r["valid?"] is True


def test_reductions_at_scale():
    from jepsen_tpu.workloads import counter as counter_wl
    from jepsen_tpu.workloads import set as set_wl
    from jepsen_tpu.suites.hazelcast import _queue_workload, IdGenClient
    from jepsen_tpu.generator import pure as gen

    # set-full over thousands of elements (the blocked reduction)
    spec = set_wl.workload(n_adds=4000, rng=random.Random(5))
    out = run({**spec, "concurrency": 4})
    assert out["results"]["valid?"] is True

    # counter with thousands of deltas
    spec = counter_wl.workload(n_ops=4000, rng=random.Random(6))
    out = run({**spec, "concurrency": 4})
    assert out["results"]["valid?"] is True

    # queue conservation over thousands of enqueues + final drain
    spec = _queue_workload({"ops": 4000, "rng": random.Random(7)})
    out = run({**spec, "checker": total_queue(), "concurrency": 4})
    assert out["results"]["valid?"] is True

    # unique ids at scale
    out = run({
        "client": IdGenClient(),
        "generator": gen.clients(gen.limit(4000, {"f": "generate"})),
        "checker": unique_ids(),
        "concurrency": 4,
    })
    assert out["results"]["valid?"] is True


def test_dirty_reads_at_scale():
    from jepsen_tpu.workloads import dirty_reads

    spec = dirty_reads.workload(n_ops=4000, rng=random.Random(8))
    out = run({**spec, "concurrency": 4})
    r = out["results"]
    assert r["valid?"] is True and r["read_count"] > 500


def test_bench_register_plane_pipelined_interpret():
    """The bench's suite-mode pass (one DispatchPlane coalescing the
    etcd + zookeeper key batches and the north star's segment chain) —
    exercised on CPU via Pallas interpret mode so the TPU-only path
    can't bit-rot between driver runs."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    ))
    import bench

    old = bench.SMOKE
    bench.SMOKE = True
    try:
        etcd = bench._etcd_streams()[:3]
        zk = bench._zk_streams()[:3]
        ns = bench._northstar_stream()
        out = bench._register_plane_pipelined(
            etcd, zk, ns, interpret=True
        )
        assert out is not None
        ok, walls, dstats = out
        assert ok is True
        # per-config cumulative walls feed the bench JSON's
        # pipelined_wall_s field — all three configs must report
        assert set(walls) == {
            "etcd-1k", "zookeeper-10kx16", "northstar-100k",
        }
        assert all(w > 0 for w in walls.values()), walls
        # dispatch_stats feed the bench JSON: all 7 submits must have
        # been served by coalesced or solo launches (never the
        # sequential fallback), and amortization must beat
        # one-sync-per-request. (Whether the smoke-sized north star
        # rides a batch or dispatches its segment chain solo depends
        # on SMOKE sizing — both are valid plans.)
        assert dstats["requests"] == 7, dstats
        assert (
            dstats["batched_requests"] + dstats["solo_launches"] == 7
        ), dstats
        assert dstats["fallbacks"] == 0, dstats
        assert dstats["floor_amortization"] > 1.0, dstats
    finally:
        bench.SMOKE = old


def test_host_prep_2x_on_100k_stream():
    """The prep acceptance bar: events_to_steps (fused numpy + native
    fast path) at least 2x faster than the round-5 vectorized baseline
    (_events_to_steps_v1) on a 100k-op history, with byte-identical
    ReturnSteps (asserted inside bench_host_prep). Ratio of two walls
    on the same host — not an absolute-time assertion."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    ))
    import bench

    from jepsen_tpu.checker.wgl_native import prep_available

    if not prep_available():
        import pytest

        pytest.skip("no C++ toolchain: native prep path unavailable")
    out = bench.bench_host_prep()
    assert out["n_history_ops"] >= 100_000
    assert out["native"] is True
    assert out["speedup"] >= 2.0, out


def test_tracing_on_overhead_bounded_8dev_mesh():
    """Leaving the flight recorder ON during a real 8-device sharded
    check must cost a bounded fraction of the check's wall — emission
    is per-thread ring appends, O(1) per plane crossing, so on/off is
    a same-host ratio assertion (min-of-N to shed scheduler noise),
    never an absolute-time bar. The guard exists to catch an
    accidental O(events) insert on the hot path."""
    import time

    from jepsen_tpu import obs
    from jepsen_tpu.checker.events import history_to_events
    from jepsen_tpu.checker.sharded import check_keys, default_mesh
    from jepsen_tpu.sim import gen_register_history

    streams = []
    for seed in range(8):
        rng = random.Random(seed)
        h = gen_register_history(rng, n_ops=200, n_procs=3)
        streams.append(history_to_events(h))
    mesh = default_mesh()

    def one_pass():
        t0 = time.perf_counter()
        res = check_keys(streams, mesh=mesh)
        t1 = time.perf_counter()
        assert all(bool(r["valid?"]) for r in res)
        return t1 - t0

    was_enabled = obs.TRACER.enabled
    try:
        obs.disable()
        one_pass()  # warm the jit cache outside both measurements
        off = min(one_pass() for _ in range(3))
        obs.enable()
        on = min(one_pass() for _ in range(3))
    finally:
        obs.reset()
        if was_enabled:
            obs.enable()
        else:
            obs.disable()
    # generous budget + absolute slack: recorder cost should be noise
    assert on <= off * 1.5 + 0.05, (on, off)

"""Multi-device sharded checking tests — run on the virtual 8-CPU mesh
(tests/conftest.py) the way the driver's dryrun does."""

import random

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from jepsen_tpu.checker.events import history_to_events
from jepsen_tpu.checker.sharded import check_keys
from jepsen_tpu.checker.wgl_oracle import check_events as oracle_check
from jepsen_tpu.sim import corrupt_history, gen_register_history


def _streams(n_keys, n_ops=24, corrupt_every=3):
    out = []
    for seed in range(n_keys):
        rng = random.Random(seed)
        h = gen_register_history(rng, n_ops=n_ops, n_procs=3, p_crash=0.05)
        if corrupt_every and seed % corrupt_every == 0:
            h = corrupt_history(h, rng)
        out.append(history_to_events(h))
    return out


def test_vmap_batch_matches_oracle():
    streams = _streams(12)
    results = check_keys(streams)
    assert len(results) == 12
    for s, r in zip(streams, results):
        assert r["valid?"] == oracle_check(s)


def test_sharded_mesh_matches_oracle():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(np.asarray(devs[:8]), axis_names=("keys",))
    streams = _streams(13)  # deliberately not a multiple of 8
    results = check_keys(streams, mesh=mesh)
    assert len(results) == 13
    for s, r in zip(streams, results):
        assert r["valid?"] == oracle_check(s)


def test_sharded_2d_mesh_matches_oracle():
    """Keys shard over the product of a multi-axis mesh (the hosts x
    chips / DCN x ICI layout) — same verdicts as the oracle."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(
        np.asarray(devs[:8]).reshape(4, 2),
        axis_names=("hosts", "chips"),
    )
    streams = _streams(11)
    results = check_keys(streams, mesh=mesh)
    assert len(results) == 11
    for s, r in zip(streams, results):
        assert r["valid?"] == oracle_check(s)


def test_graft_entry_contract(capfd):
    import json

    import __graft_entry__ as g

    fn, args = g.entry()
    alive, overflow, died = jax.jit(fn)(*args)
    assert bool(alive) is True
    assert int(died) == -1
    g.dryrun_multichip(8)
    # The multichip dryrun must publish exactly one parsable JSON
    # metric line on stdout (the driver's MULTICHIP tail was empty in
    # r03-r05). It runs in a subprocess, so capture at the fd level.
    tail = [
        ln for ln in capfd.readouterr()[0].strip().splitlines() if ln
    ]
    assert tail, "dryrun_multichip printed nothing"
    rec = json.loads(tail[-1])
    assert rec["metric"] == "sharded_keys_per_sec"
    assert rec["n_devices"] == 8
    assert rec["n_devices_used"] == 8
    assert rec["value"] > 0
    assert rec["scaling_efficiency"] >= 0.6
    assert rec["mesh_wall_s"] > 0 and rec["single_wall_s"] > 0
    # Device residency rides the metric line: a timed whole-batch
    # check pays the host sync floor exactly once.
    assert rec["syncs_per_check"] == 1.0
    # Pod topology rides the same line: a single-process dryrun is a
    # one-host pod on the CPU backend, and the driver reads both
    # fields when it assembles the backend matrix.
    assert rec["n_hosts"] == 1
    assert rec["backend"] == "cpu"
    # Resilience accounting rides the same line: a clean dryrun
    # publishes integer zeros (nonzero means faults were survived).
    assert isinstance(rec["retries"], int) and rec["retries"] >= 0
    assert isinstance(rec["quarantines"], int) and rec["quarantines"] >= 0
    # Static-analysis validity rides the same line: the tree that
    # produced this number carries zero non-baselined planelint
    # findings (hot-path residency + lock discipline hold at review
    # time, not just at runtime).
    assert rec["lint_findings"] == 0
    # Observability rides the same line: launch-plane accounting and
    # the flight-recorder membership. A single-process dryrun is a
    # one-member pod (trace_members=1); the pod dryrun's contract in
    # test_pod.py sums these same counters across members.
    assert isinstance(rec["launches"], int) and rec["launches"] > 0
    assert isinstance(rec["host_syncs"], int) and rec["host_syncs"] > 0
    assert rec["trace_members"] == 1
    # ... and names the rule catalog that judged it: all five
    # families (A hotpath, B concurrency, C obsrules, D lockorder,
    # E podrules/determinism) plus the meta rules.
    from jepsen_tpu import analysis

    assert rec["lint_rules_total"] == analysis.rules_total()
    assert rec["lint_rules_total"] >= 25
    # Flight-recorder liveness rides the same line: the dryrun runs
    # traced, so the metric that claims the floor was paid once comes
    # with the timeline that shows where.
    assert int(rec["trace_spans"]) > 0
    # Perf-plane identity rides the same line: the knob config this
    # number was measured under is always disclosed — a profile path
    # when a tuned profile loaded, the defaults config hash otherwise.
    assert isinstance(rec["tuned_profile"], str) and rec["tuned_profile"]


def test_sharded_at_scale_with_escalation_keys():
    # VERDICT weak #7: the per-key overflow-escalation branch and
    # larger key counts. 48 keys across the 8-device mesh, including
    # crash-heavy keys whose first-rung frontier overflows and must
    # re-check individually through the ladder — verdicts must still
    # match the oracle on every key.
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(np.asarray(devs[:8]), axis_names=("keys",))
    streams = []
    for seed in range(48):
        rng = random.Random(9000 + seed)
        crashy = seed % 6 == 0
        h = gen_register_history(
            rng, n_ops=40, n_procs=4,
            p_crash=0.3 if crashy else 0.02,
        )
        if seed % 4 == 0:
            h = corrupt_history(h, rng)
        streams.append(history_to_events(h))
    results = check_keys(streams, mesh=mesh, k_ladder=(2, 128))
    assert len(results) == 48
    n_escalated = 0
    for i, (s, r) in enumerate(zip(streams, results)):
        assert r["valid?"] == oracle_check(s), f"key {i}: {r}"
        # keys that left the sharded batch re-checked individually
        # through the ladder (their method is the single-key one)
        if r["method"] != "tpu-wgl-sharded":
            n_escalated += 1
    # the tiny first rung guarantees some keys actually escalated
    assert n_escalated >= 1


def test_batch_path_escalation_on_one_device():
    # Same shape through the single-device batched path: mesh=False
    # pins one device even when tier-1 exposes 8 host devices.
    streams = []
    for seed in range(24):
        rng = random.Random(9500 + seed)
        h = gen_register_history(
            rng, n_ops=40, n_procs=4,
            p_crash=0.3 if seed % 5 == 0 else 0.02,
        )
        if seed % 3 == 0:
            h = corrupt_history(h, rng)
        streams.append(history_to_events(h))
    results = check_keys(streams, k_ladder=(2, 128), mesh=False)
    for i, (s, r) in enumerate(zip(streams, results)):
        assert r["valid?"] == oracle_check(s), f"key {i}: {r}"


def test_check_keys_bitset_batch_single_launch():
    """The multi-key default plane: 16 keys ride ONE batched bitset
    launch + one host sync (the zookeeper-10kx16 shape pays the sync
    floor once, not 16 times). Clean streams never escalate, so the
    launch counter must read exactly 1."""
    from jepsen_tpu.checker import wgl_bitset as bs

    streams = _streams(16, corrupt_every=0)
    bs.reset_launch_stats()
    results = check_keys(streams, interpret=True)
    assert len(results) == 16
    for s, r in zip(streams, results):
        assert r["method"] == "tpu-wgl-bitset-batch"
        assert r["valid?"] == oracle_check(s)
    assert bs.LAUNCH_STATS["launches"] == 1
    assert bs.LAUNCH_STATS["escalations"] == 0


def test_check_keys_bitset_batch_escalation_parity():
    """Corrupted keys in the batch: a fast-tier death escalates the
    WHOLE batch to the exact kernel in one more launch (2 total, 1
    escalation), and every key's verdict still matches the per-key
    oracle."""
    from jepsen_tpu.checker import wgl_bitset as bs

    streams = _streams(16, corrupt_every=3)
    assert not all(oracle_check(s) for s in streams)
    bs.reset_launch_stats()
    results = check_keys(streams, interpret=True)
    for i, (s, r) in enumerate(zip(streams, results)):
        assert r["method"] == "tpu-wgl-bitset-batch", (i, r)
        assert r["valid?"] == oracle_check(s), (i, r)
    assert bs.LAUNCH_STATS["launches"] == 2
    assert bs.LAUNCH_STATS["escalations"] == 1

#!/usr/bin/env python3
"""Bring-up smoke: the checker's main path, once, on the chip.

One process owns the chip. It refuses to run anywhere but a TPU (no CPU
or interpret fallback), then drives four phases at the BASELINE sizes
through the entry points a user calls, each cold (compiles) and then
warm:

  a. north star: a seeded 100k-op etcd CAS-register history (bench.py's
     ``_northstar_stream`` parameters) stored and checked through
     ``cli analyze`` in-process; a corrupted twin must come back invalid;
  b. zookeeper shape: 16 keys x 625 ops through ``check_keys`` (the
     stacked bitset batch);
  c. transactions: g1c-200k through ``TxnGraphChecker`` against
     ``fold_txn_graph``, planted G1c included;
  d. daemon: an in-process ``CheckerDaemon`` answers 4 ``POST /check``
     requests (1k-op etcd histories) from ``CheckerClient``.

Every verdict must match its host oracle, come from a device engine,
with its result arrays fetched from a TPU, and with no oracle, host,
plane-fault or racer resolution. ``--chips 4`` runs only the mesh phase
(sharded vs one-device ``check_keys`` and ``launch_graph_batch``).

The last stdout line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it is printed. The printed walls are a bring-up
reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

#: BASELINE sizes (the rehearsal on the CPU shrinks these in its own
#: script, never through an option of this one)
NS_OPS = 100_000
ZK_KEYS = 16
ZK_OPS_PER_KEY = 625
TXNS = 200_000
DAEMON_OPS = 1000
DAEMON_CHECKS = 4

#: methods of the device engines; anything else is a host resolution
DEVICE_METHODS = ("tpu-",)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class SmokeFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- what the device returned -------------------------------------------------


class FetchRecorder:
    """Wraps ``jax.device_get`` (the funnel every device->host fetch of
    the checker goes through) and records the platform and id of every
    device that held a fetched array."""

    def __init__(self):
        import jax

        self._jax = jax
        self._orig = jax.device_get
        self._lock = threading.Lock()
        self.reset()
        jax.device_get = self._get

    def reset(self) -> None:
        with self._lock:
            self.fetches = 0
            self.platforms = set()
            self.device_ids = set()

    def _get(self, x):
        leaves = self._jax.tree_util.tree_leaves(x)
        with self._lock:
            self.fetches += 1
            for leaf in leaves:
                if isinstance(leaf, self._jax.Array):
                    for d in leaf.devices():
                        self.platforms.add(d.platform)
                        self.device_ids.add(d.id)
        return self._orig(x)


class WRecorder:
    """Records the W bucket of every bitset scan traced or called from
    Python (a phase's cold run traces every bucket it uses)."""

    def __init__(self):
        from jepsen_tpu.checker import wgl_bitset as bs

        self.ws = set()
        orig = bs._bitset_scan

        def scan(*a, **kw):
            self.ws.add(kw["W"])
            return orig(*a, **kw)

        bs._bitset_scan = scan


def _find_degraded(obj) -> bool:
    if isinstance(obj, dict):
        return "degraded" in obj or any(
            _find_degraded(v) for v in obj.values()
        )
    if isinstance(obj, (list, tuple)):
        return any(_find_degraded(v) for v in obj)
    return False


class Smoke:
    def __init__(self, platform: str = "tpu", interpret: bool = False):
        self.platform = platform
        self.interpret = interpret
        self.fetch = FetchRecorder()
        self.wrec = WRecorder()
        self.report = {}

    # -- one run of one phase ------------------------------------------

    def _reset(self) -> None:
        from jepsen_tpu.checker.linearizable import reset_race_stats
        from jepsen_tpu.obs.snapshot import reset_engine_stats

        reset_engine_stats()
        reset_race_stats()
        self.fetch.reset()
        self.wrec.ws.clear()

    def _audit(self, name: str, verdicts, all_devices=None) -> dict:
        """The checks every phase run must pass after it ran."""
        from jepsen_tpu.checker.linearizable import RACE_STATS
        from jepsen_tpu.obs.snapshot import engine_snapshot

        snap = engine_snapshot()
        res = snap["resilience"]
        for key in ("oracle_fallbacks", "plane_faults"):
            require(res.get(key, 0) == 0,
                    f"{name}: {key}={res.get(key)} (resilience {res})")
        hfc = snap["txn_graph"]["host_fallback_components"]
        require(hfc == 0, f"{name}: host_fallback_components={hfc}")
        require(RACE_STATS["native_wins"] == 0,
                f"{name}: a native racer decided a verdict "
                f"({RACE_STATS})")
        methods = sorted({v.get("method") for v in verdicts})
        for m in methods:
            require(m and m.startswith(DEVICE_METHODS),
                    f"{name}: method {m!r} is not a device engine")
        require(not _find_degraded(list(verdicts)),
                f"{name}: a verdict is degraded")
        require(self.fetch.fetches > 0,
                f"{name}: no result was fetched from a device")
        require(self.fetch.platforms == {self.platform},
                f"{name}: results came from {self.fetch.platforms}, "
                f"not {self.platform}")
        if all_devices is not None:
            require(self.fetch.device_ids == all_devices,
                    f"{name}: results held on devices "
                    f"{sorted(self.fetch.device_ids)}, want all of "
                    f"{sorted(all_devices)}")
        return {
            "methods": methods,
            "w_buckets": sorted(self.wrec.ws),
            "launches": snap["launch"]["launches"],
            "host_syncs": snap["launch"]["host_syncs"],
            "fetches": self.fetch.fetches,
            "devices": sorted(self.fetch.device_ids),
        }

    def phase(self, name: str, run) -> None:
        """run() -> list of verdict dicts; called cold, then warm."""
        out = {}
        ws = set()
        for temp in ("cold", "warm"):
            self._reset()
            t0 = time.perf_counter()
            verdicts = run()
            wall = time.perf_counter() - t0
            audit = self._audit(f"{name}/{temp}", verdicts)
            audit["wall_s"] = wall
            out[temp] = audit
            ws.update(audit.pop("w_buckets"))
            log(f"{name} {temp}: wall={wall:.3f}s "
                f"launches={audit['launches']} "
                f"host_syncs={audit['host_syncs']} "
                f"engines={audit['methods']}")
        out["w_buckets"] = sorted(ws)
        log(f"{name}: bitset W buckets {sorted(ws)}")
        self.report[name] = out

    # -- phases ---------------------------------------------------------

    def northstar(self) -> None:
        from jepsen_tpu import cli
        from jepsen_tpu.checker.events import history_to_events
        from jepsen_tpu.checker.wgl_native import check_events_native
        from jepsen_tpu.sim import corrupt_history, gen_register_history
        from jepsen_tpu.store import Store

        h = gen_register_history(
            random.Random(9), n_ops=NS_OPS, n_procs=5, p_crash=0.0002
        )
        bad = corrupt_history(h, random.Random(9))
        root = tempfile.mkdtemp(prefix="chip-smoke-")
        st = Store(root)
        runs = []
        for tag, hist in (("northstar", h), ("northstar-corrupt", bad)):
            want = check_events_native(history_to_events(hist))
            require(want is not None, f"{tag}: native oracle declined")
            test = {"name": tag, "history": hist}
            st.make_run_dir(test)
            st.save_1(test)
            runs.append((tag, test["run_dir"], want))
        require(runs[0][2] is True, "northstar: oracle says invalid")
        require(runs[1][2] is False,
                "northstar-corrupt: the corruption kept it linearizable")

        def run():
            verdicts = []
            for tag, run_dir, want in runs:
                rc = cli.main(["analyze", run_dir, "--workload",
                               "register", "--store", root])
                res = st.load_results(run_dir)
                require(res is not None, f"{tag}: no results.json")
                require(res["valid?"] is want,
                        f"{tag}: device says {res['valid?']}, oracle "
                        f"{want}")
                require(rc == (0 if want else 1),
                        f"{tag}: analyze exit code {rc}")
                verdicts.append(res)
            return verdicts

        self.phase("a-northstar-100k", run)

    def zk_streams(self):
        """bench.py's _zk_streams parameters at BASELINE size."""
        from jepsen_tpu.checker.events import history_to_events
        from jepsen_tpu.sim import gen_register_history

        return [
            history_to_events(gen_register_history(
                random.Random(1000 + key), n_ops=ZK_OPS_PER_KEY,
                n_procs=5, p_crash=0.005,
            ))
            for key in range(ZK_KEYS)
        ]

    def zookeeper(self) -> None:
        from jepsen_tpu.checker.events import clear_memos
        from jepsen_tpu.checker.sharded import check_keys
        from jepsen_tpu.checker.wgl_native import check_events_native

        streams = self.zk_streams()
        want = [check_events_native(s) for s in streams]
        require(None not in want, "zookeeper: native oracle declined")

        def run():
            for s in streams:
                clear_memos(s)
            out = check_keys(streams, interpret=self.interpret)
            got = [r["valid?"] for r in out]
            require(got == want,
                    f"zookeeper: device {got} != oracle {want}")
            return out

        self.phase("b-zookeeper-10kx16", run)

    def transactions(self) -> None:
        from jepsen_tpu.checker import txn_graph as tg
        from jepsen_tpu.sim import gen_txn_graph_history

        h = gen_txn_graph_history(
            random.Random(66), n_txns=TXNS, anomaly="g1c", cycle_len=3
        )
        full = tg.fold_txn_graph(h)
        require(full["valid?"] is False and full["census"]["G1c"] > 0,
                f"g1c: the planted G1c is missing from the oracle "
                f"({full['census']})")
        plane = tg.encode_txn_graph(h)
        drop = ("method", "components", "matmul_rounds")

        def run():
            r = tg.TxnGraphChecker().check({}, plane)
            got = {k: v for k, v in r.items() if k not in drop}
            ref = {k: v for k, v in full.items() if k not in drop}
            require(got == ref,
                    f"g1c: device verdict != fold_txn_graph "
                    f"(census {r.get('census')} vs {full['census']})")
            return [r]

        self.phase("c-g1c-200k", run)

    def daemon(self) -> None:
        from jepsen_tpu.checker.events import history_to_events
        from jepsen_tpu.checker.wgl_native import check_events_native
        from jepsen_tpu.service.client import CheckerClient
        from jepsen_tpu.service.server import CheckerDaemon
        from jepsen_tpu.sim import gen_register_history

        # distinct histories per round: the daemon's verdict memo must
        # not answer the warm round
        rounds = [
            [
                gen_register_history(
                    random.Random(100 + r * DAEMON_CHECKS + i),
                    n_ops=DAEMON_OPS, n_procs=5, p_crash=0.01,
                )
                for i in range(DAEMON_CHECKS)
            ]
            for r in range(2)
        ]
        wants = [
            [check_events_native(history_to_events(h)) for h in hs]
            for hs in rounds
        ]
        root = tempfile.mkdtemp(prefix="chip-smoke-daemon-")
        daemon = CheckerDaemon(root=root, port=0,
                               interpret=self.interpret)
        thread = threading.Thread(target=daemon.serve_forever,
                                  daemon=True)
        thread.start()
        client = CheckerClient(port=daemon.port, timeout_s=900,
                               tenant="chip-smoke")
        it = iter(zip(rounds, wants))

        def run():
            hs, want = next(it)
            out = [client.check(h, model="cas-register") for h in hs]
            got = [o.get("valid?") for o in out]
            require(got == want,
                    f"daemon: device {got} != oracle {want}")
            return out

        try:
            self.phase("d-daemon-4x1k", run)
        finally:
            daemon.admission.start_drain()
            daemon.httpd.shutdown()
            thread.join(timeout=30)
            daemon.close()

    def mesh(self, n_chips: int) -> None:
        """Sharded over every visible chip vs one device: check_keys on
        the zookeeper shape, and one launch_graph_batch."""
        import jax
        import numpy as np

        from jepsen_tpu.checker import txn_graph as tg
        from jepsen_tpu.checker.events import clear_memos
        from jepsen_tpu.checker.sharded import (
            MESH_STATS, check_keys, default_mesh, mesh_size,
        )
        from jepsen_tpu.checker.wgl_native import check_events_native

        devs = jax.devices()
        require(len(devs) == n_chips,
                f"mesh: {len(devs)} devices visible, want {n_chips}")
        mesh = default_mesh()
        require(mesh is not None and mesh_size(mesh) == n_chips,
                f"mesh: default mesh {mesh} does not span {n_chips}")
        all_ids = {d.id for d in devs}
        streams = self.zk_streams()
        want = [check_events_native(s) for s in streams]
        require(None not in want, "mesh: native oracle declined")

        rng = np.random.default_rng(21)
        stacks = []
        for n in (16, 64):  # both sides of packed_word_max_n
            b = 4 * n_chips
            wrww = (rng.random((b, n, n)) < 1.5 / n).astype(np.float32)
            rw = rng.random((b, n, n)) < 1.0 / n
            allm = np.maximum(wrww, rw.astype(np.float32))
            stacks.append((wrww, allm, rw))

        results = {}
        for label, layout in (("sharded", None), ("one-device", False)):
            for temp in ("cold", "warm"):
                self._reset()
                for s in streams:
                    clear_memos(s)
                t0 = time.perf_counter()
                out = check_keys(streams, mesh=layout,
                                 interpret=self.interpret)
                graphs = [
                    tuple(np.asarray(a)[: w.shape[0]] for a in
                          jax.device_get(tg.launch_graph_batch(
                              w, a, r,
                              mesh=mesh if layout is None else None)))
                    for w, a, r in stacks
                ]
                wall = time.perf_counter() - t0
                got = [r["valid?"] for r in out]
                require(got == want,
                        f"mesh/{label}: device {got} != oracle {want}")
                audit = self._audit(
                    f"mesh/{label}/{temp}", out,
                    all_devices=all_ids if layout is None else None,
                )
                if layout is None:
                    require(MESH_STATS["sharded_launches"] >= 1,
                            f"mesh/{label}: no sharded launch")
                audit["wall_s"] = wall
                audit["sharded_launches"] = MESH_STATS["sharded_launches"]
                log(f"mesh {label} {temp}: wall={wall:.3f}s "
                    f"sharded_launches={audit['sharded_launches']} "
                    f"devices={audit['devices']} "
                    f"engines={audit['methods']}")
                self.report[f"mesh-{label}-{temp}"] = audit
                results[label] = (out, graphs)
        s_out, s_graphs = results["sharded"]
        o_out, o_graphs = results["one-device"]
        require([r["valid?"] for r in s_out] == [r["valid?"] for r in o_out],
                "mesh: sharded and one-device verdicts differ")
        for (sg, og) in zip(s_graphs, o_graphs):
            for a, b in zip(sg, og):
                require(np.array_equal(a, b),
                        "mesh: sharded and one-device graph counts "
                        "differ")
        require(any(int(a.sum()) for g in s_graphs for a in g),
                "mesh: the graph batch found no anomaly to compare")


def round_trip_ms() -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))
    walls = []
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(f(x))
        walls.append(time.perf_counter() - t0)
    walls.sort()
    return walls[len(walls) // 2] * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, over four chips")
    args = ap.parse_args(argv)

    interp = os.environ.get("JEPSEN_TPU_INTERPRET", "")
    if interp not in ("", "0"):
        log(f"FAIL: JEPSEN_TPU_INTERPRET={interp!r}: the smoke runs "
            "the compiled kernels only")
        return 2
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        log(f"FAIL: no TPU: JAX found platform {d0.platform!r} "
            f"({d0.device_kind!r}) x{len(devs)}")
        return 2
    if len(devs) < args.chips:
        log(f"FAIL: --chips {args.chips} but {len(devs)} device(s)")
        return 2
    log(f"device_kind={d0.device_kind!r} count={len(devs)} "
        f"jax={jax.__version__}")

    from jepsen_tpu.checker import wgl_native
    from jepsen_tpu.perf.autotune import enable_persistent_compile_cache

    if not (wgl_native.available() and wgl_native.prep_available()):
        log("FAIL: the native oracle / prep libraries did not build "
            "(jepsen_tpu/resources/*.cc)")
        return 2
    log(f"compile cache: {enable_persistent_compile_cache()}")
    log(f"host<->device round trip: {round_trip_ms():.4f} ms")

    smoke = Smoke()
    if args.chips == 4:
        smoke.mesh(4)
    else:
        smoke.northstar()
        smoke.zookeeper()
        smoke.transactions()
        smoke.daemon()
    stats = d0.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump({"device_kind": d0.device_kind, "jax": jax.__version__,
                   "phases": smoke.report,
                   "peak_bytes_in_use": stats.get("peak_bytes_in_use")},
                  f, indent=1, sort_keys=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

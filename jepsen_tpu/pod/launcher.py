"""Localhost pod launcher: a real N-process mesh for tier-1.

The conftest ``JEPSEN_TPU_HOST_DEVICES`` seam fakes N chips inside one
process; this is the same trick one level up — N *processes*, each
with its own XLA client and host-local CPU devices, joined through a
TCP coordinator on 127.0.0.1 into one global mesh. Tests (and
``__graft_entry__.dryrun_multichip`` in pod mode, and bench's backend
matrix ``--pod`` row) use it to pin cross-host behavior — host-local
placement, the one-allgather collect, host-death fault domains —
without ever needing a second machine.

Children run ``python -c`` with a prelude that calls
``topology.init_pod()`` from the env seam, so the supplied script body
starts INSIDE the initialized pod. The child env deliberately
overrides inherited ``XLA_FLAGS`` (the parent pytest process pins
``--xla_force_host_platform_device_count=8``; a pod child wants its
own local count) and pins ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from jepsen_tpu.obs import podtrace
from jepsen_tpu.pod import topology

#: prepended to every child script: join the pod before user code.
PRELUDE = "import jepsen_tpu.pod.topology as _pod_t; _pod_t.init_pod()\n"


@dataclass
class PodProc:
    """One finished pod member."""

    process_id: int
    returncode: Optional[int]
    stdout: str
    stderr: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0


def free_port() -> int:
    """An OS-assigned free TCP port for the coordinator. The tiny
    bind-release race is acceptable: the coordinator binds within
    milliseconds and tier-1 runs serially."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def pod_env(
    coordinator: str,
    n_procs: int,
    process_id: int,
    n_local_devices: int,
    base_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The env one pod child needs: the JEPSEN_TPU_POD_* seam, a CPU
    backend with exactly ``n_local_devices`` virtual chips, and the
    repo importable."""
    env = dict(os.environ if base_env is None else base_env)
    env[topology.ENV_COORDINATOR] = coordinator
    env[topology.ENV_NPROCS] = str(n_procs)
    env[topology.ENV_PROCESS_ID] = str(process_id)
    env["JAX_PLATFORMS"] = "cpu"
    # override, don't append: the parent test process already carries
    # a conflicting device-count flag from conftest.
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(n_local_devices)}"
    )
    env["PYTHONPATH"] = (
        _repo_root() + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    # Persistent compile cache shared across pod spawns AND the
    # single-process entry points (cli analyze/daemon, bench — they
    # call perf.autotune.enable_persistent_compile_cache, the same
    # path): tier-1 launches several short-lived pods, and without
    # this every member re-pays the full XLA compile of the same
    # shard_map programs.
    from jepsen_tpu.perf.autotune import compile_cache_dir

    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    return env


def member_env(
    n_local_devices: int = 4,
    base_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The env one FLEET member needs: a CPU backend with its own
    virtual chips, the repo importable, and the shared compile cache —
    ``pod_env`` minus the pod-coordinator seam. Fleet members are
    independent planes (each owns its own mesh over its own process's
    devices); the pod seam would make every member block in
    ``init_pod`` waiting for a collective peer it must not have."""
    env = dict(os.environ if base_env is None else base_env)
    # a fleet member must NOT inherit a pod identity from a pod-member
    # parent: scrub the seam so topology sees a solo process
    for k in (
        topology.ENV_COORDINATOR,
        topology.ENV_NPROCS,
        topology.ENV_PROCESS_ID,
    ):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(n_local_devices)}"
    )
    env["PYTHONPATH"] = (
        _repo_root() + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    from jepsen_tpu.perf.autotune import compile_cache_dir

    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    return env


def spawn_fleet_member(
    member_id: int,
    fleet_dir: str,
    root: str,
    *,
    n_local_devices: int = 4,
    interpret: bool = True,
    epoch: int = 0,
    python: Optional[str] = None,
    extra_args: Optional[List[str]] = None,
    extra_env: Optional[Dict[str, str]] = None,
    log_path: Optional[str] = None,
) -> subprocess.Popen:
    """Spawn ONE checker-daemon fleet member as a subprocess on an
    ephemeral port. The member announces its bound URL into
    ``fleet_dir`` itself (service/membership.py), so the parent
    discovers it through the registry rather than picking ports —
    poll ``wait_fleet`` for readiness. The caller owns the process
    (terminate/kill/wait); SIGKILL-ing one is the fleet durability
    drill, and the front door declares the death on first contact.

    ``epoch`` is the supervision fence (service/supervisor.py): a
    respawned member announces ``epoch = prior + 1`` so any
    resurrected earlier incarnation fences itself instead of
    double-owning handed-off checks."""
    env = member_env(n_local_devices)
    if interpret:
        env["JEPSEN_TPU_INTERPRET"] = "1"
    if extra_env:
        env.update(extra_env)
    cmd = [
        python or sys.executable, "-m", "jepsen_tpu.cli", "daemon",
        "--store", root, "--port", "0",
        "--fleet-dir", fleet_dir, "--member-id", str(member_id),
    ]
    if epoch:
        cmd += ["--member-epoch", str(int(epoch))]
    cmd += list(extra_args or [])
    logf = open(log_path, "ab") if log_path else subprocess.DEVNULL
    try:
        return subprocess.Popen(
            cmd, env=env, stdout=logf, stderr=logf,
            cwd=_repo_root(),
        )
    finally:
        if log_path:
            logf.close()


def wait_fleet(
    fleet_dir: str, n_members: int, timeout_s: float = 90.0
) -> list:
    """Block until ``n_members`` members are announced + alive in
    ``fleet_dir`` (or raise TimeoutError). Returns their MemberInfo
    rows. First-launch members pay JAX import + first compile before
    they bind, so the default budget is generous; warm spawns clear
    it in a couple of seconds."""
    from jepsen_tpu.service.membership import FleetRegistry

    reg = FleetRegistry(fleet_dir)
    deadline = time.monotonic() + timeout_s
    while True:
        alive = reg.alive_members()
        if len(alive) >= n_members:
            return alive
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"fleet incomplete: {len(alive)}/{n_members} members "
                f"alive in {fleet_dir} after {timeout_s:.0f}s"
            )
        time.sleep(0.1)


def launch_pod(
    n_procs: int,
    script: str,
    *,
    n_local_devices: int = 4,
    timeout_s: float = 240.0,
    python: Optional[str] = None,
    extra_env: Optional[Dict[str, str]] = None,
    cwd: Optional[str] = None,
    trace_dir: Optional[str] = None,
) -> List[PodProc]:
    """Spawn an ``n_procs``-process CPU pod on localhost running
    ``script`` (a Python source string) in every member, and wait for
    all of them. Pod collectives are barriers: one hung member wedges
    the rest, so blowing ``timeout_s`` kills the WHOLE pod (survivors
    would never finish) and the dead members report returncode=None
    or the kill signal.

    ``trace_dir`` propagates the tracing env seam
    (``JEPSEN_TPU_TRACE_DIR``) to every member so each persists its
    flight-recorder ring there for ``podtrace.merge_pod_trace``."""
    coordinator = f"127.0.0.1:{free_port()}"
    procs: List[subprocess.Popen] = []
    for pid in range(n_procs):
        env = pod_env(coordinator, n_procs, pid, n_local_devices)
        if trace_dir is not None:
            env[podtrace.ENV_TRACE_DIR] = trace_dir
        if extra_env:
            env.update(extra_env)
        procs.append(
            subprocess.Popen(
                [python or sys.executable, "-c", PRELUDE + script],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                cwd=cwd,
            )
        )
    deadline = time.monotonic() + timeout_s
    out: List[Optional[PodProc]] = [None] * n_procs
    timed_out = False
    for pid, p in enumerate(procs):
        budget = deadline - time.monotonic()
        try:
            so, se = p.communicate(timeout=max(budget, 0.1))
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                if q.poll() is None:
                    q.kill()
            so, se = p.communicate()
        out[pid] = PodProc(pid, p.returncode, so or "", se or "")
    if timed_out:
        for q in procs:  # reap any member killed after its collect
            if q.poll() is None:
                q.kill()
                q.wait()
    return [p for p in out if p is not None]

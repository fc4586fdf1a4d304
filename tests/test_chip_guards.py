"""Guards that keep a CPU run from passing for a chip run.

chip_smoke.py and bench.py refuse to run their measured paths off a
TPU, the compile cache sits at one fixed path, and no child process the
bench starts asks for the chip its parent holds.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, **env),
    )


def _last_line(text):
    lines = [x for x in text.strip().splitlines() if x.strip()]
    return lines[-1] if lines else ""


def test_chip_smoke_refuses_the_cpu():
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert "no TPU" in p.stdout and "'cpu'" in p.stdout
    assert '"ok": true' not in _last_line(p.stdout)


def test_chip_smoke_refuses_interpret_mode():
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu",
             JEPSEN_TPU_INTERPRET="1")
    assert p.returncode != 0
    assert "JEPSEN_TPU_INTERPRET" in p.stdout
    assert '"ok": true' not in _last_line(p.stdout)


def test_bench_without_smoke_refuses_the_cpu():
    p = _run(["bench.py", "--allow-dirty-lint", "--no-trend",
              "--allow-trend-regression"], JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_bench_device_gate_without_smoke(monkeypatch):
    """In-process: the gate names the device and refuses anything but a
    TPU when --smoke is off; --smoke on a chosen CPU interprets."""
    import bench

    monkeypatch.setattr(bench, "SMOKE", False)
    with pytest.raises(SystemExit, match="no TPU"):
        bench._device_gate()
    monkeypatch.setattr(bench, "SMOKE", True)
    monkeypatch.setattr(bench, "INTERPRET", False)
    device = bench._device_gate()
    assert device["platform"] == "cpu" and bench.INTERPRET is True


def test_interpret_only_on_a_chosen_cpu(monkeypatch):
    from jepsen_tpu.checker.linearizable import interpret_off_chip

    assert interpret_off_chip("t") is True
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no TPU found"):
        interpret_off_chip("tune")


def test_compile_cache_dir(monkeypatch, tmp_path):
    from jepsen_tpu.perf import autotune

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert autotune.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert autotune.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO
    )
    assert ignored.returncode in (0, 128)  # 128: not a git checkout


def test_backend_matrix_children_are_cpu_only(monkeypatch):
    """The parent's own row is the TPU row; every child env it builds
    is pinned to the CPU, whatever the parent's backend is."""
    import bench

    envs = []
    row = {"backend": "tpu", "n_devices": 1, "n_hosts": 1,
           "resolved_walls_s": {}, "geomean_wall_s": 1.0}

    def fake_run(cmd, env=None, **kw):
        envs.append(env)
        out = json.dumps(dict(row, backend=env["JAX_PLATFORMS"]))
        return subprocess.CompletedProcess(cmd, 0, out + "\n", "")

    monkeypatch.setattr(bench, "_matrix_row", lambda: dict(row))
    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench.bench_backend_matrix(0)
    assert envs and all(e["JAX_PLATFORMS"] == "cpu" for e in envs)
    assert [r["backend"] for r in out["backends"]] == ["tpu", "cpu"]


def test_cli_names_its_device(tmp_path, capsys):
    """analyze prints the device on stderr and in --stats-json."""
    from jepsen_tpu.cli import main

    store = str(tmp_path / "store")
    assert main(["test", "--workload", "set", "--ops", "20",
                 "--store", store, "--name", "dev", "--seed", "3"]) == 0
    capsys.readouterr()
    stats = str(tmp_path / "stats.json")
    assert main(["analyze", "dev", "--workload", "set", "--store", store,
                 "--stats-json", stats]) == 0
    assert "device: platform=cpu" in capsys.readouterr().err
    with open(stats) as f:
        assert json.load(f)["device"]["platform"] == "cpu"

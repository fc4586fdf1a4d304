"""The harness finds everything by the names in BENCHMARK.json, and
refuses to measure anywhere but on a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest
import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_its_files():
    for cell in SPEC["workloads"]:
        spec, c, cfg, traffic = run.resolve(cell["name"])
        assert os.path.exists(os.path.join(run.BENCH, "modes", traffic["mode"] + ".py"))
        assert cfg["name"] == c["config"]
        assert set(cfg["reduced"]) == set(
            {x["name"]: x for x in SPEC["configs"]}[c["config"]]["reduced"])
        ends = run.for_cell(SPEC["end_to_end"], c["name"])
        assert "setup_s" in {m["name"] for m in ends} and len(ends) >= 2
        assert run.for_cell(SPEC["per_layer"], c["name"])


def test_every_per_layer_metric_has_a_reader():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in run.for_cell(SPEC["end_to_end"], w)}


def test_names_and_files():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith(SPEC["paths"][0] + "/") for f in files)


def test_refuses_off_tpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU: JAX found platform 'cpu'" in p.stderr


def test_unknown_cell_is_refused():
    with pytest.raises(run.Refused):
        run.resolve("no-such-cell")

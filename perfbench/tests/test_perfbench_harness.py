"""The harness itself: it refuses a host without a TPU before any work, and
BENCHMARK.json names only files and readers that exist."""
import json
import os
import re
import subprocess
import sys

import pytest

from perfbench_testkit import ROOT
from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_refuses_a_host_without_tpu(capsys):
    from perfbench import run
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "gfm_pretrain", "--seed", str(2 ** 33),
                  "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_exits_non_zero_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and perfbench/ has no system
    under test: the run fails and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "gfm_pretrain", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        files = harness.cell_files(bench, w["name"])
        assert files["traffic"]["runner"] in ("train", "serve")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, files["config"]["reference"]))
        harness.sources_spec(files["traffic"])
        for flag in (False, True):
            assert harness.metrics_for(bench, w["name"], flag)


def test_every_metric_has_a_reader_and_a_sound_entry(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], set()).add(m["name"])
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_configs_are_files_under_paths(bench):
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert not c["reduced"]
    assert len(json.dumps(bench)) < 64 * 1024

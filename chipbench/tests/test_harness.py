"""The harness end to end on the CPU, at small sizes."""

import os
import shutil
import subprocess
import sys

import pytest

from smallcells import ROOT, run_small, small_cell

SEED = 2**31 + 12345


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "randwire274-exec",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "randwire274-exec",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["randwire274-exec"])
@pytest.mark.parametrize("trace", [False, True])
def test_small_run_is_correct_and_reports(name, trace):
    cell = small_cell(name)
    res = run_small(cell, SEED, trace=trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-3] == "checks"      # the last key printed
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in cell.metrics(kind)}
    got = set(res["metrics"])
    assert got <= want
    # on the CPU there is no device memory reading and no device plane
    if not trace:
        assert got == want - {"peak_hbm_bytes"}
    assert res["device"]["count"] >= 1

"""Dry-run variant smoke tests (subprocess, reduced device count):
eigen-compressed train step and the paper-PCA workload must lower+compile
on both mesh topologies."""

import os
import subprocess
import sys

import pytest

from conftest import SRC


def _run_dryrun(args, tmp_path, devices=8):
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = str(devices)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args, "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


@pytest.mark.slow
def test_dryrun_eigen_variant(tmp_path):
    out = _run_dryrun(
        ["--arch", "whisper-tiny", "--shape", "train_4k", "--eigen",
         "--single-pod"],
        tmp_path,
    )
    assert "OK chips=8" in out


@pytest.mark.slow
def test_dryrun_paper_pca_both_meshes(tmp_path):
    out = _run_dryrun(["--paper-pca", "--single-pod"], tmp_path)
    assert "OK chips=8" in out
    out = _run_dryrun(["--paper-pca", "--multi-pod"], tmp_path)
    assert "OK chips=8" in out


@pytest.mark.slow
def test_dryrun_overrides_and_mesh_shape(tmp_path):
    out = _run_dryrun(
        ["--arch", "mamba2-370m", "--shape", "decode_32k", "--single-pod",
         "--set", "moe_impl=sort", "--mesh-shape", "4,2", "--tag", "t"],
        tmp_path,
    )
    assert "OK chips=8" in out

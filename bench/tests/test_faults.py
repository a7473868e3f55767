"""A whole run with the timed path broken underneath has to come out not
correct: once for each fault a cell can have."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import repro.core.distributed as distributed
import repro.stream.service as service

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pca_half_the_rows(real):
    def job(samples, mesh, r, **kw):
        return real(samples[: samples.shape[0] // 2], mesh, r, **kw)
    return job


def _pca_answer_altered(real):
    def job(samples, mesh, r, **kw):
        v = real(samples, mesh, r, **kw)
        return v.at[:, -1].set(jnp.roll(v[:, -1], 1))
    return job


def _pca_stale_answer(real):
    """The state a job returns is the previous job's, unchanged."""
    last = {}

    def job(samples, mesh, r, **kw):
        v = last.get("v")
        last["v"] = real(samples, mesh, r, **kw)
        return last["v"] if v is None else v
    return job


@pytest.mark.parametrize("fault", [_pca_half_the_rows, _pca_answer_altered,
                                   _pca_stale_answer])
def test_pca_faults(run_tiny, monkeypatch, fault):
    monkeypatch.setattr(distributed, "distributed_pca",
                        fault(distributed.distributed_pca))
    out = run_tiny("pca_1chip")
    assert not out["correct"], out["checks"]


def _update_unchanged(real):
    """After the first chunk, every update returns the state unchanged."""
    calls = []

    def update(state, batches, alive):
        calls.append(1)
        return real(state, batches, alive) if len(calls) == 1 else state
    return update


def _update_half_the_batch(real):
    def update(state, batches, alive):
        return real(state, batches[:, : batches.shape[1] // 2], alive)
    return update


@pytest.mark.parametrize("fault", [_update_unchanged, _update_half_the_batch])
def test_service_ingest_faults(run_tiny, monkeypatch, fault):
    monkeypatch.setattr(service, "_update_all", fault(service._update_all))
    out = run_tiny("serve_refresh")
    assert not out["correct"], out["checks"]


def test_service_answer_altered(run_tiny, monkeypatch):
    real = service._project_jit
    monkeypatch.setattr(service, "_project_jit",
                        jax.jit(lambda q, v: real(q, v) * (1 + 1e-3)))
    out = run_tiny("serve_refresh")
    assert not out["correct"], out["checks"]


FOUR_CHIPS = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import repro.core.distributed as distributed
from bench import harness
from bench.tests.conftest import TINY

def local_only(real):
    # The exchange left out: each shard keeps its own basis.
    def job(samples, mesh, r, **kw):
        from repro.launch.mesh import make_mesh
        from repro.comm import DATA_AXIS
        one = make_mesh((1,), (DATA_AXIS,), devices=mesh.devices.flat[:1])
        x = samples.addressable_shards[0].data
        return real(x, one, r, **kw)
    return job

run = lambda: harness.run_cell("pca_4chip", 2**31 + 3, 1.5, False,
                               require_tpu=False, overrides=TINY["pca_4chip"])
sound = run()["correct"]
distributed.distributed_pca = local_only(distributed.distributed_pca)
faulty = run()["correct"]
print("RESULT", sound, faulty)
"""


def test_pca_exchange_left_out_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(root=ROOT, src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESULT True False" in proc.stdout, proc.stdout[-2000:]

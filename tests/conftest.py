"""Shared test utilities.

NOTE: we deliberately do NOT set ``--xla_force_host_platform_device_count``
here — unit/smoke tests must see the real single CPU device.  Tests that need
a multi-device mesh spawn a subprocess via ``run_with_devices``.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a python snippet in a subprocess with N fake CPU devices (the
    CPU platform is pinned, so the child never touches an accelerator).

    Returns captured stdout; raises on non-zero exit (with stderr attached).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count", "--ignored", 1
        )
    )
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; never the chip
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout


def subspace_dist64(a, b) -> float:
    """sin of the largest principal angle between the column spans of a and
    b, in f64 (below the f32 ``dist_2`` floor).  Re-exported for the
    parity/acceptance suites; lives in ``repro.core.metrics``."""
    from repro.core.metrics import subspace_dist64 as _sd

    return _sd(a, b)


def jaxpr_primitives(closed_jaxpr) -> list:
    """All primitive names in a jaxpr, recursing into sub-jaxprs (pjit
    bodies, control flow, pallas_call kernels)."""
    names = []

    def walk(jxp):
        for eqn in jxp.eqns:
            names.append(eqn.primitive.name)
            for p in eqn.params.values():
                vals = p if isinstance(p, (list, tuple)) else [p]
                for v in vals:
                    if hasattr(v, "eqns"):
                        walk(v)
                    elif hasattr(v, "jaxpr"):
                        walk(v.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return names


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.PRNGKey(0)

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _no_compile_cache_in_the_checkout(monkeypatch, tmp_path):
    """The harness keeps JAX's compile cache where the environment says;
    tests point it away from the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


# Each cell at a size a CPU test run holds: the same paths, narrower.
TINY = {
    "pca_1chip": {"config": {"d": 256, "r": 8, "rows_per_shard": 2048}},
    "pca_4chip": {"config": {"d": 256, "r": 8, "rows_per_shard": 2048}},
    "serve_refresh": {
        "config": {"d": 256, "r": 8, "rows_per_chunk": 512, "query_batch": 32},
        "traffic": {"chunk_rate_per_s": 20, "query_rate_per_s": 50},
    },
}


@pytest.fixture
def run_tiny():
    """Drives a whole run of a cell at its small size, past the harness's
    look for a chip; returns the result object."""
    from bench import harness

    def run(cell, system="program", seed=2**31 + 11, seconds=1.5):
        return harness.run_cell(cell, seed, seconds, False, require_tpu=False,
                                system=system, overrides=TINY[cell])

    return run

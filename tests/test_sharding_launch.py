"""Sharding rules, HLO collective parser, and dry-run smoke (subprocess)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_with_devices

from repro.launch.hlo_analysis import (
    COLLECTIVE_OPS,
    collective_bytes,
    model_flops,
    roofline,
)
from repro.launch.mesh import data_axes, make_mesh
from repro.launch.sharding import batch_spec, rules_for, spec_for_axes
from repro.configs import get_config


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_divisible_shards():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = {"vocab": "model", "embed": "data", "mlp": "model"}
    spec = spec_for_axes((49408, 2048), ("vocab", "embed"), mesh, rules)
    assert spec == P("model", "data")


def test_spec_indivisible_replicates():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = {"heads": "model"}
    # llama3.2: 24 heads % 16 != 0 -> replicated
    spec = spec_for_axes((3072, 24, 128), ("embed", "heads", "head_dim"), mesh, rules)
    assert spec == P()


def test_spec_axis_used_once():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = {"experts": "model", "mlp": "model", "embed": "data"}
    # experts takes 'model' first; mlp must not double-use it
    spec = spec_for_axes(
        (384, 7168, 2048), ("experts", "embed", "mlp"), mesh, rules
    )
    assert spec == P("model", "data")


def test_rules_drop_fsdp_when_disabled():
    import dataclasses

    cfg = dataclasses.replace(get_config("whisper-tiny"))
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert "embed" not in rules_for(cfg, mesh)  # whisper: fsdp=False
    cfg2 = get_config("granite-3-2b")
    assert rules_for(cfg2, mesh)["embed"] == "data"


def test_batch_spec_divisibility():
    mesh = _FakeMesh({"data": 16, "model": 16})
    assert batch_spec(mesh, 2, leading_dim=256) == P("data", None)
    assert batch_spec(mesh, 2, leading_dim=1) == P(None, None)


# ------------------------------------------------------------- HLO parser --
HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[1024,512]{1,0} parameter(0)
  %ar = f32[1024,512]{1,0} all-reduce(f32[1024,512]{1,0} %p0), replica_groups={}
  %ag.1 = bf16[2048,64]{1,0} all-gather(bf16[1024,64]{1,0} %x), dimensions={0}
  %rs = f32[64,512]{1,0} reduce-scatter(f32[1024,512]{1,0} %y), dimensions={0}
  %cp = u32[16]{0} collective-permute(u32[16]{0} %z), source_target_pairs={}
  %dot = f32[64,64]{1,0} dot(f32[64,128]{1,0} %a, f32[128,64]{1,0} %b)
}
"""


def test_collective_bytes_parser():
    got = collective_bytes(HLO_SAMPLE)
    assert got["all-reduce"] == 1024 * 512 * 4
    assert got["all-gather"] == 1024 * 64 * 2
    assert got["reduce-scatter"] == 1024 * 512 * 4
    assert got["collective-permute"] == 16 * 4
    assert got["all-to-all"] == 0


def test_collective_bytes_real_module():
    """Parse a real partitioned module: psum over 4 devices."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import collective_bytes
        mesh = make_mesh((4,), ("d",))
        def f(x):
            return jax.lax.psum(x, "d")
        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P()))
        c = fn.lower(jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile()
        cb = collective_bytes(c.as_text())
        print("AR", cb["all-reduce"])
        """,
        n_devices=4,
    )
    ar = int(out.strip().splitlines()[-1].split()[1])
    # per-device operand is (16,128) f32 = 8192 bytes
    assert ar == 16 * 128 * 4


def test_roofline_terms():
    cost = {"flops": 197e12, "bytes accessed": 819e9}
    t = roofline(cost, HLO_SAMPLE, chips=256)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert t.bottleneck in ("compute", "memory", "collective")


def test_model_flops():
    assert model_flops(1e9, 1e6, "train") == 6e15
    assert model_flops(1e9, 1e6, "prefill") == 2e15


# ------------------------------------------------------------ dryrun smoke --
@pytest.mark.slow
def test_dryrun_smoke_subprocess(tmp_path):
    """Reduced-device dry-run of one small cell, single + multi pod."""
    import os
    import subprocess
    import sys

    from conftest import SRC

    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for flag in ("--single-pod", "--multi-pod"):
        proc = subprocess.run(
            [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", "whisper-tiny", "--shape", "decode_32k",
                flag, "--out", str(tmp_path),
            ],
            env=env, capture_output=True, text=True, timeout=900,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK chips=8" in proc.stdout


@pytest.mark.slow
def test_dryrun_mesh_function_has_no_side_effects():
    """Importing mesh.py must not initialise jax devices."""
    out = run_with_devices(
        """
        import sys
        import repro.launch.mesh  # must not touch jax backends
        import jax
        assert "jax" in sys.modules
        # backend still uninitialised until first device query
        print("OK", len(jax.devices()))
        """,
        n_devices=2,
    )
    assert "OK 2" in out

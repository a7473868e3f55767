"""Compile the Pallas kernels for a described TPU v5e at the widths the
main path runs them (``paper_pca``: d=8192, r=128; 65,536 rows per shard).

Nothing runs: each test lowers a kernel with ``interpret=False`` on
``ShapeDtypeStruct``s placed on a described chip and compiles it with the
TPU compiler installed here, which refuses what the chip would refuse
(unaligned slices, more VMEM than a kernel may use).  The topology is
described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library, so the file's tests must stay
in this one file and in the process that runs them.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import covariance, procrustes_align

D, R, M, N = 8192, 128, 8, 65536
RING_M, RING_R = 4, 64
# ukb_genotype_pca: 18,510 participants of one site, 147,604 markers, 40 PCs.
GENO_N, GENO_D, GENO_R = 18510, 147604, 40


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compiles_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_gram_compiles(shape):
    _compiles_kernel(
        lambda x: covariance.gram(x, interpret=False), shape((N, D))
    )


def test_batched_gram_polar_compiles(shape):
    _compiles_kernel(
        lambda v, ref: procrustes_align.batched_gram_polar(
            v, ref, interpret=False),
        shape((M, D, R)), shape((D, R)),
    )


def test_align_average_compiles(shape):
    _compiles_kernel(
        lambda v, z: procrustes_align.align_average(v, z, interpret=False),
        shape((M, D, R)), shape((M, R, R)),
    )


def test_fused_round_compiles(shape):
    _compiles_kernel(
        lambda v, ref: procrustes_align.fused_round(
            v, ref, n_iter=2, interpret=False),
        shape((M, D, R)), shape((D, R)),
    )


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_fused_ring_round_compiles(shape, wire):
    """The fused ring at r=64: a lane dim narrower than the 128-wide tile,
    which the kernel only ever indexes on leading dims."""
    if wire == "f32":
        args = (shape((RING_M, D, RING_R)), shape((D, RING_R)))
    else:
        args = (shape((RING_M, D, RING_R), jnp.int8), shape((D, RING_R)),
                shape((RING_M, RING_R)))
    _compiles_kernel(
        lambda *a: procrustes_align.fused_ring_round(
            *a, ring_chunk=2048, interpret=False),
        *args,
    )


def test_fused_ring_collective_compiles_on_four_chips(topo, monkeypatch):
    """The pinned (pallas, ring, newton-schulz, cholesky-qr2) cell as the
    four-chip job compiles it: staged wire gather plus one kernel launch
    per round, sharded over a (4,) mesh of the described chips."""
    from repro.core.distributed import procrustes_average_collective
    from repro.kernels import ops

    # This process's backend is the CPU, where the kernels would run
    # interpreted; compile them for the described chip instead.
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))

    def shard(v):
        return procrustes_average_collective(
            v[0], axis_name="data", n_iter=2, topology="ring",
            backend="pallas", polar="newton-schulz", orth="cholesky-qr2",
            ring_chunk=2048,
        )[None]

    fn = jax.shard_map(shard, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    vs = jax.ShapeDtypeStruct((RING_M, D, RING_R), jnp.float32,
                              sharding=NamedSharding(mesh, P("data")))
    _compiles_kernel(fn, vs)


def test_genotype_pca_compiles_at_uk_biobank_widths(topo, monkeypatch):
    """``distributed_pca`` on one site's int8 genotypes as the one-chip
    ``gpca_ukb_site`` cell runs it (backend ``auto`` is the kernels on the
    chip): the plan compiles at d=147,604, r=40, and the row form's
    program holds no (d, d) array and no floating (n, d) one."""
    import repro.core.distributed as distributed
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    # A described chip has no runtime to report its memory: a v5e's 16 GiB.
    monkeypatch.setattr(distributed, "_device_bytes", lambda dev: 16 * 2**30)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    counts = jax.ShapeDtypeStruct((GENO_N, GENO_D), jnp.int8,
                                  sharding=NamedSharding(mesh, P("data")))
    text = jax.jit(lambda c: distributed.distributed_pca(
        c, mesh, GENO_R, standardize="patterson", solver="subspace",
        iters=30, n_iter=2, backend="pallas")).lower(counts).compile(
    ).as_text()
    assert "tpu_custom_call" in text
    assert f"s8[{GENO_N},{GENO_D}]" in text
    for dtype in ("f32", "bf16", "f16"):
        for dims in ((GENO_D, GENO_D), (GENO_N, GENO_D), (GENO_D, GENO_N)):
            assert f"{dtype}[{dims[0]},{dims[1]}]" not in text

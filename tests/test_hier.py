"""Hierarchical two-level aggregation (topology="hier") over (pod, local).

Covers, fast lane:

1. The two-level cost model (``comm_cost("hier", ..., pods=)``): the
   per-level split, the local==1 / pods==1 degenerate gates, membership
   pricing (dead-shard-in-pod is free, fully-dead-pod shrinks the ring
   and adds the resync broadcast), validation errors, and the headline
   inter-pod reduction at the paper shape (<= 0.45x the flat ring).
2. ``pod_membership``: the pod-major liveness fold and its validation.
3. The (1, 1) degenerate mesh: ``hier`` with one pod and one local slot
   is exactly the serial refinement.
4. The dtype contract of the collective arms: a bf16 basis stays bf16
   through every (topology x comm_bits) cell — the wire codec's f32
   internals must not leak into the output dtype.
5. Driver/launch validation: pod_axis and topology="hier" go together;
   ``make_aggregation_mesh`` tiling errors; ``resolve_plan`` hier errors.

Slow lane (8 fake devices in a subprocess):

6. The parity cube: (mesh-shape x backend x comm_bits) plus degraded
   memberships vs the serial oracle restricted to the survivors, within
   ``PARITY_TOL[bits]`` — m=8 run both as 4 pods x 2 and 2 pods x 4.
7. HLO byte-exactness per level: the compiled collective bytes equal
   ``comm_cost("hier", ...)`` and the collective-permute bytes equal the
   inter level's prediction alone, full and degraded.
"""

from __future__ import annotations

import pytest

from conftest import run_with_devices

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.comm import (  # noqa: E402
    PARITY_TOL,
    Membership,
    comm_cost,
    pod_membership,
)
from repro.comm.quantize import message_bits  # noqa: E402


# ------------------------------------------------------------ cost model --


def test_comm_cost_hier_two_level_split():
    """Full membership, m=8 as 4x2: intra = (bcast + n rounds) exact f32
    over the local axis; inter = one wire-precision bcast stage plus
    n*(p-1) hop messages; levels sum to the flat hlo_bits breakdown."""
    m, d, r, p, n = 8, 512, 16, 4, 2
    basis = d * r
    for cb in (32, 16, 8):
        cost = comm_cost("hier", m=m, d=d, r=r, n_iter=n, comm_bits=cb,
                         pods=p)
        msg = message_bits(d, r, cb)
        intra = (basis + n * basis) * 32
        inter_ar = msg  # the pod-level reference broadcast stage
        hops = n * (p - 1) * msg
        assert cost.levels["intra"] == {"all-reduce": intra}
        assert cost.levels["inter"] == {
            "all-reduce": inter_ar, "collective-permute": hops
        }
        assert cost.hlo_bits == {
            "all-reduce": intra + inter_ar, "collective-permute": hops
        }
        assert cost.bits == intra + inter_ar + hops
        # Logical words are precision-independent: two bcast stages, one
        # intra psum + (p-1) hops per round.
        assert cost.words == 2 * basis + n * (basis + (p - 1) * basis)
        assert cost.level_bytes["inter"]["collective-permute"] == hops // 8
        if cb == 32:
            assert cost.bits == cost.words * 32


def test_comm_cost_hier_degenerate_gates():
    """pods == m (local=1) skips the intra level entirely; pods == 1
    (no inter-pod link) is communication-equivalent to flat psum."""
    m, d, r, n = 8, 256, 8, 2
    basis = d * r
    solo_local = comm_cost("hier", m=m, d=d, r=r, n_iter=n, pods=m)
    assert solo_local.levels["intra"] == {"all-reduce": 0}
    assert solo_local.levels["inter"]["collective-permute"] == \
        n * (m - 1) * basis * 32
    solo_pod = comm_cost("hier", m=m, d=d, r=r, n_iter=n, pods=1)
    assert solo_pod.levels["inter"] == {
        "all-reduce": 0, "collective-permute": 0
    }
    psum = comm_cost("psum", m=m, d=d, r=r, n_iter=n)
    assert solo_pod.words == psum.words
    assert solo_pod.bits == psum.bits


def test_comm_cost_hier_membership_per_level():
    """A dead shard inside a live pod costs nothing extra (the masked
    intra psum absorbs it); a fully dead pod shrinks the ring to p'-1
    hops and adds the exact f32 resync broadcast."""
    m, d, r, p, n = 8, 512, 16, 4, 2
    basis = d * r
    full = comm_cost("hier", m=m, d=d, r=r, n_iter=n, pods=p)
    dead_in_pod = comm_cost(
        "hier", m=m, d=d, r=r, n_iter=n, pods=p,
        membership=Membership.from_dead(m, (3,)),
    )
    assert dead_in_pod == full
    dead_pod = comm_cost(
        "hier", m=m, d=d, r=r, n_iter=n, pods=p,
        membership=Membership.from_dead(m, (2, 3)),
    )
    msg = basis * 32
    assert dead_pod.levels["inter"]["collective-permute"] == n * 2 * msg
    assert dead_pod.levels["inter"]["all-reduce"] == msg + basis * 32
    assert dead_pod.levels["intra"] == full.levels["intra"]


def test_comm_cost_hier_validation():
    with pytest.raises(ValueError, match="needs pods"):
        comm_cost("hier", m=8, d=64, r=4)
    for bad in (0, 3, -1):
        with pytest.raises(ValueError, match="tile"):
            comm_cost("hier", m=8, d=64, r=4, pods=bad)


def test_comm_cost_hier_interpod_ratio_paper_shape():
    """The acceptance shape: m=8 as 4 pods x 2 at (d=4096, r=16) — the
    slow link carries <= 0.45x the flat ring's hop bits per round."""
    kw = dict(m=8, d=4096, r=16, n_iter=1)
    hier = comm_cost("hier", pods=4, **kw)
    ring = comm_cost("ring", **kw)
    ratio = (
        hier.levels["inter"]["collective-permute"]
        / ring.hlo_bits["collective-permute"]
    )
    assert ratio <= 0.45, ratio
    assert ratio == pytest.approx(3 / 7)


def test_pod_membership_fold():
    full = Membership.full(8)
    assert pod_membership(full, 4) == Membership.full(4)
    assert pod_membership(Membership.from_dead(8, (3,)), 4) == \
        Membership.full(4)
    assert pod_membership(Membership.from_dead(8, (2, 3)), 4) == \
        Membership.from_dead(4, (1,))
    assert pod_membership(full, 1) == Membership.full(1)
    assert pod_membership(full, 8) == full
    with pytest.raises(ValueError, match="pods must be"):
        pod_membership(full, 0)
    with pytest.raises(ValueError, match="tile"):
        pod_membership(full, 3)


# ----------------------------------------------------- single-device fast --


def _qr_stack(m, d, r, seed=0):
    u = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(seed), (d, r)))[0]
    noise = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (m, d, r))
    return jnp.linalg.qr(u[None] + noise)[0]


def test_hier_degenerate_mesh_matches_serial():
    """On the (1, 1) mesh the hier schedule is communication-free and
    must equal the serial refinement of the single basis."""
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    from repro.core import refinement_rounds
    from repro.core.distributed import procrustes_average_collective
    from repro.core.metrics import subspace_dist64

    vs = _qr_stack(1, 48, 4)
    mesh = make_mesh((1, 1), ("pod", "data"))
    fn = jax.jit(shard_map(
        lambda v: procrustes_average_collective(
            v[0], axis_name="data", pod_axis="pod", n_iter=2,
            topology="hier")[None],
        mesh=mesh, in_specs=P(("pod", "data"), None, None),
        out_specs=P(("pod", "data"), None, None), check_vma=False,
    ))
    ser = refinement_rounds(vs, n_iter=2)
    assert float(subspace_dist64(ser, fn(vs)[0])) <= PARITY_TOL[32]


def test_hier_pod_axis_consistency_errors():
    """topology="hier" and pod_axis= go together, both ways."""
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import procrustes_average_collective

    vs = _qr_stack(1, 32, 4)
    mesh = make_mesh((1, 1), ("pod", "data"))

    def call(**kw):
        fn = shard_map(
            lambda v: procrustes_average_collective(
                v[0], axis_name="data", n_iter=1, **kw)[None],
            mesh=mesh, in_specs=P(("pod", "data"), None, None),
            out_specs=P(("pod", "data"), None, None), check_vma=False,
        )
        fn(vs)

    with pytest.raises(ValueError, match="pod_axis"):
        call(topology="hier")  # hier without the pod axis
    with pytest.raises(ValueError, match="pod_axis"):
        call(topology="psum", pod_axis="pod")  # pod axis without hier


def test_collective_dtype_preserved_at_lossy_tiers():
    """Satellite: a bf16 basis stays bf16 through every flat (topology x
    comm_bits) arm — the wire codec's f32 staging (decode buffers, the
    psum reference broadcast) must cast back to the payload dtype.
    Matmul-only compute knobs so CPU LAPACK never sees bf16."""
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    from repro.core.distributed import procrustes_average_collective

    vs = _qr_stack(1, 64, 4).astype(jnp.bfloat16)
    mesh = make_mesh((1,), ("data",))
    for topo in ("psum", "gather", "ring"):
        for cb in (32, 16, 8):
            fn = jax.jit(shard_map(
                lambda v, t=topo, b=cb: procrustes_average_collective(
                    v[0], axis_name="data", n_iter=2, topology=t,
                    comm_bits=b, polar="newton-schulz",
                    orth="cholesky-qr2")[None],
                mesh=mesh, in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            ))
            assert fn(vs).dtype == jnp.bfloat16, (topo, cb)


# ------------------------------------------------------------- launch/plan --


def test_make_aggregation_mesh_validation():
    from repro.launch.mesh import make_aggregation_mesh

    with pytest.raises(ValueError, match="tile"):
        make_aggregation_mesh(8, pods=3)
    with pytest.raises(ValueError, match="tile"):
        make_aggregation_mesh(8, pods=0)


def test_eigen_run_flag_coupling():
    from repro.launch import eigen

    with pytest.raises(ValueError, match="go together"):
        eigen.run(d=32, r=4, topology="hier")
    with pytest.raises(ValueError, match="go together"):
        eigen.run(d=32, r=4, pods=4)
    with pytest.raises(ValueError, match="fail-at"):
        eigen.run(d=32, r=4, topology="hier", pods=4, fail_at="2:1")


def test_resolve_plan_hier_validation():
    from repro.plan import resolve_plan

    with pytest.raises(ValueError, match="pods"):
        resolve_plan(None, m=8, d=64, r=4, topology="hier")
    with pytest.raises(ValueError, match="pods"):
        resolve_plan(None, m=8, d=64, r=4, topology="hier", pods=3)
    pl = resolve_plan(None, m=8, d=64, r=4, topology="hier", pods=4)
    assert (pl.topology, pl.pods) == ("hier", 4)
    cost = comm_cost("hier", m=8, d=64, r=4, pods=4)
    assert (pl.words, pl.bits) == (cost.words, cost.bits)
    # Flat plans keep pods=0 even when planned on a multi-pod mesh.
    flat = resolve_plan(None, m=8, d=64, r=4, topology="ring", pods=4)
    assert flat.pods == 0


# ------------------------------------------------------------- slow lane --


@pytest.mark.slow
def test_hier_parity_cube_eight_devices():
    """Acceptance cube at m=8, run both as 4 pods x 2 and 2 pods x 4:
    (mesh x backend x comm_bits) full-membership cells plus the two
    degraded memberships (dead shard in a live pod; fully dead pod) all
    match the serial oracle restricted to the survivors within
    ``PARITY_TOL[bits]``, on live and dead output rows alike."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.comm import Membership
        from repro.core import refinement_rounds
        from repro.core.distributed import procrustes_average_collective
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 4
        u = jnp.linalg.qr(
            jax.random.normal(jax.random.PRNGKey(53), (d, r)))[0]
        noise = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (m, d, r))
        vs = jnp.linalg.qr(u[None] + noise)[0]

        def run(pods, backend, cb, mem=None):
            mesh = make_mesh((pods, m // pods), ("pod", "data"))
            fn = jax.jit(shard_map(
                lambda v: procrustes_average_collective(
                    v[0], axis_name="data", pod_axis="pod", n_iter=2,
                    topology="hier", backend=backend, comm_bits=cb,
                    membership=mem)[None],
                mesh=mesh, in_specs=P(("pod", "data"), None, None),
                out_specs=P(("pod", "data"), None, None),
                check_vma=False,
            ))
            return fn(vs)

        full = refinement_rounds(vs, n_iter=2)
        for pods in (4, 2):
            for backend in ("xla", "pallas"):
                for cb in (32, 16, 8):
                    got = run(pods, backend, cb)
                    dist = float(subspace_dist64(full, got[0]))
                    print("CELL", pods, backend, cb, "full", dist, dist)
        for dead in ((3,), (2, 3)):
            mem = Membership.from_dead(m, dead)
            ser = refinement_rounds(vs[jnp.asarray(mem.indices)], n_iter=2)
            got = run(4, "xla", 32, mem=mem)
            d_live = float(subspace_dist64(ser, got[0]))
            d_dead = float(subspace_dist64(ser, got[dead[-1]]))
            tag = "dead" + "".join(str(k) for k in dead)
            print("CELL", 4, "xla", 32, tag, d_live, d_dead)
        """
    )
    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 2 * 2 * 3 + 2
    for _, pods, backend, cb, mem_tag, d_live, d_dead in cells:
        tol = PARITY_TOL[int(cb)]
        assert float(d_live) <= tol, (pods, backend, cb, mem_tag, d_live)
        assert float(d_dead) <= tol, (pods, backend, cb, mem_tag, d_dead)


@pytest.mark.slow
def test_hier_hlo_bytes_per_level_eight_devices():
    """The compiled program's collective bytes equal the two-level cost
    model — and the collective-permute bytes alone equal the inter
    level's prediction (nothing intra-pod lowers to a permute) — per
    wire tier and for both degraded memberships."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.comm import Membership
        from repro.core.distributed import procrustes_average_collective
        from repro.launch.hlo_analysis import collective_bytes

        m, d, r, pods = 8, 96, 4, 4
        mesh = make_mesh((pods, m // pods), ("pod", "data"))
        vs = jax.ShapeDtypeStruct((m, d, r), jnp.float32)

        def measure(cb, mem=None):
            fn = jax.jit(shard_map(
                lambda v: procrustes_average_collective(
                    v[0], axis_name="data", pod_axis="pod", n_iter=2,
                    topology="hier", comm_bits=cb, membership=mem)[None],
                mesh=mesh, in_specs=P(("pod", "data"), None, None),
                out_specs=P(("pod", "data"), None, None),
                check_vma=False,
            ))
            hlo = collective_bytes(fn.lower(vs).compile().as_text())
            return {k: v for k, v in hlo.items() if v}

        for cb in (32, 16, 8):
            print("CELL", json.dumps(
                {"bits": cb, "dead": [], "measured": measure(cb)}))
        for dead in ([3], [2, 3]):
            mem = Membership.from_dead(m, tuple(dead))
            print("CELL", json.dumps(
                {"bits": 32, "dead": dead, "measured": measure(32, mem)}))
        """
    )
    import json

    cells = [json.loads(ln[5:]) for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 5
    m, d, r, pods = 8, 96, 4, 4
    for cell in cells:
        mem = (
            Membership.from_dead(m, tuple(cell["dead"]))
            if cell["dead"] else None
        )
        cost = comm_cost(
            "hier", m=m, d=d, r=r, n_iter=2, comm_bits=cell["bits"],
            pods=pods, membership=mem,
        )
        predicted = {k: v for k, v in cost.hlo_bytes.items() if v}
        assert cell["measured"] == predicted, cell
        assert cell["measured"].get("collective-permute", 0) == \
            cost.level_bytes["inter"]["collective-permute"], cell

"""Distributed spectral initialization for quadratic sensing (paper §3.7).

Each shard holds measurements (a_i, y_i), forms the truncated second-moment
matrix D_N (eq. 39), and the mesh combines the local top-r eigenspaces with
Algorithm 1/2 — the exact experiment of the paper's Fig. 10, as a library
function usable to initialize local-search recovery algorithms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distributed import procrustes_average_collective
from repro.core.subspace import local_eigenbasis
from repro.data.synthetic import truncated_second_moment


def distributed_spectral_init(
    a: jax.Array,
    y: jax.Array,
    r: int,
    mesh: jax.sharding.Mesh,
    *,
    data_axis: str = "data",
    n_iter: int = 10,
    solver: str = "eigh",
    iters: int = 40,
    backend: str | None = None,
    polar: str | None = None,
    orth: str | None = None,
    topology: str | None = None,
    comm_bits=None,
    plan=None,
) -> jax.Array:
    """a: (N, d) design vectors, y: (N,) measurements, sharded over the mesh.

    ``backend`` selects the compute path ("xla" | "pallas" | "auto"),
    ``polar`` the rotation method ("svd" | "newton-schulz"), ``orth``
    the per-round orthonormalization ("qr" | "cholesky-qr2"),
    ``topology`` the communication schedule ("psum" | "gather" | "ring" |
    "auto"), and ``comm_bits`` the wire precision of its payloads
    (32 | 16 | 8 | "auto"), see ``repro.core.distributed`` /
    ``repro.comm``.  ``plan=None|"auto"|Plan`` resolves all five through
    the execution planner (``repro.plan``), resolved once here at the
    driver level.
    Returns the (d, r) Procrustes-averaged spectral initialiser X_0.
    """
    from repro.plan.planner import resolve_plan

    pl = resolve_plan(
        plan, m=mesh.shape[data_axis], d=a.shape[-1], r=r, n_iter=n_iter,
        backend=backend, topology=topology, polar=polar, orth=orth,
        comm_bits=comm_bits,
    )

    def shard_fn(a_s, y_s):
        d_n = truncated_second_moment(a_s, y_s)
        v, _ = local_eigenbasis(d_n, r, method=solver, iters=iters)
        out = procrustes_average_collective(
            v, axis_name=data_axis, n_iter=n_iter, plan=pl,
        )
        return out[None]

    fn = jax.jit(
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(data_axis, None), P(data_axis)),
            out_specs=P(data_axis, None, None),
            check_vma=False,
        )
    )
    return fn(a, y)[0]

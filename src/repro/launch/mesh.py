"""Mesh construction.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module touches no jax device state — required because the dry-run must
set XLA_FLAGS before the first device query.

Every mesh is built by ``make_mesh``: ``jax.make_mesh`` with all axes
``AxisType.Auto``, so sharding stays compiler-propagated as the
collectives here expect.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.comm.topology import DATA_AXIS, MODEL_AXIS, POD_AXIS


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The target deployment mesh: one v5e pod slice (16 x 16 = 256 chips),
    or two pods (2 x 16 x 16 = 512 chips) with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (
        (POD_AXIS, DATA_AXIS, MODEL_AXIS)
        if multi_pod else (DATA_AXIS, MODEL_AXIS)
    )
    return make_mesh(shape, axes)


def make_mesh(
    shape: Tuple[int, ...], axes: Tuple[str, ...], *, devices=None
) -> jax.sharding.Mesh:
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_host_mesh(
    *, model: Optional[int] = None, data: Optional[int] = None
) -> jax.sharding.Mesh:
    """Best-effort mesh over whatever devices this host actually has
    (tests / examples): data-major factorisation of the device count."""
    n = len(jax.devices())
    if model is None:
        model = 1
        for cand in (8, 4, 2):
            if n % cand == 0 and cand <= n:
                model = cand
                break
        if n == 1:
            model = 1
    data = data or (n // model)
    assert data * model == n, (data, model, n)
    return make_mesh((data, model), (DATA_AXIS, MODEL_AXIS))


def make_aggregation_mesh(
    m: Optional[int] = None, *, pods: Optional[int] = None
) -> jax.sharding.Mesh:
    """The mesh the aggregation collectives run over.

    Flat (``pods=None``): a 1-D ``(m,)`` mesh over ``DATA_AXIS`` — every
    flat topology's shape.  Hierarchical (``pods=p``): the 2-D
    ``(p, m // p)`` mesh over ``(POD_AXIS, DATA_AXIS)`` that
    ``topology="hier"`` requires, pod-major so the flattened device order
    matches ``Membership``'s shard numbering (shard q·local + l is local
    slot l of pod q).  ``m`` defaults to every device this process sees.
    """
    m = m or len(jax.devices())
    if pods is None:
        return make_mesh((m,), (DATA_AXIS,))
    pods = int(pods)
    if pods < 1 or m % pods:
        raise ValueError(
            f"pods={pods} does not tile m={m} into equal pods"
        )
    return make_mesh((pods, m // pods), (POD_AXIS, DATA_AXIS))


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The axes carrying batch parallelism ('pod' included when present)."""
    names = mesh.axis_names
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in names)

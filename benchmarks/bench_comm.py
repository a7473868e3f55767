"""Communication-cost table + measured HLO check for the topology registry.

The analytic bits-per-round model lives in ``repro.comm`` (one home —
``repro.launch.dryrun`` consumes the same functions); this module renders
it as the paper-narrative table (Section 2.1 / Remark 2 quantified per
registered topology) and *verifies* it: ``comm_measured`` compiles the
distributed-PCA job per (topology, comm_bits) on a forced-8-device host
and asserts the HLO collective-bytes breakdown
(``repro.launch.hlo_analysis``) equals the model's prediction, byte for
byte.  CI's bench-smoke lane runs ``python -m benchmarks.bench_comm
--check --bits 32,8`` so a topology regression (a stray all-gather on the
ring path, a reintroduced axis-size all-reduce on psum) or a wire-tier
regression (an int8 hop silently upcast back to fp32) fails the build.

Known exemption: (psum, 16) is checked only on TPU — XLA's CPU
float-normalization pass upcasts the arithmetic bf16 all-reduces to f32
(see ``repro.comm.quantize.wire_psum_mean``), so off-TPU that cell is
emitted informationally and excluded from ``--check``.

The hierarchical topology gets its own lane (``hier_measured``): the
job compiles on the 2-D (4 pods x 2) mesh and the check is *per level*
— the inter-pod ring hops lower to ``collective-permute`` (nothing
intra-pod does), so the slow-link wire bytes are compared against
``comm_cost("hier", ...).level_bytes["inter"]`` directly, and at the
paper's (d=4096, r=16) shape the measured inter-pod bytes must be
<= 0.45x the flat ring's (3 pod hops vs 7 shard hops per round).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import emit

MEASURE_N_ITERS = (1, 2)  # n_iter values measured per topology


def comm_table():
    from repro.comm import (
        TOPOLOGIES,
        comm_cost,
        fan_projector_words,
        paper_coordinator_words,
    )

    for d, r, m, pods in ((1024, 32, 16, 4), (8192, 128, 256, 16)):
        words = {
            t: comm_cost(t, m=m, d=d, r=r).words
            for t in TOPOLOGIES
            if t != "hier"
        }
        hier = comm_cost("hier", m=m, d=d, r=r, pods=pods)
        ring_b = comm_cost("ring", m=m, d=d, r=r).hlo_bytes
        coordinator = paper_coordinator_words(m, d, r)
        fan = fan_projector_words(d)
        emit(
            f"comm[d={d},r={r},m={m}]",
            0.0,
            f"coordinator_words={coordinator};"
            f"psum_words={words['psum']};gather_words={words['gather']};"
            f"ring_words={words['ring']};hier_words={hier.words};"
            f"fan_projector_words={fan};"
            f"psum_reduction_vs_coordinator={coordinator / words['psum']:.0f}x;"
            f"psum_reduction_vs_fan={fan / words['psum']:.0f}x;"
            f"hier_interpod_vs_ring_hops="
            f"{ring_b['collective-permute'] / hier.level_bytes['inter']['collective-permute']:.1f}x"
            f"[pods={pods}]",
        )


def comm_measured(*, check: bool = False, bits=(32, 8)) -> bool:
    """Compile the distributed-PCA job per (topology, n_iter, comm_bits)
    on an 8-device mesh and check the HLO collective bytes equal the
    ``repro.comm.comm_cost`` prediction.  Returns True iff every checked
    cell matches; with ``check=True`` a mismatch also raises.

    The (psum, 16) cell is informational off-TPU (XLA CPU
    float-normalization upcasts the arithmetic bf16 all-reduces to f32);
    every other cell — including every int8 cell — is byte-exact.  When
    both 32 and 8 are swept, the ring's collective-permute payload at 8
    bits is additionally asserted to be ~1/4 of the fp32 payload (the
    headline wire saving: d*r*8 + 32*r scale bits vs d*r*32).

    A degraded-mesh lane rides along: per wire tier, one ring cell with
    shard 2 masked dead (``membership=Membership.from_dead(8, (2,))``) is
    compiled and checked against ``comm_cost(..., membership=mem)`` —
    m'-1 survivor hops per round plus the one exact f32 resynchronizing
    broadcast the masked ring appends (see ``repro.comm.ring``).
    """
    from repro.comm import TOPOLOGIES, Membership, comm_cost

    flat_topos = tuple(t for t in TOPOLOGIES if t != "hier")
    d, r, n, m = 512, 16, 256, 8
    bits = tuple(bits)
    code = f"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={m}"
import jax, jax.numpy as jnp
from repro.core.distributed import distributed_pca
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_mesh
mesh = make_mesh(({m},), ("data",))
d, r, n = {d}, {r}, {n}
samples = jax.ShapeDtypeStruct(({m} * n, d), jnp.float32)
for topology in {list(flat_topos)!r}:
    for n_iter in {list(MEASURE_N_ITERS)!r}:
        for cb in {list(bits)!r}:
            fn = jax.jit(lambda s, t=topology, k=n_iter, b=cb: distributed_pca(
                s, mesh, r, n_iter=k, topology=t, comm_bits=b))
            hlo = collective_bytes(fn.lower(samples).compile().as_text())
            print("CELL", json.dumps({{"topology": topology, "n_iter": n_iter,
                                       "bits": cb,
                                       "measured": {{k: v for k, v in hlo.items() if v}}}}))
from repro.comm import Membership
mem = Membership.from_dead({m}, (2,))
for cb in {list(bits)!r}:
    fn = jax.jit(lambda s, b=cb: distributed_pca(
        s, mesh, r, n_iter=2, topology="ring", comm_bits=b, membership=mem))
    hlo = collective_bytes(fn.lower(samples).compile().as_text())
    print("CELL", json.dumps({{"topology": "ring", "n_iter": 2, "bits": cb,
                               "masked": True,
                               "measured": {{k: v for k, v in hlo.items() if v}}}}))
"""
    env = dict(os.environ)
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; never the chip
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"comm_measured subprocess failed:\n{out.stderr[-4000:]}"
        )
    cells = [
        json.loads(line[5:])
        for line in out.stdout.splitlines()
        if line.startswith("CELL ")
    ]
    # Full-membership cube plus one masked-ring cell per wire tier.
    expected = len(flat_topos) * len(MEASURE_N_ITERS) * len(bits) + len(bits)
    if len(cells) != expected:
        # Fail closed: a format drift that yields zero parseable cells must
        # not report "verified".
        raise RuntimeError(
            f"comm_measured parsed {len(cells)} cells, expected {expected};"
            f"\nstdout was:\n{out.stdout[-2000:]}"
        )
    on_tpu = any(dev.platform == "tpu" for dev in _local_devices())
    ok_all = True
    ring_cp = {}  # bits -> measured collective-permute bytes (n_iter=2)
    dead_mem = Membership.from_dead(m, (2,))
    for cell in cells:
        topology, n_iter, cb = cell["topology"], cell["n_iter"], cell["bits"]
        masked = cell.get("masked", False)
        predicted = {
            k: v
            for k, v in comm_cost(
                topology, m=m, d=d, r=r, n_iter=n_iter, comm_bits=cb,
                membership=dead_mem if masked else None,
            ).hlo_bytes.items()
            if v
        }
        # The driver's final ``stacked[0]`` replicates shard 0's answer to
        # every device — one fp32 d*r all-reduce the outer jit emits
        # regardless of topology or wire tier.  A harness term, not part
        # of the schedule, so it is added here rather than in the
        # ``repro.comm`` model.
        predicted["all-reduce"] = predicted.get("all-reduce", 0) + 4 * d * r
        exempt = topology == "psum" and cb == 16 and not on_tpu
        ok = cell["measured"] == predicted
        ok_all &= ok or exempt
        if topology == "ring" and n_iter == 2 and not masked:
            ring_cp[cb] = cell["measured"].get("collective-permute", 0)
        mask_tag = ",masked=dead2" if masked else ""
        emit(
            f"comm_measured[{topology},d={d},r={r},m={m},"
            f"n_iter={n_iter},bits={cb}{mask_tag}]",
            0.0,
            f"measured={json.dumps(cell['measured'], sort_keys=True)};"
            f"predicted={json.dumps(predicted, sort_keys=True)};"
            f"match={'yes' if ok else ('exempt-off-tpu' if exempt else 'NO')}",
        )
        if check and not ok and not exempt:
            raise AssertionError(
                f"topology {topology!r} (n_iter={n_iter}, comm_bits={cb}"
                f"{', masked' if masked else ''}): "
                f"measured HLO collective bytes {cell['measured']} != "
                f"model {predicted}"
            )
    if 32 in ring_cp and 8 in ring_cp and ring_cp[32]:
        ratio = ring_cp[8] / ring_cp[32]
        emit(
            f"comm_measured[ring-int8-ratio,d={d},r={r},m={m}]",
            0.0,
            f"cp_bytes_int8={ring_cp[8]};cp_bytes_fp32={ring_cp[32]};"
            f"ratio={ratio:.4f}",
        )
        if check and not ratio <= 0.26:
            raise AssertionError(
                f"int8 ring collective-permute payload is {ratio:.3f}x the "
                f"fp32 payload; expected ~0.25 (d*r*8 + 32*r scale bits)"
            )
    return ok_all


def hier_measured(*, check: bool = False, bits=(32, 8)) -> bool:
    """Compile the distributed-PCA job with ``topology="hier"`` on the
    2-D (4 pods x 2 local) forced-8-device mesh and check the HLO
    collective bytes against the two-level ``comm_cost`` model — per
    level, not just in total: the inter-pod ring hops are the only thing
    that lowers to ``collective-permute`` (intra-pod traffic is psum
    all-reduces), so the measured permute bytes must equal
    ``level_bytes["inter"]["collective-permute"]`` exactly.  Returns
    True iff every checked cell matches; with ``check=True`` a mismatch
    also raises.

    Degraded cells ride along at fp32: one dead shard inside a live pod
    (masked intra-pod psum, full 4-pod ring) and one fully dead pod
    (3-survivor ring plus the exact resynchronizing broadcast).

    The headline gate compiles the paper-scale shape (d=4096, r=16) for
    both hier and the flat ring and asserts the hierarchical schedule's
    inter-pod wire bytes are <= 0.45x the flat ring's — 3 pod hops
    versus 7 shard hops per round, the O(m*d*r) -> O(p*d*r) reduction
    the topology exists to claim.
    """
    from repro.comm import Membership, comm_cost

    d, r, n, m, pods = 512, 16, 256, 8, 4
    big_d, big_r = 4096, 16
    bits = tuple(bits)
    code = f"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={m}"
import jax, jax.numpy as jnp
from repro.comm import Membership
from repro.core.distributed import distributed_pca
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_mesh
hier_mesh = make_mesh(({pods}, {m // pods}), ("pod", "data"))
flat_mesh = make_mesh(({m},), ("data",))
def measure(mesh, topology, n_iter, cb, mem=None, d={d}, r={r}):
    samples = jax.ShapeDtypeStruct(({m} * {n}, d), jnp.float32)
    fn = jax.jit(lambda s: distributed_pca(
        s, mesh, r, n_iter=n_iter, topology=topology, comm_bits=cb,
        membership=mem))
    return collective_bytes(fn.lower(samples).compile().as_text())
for n_iter in {list(MEASURE_N_ITERS)!r}:
    for cb in {list(bits)!r}:
        hlo = measure(hier_mesh, "hier", n_iter, cb)
        print("CELL", json.dumps({{"kind": "hier", "n_iter": n_iter,
                                   "bits": cb, "dead": [],
                                   "measured": {{k: v for k, v in hlo.items() if v}}}}))
# Degraded cells: shard 3 dead (pod 1 limps on local slot 0's data
# alone) and shards 2+3 dead (pod 1 leaves the inter-pod ring entirely).
for dead in [[3], [2, 3]]:
    hlo = measure(hier_mesh, "hier", 2, 32,
                  mem=Membership.from_dead({m}, tuple(dead)))
    print("CELL", json.dumps({{"kind": "hier", "n_iter": 2, "bits": 32,
                               "dead": dead,
                               "measured": {{k: v for k, v in hlo.items() if v}}}}))
for kind, mesh, topo in (("hier-big", hier_mesh, "hier"),
                         ("ring-big", flat_mesh, "ring")):
    hlo = measure(mesh, topo, 1, 32, d={big_d}, r={big_r})
    print("CELL", json.dumps({{"kind": kind, "n_iter": 1, "bits": 32,
                               "dead": [],
                               "measured": {{k: v for k, v in hlo.items() if v}}}}))
"""
    env = dict(os.environ)
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # virtual devices; never the chip
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"hier_measured subprocess failed:\n{out.stderr[-4000:]}"
        )
    cells = [
        json.loads(line[5:])
        for line in out.stdout.splitlines()
        if line.startswith("CELL ")
    ]
    # Full-membership cube + two degraded cells + the two big-shape cells.
    expected = len(MEASURE_N_ITERS) * len(bits) + 2 + 2
    if len(cells) != expected:
        # Fail closed, same as comm_measured.
        raise RuntimeError(
            f"hier_measured parsed {len(cells)} cells, expected {expected};"
            f"\nstdout was:\n{out.stdout[-2000:]}"
        )
    ok_all = True
    big_cp = {}  # kind -> measured inter-pod / hop collective-permute bytes
    for cell in cells:
        kind, n_iter, cb = cell["kind"], cell["n_iter"], cell["bits"]
        dead = tuple(cell["dead"])
        big = kind.endswith("-big")
        dd, rr = (big_d, big_r) if big else (d, r)
        topo = "ring" if kind == "ring-big" else "hier"
        cost = comm_cost(
            topo, m=m, d=dd, r=rr, n_iter=n_iter, comm_bits=cb,
            pods=pods if topo == "hier" else None,
            membership=Membership.from_dead(m, dead) if dead else None,
        )
        predicted = {k: v for k, v in cost.hlo_bytes.items() if v}
        # Same harness term as comm_measured: the driver's final
        # ``stacked[0]`` replication is one fp32 d*r all-reduce.
        predicted["all-reduce"] = predicted.get("all-reduce", 0) + 4 * dd * rr
        measured_cp = cell["measured"].get("collective-permute", 0)
        ok = cell["measured"] == predicted
        inter_note = ""
        if topo == "hier":
            # Per-level slow-link check: every collective-permute byte is
            # an inter-pod hop (no intra-pod collective lowers to a
            # permute), so the measured permute traffic must equal the
            # model's inter level on its own.
            inter_cp = cost.level_bytes["inter"]["collective-permute"]
            ok = ok and measured_cp == inter_cp
            inter_note = (
                f";predicted_inter_bytes="
                f"{json.dumps(cost.level_bytes['inter'], sort_keys=True)}"
                f";predicted_intra_bytes="
                f"{json.dumps(cost.level_bytes['intra'], sort_keys=True)}"
            )
        ok_all &= ok
        dead_tag = f",dead={list(dead)}" if dead else ""
        emit(
            f"hier_measured[{kind},d={dd},r={rr},m={m},pods={pods},"
            f"n_iter={n_iter},bits={cb}{dead_tag}]",
            0.0,
            f"measured={json.dumps(cell['measured'], sort_keys=True)};"
            f"predicted={json.dumps(predicted, sort_keys=True)}"
            f"{inter_note};match={'yes' if ok else 'NO'}",
        )
        if check and not ok:
            raise AssertionError(
                f"hier lane {kind} (n_iter={n_iter}, comm_bits={cb}, "
                f"dead={list(dead)}): measured HLO collective bytes "
                f"{cell['measured']} != model {predicted} (inter level "
                f"{cost.level_bytes.get('inter') if topo == 'hier' else '-'})"
            )
        if big:
            big_cp[kind] = measured_cp
    if big_cp.get("ring-big"):
        ratio = big_cp["hier-big"] / big_cp["ring-big"]
        emit(
            f"hier_measured[interpod-ratio,d={big_d},r={big_r},m={m},"
            f"pods={pods}]",
            0.0,
            f"hier_interpod_bytes={big_cp['hier-big']};"
            f"ring_hop_bytes={big_cp['ring-big']};ratio={ratio:.4f}",
        )
        if check and not ratio <= 0.45:
            raise AssertionError(
                f"hier inter-pod wire bytes are {ratio:.3f}x the flat "
                f"ring's at (m={m} as {pods}x{m // pods}, d={big_d}, "
                f"r={big_r}); expected <= 0.45 ((p-1)/(m-1) = 3/7)"
            )
    return ok_all


def _local_devices():
    try:
        import jax

        return jax.devices()
    except Exception:
        return []


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--check", action="store_true",
        help="exit non-zero unless every (topology, comm_bits) cell's "
             "compiled HLO collective bytes equal the repro.comm cost "
             "model (the CI bench-smoke gate)",
    )
    ap.add_argument(
        "--bits", default="32,8",
        help="comma-separated comm_bits wire tiers to sweep "
             "(default '32,8'; 16 is exact off-TPU everywhere except the "
             "documented psum cell)",
    )
    ap.add_argument(
        "--lane", default="all", choices=["all", "flat", "hier"],
        help="which measured lane(s) to compile: the flat-topology cube, "
             "the hierarchical (pod, local) lane, or both (default)",
    )
    args = ap.parse_args()
    bits = tuple(int(b) for b in args.bits.split(","))
    print("name,us_per_call,derived")
    comm_table()
    ok = True
    if args.lane in ("all", "flat"):
        ok &= comm_measured(check=args.check, bits=bits)
    if args.lane in ("all", "hier"):
        ok &= hier_measured(check=args.check, bits=bits)
    if args.check:
        print("# comm cost model verified against compiled HLO for "
              f"lane={args.lane} at comm_bits in {bits}")
        sys.exit(0 if ok else 1)
    # Without --check this is an informational table: mismatches are
    # visible as match=NO rows but do not fail the run.


if __name__ == "__main__":
    main()

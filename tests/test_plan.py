"""The execution planner (``repro.plan``).

Five properties are pinned down:

1. Registry single-home: the planner's valid-values tuples are the same
   objects the base vocabularies own, and ``resolve_topology``'s error
   message renders exactly ``TOPOLOGY_CHOICES`` — the two cannot drift.
2. Legacy parity: ``plan=None`` reproduces the per-knob resolution
   byte for byte, and on a 1-shard axis ``plan="auto"`` returns the
   historical ``resolve_topology`` pairing for every backend pin.
3. Golden plans: canonical (m, d, r, device) regimes resolve to the
   documented cells (DESIGN.md §8.4), the chosen cell's predicted words
   and bits equal ``comm_cost(...)`` exactly, per-cell predictions are
   monotone in each of m, d, r, n_iter, and the wire-precision axis
   behaves as documented — pinned at 32 unless ``comm_bits="auto"``,
   flipping the bandwidth-bound TPU cell to int8 when freed.
4. The ``ring_chunk`` rule (§8.2): latency-bound bases ship whole,
   large-d bases chunk at the latency-bandwidth product with the
   MIN_RING_CHUNK floor, explicit chunks are honoured.
5. End-to-end: ``plan="auto"`` through the public aggregation API
   agrees with the serial oracle across every (backend x topology) pin
   combination (m=1 fast; m=8 in a subprocess), and the CLIs' --explain
   chosen-cell words match the model.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO, SRC, run_with_devices, subspace_dist64

from repro.comm import DEFAULT_RING_CHUNK, TOPOLOGIES, comm_cost, resolve_topology
from repro.kernels.ops import resolve_backend
from repro.plan import (
    BACKENDS_CONCRETE,
    Calibration,
    ORTH_CHOICES,
    POLAR_CHOICES,
    Plan,
    TOPOLOGY_CHOICES,
    choose_ring_chunk,
    device_model,
    load_calibration,
    plan_aggregation,
    resolve_plan,
    score_cells,
)

BACKENDS = ["xla", "pallas"]


# ------------------------------------------------------------- registry --


def test_choice_registries_are_single_homed():
    import repro.comm.topology as T
    from repro.core.orthonorm import ORTH_METHODS
    from repro.core.procrustes import POLAR_METHODS

    assert TOPOLOGY_CHOICES is T.TOPOLOGY_CHOICES
    assert TOPOLOGY_CHOICES == TOPOLOGIES + ("auto",)
    assert POLAR_CHOICES == POLAR_METHODS + ("auto",)
    assert ORTH_CHOICES == ORTH_METHODS + ("auto",)
    assert BACKENDS_CONCRETE == ("xla", "pallas")


def test_resolve_topology_error_lists_the_registry():
    """The error message is rendered from TOPOLOGY_CHOICES itself, so the
    listed valid values cannot drift from the planner's registry."""
    with pytest.raises(ValueError) as ei:
        resolve_topology("coordinator")
    assert str(TOPOLOGY_CHOICES) in str(ei.value)


def test_invalid_pins_raise():
    with pytest.raises(ValueError):
        plan_aggregation(m=4, d=64, r=4, backend="mosaic", device_kind="cpu")
    with pytest.raises(ValueError):
        plan_aggregation(m=4, d=64, r=4, topology="tree", device_kind="cpu")
    with pytest.raises(ValueError):
        resolve_plan("fastest", m=4, d=64, r=4)


# ---------------------------------------------------------- legacy parity --


def test_plan_none_is_the_legacy_resolution():
    for backend in (None, "xla", "pallas", "auto"):
        for topology in (None, "psum", "gather", "ring", "auto"):
            pl = resolve_plan(
                None, m=8, d=96, r=4, n_iter=2,
                backend=backend, topology=topology,
            )
            b_legacy = resolve_backend(backend if backend is not None else "xla")
            assert pl.backend == b_legacy
            assert pl.topology == resolve_topology(topology or "auto", b_legacy)
            assert (pl.polar, pl.orth) == ("svd", "qr")
            assert pl.ring_chunk == DEFAULT_RING_CHUNK
            assert pl.comm_bits == 32  # full-precision wires unless asked
            assert pl.source == "legacy"


def test_plan_auto_reproduces_legacy_topology_on_one_shard_axis():
    """The satellite guarantee: on a 1-device mesh every schedule is the
    same program, and the planner returns today's resolve_topology picks
    rather than an arbitrary tie-winner."""
    for backend in (None, "xla", "pallas"):
        pl = plan_aggregation(
            m=1, d=256, r=8, n_iter=2, device_kind="cpu", backend=backend,
        )
        assert pl.topology == resolve_topology("auto", backend or "xla"), pl
    # And with everything free on the CPU host, the full legacy cell.
    pl = plan_aggregation(m=1, d=256, r=8, n_iter=2, device_kind="cpu")
    assert (pl.backend, pl.topology, pl.polar, pl.orth) == (
        "xla", "psum", "svd", "qr",
    )


def test_one_shard_axis_pairing_survives_backend_flip():
    """If a calibration makes the scorer reject the guessed backend on a
    1-shard axis (e.g. pallas launches priced expensive on TPU), the
    returned (backend, topology) must still be a legacy pairing — never
    a mixed cell like (xla, gather)."""
    cal = Calibration(platform="tpu", dispatch_s=200e-6, cells=1)
    pl = plan_aggregation(
        m=1, d=512, r=16, n_iter=2, device_kind="tpu", calibration=cal,
    )
    assert pl.topology == resolve_topology("auto", pl.backend), pl


def test_legacy_auto_polar_keeps_legacy_ring_chunk():
    """plan=None with polar="auto" plans only the free knob: the ring
    chunk stays the legacy DEFAULT_RING_CHUNK, not the planner's rule."""
    pl = resolve_plan(
        None, m=8, d=96, r=4, n_iter=2, topology="ring", polar="auto",
        device_kind="cpu",
    )
    assert pl.ring_chunk == DEFAULT_RING_CHUNK
    assert pl.polar in ("svd", "newton-schulz")


def test_plan_passthrough_and_hashability():
    pl = plan_aggregation(m=8, d=512, r=16, device_kind="tpu")
    assert resolve_plan(pl, m=8, d=512, r=16) is pl
    assert hash(pl) == hash(pl)  # usable as a jit static argument
    # Prediction/provenance fields are compare=False: two plans that run
    # the same program are equal (no jit retrace on a re-resolved plan).
    a = Plan("xla", "psum", "svd", "qr", 64, words=1, source="legacy")
    b = Plan("xla", "psum", "svd", "qr", 64, words=99, source="planner")
    assert a == b and hash(a) == hash(b)


# ------------------------------------------------------------ golden plans --


def test_golden_plan_tpu_paper_scale_is_the_fused_round():
    """Latency-bound paper-scale shapes on TPU: the one-launch fused cell
    (pallas, gather, newton-schulz, cholesky-qr2) — DESIGN.md §8.4."""
    pl = plan_aggregation(m=8, d=512, r=16, n_iter=2, device_kind="tpu")
    assert (pl.backend, pl.topology, pl.polar, pl.orth) == (
        "pallas", "gather", "newton-schulz", "cholesky-qr2",
    )


def test_golden_plan_tpu_bandwidth_bound_is_psum():
    """Huge d·r: the wire dominates and psum moves (1+n)·d·r words where
    the stacked forms move m·d·r — the planner picks psum."""
    pl = plan_aggregation(m=64, d=65536, r=128, n_iter=1, device_kind="tpu")
    assert pl.topology == "psum"
    # The wire-precision axis stays pinned at full precision by default —
    # lossy tiers are opt-in, never a silent accuracy trade.
    assert pl.comm_bits == 32
    assert pl.bits == pl.words * 32


def test_golden_plan_bandwidth_bound_flips_to_int8_when_freed():
    """comm_bits="auto" on the bandwidth-bound TPU cell: the wire term
    dominates the roofline, so the planner takes the 4x payload shrink
    (d*r*8 + 32*r scale bits per message) and flips the cell to int8."""
    pl = plan_aggregation(
        m=64, d=65536, r=128, n_iter=1, device_kind="tpu", comm_bits="auto",
    )
    assert pl.comm_bits == 8
    assert pl.bits == comm_cost(
        pl.topology, m=64, d=65536, r=128, n_iter=1, comm_bits=8,
    ).bits
    assert pl.bits < pl.words * 32 / 3.9  # ~4x wire shrink


def test_golden_plan_latency_bound_auto_keeps_full_precision():
    """comm_bits="auto" on the latency-bound paper-scale cell: the wire
    is not the bottleneck, so the codec's extra passes are pure cost and
    the planner keeps 32 — quantization only wins when bandwidth-bound."""
    pl = plan_aggregation(
        m=8, d=512, r=16, n_iter=2, device_kind="tpu", comm_bits="auto",
    )
    assert pl.comm_bits == 32


def test_int8_psum_headroom_guard():
    """int8 psum sums m quantized payloads in s8: the shared-scale
    headroom rule (repro.comm.quantize.wire_psum_mean) needs m <= 126,
    so larger meshes mark the (psum, 8) cells infeasible — the planner
    routes int8 through gather/ring instead of overflowing."""
    cells = score_cells(
        m=200, d=65536, r=128, n_iter=1, device_kind="tpu", comm_bits="auto",
    )
    psum8 = [c for c in cells if c.topology == "psum" and c.comm_bits == 8]
    assert psum8 and all(not c.feasible for c in psum8)
    assert "m <= 126" in psum8[0].note
    others8 = [c for c in cells
               if c.topology in ("gather", "ring") and c.comm_bits == 8]
    assert any(c.feasible for c in others8)


def test_golden_plan_tpu_xla_pin_flips_to_matmul_only_methods():
    """With the backend pinned to XLA on TPU, LAPACK latency still makes
    newton-schulz + cholesky-qr2 the winning methods."""
    pl = plan_aggregation(
        m=8, d=512, r=16, n_iter=2, device_kind="tpu", backend="xla",
    )
    assert (pl.backend, pl.polar, pl.orth) == (
        "xla", "newton-schulz", "cholesky-qr2",
    )


def test_golden_plan_cpu_keeps_lapack_methods():
    """On CPU, LAPACK is cheap and the kernels do not compile: the plan
    stays on the classic (xla, psum, svd, qr) cell."""
    pl = plan_aggregation(m=8, d=512, r=16, n_iter=2, device_kind="cpu")
    assert (pl.backend, pl.topology, pl.polar, pl.orth) == (
        "xla", "psum", "svd", "qr",
    )


def test_pallas_never_chosen_off_tpu_unless_pinned():
    cells = score_cells(m=8, d=512, r=16, device_kind="cpu")
    assert all(not c.feasible for c in cells if c.backend == "pallas")
    pl = plan_aggregation(m=8, d=512, r=16, device_kind="cpu", backend="pallas")
    assert pl.backend == "pallas"  # pins are honoured, annotated not overridden


def test_gather_memory_guard_surfaces_the_ring():
    """A (m, d, r) stack over the memory budget makes gather infeasible
    (unless pinned); the ring — gather-without-the-stack — stays
    feasible.  DESIGN.md §8.4's 'when the ring surfaces'."""
    kw = dict(m=2048, d=65536, r=128, n_iter=1, device_kind="tpu")
    cells = score_cells(**kw)
    by_topo = {}
    for c in cells:
        by_topo.setdefault(c.topology, []).append(c)
    assert all(not c.feasible for c in by_topo["gather"])
    assert any(c.feasible for c in by_topo["ring"])
    # Pinning gather is honoured but annotated.
    pl = plan_aggregation(**kw, topology="gather")
    assert pl.topology == "gather"


def test_chosen_words_match_comm_cost_exactly():
    for kw in (
        dict(m=8, d=512, r=16, n_iter=2, device_kind="tpu"),
        dict(m=8, d=512, r=16, n_iter=2, device_kind="cpu"),
        dict(m=64, d=8192, r=128, n_iter=3, device_kind="tpu"),
        dict(m=2, d=96, r=4, n_iter=1, device_kind="cpu"),
    ):
        pl = plan_aggregation(**kw)
        cost = comm_cost(
            pl.topology, m=kw["m"], d=kw["d"], r=kw["r"],
            n_iter=kw["n_iter"], comm_bits=pl.comm_bits,
        )
        assert pl.words == cost.words, (kw, pl)
        assert pl.bits == cost.bits, (kw, pl)


def test_every_scored_cell_words_match_comm_cost():
    m, d, r, n = 8, 512, 16, 2
    for c in score_cells(m=m, d=d, r=r, n_iter=n, device_kind="tpu",
                         comm_bits="auto"):
        cost = comm_cost(c.topology, m=m, d=d, r=r, n_iter=n,
                         comm_bits=c.comm_bits)
        assert c.words == cost.words, c
        assert c.bits == cost.bits, c


# ------------------------------------------------------------ monotonicity --


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
@pytest.mark.parametrize("backend", BACKENDS)
def test_predictions_monotone_in_problem_size(topology, backend):
    """Within a fixed cell, bigger problems never predict fewer words or
    flops — the cost model has no sign errors hiding in a regime."""
    base = dict(m=8, d=512, r=16, n_iter=2)
    # hier needs the 2-D mesh declared; pods=4 tiles both m=8 and m=16.
    pods = 4 if topology == "hier" else None

    def cell(**kw):
        args = dict(base, **kw)
        [c] = score_cells(
            m=args["m"], d=args["d"], r=args["r"], n_iter=args["n_iter"],
            device_kind="tpu", backend=backend, topology=topology,
            polar="newton-schulz", orth="cholesky-qr2", pods=pods,
        )
        return c

    ref = cell()
    for knob, bigger in (
        ("m", 16), ("d", 2048), ("r", 64), ("n_iter", 5),
    ):
        grown = cell(**{knob: bigger})
        assert grown.words >= ref.words, (knob, topology, backend)
        assert grown.flops >= ref.flops, (knob, topology, backend)
        assert grown.hbm_bytes >= ref.hbm_bytes, (knob, topology, backend)


# ------------------------------------------------------------- ring chunk --


def test_ring_chunk_rule():
    tpu = device_model("tpu")
    # Latency-bound basis ships whole: chunk == d.
    assert choose_ring_chunk(512, 16, tpu) == 512
    # Large d chunks at the latency-bandwidth product / r, floored.
    big = choose_ring_chunk(8192, 128, tpu)
    assert big == 256  # floor: MIN_RING_CHUNK
    mid = choose_ring_chunk(8192, 16, tpu)
    assert 256 <= mid < 8192
    # Monotone: more columns -> same or smaller chunks; never over d.
    for d in (64, 1024, 16384):
        prev = None
        for r in (4, 16, 64, 256):
            c = choose_ring_chunk(d, r, tpu)
            assert 1 <= c <= d
            if prev is not None:
                assert c <= prev
            prev = c


def test_ring_chunk_pin_and_plan_threading():
    pl = plan_aggregation(
        m=8, d=96, r=4, device_kind="cpu", topology="ring", ring_chunk=40,
    )
    assert (pl.topology, pl.ring_chunk) == ("ring", 40)
    # Planner-chosen chunk is clamped to d.
    pl = plan_aggregation(m=8, d=96, r=4, device_kind="cpu", topology="ring")
    assert 1 <= pl.ring_chunk <= 96


# ------------------------------------------------------------- calibration --


def test_calibration_from_committed_baseline():
    cal = load_calibration(os.path.join(REPO, "BENCH_aggregate_tiny.json"))
    assert cal.platform == "cpu"
    assert cal.cells > 0
    assert cal.dispatch_s and cal.dispatch_s > 0
    assert cal.applies_to("cpu") and not cal.applies_to("tpu")
    # A calibrated plan still resolves (and stays a valid cell).
    pl = plan_aggregation(
        m=8, d=512, r=16, n_iter=2, device_kind="cpu", calibration=cal,
    )
    assert pl.backend in BACKENDS and pl.topology in TOPOLOGIES


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e", "tpu"])
def test_device_model_prices_v5e(kind):
    """``jax.devices()[0].device_kind`` of a v5e (and the backend name the
    tests pass) resolve to the one TPU model the table holds."""
    from repro.plan.roofline import TPU_V5E

    assert device_model(kind) is TPU_V5E


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "metal", ""])
def test_device_model_refuses_unknown_kind(kind):
    """An unknown chip is an error, never priced as a CPU."""
    with pytest.raises(ValueError, match="no device model"):
        device_model(kind)


def test_default_device_kind_is_the_backend_off_tpu():
    from repro.plan.planner import _default_device_kind

    assert _default_device_kind() == jax.default_backend() == "cpu"
    assert plan_aggregation(m=4, d=64, r=4).device_kind == "cpu"


def test_calibration_degrades_to_noop():
    empty = Calibration.from_records("cpu", [])
    assert empty.cells == 0 and empty.dispatch_s is None
    dm = device_model("cpu")
    assert dm.calibrated(dispatch_s=None, flops_per_s=None) == dm
    # Interpret-mode records are ignored.
    recs = [dict(topology="stacked", mode="interpret", wall_us_min=5.0,
                 m=4, d=64, r=4, n_iter=1, polar="svd", orth="qr")]
    assert Calibration.from_records("cpu", recs).cells == 0


def test_calibration_refines_device_model():
    recs = [
        dict(topology="stacked", mode="compiled", wall_us_min=100.0,
             m=4, d=64, r=4, n_iter=1, polar="svd", orth="qr"),
        dict(topology="stacked", mode="compiled", wall_us_min=9000.0,
             m=16, d=4096, r=64, n_iter=2, polar="svd", orth="qr"),
    ]
    cal = Calibration.from_records("cpu", recs)
    assert cal.dispatch_s == pytest.approx(100e-6)
    assert cal.flops_per_s and cal.flops_per_s > 0
    dm = device_model("cpu").calibrated(
        dispatch_s=cal.dispatch_s, flops_per_s=cal.flops_per_s
    )
    assert dm.launch_latency_s == pytest.approx(100e-6)
    assert dm.peak_flops == pytest.approx(cal.flops_per_s)


# -------------------------------------------------- end-to-end (plan=auto) --


def test_plan_auto_single_device_parity_all_pins():
    """plan="auto" through the public collective API, every
    (backend x topology) pin combination, against the serial oracle —
    the fast-lane slice of the acceptance parity suite."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from repro.launch.mesh import make_mesh
    from repro.core import refinement_rounds
    from repro.core.distributed import procrustes_average_collective

    d, r = 96, 4
    vs = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(3), (1, d, r)))[0]
    ser = refinement_rounds(vs, n_iter=2)
    mesh = make_mesh((1,), ("data",))
    # hier is excluded: it needs a 2-D (pod, local) mesh by construction,
    # so a 1-D single-device pin can never run it.
    for topo in [None] + [t for t in TOPOLOGIES if t != "hier"]:
        for backend in [None] + BACKENDS:
            fn = jax.jit(shard_map(
                lambda v, b=backend, t=topo: procrustes_average_collective(
                    v[0], axis_name="data", n_iter=2, backend=b, topology=t,
                    plan="auto",
                )[None],
                mesh=mesh, in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            ))
            got = fn(vs)[0]
            assert subspace_dist64(ser, got) <= 1e-5, (topo, backend)


def test_iterative_refinement_plan_auto_matches_legacy():
    vs = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (4, 64, 4)))[0]
    from repro.core import iterative_refinement

    a = iterative_refinement(vs, 2)
    b = iterative_refinement(vs, 2, plan="auto")
    assert subspace_dist64(a, b) <= 1e-5


@pytest.mark.slow
def test_plan_auto_parity_cube_eight_devices():
    """Acceptance: plan="auto" exercised end-to-end across every
    (backend x topology) pin at m=8, n_iter=2 — every planned cell
    agrees with the serial oracle to <= 1e-5 f64 subspace distance."""
    out = run_with_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import refinement_rounds
        from repro.core.distributed import procrustes_average_collective
        from repro.core.metrics import subspace_dist64

        m, d, r = 8, 96, 4
        vs = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (m, d, r)))[0]
        ser = refinement_rounds(vs, n_iter=2)
        mesh = make_mesh((m,), ("data",))
        for topo in (None, "psum", "gather", "ring"):
            for backend in (None, "xla", "pallas"):
                fn = jax.jit(shard_map(
                    lambda v, b=backend, t=topo: procrustes_average_collective(
                        v[0], axis_name="data", n_iter=2, backend=b,
                        topology=t, plan="auto")[None],
                    mesh=mesh, in_specs=P("data", None, None),
                    out_specs=P("data", None, None), check_vma=False,
                ))
                got = fn(vs)[0]
                print("CELL", topo, backend, float(subspace_dist64(ser, got)))
        """
    )
    cells = [ln.split() for ln in out.strip().splitlines()
             if ln.startswith("CELL")]
    assert len(cells) == 12
    for _, topo, backend, dist in cells:
        assert float(dist) <= 1e-5, (topo, backend, dist)


def test_eigen_run_plan_auto_records_resolved_plan(capsys):
    from repro.launch.eigen import run

    _, stats = run(d=96, r=4, n_per_shard=128, n_iter=2, solver="eigh",
                   plan="auto", explain=True)
    table = capsys.readouterr().out
    assert "chosen:" in table
    assert stats["plan_source"] == "planner"
    expect = comm_cost(
        stats["topology"], m=stats["m"], d=96, r=4, n_iter=2
    ).words
    assert stats["predicted_words"] == expect
    assert f"words={expect}" in table
    # Un-freed wire axis: pinned at 32, bits is exactly words * 32.
    assert stats["comm_bits"] == 32
    assert stats["predicted_bits"] == expect * 32


# ------------------------------------------------------- CLI --explain --


CHOSEN_RE = re.compile(
    r"chosen: (\w+)/(\w[\w-]*)/([\w-]+)/([\w-]+) ring_chunk=(\d+) "
    r"comm_bits=(\d+) words=(\d+) bits=(\d+)"
)


@pytest.mark.slow
def test_launch_eigen_explain_words_match_model():
    """Acceptance: `launch.eigen --explain` prints a scored plan table
    whose chosen-cell predicted words equal comm_cost byte for byte."""
    out = run_with_devices(
        """
        import sys
        sys.argv = ["eigen", "--d", "96", "--r", "4", "--n-per-shard", "64",
                    "--n-iter", "2", "--solver", "eigh",
                    "--plan", "auto", "--explain"]
        from repro.launch.eigen import main
        main()
        """
    )
    m = CHOSEN_RE.search(out)
    assert m, out
    _, topo, _, _, _, cbits, words, bits = m.groups()
    cost = comm_cost(topo, m=8, d=96, r=4, n_iter=2, comm_bits=int(cbits))
    assert int(words) == cost.words
    assert int(bits) == cost.bits
    # The stats echo the same resolved plan.
    assert f"predicted_words: {words}" in out
    assert f"predicted_bits: {bits}" in out


@pytest.mark.slow
def test_dryrun_paper_pca_explain_words_match_model(tmp_path):
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--paper-pca",
         "--single-pod", "--plan", "auto", "--explain",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    m = CHOSEN_RE.search(proc.stdout)
    assert m, proc.stdout
    _, topo, _, _, _, cbits, words, bits = m.groups()
    from repro.configs.paper_pca import CONFIG as pcfg

    # Reduced single-pod mesh is (2, n//2): the data axis has 2 shards.
    cost = comm_cost(topo, m=2, d=pcfg.d, r=pcfg.r, n_iter=pcfg.n_iter,
                     comm_bits=int(cbits))
    assert int(words) == cost.words
    assert int(bits) == cost.bits
    rec = json.load(open(os.path.join(
        str(tmp_path), "paper-pca__pca__singlepod.json")))
    assert rec["plan_source"] == "planner"
    assert rec["predicted_collective_words"] == cost.words
    assert rec["predicted_collective_bits"] == cost.bits
    assert rec["comm_bits"] == int(cbits)
    assert rec["topology"] == topo


# ------------------------------------------- split-bandwidth roofline --


def test_slow_dcn_flips_flat_ring_to_hier():
    """Golden flip (DESIGN.md §2.4): at the paper-scale shape where the
    gather stack is memory-infeasible and int8 prices psum out (the
    headroom guard), the 1-D plan chooses the flat ring — and handing
    the planner the 2-D (pods, local) mesh on a slow-DCN device flips
    the choice to hier, whose inter-pod ring is the only wire on the
    slow fabric.  The flat ring's cell is re-priced at ``dcn_bw`` in the
    same enumeration, so the flip is apples-to-apples."""
    import dataclasses

    kw = dict(m=2048, d=65536, r=128, n_iter=1, comm_bits=8)
    tpu = device_model("tpu")
    flat = score_cells(device=tpu, **kw)
    assert flat[0].topology == "ring"
    slow = dataclasses.replace(tpu, dcn_bw=tpu.net_bw / 100)
    assert slow.ici_bw == tpu.net_bw
    cells = score_cells(device=slow, pods=64, **kw)
    assert cells[0].topology == "hier"
    ring = next(c for c in cells if c.topology == "ring" and c.feasible)
    hier = cells[0]
    # The ring crosses the slow fabric every hop; hier only (p-1) times.
    assert ring.comm_s > 10 * hier.comm_s
    # On the uniform-fabric device the flat ring's pricing is unchanged
    # by pods= (dcn_bw == ici_bw): the re-pricing is byte-identical.
    uniform = score_cells(device=tpu, pods=64, **kw)
    ring_uniform = next(
        c for c in uniform if c.topology == "ring" and c.feasible)
    ring_flat = next(
        c for c in flat if c.topology == "ring" and c.feasible)
    assert ring_uniform == ring_flat


def test_dcn_default_reproduces_golden_plans():
    """``dcn_bw=ici_bw`` is behavior-preserving: an explicitly-split
    device with ``dcn_bw == net_bw`` scores every cell of every golden
    configuration byte-for-byte like the pre-split default (whose 0.0
    sentinel resolves to ``net_bw``), pods given or not."""
    import dataclasses

    from repro.plan.roofline import DEVICE_MODELS

    for dev in DEVICE_MODELS.values():
        assert dev.dcn_bw == dev.net_bw  # the sentinel resolved
    for kw in (
        dict(m=8, d=512, r=16, n_iter=2, device_kind="tpu"),
        dict(m=8, d=512, r=16, n_iter=2, device_kind="cpu"),
        dict(m=2048, d=65536, r=128, n_iter=1, device_kind="tpu"),
        dict(m=64, d=8192, r=128, n_iter=3, device_kind="tpu",
             comm_bits="auto"),
        dict(m=8, d=96, r=4, n_iter=2, device_kind="cpu", pods=4),
    ):
        dev = device_model(kw.pop("device_kind"))
        split = dataclasses.replace(dev, dcn_bw=dev.net_bw)
        assert score_cells(device=dev, **kw) == \
            score_cells(device=split, **kw), kw

"""Device models and roofline terms — the library home of the numbers.

Everything that prices an operation lives here: per-device-kind hardware
constants (``DeviceModel``), the three-term roofline decomposition
(``RooflineTerms`` / ``roofline_terms``), and the dry-run record table
rendering that ``benchmarks/roofline.py`` used to own.  Consumers:

  * ``repro.plan.planner`` prices every (backend x topology x polar x
    orth) cell of an aggregation with these models;
  * ``repro.launch.hlo_analysis`` derives measured roofline terms from a
    compiled module's cost analysis (it re-exports the legacy
    ``PEAK_FLOPS`` / ``HBM_BW`` / ``ICI_BW`` names, which are this
    module's TPU model);
  * ``benchmarks/roofline.py`` renders dry-run artifacts via the table
    helpers below.

This module deliberately imports nothing heavier than ``dataclasses`` so
it can sit at the bottom of the layering (even ``repro.comm`` may price
things against it without a cycle).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional

__all__ = [
    "DeviceModel",
    "DEVICE_MODELS",
    "TPU_KINDS",
    "device_model",
    "TPU_V5E",
    "CPU_HOST",
    "GPU_GENERIC",
    "PEAK_FLOPS",
    "HBM_BW",
    "ICI_BW",
    "RooflineTerms",
    "roofline_terms",
    "model_flops",
    "load_dryrun_records",
    "dryrun_csv_row",
    "dryrun_markdown_table",
]


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """Hardware constants one device kind exposes to the cost models.

    The throughput terms (``peak_flops``, ``hbm_bw``, ``net_bw``) price
    bulk work; the latency terms price the fixed overheads that dominate
    the paper's small (d, r) shapes:

      * ``op_latency_s``      — per sequential XLA op in a compiled
                                program (the cost of a 48-matmul
                                Newton–Schulz chain that a fused kernel
                                collapses to zero);
      * ``launch_latency_s``  — per ``pallas_call`` / program dispatch;
      * ``lapack_latency_s``  — per LAPACK-style custom call (SVD,
                                Householder QR): unfusable and
                                latency-bound on TPU, cheap on CPU;
      * ``coll_latency_s``    — per collective operation on the wire.

    ``interpret_penalty`` multiplies Pallas-kernel compute where the
    kernels cannot compile (off-TPU the Pallas interpreter is a
    correctness path, not a performance one); ``hbm_cap_bytes`` bounds
    working sets (the gather topology's (m, d, r) stack);
    ``vmem_cap_bytes`` bounds *kernel-resident* working sets — the fused
    ring round holds its triple-slotted hop buffer plus the running V̄ /
    ref / out tiles entirely in VMEM (DESIGN.md §3.3), so the planner
    marks that cell infeasible when (3·wire + 3·f32)·d·r outgrows the
    envelope.
    """

    kind: str
    peak_flops: float
    hbm_bw: float
    net_bw: float
    op_latency_s: float
    launch_latency_s: float
    lapack_latency_s: float
    coll_latency_s: float
    interpret_penalty: float
    hbm_cap_bytes: float
    vmem_cap_bytes: float = float(16 * 2**20)  # the 16 MiB/core envelope
    # Split interconnect: ``net_bw`` is the fast intra-pod link (ICI /
    # NVLink / shared memory — ``ici_bw`` below aliases it), while
    # ``dcn_bw`` is the slow inter-pod fabric the hierarchical topology
    # prices its pod-level ring against.  The 0.0 sentinel resolves to
    # ``net_bw`` in ``__post_init__``, so every single-pod model (and
    # every pre-split caller) keeps byte-identical behavior: with
    # ``dcn_bw == ici_bw`` there is no slow link and the planner's split
    # pricing collapses to the flat one.
    dcn_bw: float = 0.0

    def __post_init__(self):
        if self.dcn_bw <= 0.0:
            object.__setattr__(self, "dcn_bw", self.net_bw)

    @property
    def ici_bw(self) -> float:
        """The fast intra-pod link — an alias of ``net_bw`` (the name the
        split cost model uses opposite ``dcn_bw``)."""
        return self.net_bw

    def calibrated(
        self,
        *,
        dispatch_s: Optional[float] = None,
        flops_per_s: Optional[float] = None,
    ) -> "DeviceModel":
        """Refined copy: measured per-call dispatch overhead replaces the
        launch latency, a measured effective FLOP rate replaces the peak
        (see ``repro.plan.calibration`` for where the numbers come from).
        """
        updates: Dict[str, float] = {}
        if dispatch_s is not None and dispatch_s > 0:
            updates["launch_latency_s"] = dispatch_s
        if flops_per_s is not None and flops_per_s > 0:
            updates["peak_flops"] = flops_per_s
        return dataclasses.replace(self, **updates) if updates else self


# TPU v5e.  Peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
# (four links, so 50 GB/s each).  The latency terms and the 16 MiB VMEM
# envelope (the default scoped-VMEM limit) are priors, not measurements.
TPU_V5E = DeviceModel(
    kind="tpu",
    peak_flops=197e12,   # bf16 per chip
    hbm_bw=819e9,        # bytes/s per chip
    net_bw=50e9,         # bytes/s per ICI link
    op_latency_s=5e-7,
    launch_latency_s=5e-6,
    lapack_latency_s=4e-5,
    coll_latency_s=1e-6,
    interpret_penalty=200.0,
    hbm_cap_bytes=16e9,
    vmem_cap_bytes=float(16 * 2**20),
)

# A host CPU: throughput numbers are deliberately modest (the planner
# only compares cells against each other, and on one host the "wire" is
# shared memory), latency numbers reflect that LAPACK is cheap and
# dispatch is not.
CPU_HOST = DeviceModel(
    kind="cpu",
    peak_flops=1e11,
    hbm_bw=2e10,
    net_bw=2e10,
    op_latency_s=2e-7,
    launch_latency_s=2e-5,
    lapack_latency_s=2e-6,
    coll_latency_s=5e-7,
    interpret_penalty=200.0,
    hbm_cap_bytes=3.2e10,
    # Interpreted kernels hold "VMEM" scratch in host RAM — the envelope
    # is soft there, so it only rejects genuinely outsized working sets.
    vmem_cap_bytes=float(256 * 2**20),
)

# Generic accelerator fallback: the Pallas kernels are Mosaic (TPU-only),
# so GPU behaves like CPU for backend feasibility but prices collectives
# like a fast interconnect.
GPU_GENERIC = DeviceModel(
    kind="gpu",
    peak_flops=6e13,
    hbm_bw=1.5e12,
    net_bw=1e11,
    op_latency_s=2e-6,
    launch_latency_s=8e-6,
    lapack_latency_s=2e-5,
    coll_latency_s=3e-6,
    interpret_penalty=200.0,
    hbm_cap_bytes=4e10,
    vmem_cap_bytes=float(16 * 2**20),
)

DEVICE_MODELS: Dict[str, DeviceModel] = {
    m.kind: m for m in (TPU_V5E, CPU_HOST, GPU_GENERIC)
}

# ``jax.devices()[0].device_kind`` of each TPU generation priced above.
TPU_KINDS: Dict[str, DeviceModel] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def device_model(kind: str) -> DeviceModel:
    """Model for a backend name (``"tpu"``, ``"cpu"``, ``"gpu"``) or a TPU
    ``device_kind`` string.  A kind not in the tables is an error: a chip
    priced with another chip's peaks gives plans nobody measured."""
    model = DEVICE_MODELS.get(kind) or TPU_KINDS.get(kind)
    if model is None:
        raise ValueError(
            f"no device model for kind {kind!r}; known: "
            f"{sorted(DEVICE_MODELS) + sorted(TPU_KINDS)}"
        )
    return model


# Legacy names (the brief's TPU v5e numbers); ``repro.launch.hlo_analysis``
# re-exports these so its callers keep working.
PEAK_FLOPS = TPU_V5E.peak_flops
HBM_BW = TPU_V5E.hbm_bw
ICI_BW = TPU_V5E.net_bw


@dataclasses.dataclass
class RooflineTerms:
    """Three-term roofline of one step: per-device flops, HBM bytes and
    collective wire bytes, each divided by its bandwidth; the bottleneck
    is the largest term."""

    flops: float               # per-device HLO flops
    hbm_bytes: float           # per-device bytes accessed
    coll_bytes: float          # per-device collective wire bytes
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    coll_breakdown: Dict[str, int]

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(
    flops: float,
    hbm_bytes: float,
    coll_breakdown: Dict[str, int],
    chips: int,
    device: DeviceModel = TPU_V5E,
) -> RooflineTerms:
    """Pure roofline arithmetic (no HLO parsing — that stays in
    ``repro.launch.hlo_analysis.collective_bytes``)."""
    coll_total = float(sum(coll_breakdown.values()))
    compute_s = flops / device.peak_flops
    memory_s = hbm_bytes / device.hbm_bw
    collective_s = coll_total / device.net_bw
    terms = {
        "compute": compute_s,
        "memory": memory_s,
        "collective": collective_s,
    }
    bottleneck = max(terms, key=terms.get)
    return RooflineTerms(
        flops=flops,
        hbm_bytes=hbm_bytes,
        coll_bytes=coll_total,
        chips=chips,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        coll_breakdown=coll_breakdown,
    )


def model_flops(n_active_params: float, tokens: float, kind: str = "train") -> float:
    """6·N·D for train (fwd+bwd), 2·N·D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens


# ---------------------------------------------------------------------------
# Dry-run record tables (moved from benchmarks/roofline.py so the report
# rendering and the planner price against the same vocabulary).

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load_dryrun_records(dirname: str) -> List[Dict]:
    """Load and sort ``repro.launch.dryrun`` artifact JSONs."""
    recs = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    recs.sort(
        key=lambda r: (
            r.get("multi_pod", False),
            r.get("arch", ""),
            SHAPE_ORDER.index(r["shape"]) if r.get("shape") in SHAPE_ORDER else 9,
        )
    )
    return recs


def dryrun_csv_row(r: Dict) -> str:
    if "skipped" in r:
        return (
            f"{r['arch']},{r['shape']},{'multi' if r['multi_pod'] else 'single'},"
            "SKIP,,,,,,,"
        )
    if "error" in r:
        return (
            f"{r['arch']},{r['shape']},{'multi' if r['multi_pod'] else 'single'},"
            "ERROR,,,,,,,"
        )
    dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
    frac = r["compute_s"] / max(dom, 1e-30)
    return (
        f"{r['arch']},{r['shape']},{'multi' if r['multi_pod'] else 'single'},"
        f"{'eigen,' if r.get('eigen') else 'base,'}"
        f"{r['compute_s']*1e3:.2f},{r['memory_s']*1e3:.2f},"
        f"{r['collective_s']*1e3:.2f},{r['bottleneck']},"
        f"{r.get('useful_flops_ratio', 0):.3f},{frac:.3f},"
        f"{r.get('compile_s', 0):.0f}"
    )


def dryrun_markdown_table(recs: List[Dict]) -> str:
    lines = [
        "| arch | shape | mesh | compute ms | memory ms | collective ms | "
        "bottleneck | useful FLOP ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        mesh = "2x16x16" if r.get("multi_pod") else "16x16"
        if "skipped" in r:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh} | — | — | — | "
                f"skipped | — | — |"
            )
            continue
        if "error" in r:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh} | ERR | ERR | ERR | "
                f"error | — | — |"
            )
            continue
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        frac = r["compute_s"] / max(dom, 1e-30)
        tag = " (eigen)" if r.get("eigen") else ""
        lines.append(
            f"| {r['arch']}{tag} | {r['shape']} | {mesh} | "
            f"{r['compute_s']*1e3:.2f} | {r['memory_s']*1e3:.2f} | "
            f"{r['collective_s']*1e3:.2f} | {r['bottleneck']} | "
            f"{r.get('useful_flops_ratio', 0):.3f} | {frac:.3f} |"
        )
    return "\n".join(lines)

"""Post-compile HLO analysis: collective-bytes breakdown + roofline terms.

``compiled.as_text()`` is the per-device partitioned module; summing each
collective op's operand bytes gives the per-device bytes placed on the wire
per step (equivalently: the brief's total-bytes / chips).  The roofline
collective term is that divided by the per-link ICI bandwidth.

The hardware constants and the roofline arithmetic live in
``repro.plan.roofline`` (the planner prices hypothetical cells against
the same numbers this module uses to score compiled modules); this
module keeps the HLO *parsing* plus the legacy ``PEAK_FLOPS`` /
``HBM_BW`` / ``ICI_BW`` / ``RooflineTerms`` names as re-exports —
TPU v5e target from the brief: 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.
"""

from __future__ import annotations

import re
from typing import Dict

from repro.plan.roofline import (  # noqa: F401  (re-exported legacy names)
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS,
    TPU_V5E,
    RooflineTerms,
    model_flops,
    roofline_terms,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%[\w.\-]+\s*\(.*->.*\{\s*$")
_NAME_RE = re.compile(r"%([\w.\-]+)")


def _split_instruction(line: str):
    """``(name, type_text, op, operand_text)`` of one HLO instruction line,
    or None.  ``type_text`` may be a tuple ``(f32[..], s8[..])``."""
    ls = line.strip()
    if ls.startswith("ROOT "):
        ls = ls[5:]
    m = re.match(r"%([\w.\-]+)\s*=\s*", ls)
    if not m:
        return None
    rest = ls[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        type_text, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        type_text, _, rest = rest.partition(" ")
    op = re.match(r"([a-z][a-z0-9\-]*)\(", rest)
    if not op:
        return None
    depth, args = 0, []
    for ch in rest[op.end() - 1:]:
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            break
        args.append(ch)
    return m.group(1), type_text, op.group(1), "".join(args[1:])


def _top_level_operands(arg_text: str):
    """Split an operand list on the commas outside brackets and braces."""
    out, depth, cur = [], 0, []
    for ch in arg_text:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return [o.strip() for o in out if o.strip()]


def _type_bytes(type_text: str) -> int:
    return sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(type_text))


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device operand bytes by collective kind, from partitioned HLO.

    For each collective instruction we sum the bytes of its OPERANDS —
    what each device contributes to the wire.  An operand printed with
    its shape (``f32[96,4]{1,0} %x``) is read in place; one printed by
    name alone (``%bitcast``, ``%param.1``, as current XLA does) is
    resolved to the type of the instruction that defines it in the same
    computation.  ``-start`` async variants are counted; ``-done`` ops are
    not (they carry the same buffers).
    """
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    defs: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if _COMPUTATION_RE.match(line.strip()):
            defs = {}
            continue
        inst = _split_instruction(line)
        if inst is None:
            continue
        name, type_text, op, arg_text = inst
        defs[name] = type_text
        base = op[:-6] if op.endswith("-start") else op
        if base not in COLLECTIVE_OPS:
            continue
        for operand in _top_level_operands(arg_text):
            if _SHAPE_RE.search(operand):
                out[base] += _type_bytes(operand)
                continue
            ref = _NAME_RE.search(operand)
            if ref is None or ref.group(1) not in defs:
                raise ValueError(
                    f"{op} operand {operand!r} has no shape and no "
                    f"definition before it: {line.strip()[:200]}"
                )
            out[base] += _type_bytes(defs[ref.group(1)])
    return out


def roofline(
    cost: Dict[str, float],
    hlo_text: str,
    chips: int,
) -> RooflineTerms:
    """Derive the three roofline terms from cost_analysis + partitioned HLO.

    cost_analysis flops/bytes on the partitioned module are per-device
    already; terms are seconds per step on the target hardware (the
    arithmetic is ``repro.plan.roofline.roofline_terms`` against the TPU
    v5e model; this wrapper adds the HLO collective parsing).
    """
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    return roofline_terms(flops, hbm, coll, chips, device=TPU_V5E)

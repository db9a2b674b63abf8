"""Roofline terms from a compiled dry-run artifact (DESIGN.md §6).

``cost_analysis()`` supplies HLO FLOPs and bytes; collective bytes are NOT in
cost_analysis, so we parse the optimized HLO text and sum the *result* sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (result size == moved payload per participating device for
these ops; tuples are summed element-wise).

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,1024,7168]{2,1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind over the optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\]{},\d]+)\s+"
                     r"([a-z\-]+)", stripped)
        if not m:
            continue
        op = m.group(2)
        if op.rstrip("-start").rstrip("-done") in _COLLECTIVES or op in _COLLECTIVES:
            base = op
            for c in _COLLECTIVES:
                if op.startswith(c):
                    base = c
                    break
            else:
                continue
            out[base] += _shape_bytes(m.group(1))
            out["count"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    coll_breakdown: Dict[str, int]
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline(compiled, chips: int, hlo_text: Optional[str] = None,
             model_flops: Optional[float] = None) -> Roofline:
    from repro.launch import hlo_parse

    text = hlo_text if hlo_text is not None else compiled.as_text()
    # XLA's cost_analysis counts while bodies once (scan-over-layers would be
    # undercounted ~L-fold); the trip-count-aware parser fixes that.
    parsed = hlo_parse.analyze(text)
    flops = parsed.flops
    hbm = parsed.bytes
    ca = compiled.cost_analysis() or {}
    coll = {k: int(v) for k, v in parsed.coll.items()}
    coll["count"] = collective_bytes(text)["count"]
    coll["xla_flops_unscaled"] = int(ca.get("flops", 0))
    coll_total = float(sum(parsed.coll.values()))

    # The compiled module is the PARTITIONED (per-device) program, so
    # cost_analysis FLOPs/bytes and HLO shapes are already per chip.
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    collective_s = coll_total / ICI_BW

    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = (model_flops / (flops * chips)) if (model_flops and flops) else None
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=coll_total, chips=chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, coll_breakdown=coll,
        model_flops=model_flops, useful_ratio=useful,
    )


def train_model_flops(num_params: int, num_tokens: int,
                      active_params: Optional[int] = None) -> float:
    """6·N·D (dense) or 6·N_active·D (MoE) per step."""
    n = active_params if active_params is not None else num_params
    return 6.0 * n * num_tokens


def decode_model_flops(num_params: int, batch: int,
                       active_params: Optional[int] = None) -> float:
    """2·N per generated token (forward only), times the batch."""
    n = active_params if active_params is not None else num_params
    return 2.0 * n * batch


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:  # noqa: BLE001 - backend-dependent API
        return {"error": str(e)}

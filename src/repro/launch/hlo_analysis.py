"""Roofline-term extraction from a compiled (AOT) XLA artifact.

Three terms per (arch × shape × mesh), per DESIGN.md §6:

    compute    = HLO_FLOPs_total        / (chips · peak bf16 FLOP/s)
    memory     = HLO_bytes_total        / (chips · HBM bandwidth)
    collective = collective_bytes_total / (chips · ICI bandwidth/link)

The rates are the target chip's published peaks
(``repro.launch.mesh.chip_peaks``, keyed by ``device_kind``).

IMPORTANT: for an SPMD-partitioned module, ``compiled.cost_analysis()``
and the HLO text describe the PER-DEVICE program, so the measured FLOPs
/ bytes / collective-result-bytes are already divided by ``chips`` —
each term below therefore divides by the single-chip rate only.

``cost_analysis`` provides FLOPs and bytes-accessed.  Collective bytes
are NOT in cost_analysis — we parse the post-SPMD HLO text and sum the
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction; that sum = bytes one chip
injects into the ICI per step.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.launch.mesh import ChipPeaks, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# one typed buffer inside an HLO shape, e.g. ``bf16[64,128,8,128]{3,2,1,0}``
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# `  %name = <shape-or-tuple> op-name(` — post-optimization HLO instruction
_INSTR_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?))\s+"
    r"(" + "|".join(COLLECTIVE_OPS) + r")(?:-(?:start|done))?\(",
)


def _shape_bytes(shape_text: str) -> int:
    """Sum bytes over every typed buffer in ``shape_text`` (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_by_op(hlo_text: str) -> Dict[str, int]:
    """Per-collective-op result bytes from post-SPMD HLO text.

    ``-start`` ops are counted, matching ``-done`` duplicates are not
    (async pairs name the same transfer twice).
    """
    out: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    counts: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    for m in _INSTR_RE.finditer(hlo_text):
        shape_text, op = m.group(1), m.group(2)
        full = m.group(0)
        if f"{op}-done(" in full:
            continue
        out[op] += _shape_bytes(shape_text)
        counts[op] += 1
    out["_counts"] = counts  # type: ignore[assignment]
    return out


def total_collective_bytes(hlo_text: str) -> int:
    d = collective_bytes_by_op(hlo_text)
    return sum(v for k, v in d.items() if not k.startswith("_"))


def _cost_value(cost, key: str) -> float:
    return float(cost.get(key, 0.0))


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    device_kind: str            # target chip, a key of mesh.CHIP_PEAKS
    hlo_flops: float            # PER CHIP (post-SPMD module)
    hlo_bytes: float            # PER CHIP bytes accessed
    collective_bytes: int       # PER CHIP ICI bytes
    collective_detail: Dict[str, int]
    model_flops: float = 0.0    # 6·N(_active)·D — GLOBAL
    bytes_per_device: Optional[Dict[str, float]] = None

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        # collective_bytes is already per-chip
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops * self.n_chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_chips": self.n_chips, "device_kind": self.device_kind,
            "hlo_flops_per_chip": self.hlo_flops,
            "hlo_bytes_per_chip": self.hlo_bytes,
            "collective_bytes_per_chip": self.collective_bytes,
            "collective_detail": {k: v for k, v in self.collective_detail.items()
                                  if not k.startswith("_")},
            "collective_counts": self.collective_detail.get("_counts", {}),
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "bytes_per_device": self.bytes_per_device,
        }


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     n_chips: int, device_kind: str,
                     model_flops: float = 0.0) -> RooflineTerms:
    """Roofline terms of ``compiled`` against ``device_kind``'s peaks."""
    chip_peaks(device_kind)             # unknown chip: fail before work
    cost = compiled.cost_analysis()
    flops = _cost_value(cost, "flops")
    byts = _cost_value(cost, "bytes accessed")
    hlo = compiled.as_text()
    det = collective_bytes_by_op(hlo)
    coll = sum(v for k, v in det.items() if not k.startswith("_"))

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        # live at once: arguments + outputs not aliased to them + temps
        "peak_bytes": float(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            - ma.alias_size_in_bytes
                            + ma.temp_size_in_bytes),
    }

    return RooflineTerms(
        arch=arch, shape=shape, mesh=mesh_name, n_chips=n_chips,
        device_kind=device_kind,
        hlo_flops=flops, hlo_bytes=byts, collective_bytes=coll,
        collective_detail=det, model_flops=model_flops,
        bytes_per_device=mem)

"""Roofline-term derivation from compiled XLA artifacts.

The paper's §4 adapts the Roofline model to NN accelerators (ops per byte of
weight memory).  This module applies the same methodology to the *new*
system: for every (arch x shape x mesh) dry-run cell we derive three roofline
terms from the compiled artifact — no hardware required:

    compute_s    = HLO_FLOPs  / (chips * PEAK_FLOPS)
    memory_s     = HLO_bytes  / (chips * HBM_BW)
    collective_s = sum(collective operand bytes) / (chips * ICI_BW)

All three inputs come from ``core.hlo_cost``'s structural HLO analysis
(trip-count-aware, slice-aware, collective-aware), which also supplies the
per-op FLOP/byte breakdown carried on :class:`RooflineTerms` so reports can
show *where* the counts come from.

Hardware model: one chip's published peaks from ``CHIP_PEAKS``, keyed by
the device kind JAX reports; the dry-run costs its cells against the v5e.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, with where they were published."""
    bf16_flops: float       # FLOP/s
    int8_ops: float         # OP/s
    hbm_bw: float           # bytes/s
    ici_bw: float           # bytes/s per chip-to-chip link
    source: str


# keyed by jax.Device.device_kind; a kind not listed here has no peaks
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, and
    # 1,600 Gbit/s of interconnect per chip over its four links
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
        ici_bw=1600e9 / 8 / 4,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them with their source to "
                       f"CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})")
    return CHIP_PEAKS[device_kind]


V5E = chip_peaks("TPU v5 lite")       # the dry-run's hardware model

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


def op_rows_from_by_op(by_op: Optional[Dict[str, Dict[str, float]]],
                       limit: Optional[int] = None):
    """(opcode, flops, bytes, count) rows from a by_op dict (as produced by
    CostTotals.by_op_dict / RooflineTerms.to_dict), heaviest first."""
    if not by_op:
        return []
    rows = sorted(
        ((op, d.get("flops", 0.0), d.get("bytes", 0.0), d.get("count", 0.0))
         for op, d in by_op.items()),
        key=lambda r: (r[1], r[2]), reverse=True)
    return rows[:limit] if limit else rows


@dataclasses.dataclass
class RooflineTerms:
    """The three roofline terms for one (arch x shape x mesh) cell."""
    cell: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collective_counts: Dict[str, int]
    model_flops: float = 0.0           # 6*N*D etc., "useful" flops
    peak_flops: float = V5E.bf16_flops
    bytes_per_device: Optional[dict] = None
    # per-op breakdown from hlo_cost (global = per-device x chips):
    # opcode -> {"flops": .., "bytes": .., "count": ..}
    by_op: Optional[Dict[str, Dict[str, float]]] = None
    collective_bytes_by_op: Optional[Dict[str, float]] = None

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.peak_flops)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * V5E.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * V5E.ici_bw)

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Lower-bound step time: terms overlap, the max dominates."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat / redundant compute."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_frac(self) -> float:
        """Fraction of the hardware roofline achieved if the step ran at its
        dominant term: useful model flops per second / peak."""
        if self.step_s == 0:
            return 0.0
        return (self.model_flops / self.step_s) / (self.chips * self.peak_flops)

    def op_rows(self, limit: Optional[int] = None):
        """(opcode, flops, bytes, count) heaviest-first, from by_op."""
        return op_rows_from_by_op(self.by_op, limit)

    def to_dict(self) -> dict:
        return {
            "cell": self.cell, "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_counts": self.collective_counts,
            "collective_bytes_by_op": self.collective_bytes_by_op,
            "by_op": self.by_op,
            "model_flops": self.model_flops,
            "peak_flops": self.peak_flops,
            "bytes_per_device": self.bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
        }


def from_compiled(cell: str, compiled, chips: int, *,
                  model_flops: float = 0.0,
                  peak_flops: float = V5E.bf16_flops,
                  hlo_text: Optional[str] = None) -> RooflineTerms:
    """Build RooflineTerms from a jax Compiled object.

    The compiled module is the per-device SPMD program, and XLA's own
    cost_analysis counts while-loop (scan) bodies once — so the roofline
    inputs come from `core.hlo_cost` (structural trip-count-aware HLO
    analysis), scaled to global by the chip count.
    """
    text = hlo_text if hlo_text is not None else compiled.as_text()
    from repro.core import hlo_cost as HC
    totals = HC.analyze(text)
    by_op = {op: {"flops": oc.flops * chips, "bytes": oc.bytes * chips,
                  "count": oc.count}
             for op, oc in totals.by_op.items()}
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                ma, "generated_code_size_in_bytes", None),
        }
    except Exception:
        pass
    return RooflineTerms(
        cell=cell, chips=chips,
        hlo_flops=totals.flops * chips,       # per-device program -> global
        hlo_bytes=totals.bytes * chips,
        collective_bytes=totals.collective_bytes * chips,
        collective_counts={k: int(v)
                           for k, v in totals.collective_counts.items()},
        collective_bytes_by_op={k: v * chips
                                for k, v in
                                totals.collective_bytes_by_op.items()},
        by_op=by_op, model_flops=model_flops,
        peak_flops=peak_flops, bytes_per_device=mem)


def save_report(terms: List[RooflineTerms], path: str) -> None:
    with open(path, "w") as f:
        json.dump([t.to_dict() for t in terms], f, indent=1)


def load_report(path: str) -> List[dict]:
    with open(path) as f:
        return json.load(f)

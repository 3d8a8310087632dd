"""The RegDem performance predictor, adapted to XLA artifacts.

The paper's contract: *statically rank code variants from the compiled
binary, never run the worst one, tie-break toward more optimizations*.
Here the "binary" is the SPMD-partitioned HLO module of a (sharding x
remat x microbatch x attention-impl) variant, and the stall model becomes
the three-term roofline:

    t(variant) = max(compute, memory, collective)     -- bound model
               + alpha * sum(non-dominant terms)      -- overlap imperfection

mirroring eq. 2/3's structure (per-unit contention + an empirical
adjustment).  ``alpha`` plays the role of the f(occupancy) fit: it was
calibrated so the ranking matches the measured ordering on the cells where
several variants were lowered.

The selector consumes records of a compiled variant (flops, bytes and wire
collective bytes per device, and its memory analysis) and returns the
variant to ship, exactly like :func:`repro.core.predictor.predict` does
for SASS variants.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

#: TPU v5e per-chip constants
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

#: imperfect-overlap weight (calibrated; see module docstring)
ALPHA = 0.15


@dataclasses.dataclass(frozen=True)
class VariantCost:
    name: str
    compute_s: float
    memory_s: float
    collective_s: float
    fits_hbm: bool
    #: optimization-option count for the paper's tie-break rule
    n_options: int = 0

    @property
    def terms(self) -> Dict[str, float]:
        return {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }

    @property
    def dominant(self) -> str:
        return max(self.terms, key=self.terms.get)

    @property
    def estimate_s(self) -> float:
        t = self.terms
        dom = max(t.values())
        return dom + ALPHA * (sum(t.values()) - dom)


def cost_from_record(rec: Dict[str, Any], name: Optional[str] = None,
                     hbm_bytes: int = 16 * 2**30, n_options: int = 0) -> VariantCost:
    """Build a VariantCost from a compiled variant's record."""
    wire = rec["collectives"].get("wire_bytes", rec["collectives"]["total_bytes"])
    mem = rec["memory"]
    used = mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"]
    return VariantCost(
        name=name or f"{rec['arch']}/{rec['shape']}/{rec.get('variant', 'base')}",
        compute_s=rec["flops"] / PEAK_FLOPS,
        memory_s=rec["bytes_accessed"] / HBM_BW,
        collective_s=wire / LINK_BW,
        fits_hbm=used <= hbm_bytes,
        n_options=n_options,
    )


def select(variants: List[VariantCost]) -> Tuple[VariantCost, List[VariantCost]]:
    """Rank variants; infeasible (HBM-overflow) ones are never chosen when a
    feasible variant exists (the paper's worst-case-avoidance property)."""
    if not variants:
        raise ValueError("no variants")
    feasible = [v for v in variants if v.fits_hbm] or list(variants)
    ranked = sorted(feasible, key=lambda v: (v.estimate_s, -v.n_options))
    return ranked[0], sorted(variants, key=lambda v: v.estimate_s)

"""Roofline model: chip peak specs + bound-class classification.

The TPU-v4 paper framing (PAPERS.md): every region of a step is limited
by whichever peak it saturates first — the MXU (compute), HBM
(memory), or the interconnect (collective).  Given a region's analytic
FLOPs / bytes-accessed / collective bytes (``obs.perf.hlo``), the
classification is mechanical:

    t_compute    = flops            / peak_flops
    t_memory     = bytes            / peak_hbm_bytes_per_s
    t_collective = collective_bytes / peak_ici_bytes_per_s
    bound        = argmax(t_*)
    est_s        = max(t_*)          # the roofline-optimal time

``arithmetic_intensity = flops / bytes`` against the ridge point
``peak_flops / peak_hbm`` tells the same story as a ratio: regions left
of the ridge cannot be fixed by more MXU utilization — only by moving
fewer bytes (fusion, bf16, layout).

Peak numbers are public per-chip specs.  HBM/ICI figures are
coarse (generation-level, not SKU-exact) — the CLASSIFICATION is the
product here, not a promise of achievable GB/s.  The CPU backend (and
"no device": offline arithmetic, unit tests) classifies against a
reference roofline flagged ``known=False`` so reports stay
deterministic off-chip; an accelerator whose ``device_kind`` is not in
the table is an ERROR — another chip's peaks would silently mis-plan
the engine and mis-state every utilization.  Stdlib-only.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from npairloss_tpu.obs.perf.costs import PEAK_FLOPS

# Bound classes a region can carry (pinned by tests/test_perf.py; the
# report schema promises exactly these values).
BOUND_CLASSES = ("compute", "memory", "collective", "unknown")


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip peaks: dense bf16 FLOP/s, HBM bytes/s, interconnect
    bytes/s (aggregate per chip, coarse).  ``ici`` is the intra-slice
    chip fabric; ``dcn`` the per-host data-center network crossed by
    multi-host (multi-process) collectives — the TPU-v4 paper's point
    is that the two differ by ~an order of magnitude, so a fleet
    bandwidth check against the wrong one is off by that factor."""

    device_kind: str
    flops: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float
    dcn_bytes_per_s: float = 25.0e9
    known: bool = True

    @property
    def ridge_ai(self) -> float:
        """FLOPs/byte at which compute and memory time are equal."""
        return self.flops / self.hbm_bytes_per_s


# Interconnect link kinds a collective can ride (fleet comms rows carry
# one of these; pinned by tests).
LINK_KINDS = ("ici", "dcn")

# (device_kind substring, HBM GB/s, ICI GB/s, DCN GB/s per host) — peak
# FLOP/s rides costs.PEAK_FLOPS so the two tables can never disagree on
# a kind.  DCN figures are generation-coarse (~200 Gb/s-class NICs for
# v4+, less for earlier): like the HBM/ICI columns, the CLASSIFICATION
# is the product, not a promise of achievable GB/s.
_BW_SPECS = [
    ("v6", 1640.0, 448.0, 50.0),
    ("v5p", 2765.0, 450.0, 50.0),
    ("v5 lite", 819.0, 160.0, 25.0),
    ("v5e", 819.0, 160.0, 25.0),
    ("v4", 1228.0, 300.0, 25.0),
    ("v3", 900.0, 280.0, 12.5),
    ("v2", 700.0, 62.0, 12.5),
]

# The CPU backend and "no device" (kind "") classify against this
# reference roofline (v4's figures) — deterministic output off-chip,
# flagged via known=False.  Never used for an accelerator.
DEFAULT_SPEC = ChipSpec("cpu (v4 reference roofline)", 275e12,
                        1228e9, 300e9, 25e9, known=False)


def chip_peaks(device_kind: str) -> ChipSpec:
    """Resolve a device kind to its peak spec (first substring match).
    ``"cpu"``/``""`` get the flagged reference spec; any other kind
    missing from the table raises ``ValueError``."""
    kind = (device_kind or "").lower()
    flops = {k: f for k, f in PEAK_FLOPS}
    for key, hbm, ici, dcn in _BW_SPECS:
        if key in kind and key in flops:
            return ChipSpec(device_kind, flops[key], hbm * 1e9,
                            ici * 1e9, dcn * 1e9)
    if kind in ("", "cpu"):
        return DEFAULT_SPEC
    raise ValueError(
        f"no peak spec for device kind {device_kind!r}: add it to "
        "obs.perf.costs.PEAK_FLOPS and obs.perf.roofline._BW_SPECS")


def interconnect_peak(spec: ChipSpec, link: str) -> float:
    """Peak bytes/s of the named link kind — the reference a fleet
    comms row's effective bandwidth is checked against."""
    if link not in LINK_KINDS:
        raise ValueError(f"link must be one of {LINK_KINDS}, got {link!r}")
    return spec.dcn_bytes_per_s if link == "dcn" else spec.ici_bytes_per_s


def classify(
    flops: float,
    bytes_accessed: float,
    collective_bytes: float = 0.0,
    spec: Optional[ChipSpec] = None,
) -> Dict[str, object]:
    """Roofline classification of one region; returns a dict with
    ``ai`` (flops/byte, None when bytes==0), ``bound`` (one of
    :data:`BOUND_CLASSES`), ``est_ms_at_roofline`` and the three time
    components (ms) behind the argmax.  A region with no cost at all
    classifies ``unknown``."""
    spec = spec if spec is not None else DEFAULT_SPEC
    t_c = max(flops, 0.0) / spec.flops
    t_m = max(bytes_accessed, 0.0) / spec.hbm_bytes_per_s
    t_i = max(collective_bytes, 0.0) / spec.ici_bytes_per_s
    times = {"compute": t_c, "memory": t_m, "collective": t_i}
    if t_c == t_m == t_i == 0.0:
        bound = "unknown"
    else:
        # Deterministic tie-break in BOUND_CLASSES order (compute wins
        # an exact compute/memory tie — it sits ON the ridge).
        bound = max(BOUND_CLASSES[:3], key=lambda k: times[k])
    ai = (flops / bytes_accessed) if bytes_accessed > 0 else None
    return {
        "ai": ai,
        "bound": bound,
        "est_ms_at_roofline": max(t_c, t_m, t_i) * 1e3,
        "compute_ms": t_c * 1e3,
        "memory_ms": t_m * 1e3,
        "collective_ms": t_i * 1e3,
    }

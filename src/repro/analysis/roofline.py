"""Roofline model: the three-term analysis over compiled-program artifacts.

Peaks come from :data:`PEAKS`, one row per ``device_kind`` as JAX reports
it (``jax.devices()[0].device_kind``), each with its published source.  An
artifact names the device kind it was compiled for; a kind missing from
the table is an error (:func:`peaks`), never a default.

    compute term    = HLO_FLOPs / (chips * peak)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = wire_bytes_per_device / link_bw
                      (wire bytes are already per-device -- see hlo.py)

The dominant term is the projected step time's lower bound; the roofline
fraction we report for the hillclimb is useful_model_flops / (dominant_term *
chips * peak).  ``repro.obs.report`` writes the table for the compiled
episode rollout.
"""
from __future__ import annotations

import dataclasses



@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # peak bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # inter-chip bytes/s per link
    source: str


#: published per-chip peaks, keyed by ``device_kind``
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for a kind with no
    row in :data:`PEAKS`."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to roofline.PEAKS (known: "
            f"{sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops: float
    chips: int
    peak_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs: how much compiled compute is useful."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful model FLOPs over what the chips could do in the bound
        time -- the score we hillclimb."""
        cap = self.bound_s * self.chips * self.peak_flops
        return self.model_flops / cap if cap > 0 else 0.0


def from_artifact(art: dict) -> Roofline:
    """The roofline of one compiled-program artifact from its
    cost_analysis numbers; the peaks are those of ``art["device_kind"]``
    (:func:`peaks`)."""
    pk = peaks(art["device_kind"])
    chips = art["n_devices"]
    flops, bytes_ = art["hlo_flops"], art["hlo_bytes"]
    return Roofline(
        compute_s=flops / (chips * pk.flops),
        memory_s=bytes_ / (chips * pk.hbm_bw),
        collective_s=art["collective_wire_bytes"] / pk.ici_bw,
        model_flops=art["model_flops"],
        hlo_flops=flops,
        chips=chips,
        peak_flops=pk.flops,
    )


def format_row(name: str, art: dict) -> str:
    r = from_artifact(art)
    return (f"| {name} | {r.compute_s*1e3:.1f} | {r.memory_s*1e3:.1f} | "
            f"{r.collective_s*1e3:.1f} | {r.dominant} | "
            f"{r.useful_flops_ratio:.2f} | {r.roofline_fraction:.3f} |")


"""Three-term roofline from a compiled dry-run artifact.

compute    = FLOPs_per_chip / peak_FLOPs
memory     = HBM_bytes_per_chip / HBM_bw
collective = collective_bytes_per_chip / link_bw

FLOPs / bytes come from ``compiled.cost_analysis()`` (per-partition
module).  Collective bytes are parsed from the post-SPMD HLO text
(``compiled.as_text()``): the summed result sizes of every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute op.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # bytes/s
    ici_bw: float   # bytes/s per link


# Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" - 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s interchip interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a chip not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?\S+\s*=\s*(\([^)]*\)|\S+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[\s(]",
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result bytes per collective op kind."""
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        shape_txt, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_txt)
        d = out.setdefault(kind, {"bytes": 0.0, "count": 0})
        d["bytes"] += b
        d["count"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes_per_chip: float
    n_chips: int
    device_kind: str
    model_flops: float = 0.0

    def __post_init__(self):
        self.peaks = chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_chip * self.n_chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful work / (chips x peak x achievable step time).  The
        achievable step time is the max of the three terms (perfect
        overlap assumption)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return self.model_flops / (self.n_chips * self.peaks.flops * t)

    def to_dict(self) -> dict:
        extra = {}
        if hasattr(self, "raw_cost_analysis"):
            extra["raw_cost_analysis"] = self.raw_cost_analysis
        if hasattr(self, "collectives_by_kind"):
            extra["collectives_by_kind"] = self.collectives_by_kind
        return {
            **extra,
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "n_chips": self.n_chips,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def from_compiled(compiled, n_chips: int, model_flops: float,
                  device_kind: str,
                  hlo_text: Optional[str] = None) -> Roofline:
    from . import hlo_cost

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    # trip-count-aware walk (XLA cost_analysis counts scan bodies once)
    walked = hlo_cost.analyze(text)
    flops = max(raw_flops, walked["flops"])
    byts = max(raw_bytes, walked["bytes"])
    coll_bytes = walked["collective_bytes"]
    if coll_bytes == 0.0:
        coll = parse_collectives(text)
        coll_bytes = sum(d["bytes"] for d in coll.values())
    r = Roofline(
        flops_per_chip=flops,
        hbm_bytes_per_chip=byts,
        collective_bytes_per_chip=coll_bytes,
        n_chips=n_chips,
        device_kind=device_kind,
        model_flops=model_flops,
    )
    r.raw_cost_analysis = {"flops": raw_flops,  # type: ignore[attr-defined]
                           "bytes": raw_bytes}
    r.collectives_by_kind = walked["collectives"]  # type: ignore
    return r

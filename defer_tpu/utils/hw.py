"""TPU hardware constants + chip identification (shared by the benches).

Public per-generation numbers used for MFU and for the analytic pipeline
model.  Peaks are bf16 dense FLOP/s per chip; ICI figures are one-way
bytes/s per link (the stage->stage hop rides one link of the torus).
Sources: public TPU spec sheets / the scaling-book tables.
"""

from __future__ import annotations

PEAK_BF16_FLOPS: dict[str, float] = {
    "v2": 46e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

#: one-way ICI bandwidth per link, bytes/s
ICI_BW_BYTES_S: dict[str, float] = {
    "v2": 5.0e10,
    "v3": 7.0e10,
    "v4": 4.5e10,
    "v5e": 4.5e10,
    "v5p": 9.0e10,
    "v6e": 9.0e10,
}

#: HBM bandwidth, bytes/s (public spec-sheet numbers)
HBM_BW_BYTES_S: dict[str, float] = {
    "v2": 7.0e11,
    "v3": 9.0e11,
    "v4": 1.228e12,
    "v5e": 8.19e11,
    "v5p": 2.765e12,
    "v6e": 1.64e12,
}


def hbm_bandwidth(gen: str) -> float:
    """HBM bytes/s for a generation; 0.0 when unknown."""
    return HBM_BW_BYTES_S.get(gen, 0.0)


def identify_chip(device) -> str:
    """Generation string for a jax device, read from its PJRT
    ``device_kind`` and nothing else; "unknown" when the kind names no
    generation in the tables above (every non-TPU device)."""
    kind = str(getattr(device, "device_kind", "")).lower().replace(" ", "")
    for gen in ("v6e", "v5p", "v5e", "v4", "v3", "v2"):
        if gen in kind:
            return gen
    if "v5lite" in kind:
        return "v5e"
    return "unknown"


def detect_chip(device) -> str:
    """:func:`identify_chip` for a device this process computes on: a
    TPU whose kind is not in the tables is an error (a peak for a chip
    nobody identified is a guess), any other platform is "unknown"."""
    gen = identify_chip(device)
    if gen == "unknown" and getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"TPU device_kind {getattr(device, 'device_kind', '')!r} is "
            f"not in defer_tpu/utils/hw.py's peak tables; add its "
            f"published peaks there before computing on it")
    return gen


def peak_flops(gen: str) -> float:
    """bf16 peak FLOP/s for a generation; 0.0 when unknown (callers must
    not fabricate MFU against a guessed peak)."""
    return PEAK_BF16_FLOPS.get(gen, 0.0)


def ici_bandwidth(gen: str) -> float:
    """One-way ICI bytes/s per link; 0.0 when unknown."""
    return ICI_BW_BYTES_S.get(gen, 0.0)


def analytic_pipeline_model(stage_latencies_s: list[float],
                            bytes_per_hop: int,
                            ici_bw_bytes_s: float) -> dict:
    """Predicted N-chip pipeline speedup from measured single-chip inputs.

    The written, checkable basis for the >=1.5x multi-chip claim when only
    one chip exists to measure (BASELINE.md target):

    * single device runs the stages back to back: ``T1 = sum(lat)``;
    * the full pipeline's steady-state step time is its slowest stage,
      plus the ICI hop where it cannot overlap:
      ``Tstep = max(lat) + hop`` (hop fully serialized — conservative;
      XLA overlaps collective-permute with compute when it can);
    * predicted speedup = ``T1 / Tstep``; the balance ratio
      ``max/mean`` says how much of the ideal N is lost to partition skew.
    """
    lats = list(stage_latencies_s)
    n = len(lats)
    t1 = sum(lats)
    tmax = max(lats)
    hop_s = (bytes_per_hop / ici_bw_bytes_s) if ici_bw_bytes_s > 0 else 0.0
    tstep = tmax + hop_s
    return {
        "num_stages": n,
        "sum_stage_ms": round(t1 * 1e3, 4),
        "max_stage_ms": round(tmax * 1e3, 4),
        "hop_ms": round(hop_s * 1e3, 5),
        "balance_max_over_mean": round(tmax / (t1 / n), 4) if t1 else None,
        "predicted_speedup_vs_single_chip": round(t1 / tstep, 4)
        if tstep else None,
        "predicted_efficiency_vs_ideal": round(t1 / tstep / n, 4)
        if tstep else None,
        "comm_model": "hop serialized after slowest stage (conservative)",
    }

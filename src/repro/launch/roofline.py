"""Roofline terms from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / peak FLOP/s
    memory term     = HLO_bytes / peak HBM bytes/s
    collective term = wire_bytes / ICI bytes/s per link

with the peaks of the device the terms are for, looked up by its
``jax.Device.device_kind`` in ``PEAKS`` (a kind not in the table raises).

cost_analysis() is per-device and counts scan bodies once (measured fact,
DESIGN.md §5), so per-cell totals are assembled as

    total = step_cost + sum_probes multiplier x probe_cost

where probes re-compile one scanned layer group (and, for SSM archs, one
chunk-scan body) at full shapes/shardings. Collective bytes come from the HLO
parser, which multiplies loop bodies by their trip counts directly.
"""
from __future__ import annotations

from typing import Dict

# Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s_per_link": 50e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; raises for an unknown kind
    rather than measuring against another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def combine_costs(step_cost: Dict, probe_costs) -> Dict[str, float]:
    flops = step_cost.get("flops", 0.0)
    byts = step_cost.get("bytes accessed", 0.0)
    for mult, cost in probe_costs:
        flops += mult * cost.get("flops", 0.0)
        byts += mult * cost.get("bytes accessed", 0.0)
    return {"flops_per_device": float(flops), "bytes_per_device": float(byts)}


def roofline(device_kind: str, flops_per_device: float,
             bytes_per_device: float,
             wire_bytes_per_device: float) -> Dict[str, float]:
    peak = peaks(device_kind)
    compute_s = flops_per_device / peak["flops_bf16"]
    memory_s = bytes_per_device / peak["hbm_bytes_per_s"]
    collective_s = wire_bytes_per_device / peak["ici_bytes_per_s_per_link"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms.update({
        "dominant": dom,
        "step_time_lower_bound_s": bound,
        # fraction of the bound the compute term occupies = roofline fraction
        "roofline_fraction": compute_s / bound if bound > 0 else 0.0,
    })
    return terms


def model_flops(cfg, kind: str, seq: int, batch: int) -> float:
    """MODEL_FLOPS (all devices): 6·N_active·D train; 2·N_active·tokens decode."""
    n_act = cfg.n_active_params
    if kind == "train":
        return 6.0 * n_act * seq * batch
    if kind == "prefill":
        return 2.0 * n_act * seq * batch
    return 2.0 * n_act * batch  # decode: one token per sequence

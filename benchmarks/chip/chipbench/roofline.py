"""Published peaks per chip and the bytes the fused scan needs.

``PEAKS`` is copied from ``repro/launch/roofline.py``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
1,600 Gbit/s chip-to-chip interconnect (4 links of 50 GB/s).
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s_per_link": 50e9,
    },
}

WORD = 4  # bytes: the scan compares and accumulates in 32-bit words


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def scan_bytes(rows: int, n_num: int, n_cat: int, measures_read: int,
               snippets: int, cat_width: int, n_measures: int) -> int:
    """Bytes one scan call must move: each of ``rows`` sample rows read
    once -- its numeric and categorical columns and the measures the
    snippets read -- plus the snippet arrays (bounds, categorical
    membership, aggregate and measure codes) and the per-snippet partials
    written back (sum and sum of squares per measure, and the count).

    Only shapes enter the count: it is the same whatever tiles, padding or
    dtypes an implementation uses.
    """
    per_row = n_num + n_cat + measures_read
    per_snippet = 2 * n_num + cat_width + 2 + 2 * n_measures + 1
    return WORD * (rows * per_row + snippets * per_snippet)

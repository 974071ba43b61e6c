"""Operations and bytes each Pallas kernel's algorithm needs, counted from
the shapes the harness knows, never from the padded operands, so that a
later kernel that drops the padding or fuses the launches is read
against the same work.

``price_bundle`` (``kernels/pricing.py``): three float32 dot products
over R resources for each (slot, machine) row a bundle pass prices (W*H
rows for a whole plan, H for one slot): 2*3*R flops a row; it reads the
rows' R float32 prices and, once a launch, the 3*R weights, and writes 3
float32 sums a row.

``minplus`` (``kernels/minplus.py``): one launch per DP slot step over
Q+1 states: cur[u] = min_{v<=u} prev[u-v] + cost[v] is (Q+1)(Q+2)/2 adds
and as many compares; it reads prev and cost and writes cur, (Q+1)
float32 each.

The roofline share is least time over measured time, with least time the
larger of flops/peak_flops and bytes/peak_bytes_per_s.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
F32 = 4


def price_bundle(rows: int, R: int, launches: int) -> Tuple[float, float]:
    flops = 2.0 * 3 * R * rows
    nbytes = F32 * (rows * R + 3 * R * launches + 3 * rows)
    return flops, nbytes


def minplus(Q: int) -> Tuple[float, float]:
    n = Q + 1
    pairs = n * (n + 1) // 2
    return 2.0 * pairs, F32 * 3.0 * n


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 pk: Dict[str, float]) -> Tuple[float, str]:
    """(share of the roofline in %, the bound that sets it)."""
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound

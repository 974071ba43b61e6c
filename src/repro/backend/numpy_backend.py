"""Host NumPy backend: the bit-parity reference implementation.

Every operation here is the verbatim arithmetic ``Cluster`` ran before the
backend split (same np calls, same order, same in-place updates), so a
numpy-backed cluster remains bit-identical to ``core/_reference.py`` at
the golden seeds — the parity guarantee the rest of the repo leans on.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from . import ArrayBackend


class NumpyBackend(ArrayBackend):
    name = "numpy"
    is_device = False

    # ---- array lifecycle ------------------------------------------------
    def zeros(self, shape) -> np.ndarray:
        return np.zeros(shape)

    def to_host(self, arr, site: str = "to_host") -> np.ndarray:
        return np.asarray(arr)

    # ---- ledger mutations ----------------------------------------------
    def ledger_add(self, used: np.ndarray, t: int, needs) -> np.ndarray:
        for h, need in needs:
            used[t, h] += need
        return used

    def ledger_sub_clamped(self, used: np.ndarray, t: int, needs) -> np.ndarray:
        for h, need in needs:
            row = used[t, h] - need
            assert np.all(row >= -1e-6), (
                f"release would drive ledger negative at t={t} h={h}: {row}"
            )
            np.maximum(row, 0.0, out=row)
            used[t, h] = row
        return used

    def ledger_advance(self, used: np.ndarray, steps: int) -> np.ndarray:
        k = min(steps, used.shape[0])
        if k >= used.shape[0]:
            used[:] = 0.0
        else:
            used[:-k] = used[k:]
            used[-k:] = 0.0
        return used

    # ---- derived tensors ------------------------------------------------
    def free_tensor(self, used: np.ndarray, cap: np.ndarray) -> np.ndarray:
        return cap[None, :, :] - used

    def price_tensor(self, used: np.ndarray, cap: np.ndarray,
                     u: np.ndarray, L: float) -> np.ndarray:
        # the exact clip/divide/pow sequence of PriceTable.prewarm
        capb = cap[None, :, :]
        pos = capb > 0
        frac = np.zeros_like(used)
        np.divide(used, np.broadcast_to(capb, used.shape), out=frac,
                  where=np.broadcast_to(pos, used.shape))
        np.clip(frac, 0.0, 1.0, out=frac)
        out = L * (u[None, None, :] / L) ** frac
        return np.where(pos, out, u[None, None, :])

    def oversubscribed(self, used: np.ndarray, cap: np.ndarray,
                       tol: float) -> bool:
        over = used - cap[None, :, :]
        return bool((over > tol).any())

    def snapshot_bundle(self, price_row, free_row, wdem, sdem, gamma):
        from ..kernels.pricing import price_bundle_numpy
        return price_bundle_numpy(np.asarray(price_row),
                                  np.asarray(free_row), wdem, sdem, gamma)

    def snapshot_bundle_batch(self, price_ops, free_ops, wdem, sdem, gamma):
        from ..kernels.pricing import price_bundle_batch_numpy
        return price_bundle_batch_numpy(np.asarray(price_ops),
                                        np.asarray(free_ops),
                                        wdem, sdem, gamma)

    def minplus_default(self) -> Optional[str]:
        return None

    def lp_solver_default(self) -> str:
        # host ledger, host LP: the exact-replay cover/packing solver is
        # bit-identical to the stacked simplex and strictly faster
        return "cover_packing"

"""Pluggable array backend for the (T, H, R) ledger and Q_h^r pricing.

The scheduler's per-admission hot loop — rebuilding the price tensor
p_h^r[t] = Q_h^r(rho_h^r[t]) and the per-machine feasibility/head-room
vectors over the dense ledger — is pure array arithmetic. This package
abstracts *where* that arithmetic runs:

  * ``numpy`` (default) — the ledger is a host ``np.ndarray`` and every
    operation is byte-for-byte the pre-backend code path, preserving the
    repo's bit-parity guarantee against ``core/_reference.py``;
  * ``jax``   — the ledger lives as a device-resident ``jax.Array``
    (float64 via the scoped ``jax.enable_x64(True)``), commits/releases
    are functional ``.at[]`` updates, and repricing + free-capacity
    tensors are jit-compiled on device. Host syncs happen at explicit,
    version-cached points only: when an admission decision needs the
    (T, H, R) price/free tensors on the host (``PriceTable.prewarm`` /
    ``Cluster.free_matrix``) and when a ``PriceSnapshot`` pulls its five
    per-machine (H,) decision vectors. The jax backend is *tolerance*
    -parity (see ``tests/test_backend.py``): device pow/exp differ from
    NumPy by ulps, so decisions are checked for equivalence rather than
    bit-equality.

Selection
---------
``get_backend(None)`` resolves, in order: the ``REPRO_BACKEND``
environment variable (``numpy`` | ``jax``) and then the ``numpy``
default. ``make_cluster(..., backend="jax")`` or
``Cluster(machines, horizon, backend="jax")`` select per-cluster; an
``ArrayBackend`` instance is also accepted anywhere a name is.

The backend boundary (see ``docs/ARCHITECTURE.md``) deliberately sits
*below* the decision logic: Algorithm 2/3/4's host-side control flow (LP
pivots, rounding draws, greedy repair) is identical under both backends —
only the ledger state, the repricing sweep, and the snapshot reductions
move to the device.
"""
from __future__ import annotations

import os
from typing import List, Optional, Union

import numpy as np

_INSTANCES = {}


class ArrayBackend:
    """Contract for ledger/pricing array operations.

    Implementations hold no per-cluster state (they are process-wide
    singletons); the ledger array itself is owned by ``Cluster`` and
    passed in/out of every mutating op (functional style — the numpy
    backend mutates in place and returns the same array, the jax backend
    returns a new device array).
    """

    name = "abstract"
    #: True when the ledger array lives off-host (callers must route host
    #: reads through ``to_host`` / the version-cached host mirrors).
    is_device = False

    # ---- array lifecycle ------------------------------------------------
    def zeros(self, shape) -> "np.ndarray":
        """A fresh all-zero ledger array of the backend's native type."""
        raise NotImplementedError

    def to_host(self, arr, site: str = "to_host") -> np.ndarray:
        """The array as a host ``np.ndarray`` (no-op for numpy; a device
        sync for jax — call only at the documented sync points). ``site``
        names the read in the jax backend's ``device.sync`` span."""
        raise NotImplementedError

    # ---- ledger mutations (Algorithm 1 step 3 and its inverses) ---------
    def ledger_add(self, used, t: int, needs):
        """rho[t, h] += need for every (h, need (R,)) pair in ``needs``."""
        raise NotImplementedError

    def ledger_sub_clamped(self, used, t: int, needs):
        """rho[t, h] -= need, clamped at zero (double-release guard)."""
        raise NotImplementedError

    def ledger_advance(self, used, steps: int):
        """Slide the ledger ``steps`` rows toward t=0, zero-filling the
        tail (rolling-horizon mode; see ``Cluster.advance``)."""
        raise NotImplementedError

    # ---- derived tensors ------------------------------------------------
    def free_tensor(self, used, cap: np.ndarray):
        """C - rho as a full (T, H, R) tensor (device-resident for jax)."""
        raise NotImplementedError

    def price_tensor(self, used, cap: np.ndarray, u: np.ndarray, L: float):
        """Q_h^r over the whole ledger: the (T, H, R) price tensor of
        Eq. (12), ``L * (U^r/L) ** clip(rho/C, 0, 1)`` with zero-capacity
        resources pinned at their ceiling U^r."""
        raise NotImplementedError

    def oversubscribed(self, used, cap: np.ndarray, tol: float) -> bool:
        """True if any ledger cell exceeds capacity by more than tol."""
        raise NotImplementedError

    def snapshot_bundle(self, price_row, free_row, wdem: np.ndarray,
                        sdem: np.ndarray, gamma: float):
        """The five per-machine decision vectors a ``PriceSnapshot``
        needs, reduced from one slot's (H, R) price/free matrices:
        (wprice, sprice, coloc, max_w, max_s) as host float64 arrays.
        The masked reductions run on device for the jax backend (via
        ``repro.kernels.pricing``)."""
        raise NotImplementedError

    def snapshot_bundle_batch(self, price_ops, free_ops, wdem: np.ndarray,
                              sdem: np.ndarray, gamma: float):
        """Fused form of ``snapshot_bundle`` over a (W, H, R) slot stack:
        five (W, H) host float64 arrays, one row per slot. This is the
        solve-plan layer's one bundle pass per (job, plan) — on the jax
        backend the whole stack reduces in a single device dispatch and
        a single host sync instead of W per-slot round trips; on numpy
        the per-resource accumulation order is preserved per slot, so
        each row is bit-identical to the per-slot call."""
        raise NotImplementedError

    # ---- policy hints ---------------------------------------------------
    def minplus_default(self) -> Optional[str]:
        """Preferred ``kernels.minplus`` backend when
        ``SubproblemConfig.minplus_backend`` is None. The numpy backend
        returns None (bit-stable NumPy step); the jax backend returns
        "pallas" only when actually running on a TPU, so CPU-only jax
        keeps the decision-stable float64 path."""
        return None

    def lp_solver_default(self) -> str:
        """Preferred external-LP dispatch when
        ``SubproblemConfig.lp_solver`` is None: "cover_packing" routes
        shape-matched Algorithm-4 LPs through the structure-aware
        exact-replay solver (``repro.core.cover_packing``; bit-identical
        to the stacked simplex, which remains the fallback), "simplex"
        forces the stacked-tableau path.  Both current backends prefer
        "cover_packing" — the LP solve is host-side float64 control flow
        under both — but the hint sits on the backend so a future
        device-resident LP can claim its own dispatch without touching
        the plan layer."""
        return "cover_packing"


def available_backends() -> List[str]:
    return ["numpy", "jax"]


def get_backend(
    spec: Union[None, str, ArrayBackend] = None
) -> ArrayBackend:
    """Resolve a backend: an instance passes through; a name selects the
    singleton; None reads ``REPRO_BACKEND`` and falls back to numpy."""
    if isinstance(spec, ArrayBackend):
        return spec
    name = spec or os.environ.get("REPRO_BACKEND", "").strip() or "numpy"
    inst = _INSTANCES.get(name)
    if inst is not None:
        return inst
    if name == "numpy":
        from .numpy_backend import NumpyBackend
        inst = NumpyBackend()
    elif name == "jax":
        from .jax_backend import JaxBackend
        inst = JaxBackend()
    else:
        raise ValueError(
            f"unknown REPRO_BACKEND {name!r}; available: {available_backends()}"
        )
    _INSTANCES[name] = inst
    return inst


__all__ = ["ArrayBackend", "available_backends", "get_backend"]

"""Device-resident jax backend for the ledger and pricing tensors.

The (T, H, R) ledger is a float64 ``jax.Array`` (double precision via
the scoped ``jax.enable_x64(True)`` context — the global x64 flag is never
flipped, so the rest of the repo's float32 jax code is unaffected).
Mutations are functional ``.at[]`` updates; the two hot derived tensors —
``free_tensor`` (C - rho) and ``price_tensor`` (Eq. 12 over the whole
ledger) — are jit-compiled and stay on device until a caller explicitly
syncs via ``to_host`` at the documented admission-decision points.

``trace_counts`` records how many times each jitted function was actually
*traced* (the counter increments inside the traced Python body, which only
runs at trace time). The no-host-copy regression test asserts the count
stays flat across repeated repricings: a silent fallback to eager numpy —
or a shape-instability retrace storm — would show up as a growing count.

Snapshot reductions (``snapshot_bundle``) run through
``repro.kernels.pricing``: the jitted jnp path by default, the Pallas
masked-reduction kernel when running on TPU (or when forced via
``REPRO_PRICE_KERNEL=pallas``, which off-TPU uses Pallas interpret mode —
slow, test-only). The release clamp never asserts on this backend (the
assert would force a device sync per release); the clamp itself is
preserved, and the invariant is covered by the parity tests.

Every op that enqueues device work runs under a ``device.launch`` span
and every blocking read under a ``device.sync`` span (``repro.obs.trace``),
each named by its ``site``; every jitted function carries a stable name
(``jit_ledger_scatter_add`` and so on, with a ``jax.named_scope`` of the
same name over its body) so that its ops can be told apart in a profile.

Building the backend on a TPU configures JAX's persistent compilation cache
(``configure_compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` when set,
else the fixed ``<checkout>/.jax_cache``, keeping even sub-second jits.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _trace
from . import ArrayBackend

#: default persistent compile cache: a fixed path inside the checkout (the
#: path is part of the cache key, so it must not move between runs)
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at a stable directory.

    ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself) wins when set;
    otherwise the cache lives at ``<checkout>/.jax_cache``. The minimum
    compile time to persist is lowered to zero: the scheduler's jits are
    many and each compiles in well under JAX's default one second.

    Only a TPU process is configured: XLA:CPU reloads its cached
    executables with a host-feature mismatch error on every hit, and the
    CPU compiles are cheap."""
    if jax.default_backend() != "tpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class JaxBackend(ArrayBackend):
    name = "jax"
    is_device = True

    def __init__(self):
        configure_compile_cache()
        self.trace_counts: Dict[str, int] = {
            "free_tensor": 0, "price_tensor": 0,
        }

        def free_tensor(used, cap):
            self.trace_counts["free_tensor"] += 1
            with jax.named_scope("free_tensor"):
                return cap[None, :, :] - used

        def price_tensor(used, cap, u, L):
            self.trace_counts["price_tensor"] += 1
            with jax.named_scope("price_tensor"):
                capb = cap[None, :, :]
                pos = capb > 0
                frac = jnp.where(pos, used / jnp.where(pos, capb, 1.0), 0.0)
                frac = jnp.clip(frac, 0.0, 1.0)
                ub = u[None, None, :]
                out = L * (ub / L) ** frac
                return jnp.where(pos, out, ub)

        # jitted ledger scatters with the slot index as a TRACED scalar:
        # a python-int `t` would be baked into the jaxpr as a constant,
        # recompiling per (slot, width) pair instead of per width only
        def ledger_scatter_add(used, t, hs, vecs):
            with jax.named_scope("ledger_scatter_add"):
                return used.at[t, hs].add(vecs)

        def ledger_scatter_sub_clamped(used, t, hs, vecs):
            with jax.named_scope("ledger_scatter_sub_clamped"):
                rows = jnp.maximum(used[t, hs] - vecs, 0.0)
                return used.at[t, hs].set(rows)

        # the shift k (0 <= k <= T) is traced too: one compile serves
        # every step count; rows shifted in from the zero half are zero
        def ledger_advance(used, k):
            with jax.named_scope("ledger_advance"):
                padded = jnp.concatenate([used, jnp.zeros_like(used)])
                return jax.lax.dynamic_slice_in_dim(padded, k, used.shape[0])

        def oversubscribed(used, cap, tol):
            with jax.named_scope("oversubscribed"):
                return ((used - cap[None, :, :]) > tol).any()

        self._free_jit = jax.jit(free_tensor)
        self._price_jit = jax.jit(price_tensor)
        self._scatter_add = jax.jit(ledger_scatter_add)
        self._scatter_sub = jax.jit(ledger_scatter_sub_clamped)
        self._advance_jit = jax.jit(ledger_advance)
        self._over_jit = jax.jit(oversubscribed)

    # ---- array lifecycle ------------------------------------------------
    def zeros(self, shape):
        with _trace.launch("zeros"), jax.enable_x64(True):
            return jnp.zeros(shape, dtype=jnp.float64)

    def to_host(self, arr, site: str = "to_host") -> np.ndarray:
        return _trace.device_get(arr, site)

    # ---- ledger mutations ----------------------------------------------
    @staticmethod
    def _pad_scatter(hs: np.ndarray, vecs: np.ndarray, neutral_vec: bool):
        """Pad a per-machine scatter to the next power-of-two width so
        XLA compiles O(log H) scatter shapes instead of one per distinct
        machine count (each shape is a fresh ~50ms compile — the
        dominant cost of jax-backend commits before this padding).

        Padding entries repeat the LAST real machine index with either a
        zero vector (add form: duplicates sum, +0 is a no-op) or the
        last real vector (set form: duplicates write the same computed
        value, so scatter order cannot matter)."""
        k = hs.size
        width = 1
        while width < k:
            width <<= 1
        if width == k:
            return hs, vecs
        pad = width - k
        hs = np.concatenate([hs, np.full(pad, hs[-1], dtype=hs.dtype)])
        if neutral_vec:
            vecs = np.concatenate(
                [vecs, np.zeros((pad,) + vecs.shape[1:], dtype=vecs.dtype)]
            )
        else:
            vecs = np.concatenate(
                [vecs, np.broadcast_to(vecs[-1], (pad,) + vecs.shape[1:])]
            )
        return hs, vecs

    def ledger_add(self, used, t: int, needs):
        # one batched scatter-add: a per-machine loop of functional .at[]
        # updates would copy the whole (T, H, R) ledger once per machine
        if not needs:
            return used
        hs = np.array([h for h, _ in needs], dtype=np.int64)
        vecs = np.stack([need for _, need in needs])
        hs, vecs = self._pad_scatter(hs, vecs, neutral_vec=True)
        with _trace.launch("ledger_add"), jax.enable_x64(True):
            return self._scatter_add(used, np.int64(t), hs,
                                     jnp.asarray(vecs))

    def ledger_sub_clamped(self, used, t: int, needs):
        # _alloc_need yields each machine once, so gather-sub-clamp-set is
        # a single scatter; the power-of-two padding repeats the last
        # (machine, need) pair, whose recomputed row value is identical —
        # duplicate set-scatters of equal values are order-independent
        if not needs:
            return used
        hs = np.array([h for h, _ in needs], dtype=np.int64)
        vecs = np.stack([need for _, need in needs])
        hs, vecs = self._pad_scatter(hs, vecs, neutral_vec=False)
        with _trace.launch("ledger_sub_clamped"), jax.enable_x64(True):
            return self._scatter_sub(used, np.int64(t), hs,
                                     jnp.asarray(vecs))

    def ledger_advance(self, used, steps: int):
        k = min(steps, used.shape[0])
        with _trace.launch("ledger_advance"), jax.enable_x64(True):
            return self._advance_jit(used, np.int64(k))

    # ---- derived tensors ------------------------------------------------
    def free_tensor(self, used, cap: np.ndarray):
        with _trace.launch("free_tensor"), jax.enable_x64(True):
            return self._free_jit(used, cap)

    def price_tensor(self, used, cap: np.ndarray, u: np.ndarray, L: float):
        with _trace.launch("price_tensor"), jax.enable_x64(True):
            return self._price_jit(used, cap, u, np.float64(L))

    def oversubscribed(self, used, cap: np.ndarray, tol: float) -> bool:
        with _trace.launch("oversubscribed"), jax.enable_x64(True):
            over = self._over_jit(used, cap, np.float64(tol))
        with _trace.sync("oversubscribed"):
            return bool(over)

    @staticmethod
    def _price_kernel() -> Optional[str]:
        kernel = os.environ.get("REPRO_PRICE_KERNEL", "").strip() or None
        if kernel is None and jax.default_backend() == "tpu":
            kernel = "pallas"
        return kernel

    def snapshot_bundle(self, price_row, free_row, wdem, sdem, gamma):
        from ..kernels.pricing import price_bundle
        with jax.enable_x64(True):
            return price_bundle(price_row, free_row, wdem, sdem, gamma,
                                backend=self._price_kernel())

    def snapshot_bundle_batch(self, price_ops, free_ops, wdem, sdem, gamma):
        from ..kernels.pricing import price_bundle_batch
        with jax.enable_x64(True):
            return price_bundle_batch(price_ops, free_ops, wdem, sdem,
                                      gamma, backend=self._price_kernel())

    def minplus_default(self) -> Optional[str]:
        return "pallas" if jax.default_backend() == "tpu" else None

    def lp_solver_default(self) -> str:
        # the LP solve stays host-side float64 under the jax backend too
        # (pivot control flow is branch-heavy and decision-critical);
        # the structure-aware solver applies unchanged
        return "cover_packing"

"""Masked price-matrix reduction for Algorithm 4's per-(job, slot) snapshot.

A ``PriceSnapshot`` reduces one slot's (H, R) price and free-capacity
matrices into the five per-machine vectors every Algorithm-3/4 decision
reads:

    wprice[h] = sum_r p_h^r alpha_i^r          (worker price, below Eq. 26)
    sprice[h] = sum_r p_h^r beta_i^r           (PS price)
    coloc[h]  = sum_r p_h^r (alpha^r gamma + beta^r)   (internal sort key)
    max_w[h]  = floor(min_{r: alpha^r > 0} free_h^r / alpha^r)  (head-room)
    max_s[h]  = floor(min_{r: beta^r  > 0} free_h^r / beta^r)

i.e. three masked matrix-vector reductions plus two masked ratio
min-reductions. Three implementations:

  * ``price_bundle_numpy``  — the reference; reproduces the snapshot's
    per-resource accumulation order exactly (what the numpy backend's
    inline code computes);
  * ``price_bundle_jnp``    — one jit-compiled device pass; the jax
    backend's default off-TPU (float64 under the caller's
    ``jax.enable_x64(True)`` scope);
  * ``price_bundle_pallas`` — a Pallas TPU kernel for the three *price*
    reductions as an (8, Rp) x (tile, Rp) ``dot_general`` contraction on
    the MXU per grid step, over ``ROW_TILE``-row tiles of the operand
    padded to the float32 tile grid with zero-neutral padding. Off-TPU it
    runs in interpret mode. A lowering or compile failure raises: no
    path falls back to another implementation.

The Pallas path's price rows are float32 (like ``kernels/minplus.py``):
tolerance-tested against the references, auto-selected on a TPU, and
forceable via ``REPRO_PRICE_KERNEL=pallas`` for interpret-mode testing
(``REPRO_PRICE_KERNEL=jnp`` forces the float64 jnp pass on a TPU). The
head-room rows are NEVER float32 on any path: ``max_w`` / ``max_s`` are
integer-valued decisions (a float32 reciprocal-multiply can
overestimate them by a whole unit at exact-capacity boundaries, e.g.
free=8.9999999/demand=3 rounding up through floor), so the Pallas wrapper
computes them host-side in float64 with exactly the reference arithmetic.

``price_bundle`` dispatches and always returns five host float64 arrays —
the snapshot's host sync point under the jax backend. Each device path
runs its kernel call under a ``device.launch`` span and its host read
under a ``device.sync`` span (``repro.obs.trace``), both with the site
``price_bundle`` (one slot) or ``price_bundle_batch`` (the fused pass).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..obs import trace as _trace

_jnp_bundle = None                     # lazily created jit
_jnp_bundle_batch = None               # lazily created jit (fused multi-slot)
TRACE_COUNTS = {"bundle_jnp": 0, "bundle_batch_jnp": 0, "bundle_pallas": 0}

Bundle = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def price_bundle_numpy(price: np.ndarray, free: np.ndarray,
                       wdem: np.ndarray, sdem: np.ndarray,
                       gamma: float) -> Bundle:
    """Reference reduction — the exact arithmetic ``PriceSnapshot`` runs
    inline on the numpy backend (per-resource accumulation, zero-demand
    columns skipped, stable min-then-floor head-room)."""
    H = price.shape[0]
    wprice = np.zeros(H)
    sprice = np.zeros(H)
    coloc = np.zeros(H)
    for k in range(price.shape[1]):
        a = wdem[k]
        b = sdem[k]
        pcol = price[:, k]
        if a:
            wprice += pcol * a
        if b:
            sprice += pcol * b
        coloc += pcol * (a * gamma + b)

    def headroom(dem: np.ndarray) -> np.ndarray:
        pos = dem > 0
        if not pos.any():
            return np.full(H, np.inf)
        ratio = (free[:, pos] / dem[pos][None, :]).min(axis=1)
        return np.floor(np.maximum(ratio, 0.0))

    return wprice, sprice, coloc, headroom(wdem), headroom(sdem)


# ------------------------------------------------------------------- jnp
def _get_jnp_bundle():
    global _jnp_bundle
    if _jnp_bundle is None:
        import jax
        import jax.numpy as jnp

        def bundle_jnp(price, free, wdem, sdem, gamma):
            TRACE_COUNTS["bundle_jnp"] += 1
            wprice = price @ wdem
            sprice = price @ sdem
            coloc = price @ (wdem * gamma + sdem)

            def headroom(dem):
                pos = dem > 0
                ratio = jnp.where(
                    pos[None, :],
                    free / jnp.where(pos, dem, 1.0)[None, :],
                    jnp.inf,
                )
                return jnp.floor(jnp.maximum(jnp.min(ratio, axis=1), 0.0))

            return wprice, sprice, coloc, headroom(wdem), headroom(sdem)

        _jnp_bundle = jax.jit(bundle_jnp)
    return _jnp_bundle


def price_bundle_jnp(price, free, wdem: np.ndarray, sdem: np.ndarray,
                     gamma: float) -> Bundle:
    """One jit-compiled device pass; accepts device or host operands.

    The matrix-vector reductions accumulate in dot order rather than the
    reference's per-resource order — equal to ulps, covered by the
    tolerance parity tests, never by the bit-parity ones."""
    fn = _get_jnp_bundle()
    with _trace.launch("price_bundle"):
        out = fn(price, free, np.asarray(wdem, dtype=np.float64),
                 np.asarray(sdem, dtype=np.float64), float(gamma))
    return _trace.device_get(tuple(out), "price_bundle", np.float64)


# ---------------------------------------------------------------- pallas
#: rows of the price operand per grid step: a (4096, 128) f32 tile is
#: 2 MiB, so the double-buffered input stays far inside the default
#: scoped VMEM at any row count the plan can pass
ROW_TILE = 4096
_LANES = 128
#: block-index constant pinned to int32: a Python 0 traces as int64 under
#: the caller's x64 scope, which Mosaic cannot lower
_ZERO = np.int32(0)
_pallas_bundle = None                  # lazily created jit


def _round_up(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def _get_pallas_bundle():
    """jit: (W, H, R) or (H, R) prices + (8, Rp) weights -> (3, W*H) f32
    reductions.

    The f32 cast and the zero padding to the (row tile, 128-lane) grid run
    on the device, so a device-resident price tensor never visits the
    host. Zero padding is reduction-neutral: padded weight and price
    columns add nothing to a dot row, and padded rows are sliced off."""
    global _pallas_bundle
    if _pallas_bundle is None:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(p_ref, w_ref, o_ref):
            # HIGHEST: the MXU's default f32 precision rounds operands to
            # bf16 (relative error ~4e-3 on the chip); full-f32 passes
            # keep the reductions within float32 rounding
            o_ref[...] = jax.lax.dot_general(
                w_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                          # (8, tile)

        def bundle_pallas(price, wmat, interpret):
            TRACE_COUNTS["bundle_pallas"] += 1
            R = price.shape[-1]
            rows = price.size // R
            Rp = wmat.shape[1]
            tile = min(ROW_TILE, _round_up(rows, _LANES))
            Hp = _round_up(rows, tile)
            P = jnp.pad(price.reshape(rows, R).astype(jnp.float32),
                        ((0, Hp - rows), (0, Rp - R)))
            out = pl.pallas_call(
                kernel,
                grid=(Hp // tile,),
                in_specs=[pl.BlockSpec((tile, Rp), lambda i: (i, _ZERO)),
                          pl.BlockSpec((8, Rp), lambda i: (_ZERO, _ZERO))],
                out_specs=pl.BlockSpec((8, tile), lambda i: (_ZERO, i)),
                out_shape=jax.ShapeDtypeStruct((8, Hp), jnp.float32),
                interpret=interpret,
                name="price_bundle",
            )(P, wmat)
            return out[:3, :rows]

        _pallas_bundle = jax.jit(bundle_pallas, static_argnames="interpret")
    return _pallas_bundle


def bundle_weights(wdem: np.ndarray, sdem: np.ndarray,
                   gamma: float) -> np.ndarray:
    """(8, Rp) f32 weight rows: alpha, beta, alpha*gamma+beta, then zero."""
    R = wdem.shape[0]
    wmat = np.zeros((8, _round_up(R, _LANES)), dtype=np.float32)
    wmat[0, :R] = wdem
    wmat[1, :R] = sdem
    wmat[2, :R] = wdem * gamma + sdem
    return wmat


def _headroom_exact(free64: np.ndarray, dem: np.ndarray) -> np.ndarray:
    """floor(min over demand-positive resources of free/dem) in float64 —
    the reference arithmetic over the last axis; integer-valued, so never
    float32."""
    pos = dem > 0
    if not pos.any():
        return np.full(free64.shape[:-1], np.inf)
    ratio = (free64[..., pos] / dem[pos]).min(axis=-1)
    return np.floor(np.maximum(ratio, 0.0))


def price_bundle_batch_pallas(price, free, wdem: np.ndarray,
                              sdem: np.ndarray, gamma: float,
                              interpret: Optional[bool] = None,
                              site: str = "price_bundle_batch") -> Bundle:
    """Pallas TPU kernel for the fused batch (float32 prices).

    The (W, H, R) price stack is flattened to one (W*H, R) operand and
    reduced by a row-tiled MXU ``dot_general`` — one kernel launch for
    every slot of the plan. The head-room rows are computed host-side in
    float64 (see the module docstring: a float32 ratio can overestimate
    the integer head-room by a whole unit at exact-capacity boundaries,
    which would let the snapshot advertise a worker that does not fit).
    ``site`` names the call in its device spans; a device-resident
    ``free`` is read under its own ``device.sync`` (``<site>.free``)."""
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    free64 = _trace.device_get(free, site + ".free", np.float64)
    wdem = np.asarray(wdem, dtype=np.float64)
    sdem = np.asarray(sdem, dtype=np.float64)
    W, H = free64.shape[0], free64.shape[1]
    fn = _get_pallas_bundle()
    with _trace.launch(site):
        red = fn(price, bundle_weights(wdem, sdem, gamma),
                 interpret=interpret)
    out = _trace.device_get(red, site, np.float64).reshape(3, W, H)
    return (out[0], out[1], out[2],
            _headroom_exact(free64, wdem), _headroom_exact(free64, sdem))


def price_bundle_pallas(price, free, wdem: np.ndarray, sdem: np.ndarray,
                        gamma: float,
                        interpret: Optional[bool] = None) -> Bundle:
    """One slot's (H, R) reduction through the batch kernel (W = 1)."""
    free = _trace.device_get(free, "price_bundle.free", np.float64)
    out = price_bundle_batch_pallas(price, free[None], wdem, sdem,
                                    gamma, interpret=interpret,
                                    site="price_bundle")
    return tuple(o[0] for o in out)


# ------------------------------------------------- fused multi-slot batch
def price_bundle_batch_numpy(price: np.ndarray, free: np.ndarray,
                             wdem: np.ndarray, sdem: np.ndarray,
                             gamma: float) -> Bundle:
    """``price_bundle_numpy`` over a whole (W, H, R) slot stack in one
    pass, returning five (W, H) arrays. The per-resource accumulation
    loop is identical — each (t, h) element receives the same sequence of
    multiply-adds as the per-slot call, so every float is bit-identical
    to W separate ``price_bundle_numpy`` invocations."""
    W, H, _ = price.shape
    wprice = np.zeros((W, H))
    sprice = np.zeros((W, H))
    coloc = np.zeros((W, H))
    for k in range(price.shape[2]):
        a = wdem[k]
        b = sdem[k]
        pcol = price[:, :, k]
        if a:
            wprice += pcol * a
        if b:
            sprice += pcol * b
        coloc += pcol * (a * gamma + b)

    def headroom(dem: np.ndarray) -> np.ndarray:
        pos = dem > 0
        if not pos.any():
            return np.full((W, H), np.inf)
        ratio = (free[:, :, pos] / dem[pos][None, None, :]).min(axis=2)
        return np.floor(np.maximum(ratio, 0.0))

    return wprice, sprice, coloc, headroom(wdem), headroom(sdem)


def _get_jnp_bundle_batch():
    global _jnp_bundle_batch
    if _jnp_bundle_batch is None:
        import jax
        import jax.numpy as jnp

        def bundle_batch_jnp(price, free, wdem, sdem, gamma):
            TRACE_COUNTS["bundle_batch_jnp"] += 1
            wprice = price @ wdem                       # (W, H)
            sprice = price @ sdem
            coloc = price @ (wdem * gamma + sdem)

            def headroom(dem):
                pos = dem > 0
                ratio = jnp.where(
                    pos[None, None, :],
                    free / jnp.where(pos, dem, 1.0)[None, None, :],
                    jnp.inf,
                )
                return jnp.floor(jnp.maximum(jnp.min(ratio, axis=2), 0.0))

            return wprice, sprice, coloc, headroom(wdem), headroom(sdem)

        _jnp_bundle_batch = jax.jit(bundle_batch_jnp)
    return _jnp_bundle_batch


def price_bundle_batch_jnp(price, free, wdem: np.ndarray, sdem: np.ndarray,
                           gamma: float) -> Bundle:
    """One jit-compiled device pass over the whole (W, H, R) slot stack —
    the jax backend's fused bundle: W slots' decision vectors reduced
    with ONE dispatch and ONE host sync instead of W per-slot round
    trips. Dot-order accumulation (tolerance-equal to the reference, like
    the per-slot jnp path)."""
    fn = _get_jnp_bundle_batch()
    with _trace.launch("price_bundle_batch"):
        out = fn(price, free, np.asarray(wdem, dtype=np.float64),
                 np.asarray(sdem, dtype=np.float64), float(gamma))
    return _trace.device_get(tuple(out), "price_bundle_batch", np.float64)


def price_bundle_batch(price, free, wdem: np.ndarray, sdem: np.ndarray,
                       gamma: float, backend: Optional[str] = None) -> Bundle:
    """Fused multi-slot snapshot reduction; same backend contract as
    ``price_bundle`` but over (W, H, R) operands, returning five (W, H)
    host float64 arrays (one row per slot)."""
    if backend == "pallas":
        return price_bundle_batch_pallas(price, free, wdem, sdem, gamma)
    if backend == "numpy":
        return price_bundle_batch_numpy(np.asarray(price), np.asarray(free),
                                        wdem, sdem, gamma)
    return price_bundle_batch_jnp(price, free, wdem, sdem, gamma)


# -------------------------------------------------------------- dispatch
def price_bundle(price, free, wdem: np.ndarray, sdem: np.ndarray,
                 gamma: float, backend: Optional[str] = None) -> Bundle:
    """Snapshot reduction; backend in {None/"jnp", "pallas", "numpy"}.

    None means the jitted jnp pass — the jax array backend's default
    (Pallas is auto-selected by ``JaxBackend.snapshot_bundle`` only on an
    actual TPU). "numpy" forces the host reference (used by tests and the
    numpy array backend)."""
    if backend == "pallas":
        return price_bundle_pallas(price, free, wdem, sdem, gamma)
    if backend == "numpy":
        return price_bundle_numpy(np.asarray(price), np.asarray(free),
                                  wdem, sdem, gamma)
    return price_bundle_jnp(price, free, wdem, sdem, gamma)

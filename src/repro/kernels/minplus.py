"""Min-plus (tropical) vector-matrix step for the Algorithm-3 workload DP.

One forward step of the DP (Eq. 21) is a min-plus convolution

    cur[u] = min_{0 <= v <= u} prev[u - v] + tcost[v],

equivalently a tropical vector-matrix product ``cur = A (min,+) tcost`` with
the lower-triangular Toeplitz operand ``A[u, v] = prev[u - v]`` (+inf above
the diagonal). Three implementations, all returning the same values:

  * ``minplus_scalar``  — the pre-vectorization double loop (reference; also
    what the golden parity tests pin against);
  * ``minplus_numpy``   — one fancy-indexed Toeplitz build + row-min
    reduction; the default CPU path;
  * ``minplus_sweep_pallas`` — a Pallas TPU kernel of the tropical
    vec-mat product (broadcast add + lane-min reduce on the VPU), padded
    to the float32 tile grid and tiled over ``ROW_TILE``-row blocks up to
    ``MAX_P`` padded states, run for every step of a DP sweep inside one
    jitted device loop: one launch and one read for the whole sweep.
    ``minplus_pallas`` is its one-step case. Off-TPU it runs in interpret
    mode. A lowering or compile failure raises: nothing falls back to
    NumPy.

Besides the min values every implementation returns the DP ``choice`` array
(-1 for an unreachable state). Scalar and NumPy share the exact contract —
choice[u] = the smallest v whose candidate is within 1e-12 of the row
minimum (the scalar loop's acceptance hysteresis) — so backtracking
reconstructs bit-identical schedules on either. The Pallas path recovers
choice host-side via a plain float32 argmin (no hysteresis): near-ties
within ~1e-12, or values float32 rounding reorders, may backtrack
differently — one more reason the float32 kernel is opt-in and excluded
from the parity-guaranteed paths (see ``minplus_step``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..obs import trace as _trace

_INF = float("inf")


def minplus_scalar(
    prev: np.ndarray, tcost: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Reference double loop (the pre-vectorization dp.py inner loop)."""
    Q1 = prev.size
    cur = np.full(Q1, _INF)
    choice = np.full(Q1, -1, dtype=np.int64)
    for u in range(Q1):
        best, bestv = _INF, -1
        for v in range(0, u + 1):
            pu = prev[u - v]
            tc = tcost[v]
            if pu == _INF or tc == _INF:
                continue
            val = pu + tc
            if val < best - 1e-12:
                best, bestv = val, v
        cur[u] = best
        choice[u] = bestv
    return cur, choice


def _toeplitz_vals(prev: np.ndarray, tcost: np.ndarray) -> np.ndarray:
    """vals[u, v] = prev[u-v] + tcost[v], +inf above the diagonal."""
    Q1 = prev.size
    idx = np.arange(Q1)
    diff = idx[:, None] - idx[None, :]
    vals = np.where(diff >= 0, prev[np.abs(diff)], _INF) + tcost[None, :]
    return vals


def _choice_from_vals(vals: np.ndarray, best: np.ndarray) -> np.ndarray:
    """Smallest v within the 1e-12 hysteresis of each row minimum."""
    hit = vals <= best[:, None] + 1e-12
    choice = np.argmax(hit, axis=1).astype(np.int64)
    choice[~np.isfinite(best)] = -1
    return choice


def minplus_numpy(
    prev: np.ndarray, tcost: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized step, bit-identical to ``minplus_scalar``.

    The scalar loop's 1e-12 acceptance hysteresis can settle on a candidate
    up to 1e-12 ABOVE the true row minimum when near-ties are present, so
    rows whose value set contains entries strictly between the minimum and
    minimum+2e-12 are replayed through the sequential scan (exact ties and
    isolated minima — the overwhelmingly common cases — already agree)."""
    vals = _toeplitz_vals(prev, tcost)
    best = vals.min(axis=1)
    choice = _choice_from_vals(vals, best)
    finite = np.isfinite(best)
    near = (vals <= best[:, None] + 2e-12) & (vals > best[:, None])
    replay = np.flatnonzero(finite & near.any(axis=1))
    for u in replay:
        b, bv = _INF, -1
        row = vals[u]
        for v in range(u + 1):
            val = row[v]
            if val == _INF:
                continue
            if val < b - 1e-12:
                b, bv = val, v
        best[u] = b
        choice[u] = bv
    return best, choice


# ----------------------------------------------------------------- pallas
#: rows of the Toeplitz operand per grid step (one 128-lane output block)
ROW_TILE = 128
#: largest padded width the kernel takes: one (ROW_TILE, MAX_P) f32 row
#: block is 4 MiB, which with double buffering and the broadcast-add
#: temporary stays inside the default scoped VMEM (checked by an ahead-of-
#: time compile for a described v5e in ``tests/test_tpu_compile.py``)
MAX_P = 8192
#: block-index constant pinned to int32 (a Python 0 traces as int64 under
#: an x64 scope, which Mosaic cannot lower)
_ZERO = np.int32(0)
#: +inf surrogate of the float32 operands, safe under one add
_BIG = np.float32(3.4e38 / 4)
#: float32 elements of the host's choice temporary per block of steps
_CHOICE_BLOCK = 1 << 22
_pallas_sweep = None                    # lazily created jit


def _get_pallas_sweep():
    """jit of a whole sweep: ``minplus_sweep(prev0, costs, k)`` runs ``k``
    steps of the ``minplus`` kernel in one device loop, from a (P,) start
    row over (K_pad, P) cost rows. One step is the product cur[u] =
    min_v A[u, v] + b[v] on padded (P, P)/(1, P) operands, tiled over
    ``ROW_TILE``-row blocks of A."""
    global _pallas_sweep
    if _pallas_sweep is None:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(a_ref, b_ref, o_ref):
            vals = a_ref[...] + b_ref[...]      # (tile, P) broadcast over rows
            o_ref[...] = jnp.min(vals, axis=1, keepdims=True).T

        def minplus_pallas_step(A, b, interpret):
            P = A.shape[0]
            return pl.pallas_call(
                kernel,
                grid=(P // ROW_TILE,),
                in_specs=[pl.BlockSpec((ROW_TILE, P), lambda i: (i, _ZERO)),
                          pl.BlockSpec((1, P), lambda i: (_ZERO, _ZERO))],
                out_specs=pl.BlockSpec((1, ROW_TILE), lambda i: (_ZERO, i)),
                out_shape=jax.ShapeDtypeStruct((1, P), A.dtype),
                interpret=interpret,
                name="minplus",
            )(A, b)

        def toeplitz(prev):
            """A[u, v] = prev[u - v], _BIG above the diagonal, by layout
            alone: row u of the (P, 2P) reshape of z = [_BIG]*(P-1) + prev
            repeated is z shifted by u, so A[u, v] = z[u - v + P - 1] (a
            gather of the same entries costs ~0.13 ms a step on a v5e)."""
            P = prev.shape[0]
            z = jnp.concatenate([jnp.full(P - 1, _BIG, jnp.float32), prev])
            rows = jnp.broadcast_to(z, (P + 1, 2 * P - 1)).reshape(-1)
            return rows[:2 * P * P].reshape(P, 2 * P)[:, P - 1::-1]

        def minplus_sweep(prev0, costs, k, interpret):
            # prev0 (P,) and costs (K_pad, P) are clamped float32 rows;
            # row i of the result is step i's output, rows >= k stay _BIG
            def body(i, carry):
                prev, out = carry
                cur = minplus_pallas_step(
                    toeplitz(prev), lax.dynamic_slice_in_dim(costs, i, 1),
                    interpret)
                out = lax.dynamic_update_slice_in_dim(out, cur, i, 0)
                return jnp.minimum(cur[0], _BIG), out

            out = jnp.full(costs.shape, _BIG, jnp.float32)
            return lax.fori_loop(np.int32(0), k, body, (prev0, out))[1]

        _pallas_sweep = jax.jit(minplus_sweep, static_argnames="interpret")
    return _pallas_sweep


def minplus_sweep_pallas(
    prev0: np.ndarray, tcosts: np.ndarray, interpret: Optional[bool] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """k DP steps on TPU in one device call (float32 accumulation).

    ``prev0`` is the (Q+1,) start row and ``tcosts`` the (k, Q+1) theta
    costs of the k slots; returns ``best`` and ``choice``, both (k, Q+1):
    row i is the output of step i, fed the output of step i-1 (``prev0``
    for i = 0). The rows are clamped to a float32 +inf surrogate and
    padded to the 128-lane tile (padding is +inf-neutral: inf + inf = inf
    never wins a min) and the cost rows to the next power of two >= k, so
    one compile serves every k of a bucket. On the device a loop of k
    steps rebuilds each step's Toeplitz operand from the carried row and
    runs the ``minplus`` kernel on it. The call runs under one
    ``device.launch`` span and the read of all k rows under one
    ``device.sync`` span, both with the site ``minplus_sweep``.

    The carried row is the step's float32 output clamped as the host
    clamps it, so every step sees the operands that k calls of one step
    would: the result is bit-identical to feeding the k = 1 sweep its own
    output k times. Raises ``ValueError`` when the padded width would
    exceed ``MAX_P``."""
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k, Q1 = tcosts.shape
    P = max(ROW_TILE, int(np.ceil(Q1 / ROW_TILE)) * ROW_TILE)
    if P > MAX_P:
        raise ValueError(
            f"minplus_pallas: {Q1} DP states pad to {P} > MAX_P={MAX_P}; "
            "use the numpy min-plus backend for this quanta count"
        )
    K_pad = 1 << (k - 1).bit_length()
    row0 = np.full(P, _BIG, dtype=np.float32)
    row0[:Q1] = np.minimum(prev0, _BIG)
    costs = np.full((K_pad, P), _BIG, dtype=np.float32)
    costs[:k, :Q1] = np.minimum(tcosts, _BIG)
    fn = _get_pallas_sweep()
    with _trace.launch("minplus_sweep"):
        out = fn(row0, costs, np.int32(k), interpret=interpret)
    cur32 = _trace.device_get(out, "minplus_sweep")[:k, :Q1]
    best = np.where(cur32 >= _BIG, _INF, cur32.astype(np.float64))
    # backtracking pointers recovered host-side from the same float32
    # operands (standard for DP kernels: the device computes values, not
    # argmins): vals32[i, u, v] = prev_i[u - v] + cost_i[v], in blocks of
    # steps that keep the (steps, Q+1, Q+1) temporary bounded
    prevs = np.empty((k, Q1), dtype=np.float32)
    prevs[0] = row0[:Q1]
    prevs[1:] = np.minimum(cur32[:-1], _BIG)
    idx = np.arange(Q1)
    diff = idx[:, None] - idx[None, :]
    choice = np.empty((k, Q1), dtype=np.int64)
    block = max(1, _CHOICE_BLOCK // (Q1 * Q1))
    for s in range(0, k, block):
        vals32 = (np.where(diff >= 0, prevs[s:s + block, np.abs(diff)], _BIG)
                  + costs[s:min(s + block, k), None, :Q1])
        choice[s:s + block] = np.argmin(vals32, axis=2)
    choice[~np.isfinite(best)] = -1
    return best, choice


def minplus_pallas(
    prev: np.ndarray, tcost: np.ndarray, interpret: Optional[bool] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """One DP step on TPU: the k = 1 case of ``minplus_sweep_pallas``."""
    best, choice = minplus_sweep_pallas(prev, tcost[None, :], interpret)
    return best[0], choice[0]


# --------------------------------------------------------------- dispatch
def default_backend() -> str:
    """Advisory: which backend a TPU-aware caller could pick.

    "pallas" only when jax is already loaded AND running on TPU; never
    imports jax itself, so CPU-only probes stay jax-free."""
    import sys
    jax = sys.modules.get("jax")
    if jax is not None and jax.default_backend() == "tpu":
        return "pallas"
    return "numpy"


def minplus_step(
    prev: np.ndarray, tcost: np.ndarray, backend: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """One DP forward step; backend in {None, "numpy", "pallas", "scalar"}.

    None means NumPy *to this function*: the scheduler guarantees
    bit-identical decisions across hosts, so the float32 Pallas kernel
    (interpret mode off-TPU) never self-selects here. Callers opt in via
    SubproblemConfig(minplus_backend="pallas"), or implicitly by running the jax *array* backend on an actual TPU
    (WorkloadDP resolves a None config through
    ``ArrayBackend.minplus_default``) — the jax backend's contract is
    tolerance parity, not bit parity, so accelerator-dependent float32
    rounding is inside its documented envelope. On the default numpy
    array backend admissions never depend on which accelerator — or
    import order — a process happens to have."""
    if backend == "pallas":
        return minplus_pallas(prev, tcost)
    if backend == "scalar":
        return minplus_scalar(prev, tcost)
    return minplus_numpy(prev, tcost)

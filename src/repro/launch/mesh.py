"""Production meshes (functions only — importing this module never touches
jax device state; the dry-run sets XLA_FLAGS before any jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, model_split: int = 0):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis.

    model_split > 0 re-factorizes the 16-way model axis into
    (model=16//model_split, model2=model_split) over the SAME 256 chips —
    used by the §Perf head-sharding iteration for head counts (40, 25, ...)
    that don't divide 16."""
    if model_split:
        assert 16 % model_split == 0
        if multi_pod:
            shape = (2, 16, 16 // model_split, model_split)
            axes = ("pod", "data", "model", "model2")
        else:
            shape = (16, 16 // model_split, model_split)
            axes = ("data", "model", "model2")
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

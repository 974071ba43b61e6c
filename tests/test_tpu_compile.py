"""Ahead-of-time compiles of the device path for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached. These compiles catch what
interpret mode cannot — Mosaic lowering errors, VMEM overflows, f64
emulation gaps — at the sizes the chip smoke drives. Nothing runs, so
they say nothing about results or times.

The topology is described inside a fixture (never at import time): only
the one pytest worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

# the smoke's served-path ledger: 64 slots x 1024 machines x 4 resources
T, H, R = 64, 1024, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("slots,machines", [
    (64, 1024),     # 65,536 rows: refused by the gridless kernel
    (256, 4096),    # 1,048,576 rows
])
def test_pricing_kernel_compiles(one_chip, slots, machines):
    from repro.kernels.pricing import _get_pallas_bundle

    fn = _get_pallas_bundle()
    with jax.enable_x64(True):          # the jax backend's calling scope
        compiled = fn.lower(
            _spec(one_chip, (slots, machines, R), jnp.float64),
            _spec(one_chip, (8, 128), jnp.float32),
            interpret=False,
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P", [128, 1024, "max"])
def test_minplus_kernel_compiles(one_chip, P):
    """The kernel at each width, as one step runs it: a sweep of one."""
    from repro.kernels.minplus import MAX_P, _get_pallas_sweep

    P = MAX_P if P == "max" else P
    compiled = _get_pallas_sweep().lower(
        _spec(one_chip, (P,), jnp.float32),
        _spec(one_chip, (1, P), jnp.float32),
        _spec(one_chip, (), jnp.int32),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P", [128, "max"])
def test_minplus_sweep_compiles(one_chip, P):
    """The whole DP sweep as one device loop: 64 steps (W = 64) padded to
    a bucket of 64 cost rows, at the benchmark's width and at MAX_P."""
    from repro.kernels.minplus import MAX_P, _get_pallas_sweep

    P = MAX_P if P == "max" else P
    compiled = _get_pallas_sweep().lower(
        _spec(one_chip, (P,), jnp.float32),
        _spec(one_chip, (64, P), jnp.float32),
        _spec(one_chip, (), jnp.int32),
        interpret=False,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text


@pytest.mark.parametrize("op", [
    "price_tensor", "free_tensor", "scatter_add", "scatter_sub",
    "ledger_advance", "oversubscribed",
])
def test_f64_ledger_ops_compile(one_chip, op):
    from repro.backend import get_backend

    be = get_backend("jax")
    f64 = jnp.float64
    ledger = _spec(one_chip, (T, H, R), f64)
    cap = _spec(one_chip, (H, R), f64)
    width = 16                          # one power-of-two scatter width
    fn, args = {
        "price_tensor": (be._price_jit,
                         (ledger, cap, _spec(one_chip, (R,), f64),
                          _spec(one_chip, (), f64))),
        "free_tensor": (be._free_jit, (ledger, cap)),
        "scatter_add": (be._scatter_add,
                        (ledger, _spec(one_chip, (), jnp.int64),
                         _spec(one_chip, (width,), jnp.int64),
                         _spec(one_chip, (width, R), f64))),
        "scatter_sub": (be._scatter_sub,
                        (ledger, _spec(one_chip, (), jnp.int64),
                         _spec(one_chip, (width,), jnp.int64),
                         _spec(one_chip, (width, R), f64))),
        "ledger_advance": (be._advance_jit,
                           (ledger, _spec(one_chip, (), jnp.int64))),
        "oversubscribed": (be._over_jit,
                           (ledger, cap, _spec(one_chip, (), f64))),
    }[op]
    with jax.enable_x64(True):
        compiled = fn.lower(*args).compile()
    out = compiled.memory_analysis()
    want = 1 if op == "oversubscribed" else T * H * R * 8   # bool, or a ledger
    assert out is None or out.output_size_in_bytes >= want

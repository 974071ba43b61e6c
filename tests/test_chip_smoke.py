"""The chip smoke's phases, end to end on CPU at tiny sizes.

``chip_smoke.py`` needs a TPU to pass as a whole (its device phase
refuses anything else); every other phase is plain code that runs here
with the Pallas kernels in interpret mode. Running them catches wrong
paths, arguments and control flow before any chip time is spent."""
from __future__ import annotations

import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(RuntimeError, match="no TPU"):
        smoke.phase_device()


def test_kernel_phase_matches_references(smoke):
    out = smoke.phase_kernels(slots=3, machines=64, quanta=8, repeats=1)
    # off-TPU both kernels run in interpret mode: no Mosaic custom call
    assert out == {"pricing_mosaic": False, "minplus_mosaic": False}


@pytest.mark.parametrize("scale", [0.1, 0.3])
def test_served_phase_agrees_with_numpy(smoke, scale):
    out = smoke.phase_served(6, 10, 8, scale, seed=3)
    assert out["admitted"] > 0
    # off-TPU the default kernel path is the f64 one: nothing differs
    assert out["default_differing"] == 0


def test_online_phase_agrees_with_numpy(smoke):
    out = smoke.phase_online(machines=4, horizon=8, num_jobs=15,
                             arrival_rate=4.0, failure_rate=0.1, seed=1)
    assert out["admitted"] > 0
    assert out["default_differing"] == []


def test_kernel_path_restores_environment(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_PRICE_KERNEL", "pallas")
    with smoke.kernel_path("f64") as cfg:
        assert os.environ["REPRO_PRICE_KERNEL"] == "jnp"
        assert cfg.minplus_backend == "numpy"
    assert os.environ["REPRO_PRICE_KERNEL"] == "pallas"
    with smoke.kernel_path("default") as cfg:
        assert "REPRO_PRICE_KERNEL" not in os.environ
        assert cfg.minplus_backend is None

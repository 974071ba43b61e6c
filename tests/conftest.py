"""Test-suite bootstrap: one Hypothesis profile for every property test.

Property tests here drive whole scheduler runs, which routinely outlast
Hypothesis's default 200 ms per-example deadline; the profile turns the
deadline off (each test still sets its own ``max_examples``)."""
from hypothesis import settings

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (subprocess compiles)")

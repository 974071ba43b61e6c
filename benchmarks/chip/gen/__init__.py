"""Frozen traffic for the chip benchmark.

A copy of the repo's seeded job-stream generator (``repro.sim.traces``
and ``repro.core.workload.draw_job``, at the presets a cell uses) and its
price calibration (``repro.core.pricing.estimate_price_params``), kept
here so that a change to the
program cannot change the benchmark's inputs. ``test_chipbench_gen.py``
checks that the copy still yields what the program's generators yield.
"""

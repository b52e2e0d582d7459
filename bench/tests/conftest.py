"""Shared helpers of the benchmark's CPU tests: the harness modules on
the import path, and a run of a cell at a tiny scale with the look for
a chip skipped."""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402


@pytest.fixture
def cpu_run(monkeypatch):
    """``cpu_run(cell, sf, seconds, seed)``: one run of ``cell`` on the
    CPU at scale factor ``sf``, with everything but the device check."""
    monkeypatch.setattr(harness, "device_check",
                        lambda jax, chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(harness, "memory_peak", lambda devices: 0)

    def go(name, sf=0.01, seconds=0.5, seed=2 ** 31 + 11):
        cell = harness.load_cell(name)
        cell["config"] = dict(cell["config"], scale_factor=sf)
        return harness.run(cell, seed, seconds, False, time.perf_counter())

    return go

"""Tenant-catalog soak tool: the ISSUE 17 acceptance drills at tiny
tier-1 scale (the full-size soak is `python -m tools.catalog_soak`)."""

import pytest

from tools.catalog_soak import run_gate_throughput, run_tiering_soak

pytestmark = pytest.mark.catalog


def test_small_tiering_soak_drills_hold():
    summary = run_tiering_soak(registered=20, active=4, batches=2,
                               rows=512, workers=2)
    assert summary["ok"], summary
    assert summary["hot_count"] == 4  # hot tier tracks ACTIVE tenants
    assert summary["registered_count"] == 20
    assert summary["edit_drill"]["reloads"] == 1
    assert summary["corrupt_drill"]["quarantine_bumps"] == 1
    assert summary["corrupt_drill"]["preserved"] == 1


def test_small_gate_throughput_bit_exact():
    """Tiny frames make the timing fraction meaningless (interpreter
    noise dwarfs both folds) — tier-1 pins the CORRECTNESS half of the
    drill: bit-exact metrics and the gate-rows counter."""
    summary = run_gate_throughput(batches=3, rows=2048)
    assert summary["ok"], summary
    assert summary["bit_exact"]
    assert summary["gate_rows"] == 3 * 2048

"""Compile-budget regression tests for the signature-bundled device scan.

The device tier used to compile ONE monolithic PackedScanProgram keyed on
the full analyzer tuple: a 50-column battery was one giant XLA compile
(1140.6s staging vs 1.98s warm in the July chip round — 575x) that no
other battery could reuse. The bundled design partitions a battery into
(analyzer-class, state-shape) signature bundles and compiles one SMALL
program per bundle signature, shared across columns, batteries and runs.

These tests pin the budget that redesign buys, via RunMonitor's
``program_compiles`` delta counter:

- a 50-column battery compiles at most (distinct signatures + a small
  constant for bundle-shape variants) programs — NOT one per analyzer and
  NOT one monolith whose cost scales superlinearly with battery width;
- re-running the same battery compiles 0 new programs;
- a DIFFERENT battery over different columns with the same analyzer
  classes at the same group sizes compiles 0 new programs (cross-battery
  sharing — the property that makes profile pass 2 and the suggestion
  stage nearly compile-free).
"""

from __future__ import annotations

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    Maximum,
    Mean,
    Minimum,
    StandardDeviation,
    Sum,
)
from deequ_tpu.data import Dataset
from deequ_tpu.runners import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor


def wide_data(n_cols: int, rows: int = 4096, seed: int = 0) -> Dataset:
    rng = np.random.default_rng(seed)
    return Dataset.from_dict(
        {f"c{i}": rng.normal(size=rows) for i in range(n_cols)}
    )


def battery_for(columns):
    analyzers = []
    for c in columns:
        analyzers += [
            Completeness(c), Mean(c), Sum(c), Minimum(c), Maximum(c),
            StandardDeviation(c), ApproxCountDistinct(c),
        ]
    return analyzers


class TestCompileBudget:
    def test_50_column_battery_compiles_at_most_signatures_plus_constant(self):
        data = wide_data(8, seed=1)
        cols = [f"c{i}" for i in range(8)]
        battery = battery_for(cols)  # 56 analyzers, 7 distinct signatures
        distinct_signatures = 7
        mon = RunMonitor()
        ctx = AnalysisRunner.do_analysis_run(
            data, battery, batch_size=2048, monitor=mon, placement="device"
        )
        assert all(m.value.is_success for m in ctx.metric_map.values())
        # each signature compiles one full-size bundle program; the
        # "small constant" covers at most one extra shape variant per
        # signature (a power-of-two tail), never per-column growth
        assert 0 < mon.program_compiles <= distinct_signatures * 2, (
            mon.program_compiles
        )

    def test_rerunning_same_battery_compiles_zero(self):
        data = wide_data(4, seed=2)
        battery = battery_for([f"c{i}" for i in range(4)])
        AnalysisRunner.do_analysis_run(
            data, battery, batch_size=2048, placement="device"
        )
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            data, battery, batch_size=2048, monitor=mon, placement="device"
        )
        assert mon.program_compiles == 0, mon.program_compiles

    def test_same_shape_battery_over_new_columns_compiles_zero(self):
        # same classes, same per-class group SIZE, different column names
        # and different dataset: the signature-keyed programs must be
        # reused wholesale (feature arrays are remapped positionally)
        data_a = wide_data(4, seed=3)
        AnalysisRunner.do_analysis_run(
            data_a, battery_for([f"c{i}" for i in range(4)]),
            batch_size=2048, placement="device",
        )
        rng = np.random.default_rng(7)
        data_b = Dataset.from_dict(
            {f"other{i}": rng.normal(size=4096) for i in range(4)}
        )
        mon = RunMonitor()
        ctx = AnalysisRunner.do_analysis_run(
            data_b, battery_for([f"other{i}" for i in range(4)]),
            batch_size=2048, monitor=mon, placement="device",
        )
        assert all(m.value.is_success for m in ctx.metric_map.values())
        assert mon.program_compiles == 0, mon.program_compiles

    @pytest.mark.slow
    def test_50_columns_full_shape(self):
        """The literal 50-column shape from the acceptance bar (slow: ~350
        analyzer states on the 8-virtual-device CPU backend)."""
        data = wide_data(50, rows=2048, seed=4)
        battery = battery_for([f"c{i}" for i in range(50)])
        mon = RunMonitor()
        ctx = AnalysisRunner.do_analysis_run(
            data, battery, batch_size=1024, monitor=mon, placement="device"
        )
        assert all(m.value.is_success for m in ctx.metric_map.values())
        assert mon.program_compiles <= 7 * 2, mon.program_compiles
        mon2 = RunMonitor()
        AnalysisRunner.do_analysis_run(
            data, battery, batch_size=1024, monitor=mon2, placement="device"
        )
        assert mon2.program_compiles == 0

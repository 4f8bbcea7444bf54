"""Compile-count invariants and phase timers (VERDICT round-2 item 8).

The jit-compile counter is the codegen-cache analog of the reference's
Spark-job-count asserts (`AnalysisRunnerTests.scala:50-74`): re-running the
SAME battery must hit the cached XLA programs, never re-trace — a recompile
regression multiplies run latency by the ~20-40s compile cost."""

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    KLLParameters,
    KLLSketch,
    Maximum,
    Mean,
    Minimum,
    StandardDeviation,
    Sum,
)
from deequ_tpu.data import Dataset
from deequ_tpu.runners import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor

BATTERY = [
    Completeness("x"),
    Mean("x"),
    Sum("x"),
    Minimum("x"),
    Maximum("x"),
    StandardDeviation("x"),
    ApproxCountDistinct("x"),
    KLLSketch("x", KLLParameters(256, 0.64, 10)),
]


def _data(seed: int, n: int = 20_000) -> Dataset:
    rng = np.random.default_rng(seed)
    return Dataset.from_dict({"x": rng.normal(size=n)})


class TestCompileCountInvariants:
    @pytest.mark.parametrize("placement", ["device", "host"])
    def test_no_recompiles_across_identical_runs(self, placement):
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(0), BATTERY, batch_size=4096, monitor=mon, placement=placement
        )
        warm = mon.jit_compiles
        for seed in (1, 2):
            mon2 = RunMonitor()
            AnalysisRunner.do_analysis_run(
                _data(seed), BATTERY, batch_size=4096, monitor=mon2,
                placement=placement,
            )
            assert mon2.jit_compiles == warm, (
                f"recompile regression: warmup={warm}, rerun={mon2.jit_compiles}"
            )

    def test_different_row_counts_share_programs(self):
        """Batch padding keeps program shapes static: a run with a ragged
        final batch must not compile new programs."""
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(0, 8192), BATTERY, batch_size=4096, monitor=mon, placement="device"
        )
        warm = mon.jit_compiles
        mon2 = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(1, 10_000), BATTERY, batch_size=4096, monitor=mon2, placement="device"
        )
        assert mon2.jit_compiles == warm


class TestPhaseTimers:
    def test_device_path_phases_recorded(self):
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(0), BATTERY, batch_size=4096, monitor=mon, placement="device"
        )
        assert {
            "feature_build", "device_feed", "device_dispatch", "device_wait",
            "state_fetch",
        } <= set(mon.phase_seconds)
        assert all(v >= 0 for v in mon.phase_seconds.values())

    def test_host_path_phases_recorded(self):
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(0), BATTERY, batch_size=4096, monitor=mon, placement="host"
        )
        assert {"host_partials", "ingest_fold", "state_fetch"} <= set(mon.phase_seconds)

    def test_reset_clears_phases(self):
        mon = RunMonitor()
        mon.add_phase_time("x", 1.0)
        mon.reset()
        assert mon.phase_seconds == {}

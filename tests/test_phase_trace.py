"""Run phases on both clocks: ``RunMonitor.phase_seconds`` and the
``deequ/<phase>`` annotations a ``jax.profiler`` trace records, the stable
names of the bundle programs, and the benchmark's readers of the phases."""

import glob
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deequ_tpu.analyzers import KLLSketch, Mean, StandardDeviation
from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.profiles import ColumnProfilerRunner
from deequ_tpu.runners.engine import PackedScanProgram, RunMonitor, program_name
from deequ_tpu.verification import VerificationSuite

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the phases a multi-batch verification with a device frequency table
#: records, on the entry, dispatching and feed threads
VERIFY_PHASES = (
    "plan", "batch_slice", "feed_wait", "device_wait", "freq_drain",
    "metric_derivation", "constraint_evaluation",
)


def _table(rows=6000, seed=0) -> Dataset:
    rng = np.random.default_rng(seed)
    key = rng.permutation(rows)  # (order, line) is unique
    return Dataset.from_dict({
        "order": key // 3,
        "line": key % 3,
        "x": rng.normal(size=rows),
    })


def _verify(data: Dataset, monitor: RunMonitor):
    check = (
        Check(CheckLevel.ERROR, "phases")
        .has_uniqueness(["order", "line"], lambda u: u == 1.0)
        .is_complete("x")
        .has_mean("x", lambda m: -1.0 < m < 1.0)
    )
    return (
        VerificationSuite.on_data(data).add_check(check)
        .with_batch_size(1024).with_placement("device")
        .with_monitor(monitor).run()
    )


def _traced_verify(trace_dir):
    """A warm verification under ``jax.profiler``: its monitor, and the
    summed seconds of each ``deequ/<phase>`` host event in the trace."""
    data = _table()
    _verify(data, RunMonitor())  # every program loaded before the trace
    mon = RunMonitor()
    jax.profiler.start_trace(str(trace_dir))
    try:
        _verify(data, mon)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert paths
    totals = {}
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("deequ/"):
                    phase = event.name[len("deequ/"):]
                    totals[phase] = totals.get(phase, 0.0) + event.duration_ns / 1e9
    return mon, totals


class TestRunPhases:
    def test_verification_records_every_phase(self):
        mon = RunMonitor()
        result = _verify(_table(), mon)
        assert result.status == CheckStatus.SUCCESS
        assert mon.device_freq_sets == 1
        assert mon.batches >= 3
        for phase in VERIFY_PHASES:
            assert phase in mon.phase_seconds, phase
        # the pass-end drain moved out of the enqueue phase
        assert "device_dispatch" in mon.phase_seconds

    def test_profiler_records_plan(self):
        mon = RunMonitor()
        ColumnProfilerRunner.on_data(_table()).with_monitor(mon).run()
        assert mon.phase_seconds["plan"] > 0.0

    def test_annotations_match_phase_seconds(self, tmp_path):
        mon, totals = _traced_verify(tmp_path)
        for phase in VERIFY_PHASES:
            assert phase in totals, phase
            assert totals[phase] == pytest.approx(
                mon.phase_seconds[phase], abs=5e-3
            ), phase

    def test_trace_off_drops_annotations_not_phase_seconds(
        self, tmp_path, monkeypatch
    ):
        from deequ_tpu.observability import trace

        monkeypatch.setenv(trace.TRACE_ENV, "0")
        mon, totals = _traced_verify(tmp_path)
        assert totals == {}
        for phase in VERIFY_PHASES:
            assert phase in mon.phase_seconds, phase

    def test_program_load_names_the_program(self):
        class Fresh(Mean):
            """A class no other test has loaded a program for."""

        mon = RunMonitor()
        battery = [Fresh("x"), Fresh("order")]
        from deequ_tpu.runners import AnalysisRunner

        AnalysisRunner.do_analysis_run(
            _table(), battery, batch_size=1024, monitor=mon, placement="device"
        )
        assert mon.phase_seconds["program_load.scan_fresh_x2"] > 0.0
        assert mon.phase_seconds["program_load"] >= (
            mon.phase_seconds["program_load.scan_fresh_x2"]
        )
        warm = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _table(seed=1), battery, batch_size=1024, monitor=warm,
            placement="device",
        )
        assert "program_load" not in warm.phase_seconds


class TestProgramNames:
    def test_fresh_builds_share_a_name(self):
        first = PackedScanProgram((KLLSketch("a"), KLLSketch("b")), None)
        second = PackedScanProgram((KLLSketch("z"), KLLSketch("y")), None)
        assert first.name == second.name == "scan_kll_x2"
        assert PackedScanProgram((StandardDeviation("a"),), None).name == (
            "scan_standard_deviation_x1"
        )

    def test_name_is_the_jitted_module(self):
        prog = PackedScanProgram((Mean("x"), Mean("y")), None)
        data = Dataset.from_dict({"x": np.arange(8.0), "y": np.arange(8.0)})
        batch = next(data.batches(8))
        from deequ_tpu.runners.features import FeatureBuilder

        builder = FeatureBuilder(
            [s for a in prog.analyzers for s in a.feature_specs()]
        )
        feats = builder.build(batch)
        slots = tuple(tuple(feats[k] for k in keys) for keys in prog._spec_keys)
        text = prog._update.lower(prog.init_carry(), slots).as_text()
        assert "module @jit_scan_mean_x2" in text

    def test_name_is_the_same_in_another_process(self):
        code = (
            "from deequ_tpu.analyzers import Mean, KLLSketch, Completeness\n"
            "from deequ_tpu.runners.engine import program_name\n"
            "print(program_name((Completeness('a'), Mean('b'), KLLSketch('c'),"
            " Mean('d'))))\n"
        )
        env = dict(os.environ, PYTHONHASHSEED="12345", JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.strip().splitlines()[-1]
        from deequ_tpu.analyzers import Completeness

        here = program_name(
            (Completeness("a"), Mean("b"), KLLSketch("c"), Mean("d"))
        )
        assert out == here == "scan_completeness_mean_kll_x4"


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "phase_reader_" + name.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name,phase", [
    ("feed_wait_s_per_run.batch", "feed_wait"),
    ("device_wait_s_per_run.batch", "device_wait"),
    ("freq_drain_s_per_run.batch", "freq_drain"),
    ("plan_s_per_run.batch", "plan"),
])
def test_phase_readers(name, phase):
    reader = _reader(name)
    record = {"runs": 4, "phase_seconds": {phase: 2.0, "state_fetch": 1.0}}
    assert reader.read(record) == pytest.approx(0.5)
    assert reader.read({"runs": 4, "phase_seconds": {"state_fetch": 1.0}}) is None
    assert reader.read({"runs": 0, "phase_seconds": {phase: 2.0}}) is None

"""Scan-watchdog drills (ISSUE 4 acceptance): a stalled (injected) scan is
cancelled within 2x its deadline and fails over instead of hanging the
worker; escaped stalls are requeued by the scheduler; deadlines derive
from measured per-batch rates with the env override on top."""

import time

import numpy as np
import pytest

from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.exceptions import ScanStallError
from deequ_tpu.reliability import (
    SCAN_DEADLINE_ENV,
    FaultSpec,
    RateTracker,
    classify_failure,
    inject,
    rate_tracker,
    run_with_deadline,
    scan_deadline_s,
)
from deequ_tpu.runners.engine import RunMonitor


@pytest.fixture(autouse=True)
def _clean_rates(monkeypatch):
    """Each test starts with no learned rates and no env deadline, and
    leaks neither into the rest of the suite."""
    monkeypatch.delenv(SCAN_DEADLINE_ENV, raising=False)
    rate_tracker().clear()
    yield
    rate_tracker().clear()


class TestRunWithDeadline:
    def test_value_and_error_pass_through(self):
        monitor = RunMonitor()
        assert run_with_deadline(lambda: 42, 5.0, monitor, "t") == 42
        with pytest.raises(KeyError):
            run_with_deadline(
                lambda: (_ for _ in ()).throw(KeyError("x")), 5.0, monitor, "t"
            )
        assert monitor.stalls == 0

    def test_deadline_cancels_with_typed_error(self):
        monitor = RunMonitor()
        t0 = time.perf_counter()
        with pytest.raises(ScanStallError) as err:
            run_with_deadline(lambda: time.sleep(10), 0.2, monitor, "device")
        elapsed = time.perf_counter() - t0
        assert elapsed < 2 * 0.2 + 0.5  # cancelled ~at the deadline
        assert monitor.stalls == 1
        assert err.value.deadline_s == 0.2
        assert classify_failure(err.value) == "device"  # tier-failover path


class TestDeadlineDerivation:
    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "7.5")
        assert scan_deadline_s(100, "device") == 7.5

    def test_env_zero_or_negative_disables(self, monkeypatch):
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "0")
        assert scan_deadline_s(100, "device") is None
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "-3")
        assert scan_deadline_s(100, "device") is None

    def test_garbage_env_falls_back_to_derived_not_silent_off(
        self, monkeypatch
    ):
        """An operator who typo'd "60s" believes hang detection is armed;
        the unparseable value must not silently disable BOTH paths."""
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "60s")
        assert scan_deadline_s(100, "device") is None  # no rate yet either
        rate_tracker().observe("device", rows=10, seconds=10.0)
        assert scan_deadline_s(100, "device") == pytest.approx(1000.0)

    def test_cold_process_runs_unguarded(self):
        assert scan_deadline_s(100, "device") is None

    def test_derived_from_measured_rate_with_floor(self):
        tracker = rate_tracker()
        tracker.observe("device", rows=1000, seconds=1.0)  # 1ms/row
        # 10x multiple: 2000 rows -> 20s, under the 30s floor
        assert scan_deadline_s(2000, "device") == 30.0
        # 10000 rows -> 100s, over the floor
        assert scan_deadline_s(10_000, "device") == pytest.approx(100.0)
        # rates are per tier: host has no measurement yet
        assert scan_deadline_s(10_000, "host") is None

    def test_rate_is_per_row_not_per_batch(self):
        """One tier serves 512-row micro-batches AND 1M-row batches; a
        per-batch rate learned from the small ones would derive deadlines
        no healthy large-batch pass can meet (review finding). Per-row,
        the same observation covers both."""
        from deequ_tpu.reliability.watchdog import DEADLINE_RATE_MULTIPLE

        tracker = RateTracker()
        # micro-batch pass: 10 batches x 512 rows in 0.2s
        tracker.observe("host", rows=5120, seconds=0.2)
        per_row = tracker.per_row_s("host")
        # a 32M-row pass's deadline scales with ROWS, not batch count
        expected = max(30.0, DEADLINE_RATE_MULTIPLE * per_row * 32_000_000)
        assert expected > 1000  # minutes of slack, no false stall

    def test_ewma_blends_observations(self):
        tracker = RateTracker()
        tracker.observe("device", 1, 1.0)
        tracker.observe("device", 1, 2.0)
        assert tracker.per_row_s("device") == pytest.approx(
            0.3 * 2.0 + 0.7 * 1.0
        )

    def test_engine_pass_feeds_tracker(self):
        from deequ_tpu.runners.analysis_runner import AnalysisRunner
        from deequ_tpu.analyzers import Mean

        data = Dataset.from_dict({"x": np.arange(2048, dtype=np.float64)})
        AnalysisRunner.do_analysis_run(data, [Mean("x")], batch_size=1024)
        assert rate_tracker().per_row_s("device") is not None


@pytest.mark.chaos
class TestStallDrills:
    def _data(self, rows=4096):
        rng = np.random.default_rng(0)
        return Dataset.from_dict({"x": rng.normal(size=rows)})

    def _check(self):
        return (
            Check(CheckLevel.ERROR, "stall battery")
            .has_mean("x", lambda m: abs(m) < 1)
            .is_complete("x")
        )

    def test_injected_stall_cancelled_within_2x_deadline_and_fails_over(
        self, monkeypatch
    ):
        """ISSUE acceptance: a stalled (injected) scan is cancelled by the
        watchdog within 2x its deadline and fails over instead of hanging
        the worker."""
        from deequ_tpu.verification import VerificationSuite

        # warm BOTH tiers' programs first: a pinned 1s deadline applies to
        # every pass, and a cold host-tier compile would legitimately trip
        # it (the derived-deadline path never has this problem — it only
        # arms after a completed pass measured the tier's rate)
        for placement in ("device", "host"):
            (
                VerificationSuite.on_data(self._data())
                .add_check(self._check())
                .with_placement(placement)
                .run()
            )
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "1.0")
        monitor = RunMonitor()
        with inject(FaultSpec("device_update", "stall", at=1, delay_s=30.0)):
            t0 = time.perf_counter()
            result = (
                VerificationSuite.on_data(self._data())
                .add_check(self._check())
                .with_monitor(monitor)
                .with_placement("device")
                .run()
            )
            elapsed = time.perf_counter() - t0
        # the device pass was cancelled at ~1s (not the 30s sleep) and the
        # host-tier re-run finished the battery
        assert elapsed < 2 * 1.0 + 5.0
        assert monitor.stalls == 1
        assert monitor.device_failovers == 1
        assert result.status == CheckStatus.SUCCESS
        for metric in result.metrics.values():
            assert metric.value.is_success

    def test_scheduler_requeues_escaped_stall(self):
        """A stall that escapes the engine's failover must requeue the job
        (worker freed), not hang or insta-fail it."""
        from deequ_tpu.service import VerificationService

        attempts = []

        def flaky(ctx):
            attempts.append(ctx.attempt)
            if len(attempts) == 1:
                raise ScanStallError("device", 1.0, 1.2)
            return "done"

        with VerificationService(workers=1, background_warm=False) as svc:
            handle = svc.scheduler.submit(
                flaky, tenant="t", max_retries=1, retry_backoff_s=0.01
            )
            assert handle.result(timeout=30) == "done"
        assert attempts == [1, 2]

    def test_stall_counts_on_export_plane_and_probation(self):
        """A job whose monitor recorded stalls teaches the placement
        router (probation) and the export plane counter."""
        from deequ_tpu.service import VerificationService

        def stalled_then_done(ctx):
            ctx.monitor.bump("stalls")
            ctx.monitor.bump("device_stalls")  # the stall was device-tier
            ctx.monitor.placement = "host"
            return "ok"

        with VerificationService(workers=1, background_warm=False) as svc:
            handle = svc.scheduler.submit(
                stalled_then_done, tenant="t", signature=("sig",)
            )
            assert handle.result(timeout=30) == "ok"
            counters = svc.json_snapshot()["counters"]
            assert (
                counters["deequ_service_scan_stalls_total"]["tenant=t"] == 1.0
            )
            # probation: the router now refuses the device tier for this
            # battery signature
            assert svc.router.decide(("sig",), None) == "host"

    def test_host_tier_stall_does_not_probation_device(self):
        """A HOST-tier hang must not pin the battery to the tier that
        hung: monitor.stalls without device_stalls counts on the export
        plane but leaves placement routing alone."""
        from deequ_tpu.service import VerificationService

        def host_stalled(ctx):
            ctx.monitor.bump("stalls")  # tier was host: no device_stalls
            ctx.monitor.placement = "device"
            return "ok"

        with VerificationService(workers=1, background_warm=False) as svc:
            handle = svc.scheduler.submit(
                host_stalled, tenant="t", signature=("hsig",)
            )
            assert handle.result(timeout=30) == "ok"
            counters = svc.json_snapshot()["counters"]
            assert (
                counters["deequ_service_scan_stalls_total"]["tenant=t"] == 1.0
            )
            assert ("hsig",) not in svc.router._device_suspect


class TestCompileAllowance:
    """A pass about to compile gets the longest compile seen so far per
    program it has never run: a rate learned on warm passes says nothing
    about a cold TPU compile (tens of seconds)."""

    def test_cold_programs_extend_the_derived_deadline(self, monkeypatch):
        from deequ_tpu.reliability import watchdog

        monkeypatch.setattr(watchdog, "_LONGEST_COMPILE_S", 45.0)
        rate_tracker().observe("device", rows=1000, seconds=1.0)
        assert scan_deadline_s(2000, "device") == 30.0
        assert scan_deadline_s(2000, "device", cold_programs=2) == 30.0 + 90.0

    def test_allowance_never_below_the_floor(self, monkeypatch):
        from deequ_tpu.reliability import watchdog

        monkeypatch.setattr(watchdog, "_LONGEST_COMPILE_S", 0.5)
        assert watchdog.compile_allowance_s(0) == 0.0
        assert watchdog.compile_allowance_s(3) == 3 * watchdog.DEADLINE_FLOOR_S

    def test_compiles_are_recorded(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from deequ_tpu.reliability import watchdog

        monkeypatch.setattr(watchdog, "_LONGEST_COMPILE_S", 0.0)
        watchdog.watch_compiles()
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)(jnp.arange(7.0))
        assert watchdog._LONGEST_COMPILE_S > 0.0

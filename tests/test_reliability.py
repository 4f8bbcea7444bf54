"""Fault-tolerant verification engine: the acceptance proofs.

Pins the ISSUE-2 contract end to end with the deterministic fault harness
(`deequ_tpu/reliability/faults.py`):

- an injected device failure mid-pass -> `VerificationSuite.run()` still
  returns a complete result via host-tier failover;
- one injected analyzer fault in a 10-analyzer fused battery -> exactly
  that analyzer yields a typed Failure metric, the other 9 succeed;
- a run interrupted mid-ingest and resumed from the last StatePersister
  checkpoint produces metrics EQUAL to the uninterrupted run (device and
  host tiers, in-memory and filesystem providers);
- OOM -> batch bisection; poisoned host batch -> isolation rerun absorbs
  it; host accumulator faults knock out only themselves;
- the service's placement router learns device failures (probation) and
  the scheduler harvests reliability signals from RunMonitor.
"""

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    KLLSketch,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
    Uniqueness,
)
from deequ_tpu.analyzers.state_provider import (
    FileSystemStateProvider,
    InMemoryStateProvider,
)
from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.exceptions import (
    AnalyzerFaultException,
    DeviceFailureException,
    DeviceOOMException,
    PoisonedBatchException,
)
from deequ_tpu.reliability import (
    FaultSpec,
    IngestCheckpointer,
    classify_failure,
    inject,
)
from deequ_tpu.runners import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor
from deequ_tpu.verification import VerificationSuite


def _numeric_data(rows=8192, seed=0, with_group=False):
    rng = np.random.default_rng(seed)
    cols = {"x": rng.normal(size=rows), "y": rng.normal(5.0, 2.0, rows)}
    if with_group:
        cols["g"] = [f"id_{i}" for i in range(rows)]  # high-card: host accum
    return Dataset.from_dict(cols)


def _ten_analyzer_battery():
    return [
        Size(), Completeness("x"), Mean("x"), Sum("x"), Minimum("x"),
        Maximum("x"), StandardDeviation("x"), Mean("y"), Sum("y"),
        ApproxCountDistinct("x"),
    ]


class TestFaultInjector:
    def test_at_fires_on_exact_hit_once(self):
        with inject(FaultSpec("device_update", "device", at=3)) as inj:
            from deequ_tpu.reliability import fault_point

            fault_point("device_update", "a")
            fault_point("device_update", "b")
            with pytest.raises(DeviceFailureException):
                fault_point("device_update", "c")
            fault_point("device_update", "d")  # count=1 exhausted
        assert inj.fired == ["device_update:c:device"]

    def test_seeded_probability_is_deterministic(self):
        def run(seed):
            fired = []
            with inject(
                FaultSpec("worker", "worker_death", p=0.5, count=None),
                seed=seed,
            ) as inj:
                from deequ_tpu.reliability import fault_point

                for i in range(32):
                    try:
                        fault_point("worker", str(i))
                    except Exception:  # noqa: BLE001
                        pass
                fired = inj.fired
            return fired

        assert run(7) == run(7)
        assert run(7) != run(8)  # different seed, different plan

    def test_match_narrows_by_tag(self):
        target = repr(Mean("y"))
        with inject(
            FaultSpec("analyzer", "analyzer", match=target, count=None)
        ):
            from deequ_tpu.reliability import fault_point

            fault_point("analyzer", repr(Mean("x")))  # no match, no fire
            with pytest.raises(AnalyzerFaultException):
                fault_point("analyzer", target)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            FaultSpec("worker", "meteor_strike")

    def test_disarmed_fault_point_is_noop(self):
        from deequ_tpu.reliability import fault_point

        fault_point("device_update", "anything")  # must not raise


class TestClassification:
    def test_typed_taxonomy(self):
        assert classify_failure(DeviceOOMException("boom")) == "oom"
        assert classify_failure(DeviceFailureException("dead")) == "device"
        assert classify_failure(PoisonedBatchException(3)) == "data"
        assert classify_failure(ValueError("nope")) == "data"

    def test_xla_status_phrases(self):
        assert classify_failure(RuntimeError("RESOURCE_EXHAUSTED: oom")) == "oom"
        assert classify_failure(RuntimeError("INTERNAL: device lost")) == "device"


class TestAnalyzerIsolation:
    def test_one_faulty_analyzer_in_ten_degrades_alone(self):
        """ISSUE acceptance: 1 injected analyzer fault in a 10-analyzer
        fused battery -> exactly that analyzer fails typed, 9 succeed."""
        analyzers = _ten_analyzer_battery()
        target = Mean("y")
        monitor = RunMonitor()
        with inject(
            FaultSpec("analyzer", "analyzer", match=repr(target), count=None)
        ):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), analyzers, batch_size=1024, monitor=monitor
            )
        failures = {
            a: m for a, m in ctx.metric_map.items() if m.value.is_failure
        }
        assert set(failures) == {target}
        assert isinstance(failures[target].value.exception, AnalyzerFaultException)
        successes = [m for m in ctx.metric_map.values() if m.value.is_success]
        assert len(successes) == 9
        assert monitor.isolation_reruns > 0
        assert any("Mean" in tag for tag in monitor.degraded)

    def test_isolated_values_match_clean_run(self):
        analyzers = _ten_analyzer_battery()
        clean = AnalysisRunner.do_analysis_run(
            _numeric_data(), analyzers, batch_size=1024
        )
        target = Sum("x")
        with inject(
            FaultSpec("analyzer", "analyzer", match=repr(target), count=None)
        ):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), analyzers, batch_size=1024
            )
        for analyzer in analyzers:
            if analyzer == target:
                continue
            assert ctx.metric_map[analyzer].value.get() == pytest.approx(
                clean.metric_map[analyzer].value.get()
            )

    def test_poisoned_batch_absorbed_by_rerun(self):
        """A once-poisoned host batch costs isolation reruns, never a
        metric: the re-pass sees clean data and completes."""
        monitor = RunMonitor()
        with inject(FaultSpec("host_partial", "poison", at=3)) as inj:
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), [Mean("x"), Sum("x")], batch_size=1024,
                placement="host", monitor=monitor,
            )
        assert inj.fired == ["host_partial:2:poison"]
        assert all(m.value.is_success for m in ctx.metric_map.values())
        assert monitor.isolation_reruns > 0

    def test_pass_level_failure_short_circuits_bisection(self):
        """A failure every partition reproduces identically (corrupt input,
        dead tier) must cost ~log2(N) re-passes, not ~2N: once a >1-member
        subtree fully fails with the parent's signature, the sibling
        degrades without further re-runs."""
        analyzers = [
            Completeness("x"), Mean("x"), Sum("x"), Minimum("x"),
            Maximum("x"), StandardDeviation("x"), Mean("y"), Sum("y"),
        ]
        monitor = RunMonitor()
        # state_fetch fires once per pass with a tag-free (identical)
        # message — the signature every partition shares
        with inject(FaultSpec("state_fetch", "analyzer", count=None)):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), analyzers, batch_size=2048, monitor=monitor
            )
        assert all(m.value.is_failure for m in ctx.metric_map.values())
        # 8-battery chain: attempts at 8, 4, 2, 1, 1 — never the ~15 of
        # full bisection
        assert monitor.passes == 5, monitor.passes
        assert monitor.isolation_reruns == 3

    def test_single_fault_never_trips_short_circuit(self):
        """The wholesale-degradation rule must not fire for one faulty
        analyzer: its clean siblings succeed, so no >1 subtree fully
        fails — all 7 clean analyzers still complete."""
        analyzers = [
            Completeness("x"), Mean("x"), Sum("x"), Minimum("x"),
            Maximum("x"), StandardDeviation("x"), Mean("y"), Sum("y"),
        ]
        target = Completeness("x")  # FIRST member: left chain fails deepest
        with inject(
            FaultSpec("analyzer", "analyzer", match=repr(target), count=None)
        ):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), analyzers, batch_size=2048
            )
        failures = [a for a, m in ctx.metric_map.items() if m.value.is_failure]
        assert failures == [target]

    def test_host_accumulator_knockout_spares_battery(self, monkeypatch):
        from deequ_tpu.analyzers import grouping as grouping_mod

        # pin the grouping set onto the HOST accumulator tier whose
        # knockout path this test exercises — by default the set rides the
        # device frequency table engine and the poison never fires
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")
        calls = {"n": 0}
        original = grouping_mod.FrequenciesAndNumRows.update

        def poisoned(self, batch):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("poisoned frequency table")
            return original(self, batch)

        monitor = RunMonitor()
        grouping_mod.FrequenciesAndNumRows.update = poisoned
        try:
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(with_group=True),
                [Mean("x"), Uniqueness(("g",))],
                batch_size=1024, monitor=monitor,
            )
        finally:
            grouping_mod.FrequenciesAndNumRows.update = original
        assert ctx.metric_map[Mean("x")].value.is_success
        assert ctx.metric_map[Uniqueness(("g",))].value.is_failure
        assert monitor.passes == 1  # knockout, not a re-pass
        assert any(tag.startswith("host:") for tag in monitor.degraded)


class TestTierFailover:
    def test_device_failure_fails_over_to_host(self):
        """ISSUE acceptance: an injected device failure on pass batch 2 ->
        VerificationSuite.run() still returns a complete result."""
        check = (
            Check(CheckLevel.ERROR, "failover")
            .has_size(lambda n: n == 8192)
            .has_mean("x", lambda m: abs(m) < 1)
            .is_complete("y")
        )
        monitor = RunMonitor()
        with inject(FaultSpec("device_update", "device", at=2)) as inj:
            result = (
                VerificationSuite.on_data(_numeric_data())
                .add_check(check)
                .with_batch_size(1024)
                .with_monitor(monitor)
                .run()
            )
        assert inj.fired == ["device_update:2:device"]
        assert result.status == CheckStatus.SUCCESS
        assert all(m.value.is_success for m in result.metrics.values())
        assert monitor.device_failovers == 1
        assert monitor.placement == "host"  # the completing tier

    def test_failover_values_match_device_run(self):
        analyzers = [Mean("x"), Sum("x"), StandardDeviation("x")]
        clean = AnalysisRunner.do_analysis_run(
            _numeric_data(), analyzers, batch_size=1024
        )
        with inject(FaultSpec("device_update", "device", at=1)):
            failed_over = AnalysisRunner.do_analysis_run(
                _numeric_data(), analyzers, batch_size=1024
            )
        for analyzer in analyzers:
            assert failed_over.metric_map[analyzer].value.get() == pytest.approx(
                clean.metric_map[analyzer].value.get(), rel=1e-12
            )

    def test_oom_triggers_batch_bisection(self):
        monitor = RunMonitor()
        with inject(FaultSpec("device_update", "oom", at=1)):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), [Mean("x"), Sum("x")], batch_size=4096,
                monitor=monitor,
            )
        assert all(m.value.is_success for m in ctx.metric_map.values())
        assert monitor.batch_bisections == 1
        assert monitor.device_failovers == 0  # bisection sufficed

    def test_persistent_oom_falls_through_to_host(self):
        monitor = RunMonitor()
        with inject(FaultSpec("device_update", "oom", count=None)):
            ctx = AnalysisRunner.do_analysis_run(
                _numeric_data(), [Mean("x"), Sum("x")], batch_size=4096,
                monitor=monitor,
            )
        assert all(m.value.is_success for m in ctx.metric_map.values())
        assert monitor.batch_bisections >= 1
        assert monitor.device_failovers == 1
        assert monitor.placement == "host"


class TestResumableIngest:
    def _battery(self):
        return [
            Completeness("x"), Mean("x"), Sum("x"), Minimum("x"),
            Maximum("x"), StandardDeviation("x"), KLLSketch("x"),
        ]

    def _assert_equal_contexts(self, got, want):
        for analyzer, metric in want.metric_map.items():
            other = got.metric_map[analyzer]
            if analyzer.name == "KLLSketch":
                assert repr(other.value.get().buckets) == repr(
                    metric.value.get().buckets
                )
            else:
                assert other.value.get() == metric.value.get(), analyzer

    def test_device_path_resume_equals_uninterrupted(self):
        """ISSUE acceptance: interrupt mid-ingest, resume from the last
        StatePersister checkpoint, metrics EQUAL the uninterrupted run."""
        data = _numeric_data(rows=16 * 1024)
        analyzers = self._battery()
        uninterrupted = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024
        )
        checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=4)
        with inject(FaultSpec("device_update", "interrupt", at=11)):
            with pytest.raises(KeyboardInterrupt):
                AnalysisRunner.do_analysis_run(
                    data, analyzers, batch_size=1024, checkpointer=checkpointer
                )
        assert [index for index, _ in checkpointer.saves] == [4, 8]
        monitor = RunMonitor()
        resumed = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=checkpointer,
            monitor=monitor,
        )
        assert monitor.resumed_at_batch == 8
        assert monitor.batches == 8  # 16 total, 8 replayed
        self._assert_equal_contexts(resumed, uninterrupted)

    def test_completion_clears_checkpoint(self):
        data = _numeric_data(rows=8 * 1024)
        analyzers = self._battery()
        checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=2)
        AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=checkpointer
        )
        monitor = RunMonitor()
        AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=checkpointer,
            monitor=monitor,
        )
        assert monitor.resumed_at_batch is None  # fresh, not resumed
        assert monitor.batches == 8

    def test_shape_mismatch_ignores_checkpoint(self):
        data = _numeric_data(rows=8 * 1024)
        analyzers = self._battery()
        checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=2)
        with inject(FaultSpec("device_update", "interrupt", at=5)):
            with pytest.raises(KeyboardInterrupt):
                AnalysisRunner.do_analysis_run(
                    data, analyzers, batch_size=1024, checkpointer=checkpointer
                )
        assert checkpointer.saves
        monitor = RunMonitor()
        AnalysisRunner.do_analysis_run(  # DIFFERENT batch size: no resume
            data, analyzers, batch_size=2048, checkpointer=checkpointer,
            monitor=monitor,
        )
        assert monitor.resumed_at_batch is None

    def test_host_tier_resume_equals_uninterrupted(self, monkeypatch):
        from deequ_tpu.runners.engine import HOST_TIER_WORKERS_ENV

        monkeypatch.setenv(HOST_TIER_WORKERS_ENV, "2")
        rows = 80 * 512
        rng = np.random.default_rng(3)
        data = Dataset.from_dict(
            {
                "x": rng.normal(size=rows),
                "g": [f"id_{i}" for i in range(rows)],  # host accumulator
            }
        )
        analyzers = [Mean("x"), Sum("x"), KLLSketch("x"), Uniqueness(("g",))]
        uninterrupted = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=512, placement="host"
        )
        checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=8)
        with inject(FaultSpec("host_partial", "interrupt", at=75)):
            with pytest.raises(KeyboardInterrupt):
                AnalysisRunner.do_analysis_run(
                    data, analyzers, batch_size=512, placement="host",
                    checkpointer=checkpointer,
                )
        # host-tier checkpoints land on chunk (32-batch) boundaries
        assert [index for index, _ in checkpointer.saves] == [32, 64]
        monitor = RunMonitor()
        resumed = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=512, placement="host",
            checkpointer=checkpointer, monitor=monitor,
        )
        assert monitor.resumed_at_batch == 64
        assert monitor.batches == 16
        self._assert_equal_contexts(resumed, uninterrupted)

    def test_filesystem_provider_checkpoint_roundtrip(self, tmp_path):
        """Meta + states survive a PROCESS boundary: a fresh checkpointer
        over the same directory resumes (the real interruption story)."""
        data = _numeric_data(rows=8 * 1024)
        analyzers = [Completeness("x"), Mean("x"), Sum("x")]
        uninterrupted = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024
        )
        store = str(tmp_path / "ckpt")
        checkpointer = IngestCheckpointer(
            FileSystemStateProvider(store), every=2
        )
        with inject(FaultSpec("device_update", "interrupt", at=6)):
            with pytest.raises(KeyboardInterrupt):
                AnalysisRunner.do_analysis_run(
                    data, analyzers, batch_size=1024, checkpointer=checkpointer
                )
        fresh = IngestCheckpointer(FileSystemStateProvider(store), every=2)
        monitor = RunMonitor()
        resumed = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=fresh,
            monitor=monitor,
        )
        assert monitor.resumed_at_batch == 4
        self._assert_equal_contexts(resumed, uninterrupted)

    def test_checkpointer_via_suite_builder(self):
        data = _numeric_data(rows=4096)
        check = Check(CheckLevel.ERROR, "ck").has_mean("x", lambda m: abs(m) < 1)
        checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=1)
        result = (
            VerificationSuite.on_data(data)
            .add_check(check)
            .with_batch_size(1024)
            .checkpoint_with(checkpointer)
            .run()
        )
        assert result.status == CheckStatus.SUCCESS
        assert len(checkpointer.saves) >= 3

    def test_torn_save_invalidates_resume(self):
        """Invalidate-first protocol: a save that crashes after clearing
        the meta (states possibly torn) must make the next run start
        FRESH — never pair old meta with newer states and double-fold."""
        data = _numeric_data(rows=8 * 1024)
        analyzers = [Completeness("x"), Mean("x"), Sum("x")]
        base = AnalysisRunner.do_analysis_run(data, analyzers, batch_size=1024)
        provider = InMemoryStateProvider()
        checkpointer = IngestCheckpointer(provider, every=2)
        with inject(FaultSpec("device_update", "interrupt", at=6)):
            with pytest.raises(KeyboardInterrupt):
                AnalysisRunner.do_analysis_run(
                    data, analyzers, batch_size=1024, checkpointer=checkpointer
                )
        assert checkpointer.saves  # a resume point exists...
        checkpointer._write_meta(None)  # ...until a later save tears
        monitor = RunMonitor()
        resumed = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=checkpointer,
            monitor=monitor,
        )
        assert monitor.resumed_at_batch is None  # fresh, not corrupted
        assert monitor.batches == 8
        self._assert_equal_contexts(resumed, base)

    def test_workers_env_garbage_does_not_crash_host_tier(self, monkeypatch):
        from deequ_tpu.runners.engine import HOST_TIER_WORKERS_ENV

        monkeypatch.setenv(HOST_TIER_WORKERS_ENV, "banana")
        ctx = AnalysisRunner.do_analysis_run(
            _numeric_data(rows=4096), [Mean("x")], batch_size=1024,
            placement="host",
        )
        assert ctx.metric_map[Mean("x")].value.is_success

    def test_interval_validation(self):
        with pytest.raises(ValueError):
            IngestCheckpointer(InMemoryStateProvider(), every=0)
        with pytest.raises(TypeError):
            IngestCheckpointer(object())


class TestRouterLearning:
    def test_probation_routes_host_then_readmits(self):
        from deequ_tpu.service import (
            PlacementRouter,
            shape_qualified_signature,
        )

        router = PlacementRouter(background_warm=False)
        # shape-qualified: warmth rests purely on router evidence, so the
        # process-global program cache (warmed by other tests) cannot leak
        signature = shape_qualified_signature([Mean("x"), Sum("x")], 12345)
        router.note_ran(signature, worker_id=0, placement="device")
        assert router.decide(signature) is None  # warm -> device default
        router.note_device_failure(signature)
        for _ in range(router.SUSPECT_PROBATION_RUNS):
            assert router.decide(signature) == "host"
        # probation over AND warmth claim dropped: reads cold again
        assert router.decide(signature) == "host"
        router.note_ran(signature, worker_id=0, placement="device")
        assert router.decide(signature) is None
        router.close()

    def test_scheduler_harvests_device_failure(self):
        from deequ_tpu.service import VerificationService

        check = Check(CheckLevel.ERROR, "svc").has_mean("x", lambda m: abs(m) < 1)
        data = _numeric_data(rows=4096)
        with VerificationService(workers=2, background_warm=False) as service:
            # first run warms the battery so the router sends the second
            # to the DEVICE tier, where the injected fault fires
            service.verify(data, [check], timeout=120)
            with inject(FaultSpec("device_update", "device", at=1)) as inj:
                result = service.verify(data, [check], timeout=120)
            assert inj.fired  # the job really took the device path
            assert result.status == CheckStatus.SUCCESS
            snapshot = service.json_snapshot()["counters"]
            assert snapshot.get("deequ_service_device_failures_total", 0) >= 1

    def test_worker_crash_terminates_typed(self):
        from deequ_tpu.reliability import WorkerCrash
        from deequ_tpu.service import JobFailed, VerificationService

        check = Check(CheckLevel.ERROR, "crash").has_size(lambda n: n > 0)
        data = _numeric_data(rows=2048)
        with VerificationService(workers=2, background_warm=False) as service:
            with inject(FaultSpec("worker", "worker_death", count=None)):
                handle = service.submit_verification(
                    data, [check], max_retries=0
                )
                with pytest.raises(JobFailed) as info:
                    handle.result(timeout=120)
            assert isinstance(info.value.__cause__, WorkerCrash)

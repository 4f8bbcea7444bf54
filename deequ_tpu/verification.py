"""VerificationSuite: the main orchestration façade.

``VerificationSuite.on_data(data).add_check(check).run()`` collects the
analyzers every check needs, delegates metric computation to the
AnalysisRunner (one fused pass), evaluates checks against the resulting
AnalyzerContext and reports an overall status
(reference `VerificationSuite.scala:42-315`, `VerificationRunBuilder.scala:
28-341`, `VerificationResult.scala:33-119`).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from .analyzers import Analyzer
from .analyzers.state_provider import StateLoader, StatePersister
from .checks import Check, CheckLevel, CheckResult, CheckStatus
from .data import Dataset, Schema
from .metrics import Metric
from .runners.analysis_runner import AnalysisRunner
from .runners.context import AnalyzerContext


class VerificationResult:
    """(reference `VerificationResult.scala:33-119`)."""

    def __init__(
        self,
        status: CheckStatus,
        check_results: Dict[Check, CheckResult],
        metrics: Dict[Analyzer, Metric],
        cost_by_analyzer: Optional[Dict[str, float]] = None,
    ):
        self.status = status
        self.check_results = check_results
        self.metrics = metrics
        #: per-analyzer cost attribution (seconds, keyed by repr(analyzer))
        #: harvested from the run's RunMonitor: each signature bundle's
        #: measured compile+dispatch time split across its slots. Empty for
        #: state-only runs (`run_on_aggregated_states`) and when the caller
        #: evaluated checks against a pre-built context.
        self.cost_by_analyzer: Dict[str, float] = dict(cost_by_analyzer or {})

    def cost_by_analyzer_as_json(self) -> str:
        """The attribution table as JSON (sorted most-expensive first):
        ``[{"analyzer": ..., "seconds": ...}, ...]`` — round-trips through
        ``json.loads`` back to the table's contents."""
        rows = sorted(
            self.cost_by_analyzer.items(), key=lambda kv: -kv[1]
        )
        return json.dumps(
            [{"analyzer": k, "seconds": v} for k, v in rows]
        )

    def success_metrics_as_data_frame(self, for_analyzers: Sequence[Analyzer] = ()):
        return AnalyzerContext(self.metrics).success_metrics_as_dataframe(for_analyzers)

    def success_metrics_as_json(self, for_analyzers: Sequence[Analyzer] = ()) -> str:
        return AnalyzerContext(self.metrics).success_metrics_as_json(for_analyzers)

    def check_results_as_data_frame(self):
        import pandas as pd

        rows = []
        for check, result in self.check_results.items():
            for cr in result.constraint_results:
                rows.append(
                    {
                        "check": check.description,
                        "check_level": check.level.value,
                        "check_status": result.status.value,
                        "constraint": str(cr.constraint),
                        "constraint_status": cr.status.value,
                        "constraint_message": cr.message or "",
                    }
                )
        return pd.DataFrame(
            rows,
            columns=[
                "check",
                "check_level",
                "check_status",
                "constraint",
                "constraint_status",
                "constraint_message",
            ],
        )

    def check_results_as_json(self) -> str:
        df = self.check_results_as_data_frame()
        return json.dumps(df.to_dict(orient="records"))


class IncrementalVerificationResult(VerificationResult):
    """A :class:`VerificationResult` plus the incremental run's delta-plan
    report (``incremental``: an
    :class:`~deequ_tpu.runners.incremental.IncrementalRunReport` —
    scan/reuse/invalidated/dropped partition lists, rows scanned vs total,
    reuse ratio)."""

    def __init__(self, result: VerificationResult, report):
        super().__init__(
            result.status, result.check_results, result.metrics,
            result.cost_by_analyzer,
        )
        self.incremental = report


class VerificationSuite:
    """(reference `VerificationSuite.scala:42-315`)."""

    @staticmethod
    def on_data(data: Dataset) -> "VerificationRunBuilder":
        return VerificationRunBuilder(data)

    # ------------------------------------------------------------------

    @staticmethod
    def do_verification_run(
        data: Dataset,
        checks: Sequence[Check],
        required_analyzers: Sequence[Analyzer] = (),
        *,
        aggregate_with: Optional[StateLoader] = None,
        save_states_with: Optional[StatePersister] = None,
        metrics_repository: Optional[Any] = None,
        reuse_existing_results_for_key: Optional[Any] = None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key: Optional[Any] = None,
        batch_size: Optional[int] = None,
        monitor: Optional[Any] = None,
        sharding: Optional[Any] = None,
        placement: Optional[str] = None,
        checkpointer: Optional[Any] = None,
    ) -> VerificationResult:
        from .observability import trace as _trace
        from .runners.analysis_runner import collect_required_analyzers
        from .runners.engine import RunMonitor

        checks = list(checks)  # evaluate() walks them again after the run
        analyzers = collect_required_analyzers(checks, required_analyzers)
        # a monitor always exists so per-analyzer cost attribution reaches
        # the result even when the caller did not ask for one
        monitor = monitor if monitor is not None else RunMonitor()

        with _trace.span(
            "verification_run", kind="verification",
            checks=len(checks), analyzers=len(analyzers),
        ):
            analysis_results = AnalysisRunner.do_analysis_run(
                data,
                analyzers,
                aggregate_with=aggregate_with,
                save_states_with=save_states_with,
                metrics_repository=metrics_repository,
                reuse_existing_results_for_key=reuse_existing_results_for_key,
                fail_if_results_missing=fail_if_results_missing,
                # save AFTER evaluation (below), so anomaly checks never see
                # the current point in their own history (reference
                # `VerificationSuite.scala:121-139`)
                save_or_append_results_with_key=None,
                batch_size=batch_size,
                monitor=monitor,
                sharding=sharding,
                placement=placement,
                checkpointer=checkpointer,
            )
            with monitor.timed("constraint_evaluation"):
                result = VerificationSuite.evaluate(checks, analysis_results)
            result.cost_by_analyzer = dict(monitor.cost_by_analyzer)
            if metrics_repository is not None and save_or_append_results_with_key is not None:
                from .runners.analysis_runner import _save_or_append

                _save_or_append(
                    metrics_repository, save_or_append_results_with_key,
                    analysis_results,
                )
            return result

    @staticmethod
    def verify_partitioned(
        store,
        dataset_name: str,
        partitions,
        checks: Sequence[Check],
        required_analyzers: Sequence[Analyzer] = (),
        *,
        checksums=None,
        batch_size: Optional[int] = None,
        monitor: Optional[Any] = None,
        sharding: Optional[Any] = None,
        placement: Optional[str] = None,
        metrics_repository: Optional[Any] = None,
        save_or_append_results_with_key: Optional[Any] = None,
        delete_dropped: bool = False,
    ) -> "IncrementalVerificationResult":
        """Partition-aware incremental verification (ROADMAP item 4): diff
        the incoming partition set against ``store`` (a
        :class:`~deequ_tpu.repository.partition_store.PartitionStateStore`),
        scan ONLY new/changed partitions (persisting their per-partition
        algebraic states), load unchanged partitions' states with zero
        data touched, and evaluate ``checks`` against the merge — a table
        that grew 1% verifies at ~1% of a full scan. The returned
        result's ``incremental`` report carries the delta plan and the
        rows-touched accounting."""
        from .observability import trace as _trace
        from .runners.analysis_runner import collect_required_analyzers
        from .runners.engine import RunMonitor
        from .runners.incremental import run_incremental

        checks = list(checks)
        analyzers = collect_required_analyzers(checks, required_analyzers)
        monitor = monitor if monitor is not None else RunMonitor()
        with _trace.span(
            "incremental_verification", kind="verification",
            dataset=str(dataset_name), checks=len(checks),
        ):
            context, report = run_incremental(
                store, dataset_name, partitions, analyzers,
                checksums=checksums, batch_size=batch_size,
                monitor=monitor, sharding=sharding, placement=placement,
                metrics_repository=metrics_repository,
                save_or_append_results_with_key=save_or_append_results_with_key,
                delete_dropped=delete_dropped,
            )
            with monitor.timed("constraint_evaluation"):
                result = VerificationSuite.evaluate(checks, context)
            result.cost_by_analyzer = dict(monitor.cost_by_analyzer)
        return IncrementalVerificationResult(result, report)

    @staticmethod
    def on_partitions(
        store, dataset_name: str, partitions, checksums=None
    ) -> "PartitionedVerificationRunBuilder":
        """Fluent entry point of :meth:`verify_partitioned`."""
        return PartitionedVerificationRunBuilder(
            store, dataset_name, partitions, checksums
        )

    @staticmethod
    def run_on_aggregated_states(
        schema: Schema,
        checks: Sequence[Check],
        state_loaders: Sequence[StateLoader],
        *,
        required_analyzers: Sequence[Analyzer] = (),
        save_states_with: Optional[StatePersister] = None,
        metrics_repository: Optional[Any] = None,
        save_or_append_results_with_key: Optional[Any] = None,
    ) -> VerificationResult:
        """Verification from merged persisted states, no data pass
        (reference `VerificationSuite.scala:208-229`)."""
        from .runners.analysis_runner import collect_required_analyzers

        checks = list(checks)  # evaluate() walks them again after the run
        analyzers = collect_required_analyzers(checks, required_analyzers)
        context = AnalysisRunner.run_on_aggregated_states(
            schema,
            analyzers,
            state_loaders,
            save_states_with=save_states_with,
            metrics_repository=metrics_repository,
            save_or_append_results_with_key=save_or_append_results_with_key,
        )
        return VerificationSuite.evaluate(checks, context)

    @staticmethod
    def evaluate(checks: Sequence[Check], context: AnalyzerContext) -> VerificationResult:
        """(reference `VerificationSuite.scala:263-281`)."""
        check_results = {check: check.evaluate(context) for check in checks}
        if not check_results:
            status = CheckStatus.SUCCESS
        else:
            status = max(
                (r.status for r in check_results.values()), key=lambda s: s.severity
            )
        return VerificationResult(status, check_results, dict(context.metric_map))


@dataclass(frozen=True)
class AnomalyCheckConfig:
    """(reference `VerificationRunBuilder.scala:336`)."""

    level: CheckLevel
    description: str
    with_tag_values: Dict[str, str] = field(default_factory=dict)
    after_date: Optional[int] = None
    before_date: Optional[int] = None


class VerificationRunBuilder:
    """Fluent run configuration (reference `VerificationRunBuilder.scala:
    28-163`)."""

    def __init__(self, data: Dataset):
        self.data = data
        self.checks: List[Check] = []
        self.required_analyzers: List[Analyzer] = []
        self._aggregate_with: Optional[StateLoader] = None
        self._save_states_with: Optional[StatePersister] = None
        self._metrics_repository = None
        self._reuse_key = None
        self._fail_if_results_missing = False
        self._save_key = None
        self._batch_size: Optional[int] = None
        self._monitor = None
        self._sharding = None
        self._placement: Optional[str] = None
        self._checkpointer = None
        self._check_results_path: Optional[str] = None
        self._success_metrics_path: Optional[str] = None

    def add_check(self, check: Check) -> "VerificationRunBuilder":
        self.checks.append(check)
        return self

    def add_checks(self, checks: Sequence[Check]) -> "VerificationRunBuilder":
        self.checks.extend(checks)
        return self

    def add_required_analyzer(self, analyzer: Analyzer) -> "VerificationRunBuilder":
        self.required_analyzers.append(analyzer)
        return self

    def add_required_analyzers(self, analyzers: Sequence[Analyzer]) -> "VerificationRunBuilder":
        self.required_analyzers.extend(analyzers)
        return self

    def aggregate_with(self, state_loader: StateLoader) -> "VerificationRunBuilder":
        self._aggregate_with = state_loader
        return self

    def save_states_with(self, state_persister: StatePersister) -> "VerificationRunBuilder":
        self._save_states_with = state_persister
        return self

    def with_batch_size(self, batch_size: int) -> "VerificationRunBuilder":
        self._batch_size = batch_size
        return self

    def with_monitor(self, monitor) -> "VerificationRunBuilder":
        self._monitor = monitor
        return self

    def with_sharding(self, sharding) -> "VerificationRunBuilder":
        self._sharding = sharding
        return self

    def with_placement(self, placement: str) -> "VerificationRunBuilder":
        """Force the ingest tier: "device", "host", or "auto" (the service's
        cache-aware router uses this to keep cold compiles off the queue)."""
        self._placement = placement
        return self

    def checkpoint_with(self, checkpointer) -> "VerificationRunBuilder":
        """Make the multi-batch ingest resumable: a
        `reliability.IngestCheckpointer` persists algebraic states every K
        batches through its StatePersister, and an interrupted run invoked
        again with the same checkpointer resumes from the last checkpoint
        with metrics equal to the uninterrupted run (see README "Failure
        semantics")."""
        self._checkpointer = checkpointer
        return self

    def save_check_results_json_to_path(self, path: str) -> "VerificationRunBuilder":
        self._check_results_path = path
        return self

    def save_success_metrics_json_to_path(self, path: str) -> "VerificationRunBuilder":
        self._success_metrics_path = path
        return self

    def use_repository(self, repository) -> "VerificationRunBuilderWithRepository":
        return VerificationRunBuilderWithRepository(self, repository)

    def run(self) -> VerificationResult:
        result = VerificationSuite.do_verification_run(
            self.data,
            self.checks,
            self.required_analyzers,
            aggregate_with=self._aggregate_with,
            save_states_with=self._save_states_with,
            metrics_repository=self._metrics_repository,
            reuse_existing_results_for_key=self._reuse_key,
            fail_if_results_missing=self._fail_if_results_missing,
            save_or_append_results_with_key=self._save_key,
            batch_size=self._batch_size,
            monitor=self._monitor,
            sharding=self._sharding,
            placement=self._placement,
            checkpointer=self._checkpointer,
        )
        # URI-aware sinks (reference writes these through Hadoop FileSystem,
        # `VerificationSuite.scala:146-172` / `io/DfsUtils.scala:24-85`)
        from . import io as dio

        if self._check_results_path is not None:
            dio.write_text_atomic(
                self._check_results_path, result.check_results_as_json()
            )
        if self._success_metrics_path is not None:
            dio.write_text_atomic(
                self._success_metrics_path, result.success_metrics_as_json()
            )
        return result


class PartitionedVerificationRunBuilder:
    """Fluent configuration for partition-aware incremental verification
    (``VerificationSuite.on_partitions(store, name, partitions)``): the
    check-building half of :class:`VerificationRunBuilder`, running
    through the delta planner instead of a single data pass."""

    def __init__(self, store, dataset_name: str, partitions, checksums=None):
        self.store = store
        self.dataset_name = dataset_name
        self.partitions = partitions
        self.checksums = checksums
        self.checks: List[Check] = []
        self.required_analyzers: List[Analyzer] = []
        self._batch_size: Optional[int] = None
        self._monitor = None
        self._sharding = None
        self._placement: Optional[str] = None
        self._metrics_repository = None
        self._save_key = None
        self._delete_dropped = False

    def add_check(self, check: Check) -> "PartitionedVerificationRunBuilder":
        self.checks.append(check)
        return self

    def add_checks(self, checks: Sequence[Check]) -> "PartitionedVerificationRunBuilder":
        self.checks.extend(checks)
        return self

    def add_required_analyzer(self, analyzer: Analyzer) -> "PartitionedVerificationRunBuilder":
        self.required_analyzers.append(analyzer)
        return self

    def add_required_analyzers(self, analyzers: Sequence[Analyzer]) -> "PartitionedVerificationRunBuilder":
        self.required_analyzers.extend(analyzers)
        return self

    def with_batch_size(self, batch_size: int) -> "PartitionedVerificationRunBuilder":
        self._batch_size = batch_size
        return self

    def with_monitor(self, monitor) -> "PartitionedVerificationRunBuilder":
        self._monitor = monitor
        return self

    def with_sharding(self, sharding) -> "PartitionedVerificationRunBuilder":
        self._sharding = sharding
        return self

    def with_placement(self, placement: str) -> "PartitionedVerificationRunBuilder":
        self._placement = placement
        return self

    def use_repository(self, repository) -> "PartitionedVerificationRunBuilder":
        self._metrics_repository = repository
        return self

    def save_or_append_result(self, key) -> "PartitionedVerificationRunBuilder":
        self._save_key = key
        return self

    def delete_dropped_partitions(self) -> "PartitionedVerificationRunBuilder":
        """Retention: partitions absent from the incoming set are DELETED
        from the store after the merge (they were already excluded from
        the metrics by re-merge semantics)."""
        self._delete_dropped = True
        return self

    def run(self) -> "IncrementalVerificationResult":
        return VerificationSuite.verify_partitioned(
            self.store,
            self.dataset_name,
            self.partitions,
            self.checks,
            self.required_analyzers,
            checksums=self.checksums,
            batch_size=self._batch_size,
            monitor=self._monitor,
            sharding=self._sharding,
            placement=self._placement,
            metrics_repository=self._metrics_repository,
            save_or_append_results_with_key=self._save_key,
            delete_dropped=self._delete_dropped,
        )


class VerificationRunBuilderWithRepository(VerificationRunBuilder):
    """(reference `VerificationRunBuilder.scala:196-341`)."""

    def __init__(self, parent: VerificationRunBuilder, repository):
        self.__dict__.update(parent.__dict__)
        self._metrics_repository = repository

    def reuse_existing_results_for_key(
        self, key, fail_if_results_missing: bool = False
    ) -> "VerificationRunBuilderWithRepository":
        self._reuse_key = key
        self._fail_if_results_missing = fail_if_results_missing
        return self

    def save_or_append_result(self, key) -> "VerificationRunBuilderWithRepository":
        self._save_key = key
        return self

    def add_anomaly_check(
        self, anomaly_detection_strategy, analyzer: Analyzer, anomaly_check_config=None
    ) -> "VerificationRunBuilderWithRepository":
        """(reference `VerificationRunBuilder.scala:227-244`)."""
        description = f"Anomaly check for {analyzer}"
        config = anomaly_check_config or AnomalyCheckConfig(CheckLevel.WARNING, description)
        check = Check(config.level, config.description).is_newest_point_non_anomalous(
            self._metrics_repository,
            anomaly_detection_strategy,
            analyzer,
            config.with_tag_values,
            config.after_date,
            config.before_date,
        )
        self.checks.append(check)
        return self

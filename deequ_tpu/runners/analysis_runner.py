"""AnalysisRunner: the scheduler.

Reference flow (`analyzers/runners/AnalysisRunner.scala:97-203`):
dedupe vs repository cache -> precondition partition -> split
{scanning, grouping, KLL} -> fused scan + per-grouping-set frequency jobs ->
assemble AnalyzerContext -> optional repository save.

TPU-native differences: KLL updates are batched fixed-shape device ops, so
they join the SAME fused pass as every other scan analyzer (the reference
needs a dedicated RDD pass, `KLLRunner.scala:87-122`); grouping frequency
tables accumulate on host during that same pass — a full run touches the
data exactly once.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..analyzers.base import Analyzer, Preconditions, ScanShareableAnalyzer
from ..analyzers.grouping import (
    FrequenciesAndNumRows,
    GroupingAnalyzer,
    Histogram,
)
from ..analyzers.state_provider import StateLoader, StatePersister
from ..data import Dataset
from ..metrics import Metric
from .context import AnalyzerContext
from .engine import RunMonitor, ScanEngine
from .exceptions import MetricCalculationException


def collect_required_analyzers(checks, required_analyzers=()) -> List[Analyzer]:
    """Every analyzer a verification run needs: the explicitly required
    ones plus each check's, in first-encounter order. Shared by the suite,
    the aggregated-states path and the service plane (which also derives
    the placement-cache signature from it), so the three can never disagree
    about what a set of checks computes."""
    analyzers: List[Analyzer] = list(required_analyzers)
    for check in checks:
        analyzers.extend(check.required_analyzers())
    return analyzers


class AnalysisRunner:
    """Static entry points (reference `AnalysisRunner.onData/run`)."""

    @staticmethod
    def on_data(data: Dataset) -> "AnalysisRunBuilder":
        from .builder import AnalysisRunBuilder

        return AnalysisRunBuilder(data)

    # ------------------------------------------------------------------

    @staticmethod
    def do_analysis_run(data: Dataset, analyzers: Sequence[Analyzer], **kwargs) -> AnalyzerContext:
        """Tracing shell around :meth:`_do_analysis_run`: every pass this
        run triggers — the primary fused scan, bisection re-passes, tier
        failovers — nests under ONE ``analysis_run`` span, so a degraded
        run reads as a connected tree (see ``deequ_tpu.observability``)."""
        if len(analyzers) == 0:
            return AnalyzerContext.empty()
        from ..observability import trace as _trace

        with _trace.span(
            "analysis_run", kind="analysis", analyzers=len(analyzers)
        ):
            return AnalysisRunner._do_analysis_run(data, analyzers, **kwargs)

    @staticmethod
    def _do_analysis_run(
        data: Dataset,
        analyzers: Sequence[Analyzer],
        *,
        aggregate_with: Optional[StateLoader] = None,
        save_states_with: Optional[StatePersister] = None,
        metrics_repository: Optional[Any] = None,
        reuse_existing_results_for_key: Optional[Any] = None,
        fail_if_results_missing: bool = False,
        save_or_append_results_with_key: Optional[Any] = None,
        batch_size: Optional[int] = None,
        monitor: Optional[RunMonitor] = None,
        sharding: Optional[Any] = None,
        placement: Optional[str] = None,
        checkpointer: Optional[Any] = None,
    ) -> AnalyzerContext:
        if len(analyzers) == 0:
            return AnalyzerContext.empty()

        run_monitor = monitor or RunMonitor()
        # the host work before the pass: dedupe, repository reuse,
        # preconditions, feature validation, dictionary probes and the
        # frequency-table plan
        with run_monitor.timed("plan"):
            # dedupe identical analyzers, preserving order
            seen = set()
            unique: List[Analyzer] = []
            for a in analyzers:
                if a not in seen:
                    seen.add(a)
                    unique.append(a)

            # reuse existing results from the repository
            # (reference `AnalysisRunner.scala:115-134`)
            results_loaded = AnalyzerContext.empty()
            analyzers_to_run = unique
            if metrics_repository is not None and reuse_existing_results_for_key is not None:
                existing = metrics_repository.load_by_key(reuse_existing_results_for_key)
                if existing is not None:
                    loaded = {
                        a: m for a, m in existing.metric_map.items() if a in seen
                    }
                    results_loaded = AnalyzerContext(loaded)
                    analyzers_to_run = [a for a in unique if a not in loaded]
                if fail_if_results_missing and analyzers_to_run:
                    raise MetricCalculationException(
                        "Could not find all necessary results in the MetricsRepository, "
                        f"the calculation of the metrics for these analyzers would be needed: "
                        f"{', '.join(str(a) for a in analyzers_to_run)}"
                    )

            # precondition partition (reference `AnalysisRunner.scala:137-145`)
            schema = data.schema
            passed: List[Analyzer] = []
            failures: Dict[Analyzer, Metric] = {}
            for a in analyzers_to_run:
                exc = Preconditions.find_first_failing(schema, a.preconditions())
                if exc is None:
                    passed.append(a)
                else:
                    failures[a] = a.to_failure_metric(exc)

            # validate each analyzer's features on a synthetic 1-row batch so a
            # bad predicate/regex fails only that analyzer, not the shared scan
            from .features import FeatureBuilder, dry_run_batch

            dry = dry_run_batch(schema)
            validated = []
            for a in passed:
                if isinstance(a, ScanShareableAnalyzer):
                    try:
                        FeatureBuilder(a.feature_specs()).build(dry)
                    except Exception as exc:  # noqa: BLE001
                        failures[a] = a.to_failure_metric(exc)
                        continue
                validated.append(a)
            passed = validated
            precondition_failures = AnalyzerContext(failures)

            # split: device-fused scan / grouping sets / host accumulators
            from ..analyzers.grouping import (
                DeviceFrequencyScan,
                DeviceFrequencyTableScan,
                ScanShareableFrequencyBasedAnalyzer,
                device_freq_enabled,
                device_freq_max_cardinality,
                plan_table_scan,
                probably_low_cardinality,
            )

            dict_card_limit = device_freq_max_cardinality()

            # host-exclusive analyzers (e.g. exact-quantile mode, whose
            # accumulator is unbounded and has no fixed-shape device fold) opt
            # out of the fused scan even though their class is scan-shareable.
            # Their raw-value states are deliberately NOT in the persistence
            # registry, so a configured checkpointer would blow up on its first
            # save; drop it with a warning instead (the same degradation the
            # mesh path applies), keeping the run correct end to end.
            if checkpointer is not None and any(
                getattr(a, "host_exclusive", False) for a in passed
            ):
                import logging

                logging.getLogger(__name__).warning(
                    "ingest checkpointing is not supported with host-exclusive "
                    "analyzers (e.g. exact-quantile mode, whose raw-value "
                    "states are not persistable); running without checkpoints"
                )
                checkpointer = None
            scanning = [
                a
                for a in passed
                if isinstance(a, ScanShareableAnalyzer)
                and not getattr(a, "host_exclusive", False)
            ]
            scanning_set = set(scanning)
            grouping = [a for a in passed if isinstance(a, GroupingAnalyzer)]
            # binning-free Histograms over small-dictionary columns share the
            # device frequency scan instead of accumulating a host group-by per
            # batch (their metric is derived from the same counts; keys are
            # Spark-string-cast at finalize). The reference always runs its own
            # groupBy per Histogram (`analyzers/Histogram.scala:54-96`).
            device_hist = [
                a
                for a in passed
                if isinstance(a, Histogram)
                and a.binning_func is None
                and (size := data.dictionary_size(a.column)) is not None
                and size <= dict_card_limit
            ]
            device_hist_set = set(device_hist)
            host_accum = [
                a
                for a in passed
                if hasattr(a, "host_init")
                and not isinstance(a, GroupingAnalyzer)
                and a not in device_hist_set
                and a not in scanning_set
            ]
            others = [
                a
                for a in passed
                if a not in scanning_set
                and a not in grouping
                and a not in host_accum
                and a not in device_hist_set
            ]

            grouping_sets: Dict[Tuple[str, ...], List[GroupingAnalyzer]] = {}
            for g in grouping:
                grouping_sets.setdefault(tuple(g.grouping_columns()), []).append(g)

            # single-column grouping sets over dictionary-encoded columns whose
            # dictionary is small ride the fused DEVICE scan as a segment_sum
            # (SURVEY §7 step 6's low-cardinality hybrid)
            device_freq: Dict[Tuple[str, ...], DeviceFrequencyScan] = {}
            device_dicts: Dict[Tuple[str, ...], Any] = {}
            for cols in list(grouping_sets) + [(a.column,) for a in device_hist]:
                if cols in device_freq:
                    continue
                if len(cols) == 1:
                    dictionary = data.dictionary_values(cols[0])
                    if dictionary is not None and len(dictionary) <= dict_card_limit:
                        device_freq[cols] = DeviceFrequencyScan(cols[0], len(dictionary))
                        device_dicts[cols] = dictionary
            # a histogram column whose dictionary out-sizes the device path
            # falls back to the host accumulator
            for a in device_hist:
                if (a.column,) not in device_freq:
                    device_hist_set.discard(a)
                    host_accum.append(a)
            device_hist = [a for a in device_hist if a in device_hist_set]

            # every OTHER grouping set rides the device frequency TABLE engine
            # (hashed fixed-shape count tables folded in the fused pass,
            # ROADMAP item 3) when it safely can:
            #  - every member reduces the COUNT MULTISET alone (Histogram /
            #    MutualInformation read keys and stay on the dict/host paths);
            #  - nothing downstream needs value-keyed states — no persistence,
            #    aggregation or checkpointing (hashed tables and value-keyed
            #    host states must never merge);
            #  - x64 is on (uint64 keys) and the pass will run the DEVICE tier
            #    (on a feed-starved link streaming 8B/row of raw keys loses to
            #    the in-place host group-by).
            # Overflowing tables fall back per set after the pass; the host
            # accumulator (and its _SpillStore) is the last-resort tier.
            import jax as _jax

            from .engine import effective_batch_size as _ebs

            slim = (
                aggregate_with is None
                and save_states_with is None
                and checkpointer is None
            )
            table_freq: Dict[Tuple[str, ...], DeviceFrequencyTableScan] = {}
            if (
                slim
                and grouping_sets
                and device_freq_enabled()
                and _jax.config.jax_enable_x64
                and _device_tier_expected(scanning, placement)
            ):
                batch_rows = _ebs(data, batch_size)
                shards = 1
                if sharding is not None:
                    from ..parallel import mesh_batch_quantum

                    shards = int(sharding.devices.size)
                    q = mesh_batch_quantum(shards)
                    batch_rows = ((batch_rows + q - 1) // q) * q
                for cols, members in grouping_sets.items():
                    if cols in device_freq:
                        continue
                    if not all(
                        isinstance(a, ScanShareableFrequencyBasedAnalyzer)
                        for a in members
                    ):
                        continue
                    if probably_low_cardinality(data, cols):
                        # below the sweep knee the host value_counts fast
                        # path beats the device table ~3x — keep the
                        # pre-engine routing for confidently-small sets
                        continue
                    scan = plan_table_scan(
                        schema, cols, int(data.num_rows), batch_rows, shards
                    )
                    if scan is not None:
                        table_freq[cols] = scan

            # one shared pass over the data — executed through the reliability
            # layer: a device-infrastructure failure fails the battery over to
            # the host tier (OOMs first bisect the batch size), and an
            # analyzer-level fault bisects the battery until exactly the faulty
            # analyzers degrade to typed Failure metrics while the rest
            # complete (the fused-engine restoration of the reference's
            # per-expression degradation, `AnalysisRunner.scala:320-323`)
            scan_battery = (
                scanning + list(device_freq.values()) + list(table_freq.values())
            )
            if table_freq:
                run_monitor.bump("device_freq_sets", len(table_freq))

            def make_host_states():
                hs: Dict[Any, Any] = {}
                hu: Dict[Any, Any] = {}
                for cols in grouping_sets:
                    if cols in device_freq or cols in table_freq:
                        continue
                    key = ("__grouping__", cols)
                    hs[key] = FrequenciesAndNumRows.empty(list(cols))
                    hu[key] = lambda st, batch: st.update(batch)
                for a in host_accum:
                    hs[a] = a.host_init()
                    hu[a] = a.host_update
                return hs, hu

            host_keys = list(make_host_states()[0])
        need_pass = bool(scan_battery) or bool(host_keys)
        metrics: Dict[Analyzer, Metric] = {}
        if need_pass:
            from ..reliability import run_scan_resilient
            from .engine import effective_batch_size

            full_battery = tuple(scan_battery)
            # slim fetch (the hoisted ``slim``): when nothing downstream
            # needs the full states (no persistence, no cross-run
            # aggregation, no checkpoint), each analyzer ships only its
            # metric-bearing leaves back over the feed link
            # (engine._fetch_states_packed's analyzers arg)

            outer_sharding = sharding
            _KEEP_SHARDING = object()

            def run_pass(
                part, hs, hu, *, placement=None, batch_size=None,
                sharding=_KEEP_SHARDING,
            ):
                # the reliability ladder overrides ``sharding`` only after
                # a shard loss escaped the engine: the pass then re-runs
                # whole on a mesh rebuilt over the surviving devices
                pass_sharding = (
                    outer_sharding if sharding is _KEEP_SHARDING else sharding
                )
                with run_monitor.timed("plan"):
                    engine = ScanEngine(
                        list(part), monitor=run_monitor,
                        sharding=pass_sharding, placement=placement,
                    )
                    g_sets = [
                        key[1] for key in hs
                        if isinstance(key, tuple) and key
                        and key[0] == "__grouping__"
                    ]
                    h_accum = [key for key in hs if not isinstance(key, tuple)]
                    cols = _columns_needed(engine, g_sets, h_accum, schema)
                # checkpoints belong to the primary full-battery fold only:
                # bisection re-passes must not clobber its resume point
                ckpt = checkpointer if tuple(part) == full_battery else None
                return engine.run(
                    data, batch_size=batch_size, host_accumulators=hs,
                    host_update_fns=hu, columns=cols, checkpointer=ckpt,
                    slim_fetch=slim,
                )

            outcome = run_scan_resilient(
                run_pass, full_battery, make_host_states, run_monitor,
                batch_size=effective_batch_size(data, batch_size),
                placement=placement, sharding=sharding,
            )

            # the engine drained the device frequency tables into
            # summaries. A set whose table overflowed (compactions dropped
            # groups) or whose scan degraded re-runs through the host
            # accumulator in ONE dedicated last-resort pass; _SpillStore
            # sits below that, exactly the old default path, now reached
            # only when the device tiers are exhausted.
            table_shared: Dict[Tuple[str, ...], Any] = {}
            fallback_states: Dict[Any, Any] = {}
            fallback_errors: Dict[Any, BaseException] = {}
            fallback_sets: List[Tuple[str, ...]] = []
            fallback_losses: List[str] = []
            for cols, scan in table_freq.items():
                summary = outcome.states.get(scan)
                if summary is None:
                    fallback_sets.append(cols)
                    fallback_losses.append(f"{cols}: pass degraded")
                elif summary.lost_rows:
                    fallback_sets.append(cols)
                    run_monitor.bump("freq_overflow_fallbacks")
                    fallback_losses.append(
                        f"{cols}: ~{summary.lost_groups} groups / "
                        f"{summary.lost_rows} rows dropped"
                    )
                else:
                    table_shared[cols] = summary
            if fallback_sets:
                import logging

                logging.getLogger(__name__).warning(
                    "device frequency table overflowed (or degraded) for "
                    "grouping sets [%s]; re-running them through the host "
                    "accumulator tier", "; ".join(fallback_losses),
                )

                def make_fallback_states():
                    hs: Dict[Any, Any] = {}
                    hu: Dict[Any, Any] = {}
                    for cols in fallback_sets:
                        key = ("__grouping__", cols)
                        hs[key] = FrequenciesAndNumRows.empty(list(cols))
                        hu[key] = lambda st, batch: st.update(batch)
                    return hs, hu

                fb = run_scan_resilient(
                    run_pass, (), make_fallback_states, run_monitor,
                    batch_size=effective_batch_size(data, batch_size),
                    placement=placement, sharding=sharding,
                )
                fallback_states = fb.host_states
                fallback_errors = fb.host_errors

            # scanning analyzers: load old state -> merge -> persist -> metric
            # (reference `Analyzer.calculateMetric`, `Analyzer.scala:107-128`)
            # — a monitored phase, so state-merge/persist/metric cost is
            # attributable (and span-backed) like every engine phase
            with run_monitor.timed("metric_derivation"):
                for a in scanning:
                    if a in outcome.states:
                        metrics[a] = _finalize(
                            a, outcome.states[a], aggregate_with, save_states_with
                        )
                    else:
                        metrics[a] = a.to_failure_metric(outcome.errors[a])
                device_freq_states = {
                    cols: outcome.states.get(scan)
                    for cols, scan in device_freq.items()
                }

                def shared_frequencies(cols):
                    """The grouping state for ``cols``, or the typed error
                    that took its producer down (device scan, device
                    frequency table, or host accumulator)."""
                    if cols in device_freq:
                        scan = device_freq[cols]
                        if device_freq_states[cols] is None:
                            return None, outcome.errors[scan]
                        return (
                            scan.to_frequencies(
                                device_freq_states[cols], device_dicts[cols]
                            ),
                            None,
                        )
                    if cols in table_freq:
                        if cols in table_shared:
                            return table_shared[cols], None
                        key = ("__grouping__", cols)
                        if key in fallback_states:
                            return fallback_states[key], None
                        return None, fallback_errors.get(
                            key, outcome.errors.get(table_freq[cols])
                        )
                    key = ("__grouping__", cols)
                    if key in outcome.host_errors:
                        return None, outcome.host_errors[key]
                    return outcome.host_states[key], None

                for cols, members in grouping_sets.items():
                    shared, err = shared_frequencies(cols)
                    for a in members:
                        if err is not None:
                            metrics[a] = a.to_failure_metric(err)
                        else:
                            metrics[a] = _finalize(
                                a, shared, aggregate_with, save_states_with
                            )
                for a in host_accum:
                    if a in outcome.host_errors:
                        metrics[a] = a.to_failure_metric(outcome.host_errors[a])
                    else:
                        metrics[a] = _finalize(
                            a, outcome.host_states[a], aggregate_with,
                            save_states_with,
                        )
                from ..analyzers.grouping import (
                    device_counts_to_histogram_frequencies,
                )

                for a in device_hist:
                    cols = (a.column,)
                    if device_freq_states[cols] is None:
                        metrics[a] = a.to_failure_metric(
                            outcome.errors[device_freq[cols]]
                        )
                        continue
                    shared = device_counts_to_histogram_frequencies(
                        device_freq[cols],
                        device_freq_states[cols],
                        device_dicts[cols],
                    )
                    metrics[a] = _finalize(
                        a, shared, aggregate_with, save_states_with
                    )
            if slim:
                # explicit spill-dir cleanup: pass-local grouping/histogram
                # tables are dead once their metrics are derived — release
                # any _SpillStore directory NOW instead of at GC time. A
                # non-slim run may have handed the state OBJECT to a
                # persister (InMemoryStateProvider keeps the reference), so
                # those rely on the GC finalizer backstop.
                for st in (
                    *outcome.host_states.values(),
                    *fallback_states.values(),
                ):
                    if isinstance(st, FrequenciesAndNumRows):
                        st.close()
        for a in others:
            metrics[a] = a.to_failure_metric(
                MetricCalculationException(f"No execution strategy for analyzer {a}")
            )

        context = results_loaded + precondition_failures + AnalyzerContext(metrics)

        if metrics_repository is not None and save_or_append_results_with_key is not None:
            _save_or_append(metrics_repository, save_or_append_results_with_key, context)
        return context

    # ------------------------------------------------------------------

    @staticmethod
    def run_on_aggregated_states(
        schema,
        analyzers: Sequence[Analyzer],
        state_loaders: Sequence[StateLoader],
        *,
        save_states_with: Optional[StatePersister] = None,
        metrics_repository: Optional[Any] = None,
        save_or_append_results_with_key: Optional[Any] = None,
    ) -> AnalyzerContext:
        """Compute metrics purely from merged persisted states — no data pass
        (reference `AnalysisRunner.runOnAggregatedStates`,
        `AnalysisRunner.scala:385-460`)."""
        if len(analyzers) == 0 or len(state_loaders) == 0:
            return AnalyzerContext.empty()

        passed: List[Analyzer] = []
        failures: Dict[Analyzer, Metric] = {}
        for a in analyzers:
            exc = Preconditions.find_first_failing(schema, a.preconditions())
            if exc is None:
                passed.append(a)
            else:
                failures[a] = a.to_failure_metric(exc)

        from ..analyzers.base import merge_states_batched

        metrics: Dict[Analyzer, Metric] = {}
        for a in passed:
            merged = merge_states_batched(
                a, [loader.load(a) for loader in state_loaders]
            )
            if save_states_with is not None and merged is not None:
                save_states_with.persist(a, merged)
            try:
                metrics[a] = a.compute_metric_from(merged)
            except Exception as exc:  # noqa: BLE001
                metrics[a] = a.to_failure_metric(exc)

        context = AnalyzerContext(failures) + AnalyzerContext(metrics)
        if metrics_repository is not None and save_or_append_results_with_key is not None:
            _save_or_append(metrics_repository, save_or_append_results_with_key, context)
        return context


def _finalize(
    analyzer: Analyzer,
    state: Any,
    aggregate_with: Optional[StateLoader],
    save_states_with: Optional[StatePersister],
) -> Metric:
    from ..analyzers.base import merge_states_batched

    try:
        if aggregate_with is not None:
            loaded = aggregate_with.load(analyzer)
            state = merge_states_batched(analyzer, [loaded, state])
        if save_states_with is not None and state is not None:
            save_states_with.persist(analyzer, state)
        return analyzer.compute_metric_from(state)
    except Exception as exc:  # noqa: BLE001
        return analyzer.to_failure_metric(exc)


def _device_tier_expected(scanning, placement) -> bool:
    """Whether the shared pass will stream batches to the DEVICE tier —
    the gate for the device frequency table engine (its raw per-row hash
    keys cost ~8B/row/column on the feed link; on a host-tier pass the
    in-place group-by is strictly better). Delegates to the engine's own
    ``resolve_scan_placement`` so the gate can never drift from where the
    pass actually runs."""
    from ..utils import env_str
    from .engine import (
        _FEED_BANDWIDTH_THRESHOLD_MBPS,
        PLACEMENT_ENV,
        probe_feed_bandwidth,
        resolve_scan_placement,
    )

    if scanning:
        return resolve_scan_placement(scanning, placement) == "device"
    # no scan battery to ride: adding the (device-only) frequency scans
    # would CREATE a device pass, which only pays off when the feed link
    # is fast or the caller explicitly asked for the device tier
    effective = placement or env_str(PLACEMENT_ENV, "auto")
    if effective == "host":
        return False
    if effective == "device":
        return True
    return probe_feed_bandwidth() >= _FEED_BANDWIDTH_THRESHOLD_MBPS


def _columns_needed(engine: ScanEngine, grouping_sets, host_accum, schema) -> Optional[List[str]]:
    """Restrict batch materialization to columns any analyzer touches; None
    (= all columns) when a predicate may reference arbitrary columns."""
    if any(spec.kind == "pred" for spec in engine.builder.specs.values()):
        return None
    cols = set(engine.required_columns())
    for set_cols in grouping_sets:
        cols.update(set_cols)
    for a in host_accum:
        if getattr(a, "where", None) is not None:
            # a host-accumulated where-filter evaluates its predicate over
            # raw batch columns, which may reference any column
            return None
        cols.add(a.column)
    if not cols:
        return []
    return [c for c in schema.names if c in cols]


def _save_or_append(repository, key, context: AnalyzerContext) -> None:
    """Append semantics (reference `AnalysisRunner.scala:205-223`)."""
    existing = repository.load_by_key(key)
    combined = (existing or AnalyzerContext.empty()) + context
    repository.save(key, combined)

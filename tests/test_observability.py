"""End-to-end tracing, flight recorder and cost attribution (ISSUE 5).

The acceptance contract this file pins:

- a chaos-injected run (device failover + watchdog stall) produces ONE
  connected trace: the failed device pass, the typed exception event and
  the host-tier re-run all share a ``trace_id``;
- the flight recorder dumps a correlated artifact for EVERY typed failure
  kind (DeviceFailure, ScanStallError, CorruptStateError, SchemaDriftError);
- ``cost_by_analyzer`` shares sum to the bundle's measured dispatch time
  within 1%;
- the Chrome trace artifact validates against the trace-event schema
  (fields present, timestamps monotonic, parent refs resolve), so exporter
  drift fails tier-1 fast.
"""

import json
import time

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    Completeness,
    Maximum,
    Mean,
    Minimum,
    StandardDeviation,
    Sum,
)
from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.observability import export as obs_export
from deequ_tpu.observability import trace
from deequ_tpu.observability.recorder import FlightRecorder, recorder
from deequ_tpu.reliability import FaultSpec, inject
from deequ_tpu.runners.analysis_runner import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor
from deequ_tpu.verification import VerificationSuite
from deequ_tpu.reliability.watchdog import SCAN_DEADLINE_ENV, rate_tracker

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def _clean_recorder():
    recorder().clear()
    yield
    recorder().clear()


def _data(rows=4096, seed=0):
    rng = np.random.default_rng(seed)
    return Dataset.from_dict(
        {"x": rng.normal(size=rows), "y": rng.normal(10, 2, size=rows)}
    )


BATTERY = [
    Completeness("x"), Mean("x"), Sum("x"), Minimum("x"), Maximum("x"),
    StandardDeviation("x"), Mean("y"), Sum("y"),
]


def _check():
    return (
        Check(CheckLevel.ERROR, "obs battery")
        .is_complete("x")
        .has_mean("y", lambda m: 5 < m < 15)
    )


class TestSpanBasics:
    def test_nesting_and_ids(self):
        with trace.span("outer", kind="test") as outer:
            assert trace.current_span() is outer
            with trace.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                inner.add_event("hello", n=1)
        assert trace.current_span() is None
        spans = recorder().spans()
        names = [s.name for s in spans]
        assert names == ["inner", "outer"]  # children finish first
        assert spans[0].events[0]["name"] == "hello"
        assert spans[0].end_ns >= spans[0].start_ns

    def test_disabled_env_suppresses_everything(self, monkeypatch):
        monkeypatch.setenv(trace.TRACE_ENV, "0")
        with trace.span("invisible") as sp:
            assert sp is trace.NULL
            assert trace.current_span() is None
            trace.add_event("nope")
        assert recorder().spans() == []

    def test_unsampled_root_suppresses_descendants(self, monkeypatch):
        # rate 0 < r < 1 with the deterministic counter: force the
        # "sampled out" branch by rate ~0 (first roots land unsampled)
        monkeypatch.setenv(trace.TRACE_ENV, "0.000001")
        with trace.span("root") as root:
            with trace.span("child") as child:
                # whatever the sampling decided, both agree
                assert (root is trace.NULL) == (child is trace.NULL)

    def test_cross_thread_attach(self):
        import threading

        seen = {}
        with trace.span("parent") as parent:
            ctx = trace.capture()

            def worker():
                with trace.attach(ctx):
                    with trace.span("on-thread") as sp:
                        seen["trace"] = sp.trace_id
                        seen["parent"] = sp.parent_id

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["trace"] == parent.trace_id
        assert seen["parent"] == parent.span_id

    def test_ring_is_bounded(self):
        ring = FlightRecorder(capacity=16)
        for i in range(64):
            sp = trace.start_span(f"s{i}", parent=None)
            ring.on_span_finish(sp)
        assert len(ring.spans()) == 16
        assert ring.spans()[-1].name == "s63"


class TestPhaseSpans:
    def test_phase_spans_match_phase_seconds(self):
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(), BATTERY, batch_size=1024, monitor=mon, placement="device"
        )
        spans = recorder().spans()
        assert spans, "tracing is default-on"
        # one trace for the whole run
        assert len({s.trace_id for s in spans}) == 1
        phase_totals = {}
        for s in spans:
            if s.kind == "phase":
                phase_totals[s.name] = (
                    phase_totals.get(s.name, 0.0) + s.duration_s()
                )
        # every monitored phase that ran shows up span-backed, and the
        # span-summed duration equals the monitor's number (same clock)
        for phase in (
            "feature_build", "device_dispatch", "device_wait", "state_fetch",
        ):
            assert phase in phase_totals
            assert phase_totals[phase] == pytest.approx(
                mon.phase_seconds[phase], rel=1e-6, abs=1e-9
            )
        # metric derivation joined the monitored phases
        assert "metric_derivation" in mon.phase_seconds
        assert "metric_derivation" in phase_totals

    def test_engine_pass_span_carries_tier(self):
        AnalysisRunner.do_analysis_run(
            _data(), BATTERY, batch_size=1024, placement="host"
        )
        passes = [s for s in recorder().spans() if s.name == "engine_pass"]
        assert passes and passes[0].attrs["tier"] == "host"


class TestCostAttribution:
    @pytest.mark.parametrize("placement", ["device", "host"])
    def test_shares_sum_to_measured_dispatch_time(self, placement):
        """Acceptance: cost_by_analyzer shares sum to the bundle's measured
        dispatch time within 1%."""
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(16384), BATTERY, batch_size=1024, monitor=mon,
            placement=placement,
        )
        assert mon.cost_by_analyzer, "attribution must populate"
        total = sum(mon.cost_by_analyzer.values())
        assert mon.bundle_dispatch_seconds > 0
        assert total == pytest.approx(mon.bundle_dispatch_seconds, rel=0.01)
        # every scan analyzer got a share
        for a in BATTERY:
            assert repr(a) in mon.cost_by_analyzer

    def test_solo_probe_fires_periodically(self):
        mon = RunMonitor()
        # 80 batches of 512 rows: probe batches are folded==1 and
        # folded==65 — exactly 2 probes, regardless of bundle count
        AnalysisRunner.do_analysis_run(
            _data(80 * 512), BATTERY, batch_size=512, monitor=mon,
            placement="device",
        )
        assert mon.cost_probes == 2

    def test_verification_result_carries_cost_table(self):
        result = (
            VerificationSuite.on_data(_data())
            .add_check(_check())
            .with_batch_size(1024)
            .run()
        )
        assert result.cost_by_analyzer
        rows = json.loads(result.cost_by_analyzer_as_json())
        assert {r["analyzer"]: r["seconds"] for r in rows} == pytest.approx(
            result.cost_by_analyzer
        )

    def test_cost_series_reach_export_plane(self):
        from deequ_tpu.service import VerificationService

        with VerificationService(workers=2, background_warm=False) as svc:
            svc.verify(_data(), [_check()], timeout=120)
            text = svc.prometheus_text()
        assert "deequ_service_analyzer_cost_seconds_total{" in text


class TestConnectedDegradedTrace:
    def test_device_failover_is_one_connected_trace(self):
        """Acceptance: the failed device pass, the typed exception event
        and the host-tier re-run share one trace_id."""
        mon = RunMonitor()
        with inject(FaultSpec("device_update", "device", at=1)):
            ctx = AnalysisRunner.do_analysis_run(
                _data(), BATTERY, batch_size=1024, monitor=mon,
                placement="device",
            )
        assert mon.device_failovers == 1
        for metric in ctx.metric_map.values():
            assert metric.value.is_success
        spans = recorder().spans()
        passes = [s for s in spans if s.name == "engine_pass"]
        # the failed device pass and the host-tier re-pass, one trace
        assert len(passes) == 2
        assert len({s.trace_id for s in spans}) == 1
        assert passes[0].attrs["tier"] == "device"
        assert passes[0].status == "error"
        assert passes[1].attrs["tier"] == "host"
        assert passes[1].status == "ok"
        # the typed exception event rides the same trace
        events = [
            ev for s in spans for ev in s.events if ev["name"] == "failure"
        ]
        assert any(
            ev["attrs"]["type"] == "DeviceFailureException" for ev in events
        )
        assert any(
            ev["name"] == "device_failover"
            for s in spans for ev in s.events
        )

    @pytest.mark.chaos
    def test_watchdog_stall_joins_the_same_trace(self, monkeypatch):
        """Acceptance: device failover + watchdog stall in one chaos run ->
        ONE connected trace with the stall event and the host re-run."""
        # warm both tiers so the pinned 1s deadline only trips the stall
        for placement in ("device", "host"):
            AnalysisRunner.do_analysis_run(
                _data(), BATTERY, batch_size=1024, placement=placement
            )
        recorder().clear()
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "1.0")
        mon = RunMonitor()
        with inject(FaultSpec("device_update", "stall", at=1, delay_s=30.0)):
            result = (
                VerificationSuite.on_data(_data())
                .add_check(_check())
                .with_monitor(mon)
                .with_batch_size(1024)
                .with_placement("device")
                .run()
            )
        assert mon.stalls == 1
        assert mon.device_failovers == 1
        assert result.status == CheckStatus.SUCCESS
        spans = recorder().spans()
        assert len({s.trace_id for s in spans}) == 1
        passes = [s for s in spans if s.name == "engine_pass"]
        tiers = [s.attrs["tier"] for s in passes]
        assert tiers.count("host") >= 1 and tiers.count("device") >= 1
        stall_events = [
            ev for s in spans for ev in s.events if ev["name"] == "scan_stall"
        ]
        assert stall_events and stall_events[0]["attrs"]["site"] == "device"
        failures = [
            ev["attrs"]["type"]
            for s in spans for ev in s.events if ev["name"] == "failure"
        ]
        assert "ScanStallError" in failures


class TestFlightRecorder:
    def test_dump_fires_for_every_typed_failure_kind(self, monkeypatch, tmp_path):
        """Acceptance: flight-recorder dump fires on every typed failure
        kind."""
        from deequ_tpu.observability.recorder import FLIGHT_DIR_ENV

        monkeypatch.setenv(FLIGHT_DIR_ENV, str(tmp_path))
        rec = recorder()

        # 1. DeviceFailure: injected device fault -> failover path
        with inject(FaultSpec("device_update", "device", at=1)):
            AnalysisRunner.do_analysis_run(
                _data(), BATTERY, batch_size=1024, placement="device"
            )

        # 2. ScanStallError: watchdog-cancelled stall
        monkeypatch.setenv(SCAN_DEADLINE_ENV, "0.2")
        with inject(FaultSpec("device_update", "stall", at=1, delay_s=10.0)):
            AnalysisRunner.do_analysis_run(
                _data(), BATTERY, batch_size=1024, placement="device"
            )
        monkeypatch.delenv(SCAN_DEADLINE_ENV)

        # 3. CorruptStateError: checksum trip inside a traced region
        from deequ_tpu.exceptions import CorruptStateError
        from deequ_tpu.integrity import checksum_bytes, verify_checksum

        with trace.span("corrupt-drill"):
            with pytest.raises(CorruptStateError):
                verify_checksum(b"payload", "bogus", "state blob", "mem://x")
        assert checksum_bytes(b"payload") != "bogus"

        # 4. SchemaDriftError: streaming session rejects a drifted batch
        from deequ_tpu.exceptions import SchemaDriftError
        from deequ_tpu.service import VerificationService

        with VerificationService(workers=1, background_warm=False) as svc:
            session = svc.session("t", "d", [_check()])
            session.ingest(_data(512), timeout=120)
            drifted = Dataset.from_dict(
                {"x": np.arange(8, dtype=np.float64)}
            )
            with pytest.raises(SchemaDriftError):
                session.ingest(drifted, timeout=120)

        for kind in (
            "DeviceFailureException", "ScanStallError", "CorruptStateError",
            "SchemaDriftError",
        ):
            assert rec.dump_counts.get(kind, 0) >= 1, kind
        # artifacts landed, each correlating a trace
        assert rec.dump_paths
        with open(rec.dump_paths[0]) as fh:
            header = json.loads(fh.readline())
        assert header["flight_record"] is True
        assert header["failures"]

    def test_dump_releases_on_unit_of_work_not_outer_root(
        self, monkeypatch, tmp_path
    ):
        """A typed failure under a LONG-LIVED caller span must dump when
        the run's own analysis_run span closes — waiting for the outer
        root would delay the artifact past ring eviction (and a poller's
        root may never close while the service runs)."""
        from deequ_tpu.observability.recorder import FLIGHT_DIR_ENV

        monkeypatch.setenv(FLIGHT_DIR_ENV, str(tmp_path))
        rec = recorder()
        with trace.span("long-lived-poller"):
            with inject(FaultSpec("device_update", "device", at=1)):
                AnalysisRunner.do_analysis_run(
                    _data(), BATTERY, batch_size=1024, placement="device"
                )
            # artifact exists ALREADY — the outer span is still open
            assert rec.dump_counts.get("DeviceFailureException", 0) >= 1
            assert rec.dump_paths

    def test_untraced_failure_still_counts_and_dumps(self, monkeypatch, tmp_path):
        from deequ_tpu.observability.recorder import FLIGHT_DIR_ENV
        from deequ_tpu.exceptions import CorruptStateError
        from deequ_tpu.integrity import verify_checksum

        monkeypatch.setenv(FLIGHT_DIR_ENV, str(tmp_path))
        monkeypatch.setenv(trace.TRACE_ENV, "0")
        with pytest.raises(CorruptStateError):
            verify_checksum(b"payload", "bogus", "state blob", "mem://y")
        rec = recorder()
        assert rec.dump_counts.get("CorruptStateError", 0) >= 1
        assert any("untraced" in p for p in rec.dump_paths)


class TestExporters:
    def _run_and_export(self, tmp_path):
        AnalysisRunner.do_analysis_run(_data(), BATTERY, batch_size=1024)
        path = str(tmp_path / "run.trace.json")
        obs_export.write_chrome_trace(path)
        with open(path) as fh:
            return json.load(fh)

    def test_chrome_artifact_validates_against_schema(self, tmp_path):
        """Tier-1 exporter-drift guard: load an emitted artifact and
        validate the Chrome trace-event contract — required fields,
        non-negative monotonic timestamps, parent refs that resolve."""
        doc = self._run_and_export(tmp_path)
        events = doc["traceEvents"]
        assert events
        span_ids = set()
        for ev in events:
            assert ev["ph"] in ("X", "i")
            for field in ("name", "cat", "ts", "pid", "tid"):
                assert field in ev, f"missing {field}: {ev}"
            assert ev["ts"] >= 0
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
                span_ids.add(ev["args"]["span_id"])
        for ev in events:
            parent = ev["args"].get("parent_id")
            if ev["ph"] == "X" and parent is not None:
                assert parent in span_ids, f"dangling parent ref {parent}"
            # every event correlates to a trace
            assert ev["args"]["trace_id"] is not None
        # durations nest: each child's [ts, ts+dur] within its parent's
        by_id = {
            e["args"]["span_id"]: e for e in events if e["ph"] == "X"
        }
        for ev in by_id.values():
            parent = ev["args"].get("parent_id")
            if parent is None:
                continue
            p = by_id[parent]
            assert ev["ts"] >= p["ts"] - 1e3  # 1ms clock-read slack
            assert ev["ts"] + ev["dur"] <= p["ts"] + p["dur"] + 1e3

    def test_jsonl_journal_round_trips(self, tmp_path):
        AnalysisRunner.do_analysis_run(_data(), BATTERY, batch_size=1024)
        path = str(tmp_path / "run.jsonl")
        obs_export.write_jsonl(path)
        with open(path) as fh:
            rows = [json.loads(line) for line in fh]
        assert rows
        live = {s.span_id: s for s in recorder().spans()}
        for row in rows:
            assert row["span_id"] in live
            assert row["start_ns"] <= row["end_ns"]

    def test_trace_endpoint_serves_ring(self):
        import urllib.request

        from deequ_tpu.service import MetricsExporter, ServiceMetrics

        AnalysisRunner.do_analysis_run(_data(), BATTERY, batch_size=1024)
        exporter = MetricsExporter(ServiceMetrics())
        try:
            url = f"http://{exporter.host}:{exporter.port}"
            with urllib.request.urlopen(f"{url}/trace") as resp:
                doc = json.loads(resp.read())
            assert doc["traceEvents"]
            with urllib.request.urlopen(f"{url}/trace.jsonl") as resp:
                lines = resp.read().decode().strip().splitlines()
            assert lines and json.loads(lines[0])["span_id"]
        finally:
            exporter.close()


class TestTraceSummarize:
    def test_summary_from_degraded_run_artifact(self, tmp_path):
        from tools.trace_summarize import (
            critical_path,
            degradations,
            load_spans,
            summarize,
        )

        with inject(FaultSpec("device_update", "device", at=1)):
            AnalysisRunner.do_analysis_run(
                _data(), BATTERY, batch_size=1024, placement="device"
            )
        chrome = str(tmp_path / "degraded.trace.json")
        obs_export.write_chrome_trace(chrome)
        spans = load_spans(chrome)
        assert spans
        path = critical_path(spans)
        assert path and path[0]["parent_id"] is None
        # the critical path walks parent->child
        for parent, child in zip(path, path[1:]):
            assert child["parent_id"] == parent["span_id"]
        degrade = degradations(spans)
        assert any(ev["name"] == "device_failover" for _, _, ev in degrade)
        text = summarize(chrome)
        assert "critical path:" in text
        assert "device_failover" in text
        assert "top 5 spans by self-time:" in text

    def test_summary_reads_jsonl_too(self, tmp_path):
        AnalysisRunner.do_analysis_run(_data(), BATTERY, batch_size=1024)
        path = str(tmp_path / "run.jsonl")
        obs_export.write_jsonl(path)
        from tools.trace_summarize import summarize

        text = summarize(path)
        assert "critical path:" in text
        assert "(none — clean run)" in text


class TestOverheadGuards:
    def test_tracing_off_still_counts_costs(self, monkeypatch):
        """Cost attribution is monitor-driven, not span-driven: it must
        survive DEEQU_TPU_TRACE=0 (the knob an operator flips under
        overhead pressure)."""
        monkeypatch.setenv(trace.TRACE_ENV, "0")
        mon = RunMonitor()
        AnalysisRunner.do_analysis_run(
            _data(), BATTERY, batch_size=1024, monitor=mon, placement="device"
        )
        assert recorder().spans() == []
        assert mon.cost_by_analyzer
        assert mon.phase_seconds  # phase timers unaffected

    def test_rate_tracker_unaffected_by_tracing(self, monkeypatch):
        rate_tracker().clear()
        AnalysisRunner.do_analysis_run(_data(), [Mean("x")], batch_size=1024)
        with_trace = rate_tracker().per_row_s("device")
        assert with_trace is not None

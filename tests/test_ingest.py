"""Ingestion plane: columnar coercion, Arrow IPC frontend, HTTP endpoint,
prefetch pipeline, bounded-admission backpressure (PR 9)."""

import json
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from deequ_tpu.checks import Check, CheckLevel, CheckStatus
from deequ_tpu.data import Dataset
from deequ_tpu.exceptions import (
    FeedDisconnectError,
    MalformedFrameError,
    SchemaDriftError,
)
from deequ_tpu.ingest import (
    CHECKSUM_HEADER,
    PrefetchingBatchIterator,
    as_dataset,
    encode_ipc_stream,
    fold_stream,
    iter_frames,
)
from deequ_tpu.integrity import checksum_bytes
from deequ_tpu.reliability import FaultSpec, inject
from deequ_tpu.service import ServiceOverloaded, VerificationService

pytestmark = pytest.mark.ingest


def _checks():
    return [
        Check(CheckLevel.ERROR, "ingest")
        .has_size(lambda n: n > 0)
        .is_complete("x")
        .has_mean("y", lambda m: 0.0 < m < 20.0),
    ]


def _table(rows=2000, seed=0, nulls=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=rows)
    y = rng.normal(10.0, 2.0, size=rows)
    y_mask = (rng.random(rows) < 0.05) if nulls else np.zeros(rows, bool)
    cats = np.array(["alpha", "beta", "gamma", "delta"])
    c = cats[rng.integers(0, len(cats), rows)].astype(object)
    if nulls:
        c[rng.random(rows) < 0.03] = None
    return pa.table({
        "x": pa.array(x),
        "y": pa.array(y, mask=y_mask),
        "c": pa.array(c).dictionary_encode(),
    })


def _success_metrics(result):
    return {
        (a.name, a.instance): m.value.get()
        for a, m in result.metrics.items() if m.value.is_success
    }


@pytest.fixture
def service():
    with VerificationService(
        workers=2, max_queue_depth=64, background_warm=False
    ) as svc:
        yield svc


class TestAsDataset:
    def test_dataset_passthrough_is_identity(self):
        ds = Dataset.from_dict({"a": [1, 2, 3]})
        assert as_dataset(ds) is ds

    def test_table_and_record_batch(self):
        t = _table(100)
        assert as_dataset(t).num_rows == 100
        rb = t.to_batches()[0]
        ds = as_dataset(rb)
        assert ds.num_rows == len(rb)
        assert set(ds.schema.names) == {"x", "y", "c"}

    def test_dict_of_numpy_no_pandas(self):
        ds = as_dataset({
            "x": np.arange(5, dtype=np.float64),
            "n": np.array([1, 2, 3, 4, 5], dtype=np.int32),
        })
        assert ds.num_rows == 5
        assert ds.arrow["x"].to_pylist() == [0.0, 1.0, 2.0, 3.0, 4.0]

    def test_rejects_unknown_type(self):
        with pytest.raises(TypeError, match="cannot ingest"):
            as_dataset(42)

    def test_session_ingest_accepts_dict(self, service):
        rng = np.random.default_rng(1)
        session = service.session("t", "dict", _checks())
        result = session.ingest({
            "x": rng.normal(size=512), "y": rng.normal(10, 1, 512),
        })
        assert result.status == CheckStatus.SUCCESS
        assert session.batches_ingested == 1
        assert session.bytes_ingested > 0


class TestRoundTrip:
    def test_encode_decode_frames(self):
        t = _table(3000)
        payload = encode_ipc_stream(t, max_chunksize=1000)
        frames = list(iter_frames(payload))
        assert [i for i, _ in frames] == [0, 1, 2]
        got = pa.Table.from_batches([b for _, b in frames])
        assert got.num_rows == 3000

    def test_fold_stream_counts_and_checksum(self, service):
        t = _table(2000)
        payload = encode_ipc_stream(t, max_chunksize=1000)
        session = service.session("t", "rt", _checks())
        report = fold_stream(
            session, payload, checksum=checksum_bytes(payload), source="test"
        )
        assert report.frames == 2
        assert report.rows == 2000
        assert report.bytes == len(payload)
        assert session.batches_ingested == 2
        m = service.metrics
        labels = dict(tenant="t", dataset="rt")
        assert m.counter_value(
            "deequ_service_ingest_batches_total", **labels) == 2
        assert m.counter_value(
            "deequ_service_ingest_bytes_total", **labels) == len(payload)
        assert m.counter_value(
            "deequ_service_ingest_sessions_total", **labels) == 1


class TestParity:
    """Bit-exact metric parity between Arrow-fed, dict-fed and pandas-fed
    sessions — dictionary-encoded and null-bearing columns included."""

    def _battery(self):
        from deequ_tpu.analyzers import (
            ApproxCountDistinct,
            Completeness,
            Mean,
            StandardDeviation,
        )

        return [
            Completeness("x"), Completeness("y"), Completeness("c"),
            Mean("y"), StandardDeviation("y"), ApproxCountDistinct("c"),
        ]

    def test_three_feeds_bit_exact(self, service):
        t = _table(4000, seed=3)
        required = self._battery()

        arrow_s = service.session("p", "arrow", (),
                                  required_analyzers=required)
        fold_stream(arrow_s, encode_ipc_stream(t, max_chunksize=2000),
                    source="parity")

        dict_s = service.session("p", "dict", (), required_analyzers=required)
        for lo in (0, 2000):
            sl = t.slice(lo, 2000)
            dict_s.ingest({
                "x": sl["x"].to_numpy(),
                # null-bearing float column: NaN marks the nulls
                "y": sl["y"].to_numpy(zero_copy_only=False),
                "c": sl["c"].to_pylist(),
            })

        pandas_s = service.session("p", "pandas", (),
                                   required_analyzers=required)
        df = t.to_pandas()
        for lo in (0, 2000):
            pandas_s.ingest(Dataset.from_pandas(df.iloc[lo:lo + 2000]))

        ma = _success_metrics(arrow_s.current())
        md = _success_metrics(dict_s.current())
        mp = _success_metrics(pandas_s.current())
        assert len(ma) == len(required)
        assert ma == md == mp  # bit-exact, not approx

    def test_dictionary_and_null_frames_match_direct_run(self, service):
        from deequ_tpu.verification import VerificationSuite

        t = _table(3000, seed=9)
        session = service.session("p", "direct", _checks())
        fold_stream(session, encode_ipc_stream(t, max_chunksize=1000),
                    source="parity")
        direct = VerificationSuite.on_data(Dataset(t)).add_checks(
            _checks()
        ).run()
        streamed = _success_metrics(session.current())
        oracle = _success_metrics(direct)
        assert set(streamed) == set(oracle)
        # the streamed run folded 3 frames (different summation order than
        # the one-pass oracle): counts are exact, float aggregates agree
        # to 1e-12 relative
        for k, want in oracle.items():
            assert streamed[k] == pytest.approx(want, rel=1e-12, abs=1e-12)


class TestDriftGuard:
    """Drift policies fire identically on the Arrow path."""

    def test_retyped_column_rejected_typed(self, service):
        session = service.session("d", "reject", _checks())
        fold_stream(session, encode_ipc_stream(_table(1000)), source="drift")
        assert session.batches_ingested == 1
        drifted = pa.table({
            "x": pa.array(np.zeros(100)),
            "y": pa.array(["oops"] * 100),  # float -> string retype
            "c": pa.array(["alpha"] * 100).dictionary_encode(),
        })
        with pytest.raises(SchemaDriftError):
            fold_stream(session, encode_ipc_stream(drifted), source="drift")
        assert session.batches_ingested == 1  # states untouched

    def test_widening_coerces_on_arrow_path(self, service):
        rng = np.random.default_rng(4)
        first = pa.table({
            "x": pa.array(rng.normal(size=500)),
            "y": pa.array(rng.normal(10, 1, 500)),
        })
        session = service.session("d", "widen", _checks())
        fold_stream(session, encode_ipc_stream(first), source="drift")
        # float32 arriving where float64 was promised: same-family
        # widening — coerced and counted, never rejected
        narrow = pa.table({
            "x": pa.array(rng.normal(size=500).astype(np.float32)),
            "y": pa.array(rng.normal(10, 1, 500).astype(np.float32)),
        })
        fold_stream(session, encode_ipc_stream(narrow), source="drift")
        assert session.batches_ingested == 2
        assert session.drift_coercions >= 1

    def test_degrade_policy_folds_surviving_columns(self, service):
        session = service.session(
            "d", "degrade", _checks(), drift_policy="degrade"
        )
        fold_stream(session, encode_ipc_stream(_table(1000)), source="drift")
        drifted = pa.table({
            "x": pa.array(np.zeros(200)),
            "y": pa.array(["oops"] * 200),
            "c": pa.array(["alpha"] * 200).dictionary_encode(),
        })
        fold_stream(session, encode_ipc_stream(drifted), source="drift")
        assert session.batches_ingested == 2
        assert session.drift_degraded_batches == 1


class TestMalformedAndDisconnect:
    def test_garbage_nothing_folds(self, service):
        session = service.session("m", "garbage", _checks())
        with pytest.raises(MalformedFrameError):
            fold_stream(session, b"definitely not an arrow stream",
                        source="test")
        assert session.batches_ingested == 0
        assert service.metrics.counter_value(
            "deequ_service_ingest_malformed_total",
            tenant="m", dataset="garbage",
        ) == 1

    def test_checksum_mismatch_nothing_folds(self, service):
        payload = encode_ipc_stream(_table(1000))
        bad = bytearray(payload)
        bad[len(bad) // 2] ^= 0xFF  # silent under IPC decode...
        session = service.session("m", "sum", _checks())
        with pytest.raises(MalformedFrameError, match="checksum"):
            fold_stream(session, bytes(bad),
                        checksum=checksum_bytes(payload), source="test")
        assert session.batches_ingested == 0

    def test_truncated_stream_commits_leading_frames(self, service):
        import io

        tables = [_table(800, seed=s) for s in (1, 2, 3)]
        sink = io.BytesIO()
        bounds = []
        with pa.ipc.new_stream(sink, tables[0].schema) as w:
            for t in tables:
                for b in t.to_batches():
                    w.write_batch(b)
                bounds.append(sink.tell())
        payload = sink.getvalue()
        cut = bounds[1] + (bounds[2] - bounds[1]) // 2
        session = service.session("m", "torn", _checks())
        with pytest.raises(FeedDisconnectError) as exc_info:
            fold_stream(session, payload[:cut], complete=False, source="t")
        assert exc_info.value.frames_decoded == 2
        assert session.batches_ingested == 2
        assert service.metrics.counter_value(
            "deequ_service_ingest_disconnects_total",
            tenant="m", dataset="torn",
        ) == 1

    def test_injected_frame_corrupt(self, service):
        session = service.session("m", "inject", _checks())
        payload = encode_ipc_stream(_table(2000), max_chunksize=1000)
        with inject(FaultSpec("frame_decode", "frame_corrupt", at=2)) as inj:
            with pytest.raises(MalformedFrameError):
                fold_stream(session, payload, source="test")
        assert inj.fired == ["frame_decode:1:frame_corrupt"]
        assert session.batches_ingested == 1  # first frame stayed committed


class TestHttpEndpoint:
    def _post(self, exporter, path, body, headers=None):
        import http.client
        import json

        conn = http.client.HTTPConnection(
            exporter.host, exporter.port, timeout=30
        )
        try:
            conn.request("POST", path, body=body, headers=headers or {})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read().decode())
        finally:
            conn.close()

    def test_post_folds_and_counts(self, service):
        service.session("h", "ok", _checks())
        exporter = service.start_exporter()
        payload = encode_ipc_stream(_table(2000), max_chunksize=1000)
        status, body = self._post(
            exporter, "/ingest/v1/h/ok", payload,
            {CHECKSUM_HEADER: checksum_bytes(payload)},
        )
        assert status == 200
        assert body["frames"] == 2 and body["rows"] == 2000
        assert body["statuses"] == ["Success", "Success"]
        text = service.prometheus_text()
        assert "deequ_service_ingest_batches_total" in text
        assert "# HELP deequ_service_ingest_bytes_total" in text

    def test_unknown_session_is_404_never_autocreated(self, service):
        exporter = service.start_exporter()
        status, body = self._post(
            exporter, "/ingest/v1/nope/nothing",
            encode_ipc_stream(_table(100)),
        )
        assert status == 404 and body["error"] == "unknown_session"
        assert service.get_session("nope", "nothing") is None

    def test_closed_session_is_410_gone(self, service):
        session = service.session("h", "closed", _checks())
        session.close()
        exporter = service.start_exporter()
        status, body = self._post(
            exporter, "/ingest/v1/h/closed", encode_ipc_stream(_table(100))
        )
        # "gone", not "never existed": a producer must not be told to
        # re-register a deliberately closed session
        assert status == 410 and body["error"] == "session_closed"

    def test_malformed_body_is_400(self, service):
        session = service.session("h", "bad", _checks())
        exporter = service.start_exporter()
        status, body = self._post(
            exporter, "/ingest/v1/h/bad", b"garbage not arrow"
        )
        assert status == 400 and body["error"] == "malformed_frame"
        assert session.batches_ingested == 0

    def test_drift_is_409(self, service):
        session = service.session("h", "drift", _checks())
        exporter = service.start_exporter()
        self._post(exporter, "/ingest/v1/h/drift",
                   encode_ipc_stream(_table(500)))
        drifted = pa.table({"x": pa.array(np.zeros(10))})
        status, body = self._post(
            exporter, "/ingest/v1/h/drift", encode_ipc_stream(drifted)
        )
        assert status == 409 and body["error"] == "schema_drift"
        assert session.batches_ingested == 1

    def test_disconnect_mid_body_counts_and_commits_nothing_torn(
        self, service
    ):
        import socket

        session = service.session("h", "torn", _checks())
        exporter = service.start_exporter()
        payload = encode_ipc_stream(_table(2000), max_chunksize=1000)
        sock = socket.create_connection((exporter.host, exporter.port))
        head = (
            f"POST /ingest/v1/h/torn HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n"
        ).encode()
        sock.sendall(head + payload[: len(payload) // 4])
        sock.close()
        deadline = time.time() + 10
        while time.time() < deadline:
            if service.metrics.counter_value(
                "deequ_service_ingest_disconnects_total",
                tenant="h", dataset="torn",
            ) >= 1:
                break
            time.sleep(0.05)
        assert service.metrics.counter_value(
            "deequ_service_ingest_disconnects_total",
            tenant="h", dataset="torn",
        ) == 1
        # bytes of the torn stream never count as ingested
        assert service.metrics.counter_value(
            "deequ_service_ingest_bytes_total", tenant="h", dataset="torn"
        ) == 0

    def test_checksummed_torn_body_folds_nothing(self, service):
        import socket

        session = service.session("h", "csum-torn", _checks())
        exporter = service.start_exporter()
        payload = encode_ipc_stream(_table(2000), max_chunksize=1000)
        digest = checksum_bytes(payload)
        sock = socket.create_connection((exporter.host, exporter.port))
        head = (
            f"POST /ingest/v1/h/csum-torn HTTP/1.1\r\nHost: t\r\n"
            f"{CHECKSUM_HEADER}: {digest}\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n"
        ).encode()
        # ship MOST of the payload (several complete frames' worth), then
        # die: the declared digest can never verify, so NOTHING folds
        sock.sendall(head + payload[: len(payload) - 50])
        sock.close()
        deadline = time.time() + 10
        while time.time() < deadline:
            if service.metrics.counter_value(
                "deequ_service_ingest_disconnects_total",
                tenant="h", dataset="csum-torn",
            ) >= 1:
                break
            time.sleep(0.05)
        assert session.batches_ingested == 0
        assert service.metrics.counter_value(
            "deequ_service_ingest_disconnects_total",
            tenant="h", dataset="csum-torn",
        ) == 1

    def test_overload_is_429(self):
        with VerificationService(
            workers=1, max_queue_depth=1, background_warm=False
        ) as svc:
            session = svc.session("h", "busy", _checks())
            exporter = svc.start_exporter()
            release = threading.Event()
            # wedge the single worker, then fill the single queue slot
            svc.scheduler.submit(lambda ctx: release.wait(10))
            time.sleep(0.1)  # let the worker pick the wedge up
            svc.scheduler.submit(lambda ctx: None)
            payload = encode_ipc_stream(_table(100))
            status, body = self._post(
                exporter, "/ingest/v1/h/busy", payload
            )
            release.set()
            assert status == 429 and body["error"] == "overloaded"
            assert svc.metrics.counter_value(
                "deequ_service_ingest_shed_total",
                tenant="h", dataset="busy",
            ) == 1
            assert session.batches_ingested == 0


class TestPrefetch:
    def test_preserves_order_and_stops(self):
        with PrefetchingBatchIterator(lambda index: index, 10, depth=2) as it:
            assert list(it) == list(range(10))

    def test_serial_depth_zero_inline(self):
        calls = []

        def produce(index):
            calls.append(threading.current_thread().name)
            return index

        with PrefetchingBatchIterator(produce, 4, depth=0) as it:
            got = list(it)
        assert got == list(range(4))
        assert set(calls) == {threading.current_thread().name}

    def test_propagates_producer_exception(self):
        def produce(index):
            if index == 2:
                raise RuntimeError("boom")
            return index + 1

        with PrefetchingBatchIterator(produce, 10, depth=2) as it:
            assert next(it) == 1
            assert next(it) == 2
            with pytest.raises(RuntimeError, match="boom"):
                for _ in it:
                    pass

    def test_close_unblocks_parked_producer(self):
        def produce(index):
            return "item"  # endless

        it = PrefetchingBatchIterator(produce, 1 << 30, depth=1)
        assert next(it) == "item"
        it.close()  # must not hang on the full queue
        assert it._threads == []

    def test_silent_feed_trips_stall_deadline_typed(self):
        from deequ_tpu.exceptions import FeedStallError

        wedge = threading.Event()

        def produce(index):
            wedge.wait(30)  # a hung transfer: never returns, never raises
            return None

        with PrefetchingBatchIterator(
            produce, 1, depth=1, stall_timeout_s=0.3
        ) as it:
            t0 = time.perf_counter()
            with pytest.raises(FeedStallError):
                next(it)
            assert 0.2 <= time.perf_counter() - t0 < 10.0
        wedge.set()

    @staticmethod
    def _out_of_order(taken=None, bound=None, breaches=None):
        """produce(index) whose batches finish out of order (later
        batches of each triple sleep less); with ``taken`` it records any
        claim past ``bound`` items ahead of the consumer."""
        def produce(index):
            if breaches is not None and index >= taken[0] + bound:
                breaches.append(index)
            time.sleep(0.01 * (2 - index % 3))
            return index

        return produce

    @pytest.mark.parametrize("workers,depth", [(1, 2), (3, 1), (4, 2)])
    def test_pool_yields_in_batch_order_within_bound(self, workers, depth):
        taken, breaches = [0], []
        # the consumer's count lags the iterator's by the item in hand
        bound = workers + depth + 1
        produce = self._out_of_order(taken, bound, breaches)
        got = []
        with PrefetchingBatchIterator(
            produce, 30, depth=depth, workers=workers
        ) as it:
            for item in it:
                got.append(item)
                taken[0] += 1
                time.sleep(0.002)
        assert got == list(range(30))
        assert breaches == []
        assert it.workers == workers
        assert 1 <= it.inflight_peak <= workers
        if workers > 1:
            assert it.inflight_peak > 1

    @pytest.mark.parametrize("workers", [1, 4])
    @pytest.mark.parametrize("fail_at", [0, 5])
    def test_pool_error_surfaces_at_its_batch(self, workers, fail_at):
        def produce(index):
            if index == fail_at:
                time.sleep(0.05)  # later batches finish first
                raise RuntimeError(f"boom {index}")
            return index

        got = []
        it = PrefetchingBatchIterator(produce, 12, depth=2, workers=workers)
        with pytest.raises(RuntimeError, match=f"boom {fail_at}"):
            for item in it:
                got.append(item)
        assert got == list(range(fail_at))
        assert it._threads == []  # the pool shut down
        with pytest.raises(StopIteration):
            next(it)

    @pytest.mark.parametrize("workers", [1, 4])
    def test_pool_fault_site_fires_once_per_index(self, workers):
        produce = self._out_of_order()
        with inject(FaultSpec("prefetch", "stall", count=None)) as inj:
            with PrefetchingBatchIterator(
                produce, 8, depth=2, workers=workers
            ) as it:
                assert list(it) == list(range(8))
        assert inj.fired == [f"prefetch:{i}:stall" for i in range(8)]
        with inject(FaultSpec("prefetch", "feed_stall", at=3)) as inj:
            from deequ_tpu.exceptions import FeedStallError

            got = []
            with PrefetchingBatchIterator(
                produce, 8, depth=2, workers=workers
            ) as it:
                with pytest.raises(FeedStallError):
                    for item in it:
                        got.append(item)
        assert inj.fired == ["prefetch:2:feed_stall"]
        assert got == [0, 1]

    @pytest.mark.parametrize("workers", [1, 4])
    @pytest.mark.parametrize("wedged", ["all", "first"])
    def test_pool_silent_producer_trips_stall(self, workers, wedged):
        from deequ_tpu.exceptions import FeedStallError

        wedge = threading.Event()

        def produce(index):
            if wedged == "all" or index == 0:
                wedge.wait(30)  # hung: never returns, never raises
            return index

        try:
            with PrefetchingBatchIterator(
                produce, 20, depth=2, workers=workers,
                stall_timeout_s=0.3,
            ) as it:
                t0 = time.perf_counter()
                with pytest.raises(FeedStallError):
                    next(it)
                assert 0.2 <= time.perf_counter() - t0 < 10.0
        finally:
            wedge.set()

    @pytest.mark.parametrize("workers", [1, 4])
    def test_pool_close_mid_pass_joins_every_worker(self, workers):
        def produce(index):
            time.sleep(0.01)
            return index  # endless

        name = f"pool-close-{workers}"
        it = PrefetchingBatchIterator(
            produce, 1 << 30, depth=2, workers=workers, name=name
        )
        assert [next(it), next(it)] == [0, 1]
        it.close()
        assert it._threads == []
        assert not [
            t for t in threading.enumerate() if t.name.startswith(name)
        ]

    def test_pool_depth_zero_stays_inline(self):
        calls = []

        def produce(index):
            calls.append(threading.current_thread().name)
            return index

        with PrefetchingBatchIterator(
            produce, 5, start=2, depth=0, workers=4
        ) as it:
            assert list(it) == [2, 3, 4]
            assert it.workers == 0
        assert set(calls) == {threading.current_thread().name}

    def test_feed_workers_follow_cpus_and_batches(self, monkeypatch):
        from deequ_tpu.ingest import prefetch as pf

        monkeypatch.setattr(pf.os, "sched_getaffinity", lambda pid: set(range(13)))
        assert [pf.feed_workers(n) for n in (1, 4, 6, 23)] == [1, 4, 6, 6]
        monkeypatch.setattr(pf.os, "sched_getaffinity", lambda pid: {0})
        assert pf.feed_workers(23) == 1

    def test_env_depth_warn_and_fallback(self, monkeypatch):
        from deequ_tpu.ingest import prefetch as pf

        monkeypatch.setenv(pf.PREFETCH_DEPTH_ENV, "not-a-number")
        assert pf.prefetch_depth() == pf.DEFAULT_PREFETCH_DEPTH
        monkeypatch.setenv(pf.PREFETCH_DEPTH_ENV, "5")
        assert pf.prefetch_depth() == 5
        monkeypatch.setenv(pf.PREFETCH_DEPTH_ENV, "0")
        assert pf.prefetch_depth() == 0

    def test_engine_parity_across_depths(self, monkeypatch):
        from deequ_tpu.analyzers import Completeness, Mean, Sum
        from deequ_tpu.runners import AnalysisRunner

        rng = np.random.default_rng(6)
        data = Dataset.from_dict({"x": rng.normal(size=50_000)})
        analyzers = [Completeness("x"), Mean("x"), Sum("x")]

        def run(depth):
            monkeypatch.setenv("DEEQU_TPU_PREFETCH_DEPTH", str(depth))
            ctx = AnalysisRunner.do_analysis_run(
                data, analyzers, batch_size=8192, placement="device"
            )
            return {
                repr(a): m.value.get()
                for a, m in ctx.metric_map.items() if m.value.is_success
            }

        m0, m1, m3 = run(0), run(1), run(3)
        assert m0 == m1 == m3 and len(m0) == 3  # bit-exact

    def test_feed_stall_fails_over_typed(self):
        from deequ_tpu.analyzers import Completeness, Mean
        from deequ_tpu.runners import AnalysisRunner
        from deequ_tpu.runners.engine import RunMonitor

        rng = np.random.default_rng(7)
        data = Dataset.from_dict({"x": rng.normal(size=40_000)})
        analyzers = [Completeness("x"), Mean("x")]
        clean = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=8192, placement="device"
        )
        mon = RunMonitor()
        with inject(FaultSpec("prefetch", "feed_stall", at=2)) as inj:
            stalled = AnalysisRunner.do_analysis_run(
                data, analyzers, batch_size=8192, placement="device",
                monitor=mon,
            )
        assert inj.fired == ["prefetch:1:feed_stall"]
        assert mon.device_failovers == 1  # typed -> host-tier failover
        for a in analyzers:
            want = clean.metric(a).value.get()
            got = stalled.metric(a).value.get()
            assert got == pytest.approx(want, rel=1e-9)


class TestBackpressure:
    def test_block_s_waits_for_space_instead_of_shedding(self):
        with VerificationService(
            workers=1, max_queue_depth=1, background_warm=False
        ) as svc:
            release = threading.Event()
            svc.scheduler.submit(lambda ctx: release.wait(10))
            time.sleep(0.1)
            filler = svc.scheduler.submit(lambda ctx: "filler")
            # without backpressure: immediate typed shed
            with pytest.raises(ServiceOverloaded):
                svc.scheduler.submit(lambda ctx: "shed")

            def free():
                time.sleep(0.3)
                release.set()

            threading.Thread(target=free, daemon=True).start()
            handle = svc.scheduler.submit(
                lambda ctx: "waited", block_s=10.0
            )
            assert handle.result(timeout=10) == "waited"
            assert filler.result(timeout=10) == "filler"

    def test_block_s_expiry_sheds_typed(self):
        with VerificationService(
            workers=1, max_queue_depth=1, background_warm=False
        ) as svc:
            release = threading.Event()
            try:
                svc.scheduler.submit(lambda ctx: release.wait(10))
                time.sleep(0.1)
                svc.scheduler.submit(lambda ctx: None)
                t0 = time.perf_counter()
                with pytest.raises(ServiceOverloaded):
                    svc.scheduler.submit(lambda ctx: None, block_s=0.3)
                assert 0.2 <= time.perf_counter() - t0 < 5.0
            finally:
                release.set()


class TestSoakSmoke:
    def test_concurrency_soak_completes(self):
        from tools.ingest_soak import run_concurrency_soak

        summary = run_concurrency_soak(
            sessions=12, batches=2, rows=512, workers=4, queue_depth=16,
            block_s=30.0, feeders=4,
        )
        assert summary["ok"]
        assert summary["sessions_completed"] == 12
        assert summary["failed_folds"] == 0

    def test_stream_throughput_parity(self):
        from tools.ingest_soak import run_stream_throughput

        summary = run_stream_throughput(
            target_mb=1.0, rows_per_batch=1 << 14, workers=2
        )
        assert summary["ok"] and summary["parity_ok"]
        assert summary["frames"] >= 1


class TestIncrementalHttpDecode:
    """The unbuffered ingest path: an unchecksummed POST decodes frame by
    frame straight off the socket — one frame in memory, not the body."""

    def _post_chunked(self, exporter, path, payload, chunks, gap_s=0.02):
        import socket

        sock = socket.create_connection((exporter.host, exporter.port))
        head = (
            f"POST {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n"
        ).encode()
        sock.sendall(head)
        step = -(-len(payload) // chunks)
        for i in range(0, len(payload), step):
            sock.sendall(payload[i:i + step])
            time.sleep(gap_s)
        resp = b""
        sock.settimeout(20)
        try:
            while b"\r\n\r\n" not in resp or len(resp) < 10:
                part = sock.recv(65536)
                if not part:
                    break
                resp += part
        except OSError:
            pass
        sock.close()
        status = int(resp.split(b" ", 2)[1])
        body = json.loads(resp.split(b"\r\n\r\n", 1)[1])
        return status, body

    def test_frames_fold_while_body_still_arriving(self, service):
        """Frames committed BEFORE the transport delivered the full body
        prove the decode is incremental, not buffered."""
        session = service.session("inc", "stream", _checks())
        exporter = service.start_exporter()
        table = _table(4000)
        payload = encode_ipc_stream(table, max_chunksize=1000)
        committed_mid_body = []

        orig = type(session)._commit_fold

        def spy(self, result, data, pending_contract, done):
            committed_mid_body.append(time.perf_counter())
            return orig(self, result, data, pending_contract, done)

        import deequ_tpu.service.streaming as streaming_mod

        streaming_mod.StreamingSession._commit_fold = spy
        try:
            t0 = time.perf_counter()
            status, body = self._post_chunked(
                exporter, "/ingest/v1/inc/stream", payload, chunks=8,
                gap_s=0.05,
            )
            last_byte_at = t0 + 7 * 0.05  # the 8th chunk leaves then
        finally:
            streaming_mod.StreamingSession._commit_fold = orig
        assert status == 200 and body["frames"] == 4
        assert session.batches_ingested == 4
        # at least the first frame folded before the final chunk was sent
        assert committed_mid_body[0] < last_byte_at

    def test_incremental_equivalent_to_buffered(self, service):
        """HTTP-fed (incremental) == checksummed HTTP-fed (buffered) ==
        in-process fold_stream, bit-exact."""
        import urllib.request

        table = _table(3000)
        payload = encode_ipc_stream(table, max_chunksize=1000)
        exporter = service.start_exporter()
        for name, headers in (
            ("plain", {}),
            ("csum", {CHECKSUM_HEADER: checksum_bytes(payload)}),
        ):
            service.session(f"eq-{name}", "s", _checks())
            req = urllib.request.Request(
                f"http://{exporter.host}:{exporter.port}"
                f"/ingest/v1/eq-{name}/s",
                data=payload, headers=headers, method="POST",
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
        direct = service.session("eq-direct", "s", _checks())
        fold_stream(direct, payload, source="direct")
        maps = []
        for name in ("eq-plain", "eq-csum", "eq-direct"):
            s = service.get_session(name, "s")
            cum = s.current()
            maps.append({
                repr(a): m.value.get()
                for a, m in cum.metrics.items() if m.value.is_success
            })
        assert maps[0] == maps[1] == maps[2]

    def test_incremental_malformed_drains_and_400s(self, service):
        import urllib.error
        import urllib.request

        service.session("inc", "bad", _checks())
        exporter = service.start_exporter()
        req = urllib.request.Request(
            f"http://{exporter.host}:{exporter.port}/ingest/v1/inc/bad",
            data=b"definitely not an arrow stream, padded " + b"x" * 500,
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
        assert json.loads(err.value.read())["error"] == "malformed_frame"

    def test_bounded_reader_contract(self):
        import io

        from deequ_tpu.ingest.arrow_stream import BoundedReader

        raw = io.BytesIO(b"abcdefghij")
        r = BoundedReader(raw, 6)
        assert r.read(4) == b"abcd"
        assert r.read(100) == b"ef"  # capped at the declared limit
        assert r.read(1) == b""
        assert r.bytes_read == 6 and not r.short
        short = BoundedReader(io.BytesIO(b"ab"), 10)
        assert short.read(10) == b"ab"
        assert short.short and short.bytes_read == 2
        short.drain()
        assert short.bytes_read == 2

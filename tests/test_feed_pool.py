"""The feed pool: a device pass stages its batches on several feed workers
and folds them in batch order, so every metric is bit-identical to the
one-worker pass; the per-entry tables of a dictionary column are computed
once however many workers build that column's batches at once."""

import functools
import json
import os
import threading
import time

import numpy as np
import pytest

from benchmark.data.lineitem import build_lineitem
from benchmark.drivers.batch import (
    build_check,
    profile_result,
    verification_result,
)
from deequ_tpu import VerificationSuite
from deequ_tpu.analyzers import (
    Completeness,
    KLLSketch,
    Maximum,
    Mean,
    Minimum,
    StandardDeviation,
    Sum,
)
from deequ_tpu.analyzers.base import FeatureSpec
from deequ_tpu.analyzers.state_provider import InMemoryStateProvider
from deequ_tpu.data import Dataset
from deequ_tpu.profiles import ColumnProfilerRunner
from deequ_tpu.reliability import FaultSpec, IngestCheckpointer, inject
from deequ_tpu.runners import AnalysisRunner, RunMonitor, analysis_runner
from deequ_tpu.runners import features as features_mod
from deequ_tpu.runners.engine import ScanEngine

#: 30,006 rows: eight 4096-row batches a pass
SCALE = 0.005
BATCH = 4096


@pytest.fixture(scope="module")
def lineitem():
    return build_lineitem(2147483901, SCALE)


@pytest.fixture(scope="module")
def battery():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "tpch_lineitem_sf1.json")) as f:
        return json.load(f)["battery"]["verify"]


def _pool(monkeypatch, workers):
    """Every engine the runners build stages on ``workers`` feed workers."""
    monkeypatch.setattr(
        analysis_runner, "ScanEngine",
        functools.partial(ScanEngine, feed_workers=workers),
    )


def _canonical(value):
    """A repr that orders dict keys and spells every float exactly."""
    if isinstance(value, dict):
        return "{" + ", ".join(
            f"{_canonical(k)}: {_canonical(v)}"
            for k, v in sorted(value.items(), key=lambda kv: repr(kv[0]))
        ) + "}"
    if isinstance(value, (list, tuple)):
        return "[" + ", ".join(_canonical(v) for v in value) + "]"
    if isinstance(value, float):
        return float.hex(value)
    return repr(value)


@pytest.mark.parametrize("entry", ["verify", "profile"])
def test_pool_metrics_bit_identical_to_one_worker(
    monkeypatch, lineitem, battery, entry
):
    def run(workers):
        _pool(monkeypatch, workers)
        mon = RunMonitor()
        data = Dataset.from_arrow(lineitem)
        if entry == "profile":
            result = profile_result(
                ColumnProfilerRunner.on_data(data).with_batch_size(BATCH)
                .with_monitor(mon).run()
            )
        else:
            result = verification_result(
                VerificationSuite.on_data(data)
                .add_check(build_check(battery, "pool"))
                .with_batch_size(BATCH).with_monitor(mon).run()
            )
        return _canonical(result), mon

    one, one_mon = run(1)
    pooled, pooled_mon = run(4)
    assert pooled == one
    assert one_mon.feed_workers == 1 and one_mon.feed_inflight_peak == 1
    assert pooled_mon.feed_workers == 4
    assert 1 < pooled_mon.feed_inflight_peak <= 4
    assert pooled_mon.batches == one_mon.batches >= 8


def test_engine_pass_span_carries_the_pool(monkeypatch):
    from deequ_tpu.observability.recorder import recorder

    _pool(monkeypatch, 3)
    recorder().clear()
    data = Dataset.from_dict({"x": np.arange(8 * 1024, dtype=float)})
    try:
        AnalysisRunner.do_analysis_run(data, [Mean("x"), Sum("x")], batch_size=1024)
        passes = [s for s in recorder().spans() if s.name == "engine_pass"]
    finally:
        recorder().clear()
    assert passes and passes[-1].attrs["feed_workers"] == 3
    assert 1 <= passes[-1].attrs["feed_inflight_peak"] <= 3


def test_pool_resume_mid_pass_equals_uninterrupted(monkeypatch):
    rng = np.random.default_rng(26)
    data = Dataset.from_dict({"x": rng.normal(size=16 * 1024)})
    analyzers = [
        Completeness("x"), Mean("x"), Sum("x"), Minimum("x"), Maximum("x"),
        StandardDeviation("x"), KLLSketch("x"),
    ]
    _pool(monkeypatch, 1)
    uninterrupted = AnalysisRunner.do_analysis_run(
        data, analyzers, batch_size=1024
    )
    _pool(monkeypatch, 4)
    checkpointer = IngestCheckpointer(InMemoryStateProvider(), every=4)
    with inject(FaultSpec("device_update", "interrupt", at=11)):
        with pytest.raises(KeyboardInterrupt):
            AnalysisRunner.do_analysis_run(
                data, analyzers, batch_size=1024, checkpointer=checkpointer
            )
    assert [index for index, _ in checkpointer.saves] == [4, 8]
    monitor = RunMonitor()
    with inject(FaultSpec("prefetch", "stall", count=None)) as inj:
        resumed = AnalysisRunner.do_analysis_run(
            data, analyzers, batch_size=1024, checkpointer=checkpointer,
            monitor=monitor,
        )
    # staging starts at the resumed batch: batches 8..15 are probed once
    assert inj.fired == [f"prefetch:{i}:stall" for i in range(8, 16)]
    assert monitor.resumed_at_batch == 8
    assert monitor.batches == 8
    assert monitor.feed_workers == 4
    for analyzer, metric in uninterrupted.metric_map.items():
        got = resumed.metric_map[analyzer].value.get()
        if analyzer.name == "KLLSketch":
            assert repr(got.buckets) == repr(metric.value.get().buckets)
        else:
            assert got == metric.value.get(), analyzer


def test_per_entry_tables_built_once_under_concurrent_builds(monkeypatch):
    """More threads than cores build the features of as many batches of
    one dictionary column at once, switching often: each per-entry table
    is computed once."""
    import sys

    import pyarrow as pa

    n = 2 * (os.cpu_count() or 1)
    rng = np.random.default_rng(5)
    words = np.array([f"w{i}" for i in range(300)] + ["1.5", "7", "true"])
    values = words[rng.integers(0, len(words), n * 256)].astype(object)
    values[rng.random(len(values)) < 0.1] = None
    table = pa.table({"s": pa.array(values, type=pa.string()).dictionary_encode()})
    data = Dataset(table, probe_encoding=False)
    batches = list(data.batches(256))
    assert len(batches) == n

    calls = {}
    lock = threading.Lock()

    def spy(name, fn):
        def wrapped(*args, **kwargs):
            with lock:
                calls[name] = calls.get(name, 0) + 1
            time.sleep(0.05)  # widen the window for a second computation
            return fn(*args, **kwargs)

        monkeypatch.setattr(features_mod, name, wrapped)

    for name in ("hash_column", "hll_features", "classify_type_codes",
                 "string_lengths"):
        spy(name, getattr(features_mod, name))
    builder = features_mod.FeatureBuilder([
        FeatureSpec("hash", "s"), FeatureSpec("hll", "s"),
        FeatureSpec("type", "s"), FeatureSpec("len", "s"),
        FeatureSpec("pred", None, "s IN ('w1', 'w2', '7')"),
    ])
    serial = [builder.build(b) for b in batches]
    aux = batches[0].column("s").aux
    for key in ("hashes", "hll_pairs", "hll_packed", "type_codes",
                "lengths", "pred_entries", "values"):
        aux.pop(key, None)
    calls.clear()

    start = threading.Barrier(len(batches))
    built = [None] * len(batches)

    def build(i):
        start.wait()
        built[i] = builder.build(batches[i])

    threads = [threading.Thread(target=build, args=(i,))
               for i in range(len(batches))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert calls == {"hash_column": 1, "hll_features": 1,
                     "classify_type_codes": 1, "string_lengths": 1}
    for got, want in zip(built, serial):
        assert got.keys() == want.keys()
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])

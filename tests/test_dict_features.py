"""Dictionary STRING columns serve their HLL, type, length and regex
features from per-entry tables built once per dataset and gathered by code;
each must equal the per-row form over the decoded values, bit for bit."""

import numpy as np
import pyarrow as pa
import pytest

from deequ_tpu.analyzers import ApproxCountDistinct, DataType
from deequ_tpu.analyzers.base import FeatureSpec
from deequ_tpu.data import ColumnKind, Dataset
from deequ_tpu.ops.hashing import hash_column
from deequ_tpu.ops.hll import hll_pack_features
from deequ_tpu.runners import AnalysisRunner, RunMonitor
from deequ_tpu.runners import features as F

PATTERN = r"^v\d*7"


def _entries(n):
    # mixes every type class: integral, fractional, boolean, plain text
    kinds = ("{}", "{}.5", "true", "v{}", " +{}", "")
    return [kinds[i % len(kinds)].format(i) for i in range(n)]


def _no_nulls():
    codes = np.random.default_rng(1).integers(0, 40, 3000).astype(np.int32)
    return pa.DictionaryArray.from_arrays(pa.array(codes), pa.array(_entries(40)))


def _null_indices():
    codes = np.random.default_rng(2).integers(0, 100, 3000).astype(np.int8)
    nulls = np.random.default_rng(3).random(3000) < 0.2
    return pa.DictionaryArray.from_arrays(
        pa.array(codes, mask=nulls), pa.array(_entries(100))
    )


def _padded_last_batch():
    # 2500 rows in batches of 1024: the last batch carries 572 padding rows
    codes = np.random.default_rng(4).integers(0, 30, 2500).astype(np.int32)
    return pa.DictionaryArray.from_arrays(pa.array(codes), pa.array(_entries(30)))


def _empty_dictionary():
    return pa.DictionaryArray.from_arrays(
        pa.array([None] * 700, type=pa.int32()), pa.array([], type=pa.string())
    )


def _null_entry():
    # entry 1 is a null VALUE: rows pointing at it are valid by the indices
    codes = np.random.default_rng(5).integers(0, 5, 2000).astype(np.int32)
    return pa.DictionaryArray.from_arrays(
        pa.array(codes), pa.array(["a", None, "12", "4.5", "false"])
    )


CASES = {
    "no_nulls": _no_nulls,
    "null_indices": _null_indices,
    "padded_last_batch": _padded_last_batch,
    "empty_dictionary": _empty_dictionary,
    "null_entry": _null_entry,
}


def _batches(arr, batch_size=1024):
    data = Dataset.from_arrow(pa.table({"c": arr}))
    return data, list(data.batches(batch_size))


@pytest.mark.parametrize("case", sorted(CASES))
def test_dict_features_equal_per_row_form(case):
    arr = CASES[case]()
    if case == "no_nulls":
        assert arr.indices.type == pa.int32() and arr.null_count == 0
    if case == "null_indices":
        assert arr.indices.type == pa.int8() and arr.null_count > 0
    _, batches = _batches(arr)
    if case == "padded_last_batch":
        assert not batches[-1].row_mask.all()
    builder = F.FeatureBuilder([
        FeatureSpec("hll", "c"), FeatureSpec("type", "c"),
        FeatureSpec("len", "c"), FeatureSpec("match", "c", PATTERN),
    ])
    for batch in batches:
        col = batch.column("c")
        assert F._is_string_dict(col)
        values, mask = col.values, col.mask
        want = {
            "hll": hll_pack_features(
                hash_column(values, mask, ColumnKind.STRING), mask
            ),
            "type": F.classify_type_codes(values, mask, ColumnKind.STRING),
            "len": F.string_lengths(values, mask),
            "match": F.regex_matches(values, mask, PATTERN),
        }
        built = builder.build(batch)
        for spec in builder.specs.values():
            got = built[spec.key]
            assert got.dtype == want[spec.kind].dtype, spec.kind
            np.testing.assert_array_equal(got, want[spec.kind], err_msg=spec.kind)


def test_entry_table_built_once_per_dataset(monkeypatch):
    calls = {"pairs": 0, "pack": 0}
    hll_features, hll_pack_pairs = F.hll_features, F.hll_pack_pairs

    def counting(name, fn):
        def wrapped(*args):
            calls[name] += 1
            return fn(*args)
        return wrapped

    monkeypatch.setattr(F, "hll_features", counting("pairs", hll_features))
    monkeypatch.setattr(F, "hll_pack_pairs", counting("pack", hll_pack_pairs))
    rng = np.random.default_rng(6)
    rows, batch_size = 5000, 1024
    arr = pa.DictionaryArray.from_arrays(
        pa.array(rng.integers(0, 60, rows).astype(np.int32)), pa.array(_entries(60))
    )
    data = Dataset.from_arrow(pa.table({
        "c": arr, "d": arr, "x": rng.normal(size=rows),
    }))
    batteries = [ApproxCountDistinct("c"), ApproxCountDistinct("d"),
                 ApproxCountDistinct("x"), DataType("c")]
    mon = RunMonitor()
    device = AnalysisRunner.do_analysis_run(
        data, batteries, placement="device", batch_size=batch_size, monitor=mon
    )
    batches = -(-rows // batch_size)
    assert mon.dict_hll_gathers == 2 * batches
    # one per-entry pass per dictionary column, not one per batch
    assert calls == {"pairs": 2, "pack": 2}
    pairs = {}
    for col in ("c", "d"):
        aux = data._dict_aux[col]
        assert len(aux["hll_packed"]) == aux["num_categories"] + 1
        assert aux["hll_packed"][-1] == 0
        pairs[col] = aux["hll_pairs"]

    # the host tier reads the same cached pairs: no second per-entry pass
    # over this dataset's dictionaries
    host = AnalysisRunner.do_analysis_run(
        data, batteries, placement="host", batch_size=batch_size
    )
    for col in ("c", "d"):
        assert data._dict_aux[col]["hll_pairs"] is pairs[col]
    for a in batteries:
        assert device.metric(a).value.get() == host.metric(a).value.get(), a


def test_numeric_hll_battery_counts_no_dict_gathers():
    rng = np.random.default_rng(7)
    data = Dataset.from_dict({
        "i": rng.integers(0, 500, 3000), "x": rng.normal(size=3000),
    })
    mon = RunMonitor()
    AnalysisRunner.do_analysis_run(
        data, [ApproxCountDistinct("i"), ApproxCountDistinct("x")],
        placement="device", batch_size=1024, monitor=mon,
    )
    assert mon.passes == 1
    assert mon.dict_hll_gathers == 0

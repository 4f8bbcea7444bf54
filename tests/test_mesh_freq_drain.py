"""The device frequency drain, on one device and split over a mesh.

A device frequency table is drained on the device into the summary every
scalar frequency metric reads: groups, singletons, rows and the
count-of-counts histogram (whose canonical reduction gives sum(c ln c)).
On a mesh each device holds its own rows' keys, one exchange by key range
brings every key to the device that owns it, and the devices' disjoint
summaries add. The oracle is numpy's count of the same keys.
"""

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    CountDistinct,
    Distinctness,
    Entropy,
    Histogram,
    MutualInformation,
    Uniqueness,
    UniqueValueRatio,
)
from deequ_tpu.analyzers.grouping import (
    DeviceFrequencyTableScan,
    _sum_c_ln_c,
    plan_table_scan,
)
from deequ_tpu.data import Dataset
from deequ_tpu.parallel import ROW_AXIS, make_mesh
from deequ_tpu.runners import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor

SENT = np.uint64(0xFFFFFFFFFFFFFFFF)
#: rows of a drained buffer: a multiple of every mesh size below
ROWS = 24 * 1024
BATCH = 3 * 1024


def _keys(case: str, rng) -> tuple:
    """(keys appended, rows whose real key is the sentinel) of a case. A
    masked row, and a row whose real key is the sentinel (counted apart
    in ``sent_rows``), are appended as the sentinel."""
    mix = np.uint64(0x9E3779B97F4A7C15)
    sent_rows = 0
    if case == "nulls":
        keys = rng.integers(0, 1 << 62, ROWS, dtype=np.uint64)
        keys[rng.random(ROWS) < 0.3] = SENT
    elif case == "sentinel":
        keys = rng.integers(0, 5_000, ROWS).astype(np.uint64) * mix
        sent_rows = 37
        keys[rng.choice(ROWS, sent_rows, replace=False)] = SENT
    elif case == "all_unique":
        keys = rng.permutation(np.arange(ROWS, dtype=np.uint64) * mix)
    elif case == "heavy_duplicates":
        keys = (rng.zipf(1.3, ROWS) % 997).astype(np.uint64) * mix
        keys[: ROWS // 2] = np.uint64(12345)
        keys = rng.permutation(keys)
    else:
        assert case == "skew"
        # every key in the first device's range (its top bits are zero),
        # and one row whose real key is the sentinel
        keys = rng.integers(0, 3000, ROWS).astype(np.uint64)
        sent_rows = 1
        keys[0] = SENT
    return keys, sent_rows


def _oracle(keys: np.ndarray, sent_rows: int) -> tuple:
    _, counts = np.unique(keys[keys != SENT], return_counts=True)
    if sent_rows:
        counts = np.append(counts, sent_rows)
    return (len(counts), int((counts == 1).sum()), int(counts.sum()),
            _sum_c_ln_c(counts))


def _state(scan, keys: np.ndarray, sent_rows: int):
    """The state of appending ``keys`` in batches of BATCH rows (every
    device's part of a batch lands in its buffer row)."""
    import jax.numpy as jnp

    st = scan.init_state()
    for at in range(0, ROWS, BATCH):
        st = st.append_keys(
            jnp.asarray(keys[at:at + BATCH]),
            jnp.asarray(sent_rows if at == 0 else 0, jnp.int64),
            jnp.asarray(BATCH, jnp.int64), assume_fits=True,
        )
    return st


def _mesh(n: int):
    return None if n == 1 else make_mesh(n)


@pytest.mark.parametrize("case", [
    "nulls", "sentinel", "all_unique", "heavy_duplicates", "skew",
])
@pytest.mark.parametrize("devices", [1, 2, 3, 4, 8])
def test_device_summary_equals_host_count(devices, case):
    """Integer parts equal, and sum(c ln c) equal too: the histogram is
    reduced on the host by the one canonical function (a float32
    reduction would miss the float64 value by far more than 1e-12)."""
    rng = np.random.default_rng([devices, sum(map(ord, case))])
    keys, sent_rows = _keys(case, rng)
    per = 1 << int(np.ceil(np.log2(ROWS / devices)))
    scan = DeviceFrequencyTableScan(
        ("k",), ("num",), 8, per, resident=True, shards=devices
    )
    summary, stats = scan.fetch_summary(
        scan.drain(_state(scan, keys, sent_rows), _mesh(devices))
    )
    want = _oracle(keys, sent_rows)
    assert summary.stream_summary()[:3] == want[:3]
    got_clnc, want_clnc = summary.stream_summary()[3], want[3]
    assert abs(got_clnc - want_clnc) <= 1e-12 * max(1.0, abs(want_clnc))
    assert got_clnc == want_clnc
    assert summary.num_rows == ROWS
    assert stats["keys"] == int((keys != SENT).sum())


@pytest.mark.parametrize("devices", [2, 3, 4, 8])
def test_exchange_owners_and_per_device_groups_add_up(devices):
    """After the exchange every key sits on the device owning its range,
    no key is lost or copied, and the devices' group counts add up to the
    uncut count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deequ_tpu.parallel import (
        _shard_map,
        exchange_by_key_range,
        key_range_starts,
    )

    rng = np.random.default_rng(devices)
    m = 2048
    keys = rng.integers(0, 1 << 63, devices * m, dtype=np.uint64) * np.uint64(2)
    keys[rng.random(len(keys)) < 0.1] = SENT
    keys[: m // 2] = keys[m]  # a heavy duplicate on the first device
    mesh = make_mesh(devices)
    body = _shard_map(
        lambda k: exchange_by_key_range(jnp.sort(k), jnp.uint64(SENT)),
        mesh=mesh, in_specs=(P(ROW_AXIS),), out_specs=P(ROW_AXIS),
    )
    got = np.asarray(jax.jit(body)(jnp.asarray(keys))).reshape(devices, -1)
    bounds = np.concatenate([[0], key_range_starts(devices)]).astype(np.uint64)
    groups = 0
    for d in range(devices):
        mine = got[d][got[d] != SENT]
        assert (mine >= bounds[d]).all()
        if d + 1 < devices:
            assert (mine < bounds[d + 1]).all()
        groups += len(np.unique(mine))
    valid = keys[keys != SENT]
    assert np.array_equal(np.sort(got[got != SENT]), np.sort(valid))
    assert groups == len(np.unique(valid))


def _battery(cols):
    return [Uniqueness(cols), Distinctness(cols), CountDistinct(cols),
            UniqueValueRatio(cols), Entropy(cols[0])]


def _values(ctx, battery):
    return {repr(a): ctx.metric(a).value.get() for a in battery}


def test_mesh_run_counters():
    """One summary per device table; the keys counted, the bytes fetched
    (nine scalars, then the used histogram entries rounded up to a power
    of two) and the busiest device's keys, which on hashed keys sit near a
    quarter of them."""
    rng = np.random.default_rng(7)
    n = 200_000
    data = Dataset.from_dict({
        "k": rng.integers(0, 60_000, n),
        "u": np.where(rng.random(n) < 0.1, None, np.arange(n)),
    })
    battery = _battery(["k"]) + [Uniqueness(["u"])]
    mon = RunMonitor()
    ctx = AnalysisRunner.do_analysis_run(
        data, battery, monitor=mon, batch_size=16384, sharding=make_mesh(4)
    )
    _values(ctx, battery)
    assert mon.device_freq_sets == mon.freq_device_summaries == 2
    valid_u = n - data.arrow.column("u").null_count
    assert 0 < valid_u < n
    assert mon.freq_keys_drained == n + valid_u
    _, counts = np.unique(data.arrow.column("k").to_numpy(), return_counts=True)
    hist = len(np.unique(counts))
    assert mon.freq_drain_fetch_bytes == (
        2 * 9 * 8 + 16 * (1 << int(np.ceil(np.log2(hist)))) + 16 * 1
    )
    assert mon.freq_shard_max_keys <= 1.1 * n / 4


def test_mesh_run_past_the_resident_cap_compacts_exactly(monkeypatch):
    """Past the cap on the keys one device drains, a mesh run's table
    compacts, replicated, and its device summary still equals numpy's
    count: 20,480 padded rows over four devices would be 8,192 keys a
    device, 32,768 at each device's drain, over a cap of 8,192."""
    monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "8192")
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 7_000, 20_000)
    data = Dataset.from_dict({"k": keys})
    battery = _battery(["k"])
    mon = RunMonitor()
    ctx = AnalysisRunner.do_analysis_run(
        data, battery, monitor=mon, batch_size=4096, sharding=make_mesh(4)
    )
    got = _values(ctx, battery)
    assert mon.freq_device_summaries == 1 and mon.freq_overflow_fallbacks == 0
    _, counts = np.unique(keys, return_counts=True)
    assert got[repr(CountDistinct(["k"]))] == len(counts)
    assert got[repr(Uniqueness(["k"]))] == (counts == 1).sum() / len(keys)


@pytest.mark.parametrize("devices", [1, 4])
def test_verification_suite_on_mesh_equals_one_device_and_reference(devices):
    """The benchmark's SF 4 suite at scale 0.01: the program on a mesh of
    ``devices`` equals it on one device and the plain reference."""
    import json
    import os

    from benchmark.data.lineitem import build_lineitem
    from benchmark.drivers.batch import build_check, verification_result
    from benchmark.reference import tpch_lineitem_sf4 as reference
    from deequ_tpu import VerificationSuite

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "tpch_lineitem_sf4.json")) as f:
        config = json.load(f)
    battery = config["battery"]["verify"]
    table = build_lineitem(2147483901, 0.01)

    def run(mesh):
        mon = RunMonitor()
        builder = (VerificationSuite.on_data(Dataset.from_arrow(table))
                   .add_check(build_check(battery, "sf4")).with_monitor(mon))
        if mesh is not None:
            builder = builder.with_sharding(mesh)
        return verification_result(builder.run()), mon

    got, mon = run(make_mesh(devices))
    one, _ = run(None)
    assert mon.freq_device_summaries == 1
    assert got.keys() == one.keys()
    for key, value in one.items():
        if isinstance(value, float):
            # a mesh adds its partial float sums in another order
            assert got[key] == pytest.approx(value, rel=1e-12, abs=1e-15), key
        else:
            assert got[key] == value, key
    assert got[("Uniqueness", "l_orderkey,l_linenumber")] == 1.0
    gaps = reference.compare_suite(
        got, reference.suite(table, battery), battery, table.num_rows
    ).numbers()
    assert all(gaps[k] <= v for k, v in config["limits"].items()), gaps


@pytest.mark.parametrize("member", ["histogram", "mutual_information", "saved"])
def test_key_reading_or_saved_sets_keep_the_host_tables(member):
    """Only sets whose members read the summary alone, and whose states are
    not saved, take the device table: a MutualInformation member or saved
    states keep the set on the full value-keyed frequencies, and a
    Histogram keeps its own value-keyed bins beside a summarized set."""
    from deequ_tpu.analyzers.state_provider import InMemoryStateProvider

    rng = np.random.default_rng(3)
    n = 120_000
    data = Dataset.from_dict({
        "a": rng.integers(0, 40_000, n), "b": rng.integers(0, 30_000, n),
    })
    battery = _battery(["a"])
    kw = {}
    device_sets = 0
    if member == "histogram":
        battery.append(Histogram("a"))
        device_sets = 1
    elif member == "mutual_information":
        battery = [Uniqueness(["a", "b"]), MutualInformation(["a", "b"])]
    else:
        kw["save_states_with"] = InMemoryStateProvider()
    mon = RunMonitor()
    ctx = AnalysisRunner.do_analysis_run(data, battery, monitor=mon, **kw)
    got = _values(ctx, battery)
    assert mon.device_freq_sets == mon.freq_device_summaries == device_sets
    frame = data.arrow.to_pandas()
    cols = list(battery[0].columns)
    counts = frame.groupby(cols).size()
    assert got[repr(battery[0])] == (counts.to_numpy() == 1).sum() / n
    if member == "histogram":
        hist = got[repr(battery[-1])]
        assert hist.number_of_bins == len(counts)
        for label, bin_ in list(hist.values.items())[:50]:
            assert bin_.absolute == counts[int(label)]


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_resident_plan_splits_the_buffer_and_keeps_a_minimal_table(shards):
    """A resident plan is sized per device, and its table keeps 8 slots on
    a mesh too: no path compacts a resident state into its table."""
    from deequ_tpu.data import Dataset as D

    schema = D.from_dict({"k": np.arange(8)}).schema
    rows, batch = 6_001_215, 1 << 20
    padded = -(-rows // batch) * batch
    scan = plan_table_scan(schema, ["k"], rows, batch, shards)
    assert scan.resident and scan.shards == shards and scan.slots == 8
    assert scan.buffer_entries == 1 << int(np.ceil(np.log2(padded / shards)))
    assert scan.row_split_leaves() == (() if shards == 1 else (3,))
    state = scan.init_state()
    assert state.buf.shape == (shards, scan.buffer_entries)


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
@pytest.mark.parametrize("rows,resident", [
    (24_004_860, True), ((1 << 25) + 1, False),
])
def test_resident_plan_caps_the_keys_one_device_drains(shards, rows, resident):
    """The drain's exchange gives each device ``shards`` rows of the
    buffer, so the resident cap holds for all rows together: SF 4's
    24,004,860 keys stay resident on up to 8 devices, each receiving at
    most 2^25 keys at the drain, and one more padded batch than 2^25 rows
    compacts on any mesh."""
    from deequ_tpu.data import Dataset as D

    schema = D.from_dict({"k": np.arange(8)}).schema
    scan = plan_table_scan(schema, ["k"], rows, 1 << 20, shards)
    assert scan.resident == resident
    if resident:
        assert scan.shards == shards
        assert shards * scan.buffer_entries <= 1 << 25
    else:
        assert scan.shards == 1 and scan.buffer_entries <= 1 << 25


def test_split_update_appends_on_each_device_without_a_gather():
    """On a mesh the table's buffer is split over the devices in the
    carry, and the compiled update appends each device's rows locally:
    no all-gather or all-to-all of keys, only the scalar sums' reduce."""
    import jax

    from deequ_tpu.runners.engine import PackedScanProgram
    from deequ_tpu.parallel import row_sharded

    mesh = make_mesh(4)
    scan = DeviceFrequencyTableScan(
        ("k",), ("num",), 8, 1 << 14, resident=True, shards=4
    )
    prog = PackedScanProgram((scan,), mesh)
    # the array leaves: the table's keys and counts, then the buffer
    assert [s.spec for s in prog._carry_sharding[2]] == [
        (), (), row_sharded(mesh).spec
    ]
    carry = prog.init_carry()
    assert carry[2][2].sharding.spec == row_sharded(mesh).spec
    batch = 4096
    feats = {
        spec.key: jax.device_put(
            np.ones(batch, bool) if spec.kind in ("rows", "mask")
            else np.arange(batch, dtype=np.float64),
            row_sharded(mesh),
        )
        for spec in scan.feature_specs()
    }
    slots = (tuple(feats[k] for k in prog._spec_keys[0]),)
    text = prog._update.lower(carry, slots).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    out = prog.call_with_slots(carry, slots)
    (state,) = prog.unpack(out)
    buf = np.asarray(state.buf)
    # device d's quarter of the batch is the first 1024 entries of row d
    assert buf.shape == (4, 1 << 14)
    assert int(state.buf_fill) == batch
    from deequ_tpu.ops.hashing import splitmix64

    want = splitmix64(np.arange(batch, dtype=np.uint64)).reshape(4, -1)
    assert np.array_equal(buf[:, :1024], want)

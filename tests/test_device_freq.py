"""Device-resident frequency engine (ROADMAP item 3): bit-exact parity
against the host group-by/spill path across cardinalities and key types,
overflow-tier activation, spill-dir lifecycle, env-knob validation, and
ported reference `UniquenessTest.scala` scenarios.

The engine computes grouping frequencies ON DEVICE as fixed-shape sorted
(hash-key, count) tables folded in the fused pass; the host accumulator
(and its ``_SpillStore``) is the LAST-RESORT tier. Parity here is ``==``,
not approx: scalar frequency reductions are pure functions of the count
multiset, the single-column integral mixes are bijective, and Entropy's
float reduction runs in canonical (sorted-counts) order on both paths.
"""

import glob
import os
import tempfile

import numpy as np
import pandas as pd
import pytest

from deequ_tpu.analyzers import (
    CountDistinct,
    Distinctness,
    Entropy,
    Uniqueness,
    UniqueValueRatio,
)
from deequ_tpu.data import Dataset
from deequ_tpu.runners import AnalysisRunner
from deequ_tpu.runners.engine import RunMonitor


def _battery(cols):
    one = cols[0]
    return [
        Uniqueness(cols), Distinctness(cols), CountDistinct(cols),
        UniqueValueRatio(cols), Entropy(one) if len(cols) == 1 else Uniqueness(cols),
    ]


def _run(data, battery, monitor=None, **kw):
    return AnalysisRunner.do_analysis_run(
        data, battery, monitor=monitor, **kw
    )


def _values(ctx, battery):
    return {repr(a): ctx.metric(a).value.get() for a in battery}


def _parity(data, cols, monkeypatch, expect_device_sets=1, batch_size=None):
    """Run the battery through the device table engine, then with the
    engine disabled (host group-by), and require BIT-EXACT equality."""
    battery = _battery(cols)
    mon = RunMonitor()
    kw = {"batch_size": batch_size} if batch_size else {}
    dev = _values(_run(data, battery, monitor=mon, **kw), battery)
    assert mon.device_freq_sets == expect_device_sets, (
        mon.device_freq_sets, expect_device_sets
    )
    assert mon.freq_overflow_fallbacks == 0
    assert mon.freq_device_summaries == expect_device_sets
    monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")
    try:
        host = _values(_run(data, battery, **kw), battery)
    finally:
        # restore NOW: callers invoke _parity more than once per test, and
        # monkeypatch only reverts at teardown
        monkeypatch.delenv("DEEQU_TPU_DEVICE_FREQ")
    for k in dev:
        assert dev[k] == host[k], (k, dev[k], host[k])
    return dev


class TestBitExactParity:
    """Device table engine vs host spill path across cardinalities and
    key kinds — the tentpole's correctness contract."""

    @pytest.mark.parametrize("distinct", [100, 5_000, 60_000])
    def test_integral_cardinality_sweep(self, distinct, monkeypatch):
        rng = np.random.default_rng(distinct)
        n = max(4 * distinct, 20_000)
        data = Dataset.from_dict({"k": rng.integers(0, distinct, n)})
        _parity(data, ["k"], monkeypatch)

    def test_negative_and_extreme_integers(self, monkeypatch):
        rng = np.random.default_rng(2)
        vals = np.concatenate([
            rng.integers(-(2**62), 2**62, 30_000),
            np.array([0, -1, 2**63 - 1, -(2**63)], dtype=np.int64),
        ])
        data = Dataset.from_dict({"k": vals})
        _parity(data, ["k"], monkeypatch)

    def test_strings_high_cardinality(self, monkeypatch):
        rng = np.random.default_rng(3)
        vals = [f"key-{v:07d}" for v in rng.integers(0, 40_000, 120_000)]
        data = Dataset.from_dict({"s": vals})
        _parity(data, ["s"], monkeypatch)

    def test_fractional_with_nan_and_negzero(self, monkeypatch):
        rng = np.random.default_rng(4)
        vals = rng.integers(0, 9_000, 60_000).astype(np.float64) / 8.0
        vals[::13] = np.nan    # NaN VALUES form one real group
        vals[::29] = -0.0      # -0.0 and 0.0 are the same group
        vals[::31] = 0.0
        data = Dataset.from_dict({"f": vals})
        _parity(data, ["f"], monkeypatch)

    def test_nulls_masked_rows(self, monkeypatch):
        rng = np.random.default_rng(5)
        vals = pd.array(rng.integers(0, 7_000, 50_000), dtype="Int64")
        vals[::7] = pd.NA      # masked rows leave the frequency table but
        data = Dataset.from_dict({"k": vals})  # still count in num_rows
        _parity(data, ["k"], monkeypatch)

    def test_multicolumn_mixed_kinds(self, monkeypatch):
        """Multi-column grouping sets finally leave the host path: chained
        xxhash64 combined keys over int+string+float columns."""
        rng = np.random.default_rng(6)
        n = 60_000
        data = Dataset.from_dict({
            "i": rng.integers(0, 500, n),
            "s": [f"s{v}" for v in rng.integers(0, 200, n)],
            "f": np.round(rng.random(n), 2),
        })
        _parity(data, ["i", "s"], monkeypatch)
        _parity(data, ["i", "s", "f"], monkeypatch)

    def test_multicolumn_order_sensitivity(self):
        """(a,b) and (b,a) group identically as SETS of rows, and both
        orders must produce the same metrics (chained keys differ, count
        multisets cannot)."""
        rng = np.random.default_rng(7)
        n = 30_000
        data = Dataset.from_dict({
            "a": rng.integers(0, 300, n), "b": rng.integers(0, 77, n),
        })
        ab = _values(_run(data, [Uniqueness(["a", "b"])]), [Uniqueness(["a", "b"])])
        ba = _values(_run(data, [Uniqueness(["b", "a"])]), [Uniqueness(["b", "a"])])
        assert list(ab.values()) == list(ba.values())

    def test_batched_equals_single_batch(self, monkeypatch):
        """Cross-batch state folding (append + in-trace compaction) equals
        a one-batch run — the semigroup contract the mesh merge rides."""
        rng = np.random.default_rng(8)
        data = Dataset.from_dict({"k": rng.integers(0, 20_000, 100_000)})
        battery = _battery(["k"])
        whole = _values(_run(data, battery), battery)
        batched = _values(_run(data, battery, batch_size=4096), battery)
        assert whole == batched

    @pytest.mark.slow
    def test_five_million_distinct(self, monkeypatch):
        """The BENCH-scale knee: 5e6 distinct keys still fit the default
        table (2^22 slots is exceeded -> capped at rows) — overflow tier
        activates only when slots < distinct."""
        rng = np.random.default_rng(9)
        n = 10_000_000
        data = Dataset.from_dict({"k": rng.integers(0, 5_000_000, n)})
        _parity(data, ["k"], monkeypatch, batch_size=1 << 20)


class TestOverflowTier:
    def test_compaction_path_parity_when_table_fits(self, monkeypatch):
        """Force the NON-resident trace (tiny buffer cap -> in-pass
        sort-merge compactions) with a table big enough for every group:
        no loss, metrics bit-exact — the compaction machinery itself is
        parity-checked, not just the resident fast path."""
        monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "8192")
        rng = np.random.default_rng(22)
        data = Dataset.from_dict({"k": rng.integers(0, 9_000, 60_000)})
        _parity(data, ["k"], monkeypatch, batch_size=4096)

    def test_overflow_falls_back_to_host_exactly(self, monkeypatch):
        """A table too small for the key space overflows with EXACT loss
        accounting; the runner re-runs the set through the host
        accumulator and the metrics stay bit-exact. (The buffer cap is
        forced below the row count: a RESIDENT run never overflows — its
        drain is exact at any cardinality up to the buffer.)"""
        monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "8192")
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "1024")
        rng = np.random.default_rng(10)
        data = Dataset.from_dict({"k": rng.integers(0, 30_000, 80_000)})
        battery = _battery(["k"])
        mon = RunMonitor()
        dev = _values(_run(data, battery, monitor=mon, batch_size=8192), battery)
        assert mon.freq_overflow_fallbacks >= 1
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")
        host = _values(_run(data, battery, batch_size=8192), battery)
        assert dev == host

    def test_fitting_table_never_overflows(self, monkeypatch):
        """slots >= num_rows can never overflow: no fallback pass."""
        rng = np.random.default_rng(11)
        data = Dataset.from_dict({"k": rng.integers(0, 50_000, 60_000)})
        mon = RunMonitor()
        _run(data, [CountDistinct(["k"])], monitor=mon)
        assert mon.device_freq_sets == 1
        assert mon.freq_overflow_fallbacks == 0

    def test_mixed_overflow_and_fitting_sets(self, monkeypatch):
        """Only the overflowing set re-runs on the host tier; fitting sets
        keep their device result."""
        monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "8192")
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "2048")
        rng = np.random.default_rng(12)
        n = 40_000
        wide = rng.integers(0, 30_000, n)     # overflows 2048 slots
        narrow = rng.integers(0, 900, n)      # fits
        data = Dataset.from_dict({"wide": wide, "narrow": narrow})
        battery = [CountDistinct(["wide"]), CountDistinct(["narrow"])]
        mon = RunMonitor()
        ctx = _run(data, battery, monitor=mon, batch_size=8192)
        assert mon.device_freq_sets == 2
        assert mon.freq_overflow_fallbacks == 1
        assert ctx.metric(CountDistinct(["wide"])).value.get() == len(np.unique(wide))
        assert ctx.metric(CountDistinct(["narrow"])).value.get() == len(np.unique(narrow))


class TestSpillDirLifecycle:
    """Satellite: the host spill tier's temp dirs must not leak."""

    def _spill_dirs(self):
        return set(glob.glob(os.path.join(
            tempfile.gettempdir(), "deequ-tpu-freq-spill-*"
        )))

    def test_spilled_then_collected_leaves_no_directory(self, monkeypatch):
        """Regression (satellite 1): a run that spilled to disk releases
        its ``deequ-tpu-freq-spill-*`` dir as soon as metrics are derived
        — explicit close, not GC luck."""
        monkeypatch.setenv("DEEQU_TPU_MAX_FREQUENCY_ENTRIES", "500")
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")  # force host tier
        before = self._spill_dirs()
        data = Dataset.from_dict({"k": np.arange(30_000) % 20_000})
        ctx = _run(data, [Uniqueness(["k"]), CountDistinct(["k"])])
        assert ctx.metric(CountDistinct(["k"])).value.get() == 20_000.0
        # the state object may still be alive inside the result context —
        # the explicit close must already have removed the directory
        assert self._spill_dirs() == before

    def test_close_is_idempotent_and_blocks_reads(self):
        from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows

        state = FrequenciesAndNumRows.empty(["k"])
        os.environ["DEEQU_TPU_MAX_FREQUENCY_ENTRIES"] = "100"
        try:
            state._append_run(
                pd.Series(np.ones(2000, dtype=np.int64), index=pd.RangeIndex(2000))
            )
            state._flush()
        finally:
            del os.environ["DEEQU_TPU_MAX_FREQUENCY_ENTRIES"]
        assert state.spilled
        spill_dir = state._spill.dir
        assert os.path.isdir(spill_dir)
        state.close()
        state.close()  # idempotent
        assert not os.path.exists(spill_dir)
        with pytest.raises(RuntimeError, match="closed"):
            list(state.iter_merged_chunks())

    def test_unspilled_close_is_noop(self):
        from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows

        state = FrequenciesAndNumRows.empty(["k"])
        state._append_run(pd.Series(np.int64(3), index=pd.Index(["a"])))
        state.close()
        assert state.num_distinct() == 1


class TestEnvKnobs:
    """Satellite: warn-and-fallback validation (the watchdog/trace
    convention) for the frequency-engine knobs."""

    def _fresh(self, monkeypatch):
        from deequ_tpu.analyzers import grouping

        monkeypatch.setattr(grouping, "_ENV_WARNED", set())
        return grouping

    def test_invalid_table_slots_warns_and_defaults(self, monkeypatch, caplog):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "a-lot")
        with caplog.at_level("WARNING"):
            assert g.freq_table_slots() == g.DEFAULT_FREQ_TABLE_SLOTS
            assert g.freq_table_slots() == g.DEFAULT_FREQ_TABLE_SLOTS
        warned = [r for r in caplog.records if "DEEQU_TPU_FREQ_TABLE_SLOTS" in r.message]
        assert len(warned) == 1  # warn ONCE, not per pass

    def test_nonpositive_table_slots_rejected(self, monkeypatch):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "-8")
        assert g.freq_table_slots() == g.DEFAULT_FREQ_TABLE_SLOTS

    def test_valid_table_slots_honored(self, monkeypatch):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "4096")
        assert g.freq_table_slots() == 4096

    def test_invalid_buffer_entries_warns_and_defaults(self, monkeypatch, caplog):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "0x2000")
        with caplog.at_level("WARNING"):
            assert g.freq_buffer_entries() == g.DEFAULT_FREQ_BUFFER_ENTRIES
        assert any(
            "DEEQU_TPU_FREQ_BUFFER_ENTRIES" in r.message for r in caplog.records
        )

    def test_invalid_max_cardinality_warns_and_defaults(self, monkeypatch, caplog):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY", "64k")
        with caplog.at_level("WARNING"):
            assert g.device_freq_max_cardinality() == g.DEVICE_FREQ_MAX_CARDINALITY
        assert any(
            "DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY" in r.message
            for r in caplog.records
        )

    def test_invalid_device_freq_switch_stays_enabled(self, monkeypatch, caplog):
        g = self._fresh(monkeypatch)
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "yes")
        with caplog.at_level("WARNING"):
            assert g.device_freq_enabled() is True
        assert any("DEEQU_TPU_DEVICE_FREQ" in r.message for r in caplog.records)

    def test_disable_switch_routes_to_host(self, monkeypatch):
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")
        rng = np.random.default_rng(13)
        data = Dataset.from_dict({"k": rng.integers(0, 9_000, 20_000)})
        mon = RunMonitor()
        _run(data, [CountDistinct(["k"])], monitor=mon)
        assert mon.device_freq_sets == 0


class TestHashingPrimitives:
    """The numpy twins must be bit-identical to the traced jnp hashing —
    what makes host-side parity reconstruction possible at all."""

    def test_splitmix64_twins_bit_identical(self):
        import jax.numpy as jnp

        from deequ_tpu.ops.hashing import splitmix64, splitmix64_jnp

        rng = np.random.default_rng(14)
        v = rng.integers(0, 2**64, 4096, dtype=np.uint64)
        got = np.asarray(splitmix64_jnp(jnp.asarray(v)))
        assert (got == splitmix64(v)).all()

    def test_splitmix64_bijective_on_sample(self):
        from deequ_tpu.ops.hashing import splitmix64

        v = np.arange(100_000, dtype=np.uint64)
        assert len(np.unique(splitmix64(v))) == len(v)

    def test_xxhash64_u64_twins_and_chaining(self):
        import jax.numpy as jnp

        from deequ_tpu.ops.hashing import (
            xxhash64_u64,
            xxhash64_u64_jnp,
        )

        rng = np.random.default_rng(15)
        v = rng.integers(0, 2**64, 2048, dtype=np.uint64)
        seeds = rng.integers(0, 2**64, 2048, dtype=np.uint64)
        # scalar seed agrees with the pinned host xxhash64_u64
        got = np.asarray(xxhash64_u64_jnp(jnp.asarray(v), jnp.uint64(42)))
        assert (got == xxhash64_u64(v, 42)).all()
        # per-row seeds (multi-column chaining) agree with the numpy twin
        got = np.asarray(xxhash64_u64_jnp(jnp.asarray(v), jnp.asarray(seeds)))
        assert (got == xxhash64_u64(v, seeds)).all()

    def test_resident_flag_changes_program_identity(self):
        """``resident`` flips the traced update (cond-free append vs
        conditional compaction) without changing state shapes or feature
        kinds — so it MUST split the bundled-program signature, or a
        non-resident run whose (slots, buffer) match a cached resident
        program would run the cond-free trace and silently overflow."""
        from deequ_tpu.analyzers.grouping import DeviceFrequencyTableScan
        from deequ_tpu.runners.engine import _scan_signature

        res = DeviceFrequencyTableScan(
            ("k",), ("num",), 1 << 12, 1 << 12, resident=True
        )
        cond = DeviceFrequencyTableScan(
            ("k",), ("num",), 1 << 12, 1 << 12, resident=False
        )
        assert _scan_signature(res) != _scan_signature(cond)

    def test_freq_compact_overflow_accounting_exact(self):
        import jax.numpy as jnp

        from deequ_tpu.ops import freq_compact
        from deequ_tpu.ops.hashing import FREQ_KEY_SENTINEL

        sent = np.uint64(FREQ_KEY_SENTINEL)
        keys = np.array([7, 3, 3, 9, 1, 1, 1], dtype=np.uint64)
        counts = np.array([2, 1, 4, 5, 1, 1, 1], dtype=np.int64)
        pad = np.full(3, sent, dtype=np.uint64)
        ok, oc, n, kept, total = freq_compact(
            jnp.concatenate([jnp.asarray(keys), jnp.asarray(pad)]),
            jnp.concatenate([jnp.asarray(counts), jnp.zeros(3, jnp.int64)]),
            2, jnp.uint64(sent),
        )
        # 4 uniques {1:3, 3:5, 7:2, 9:5}; out_size=2 keeps the two smallest
        assert int(n) == 4
        assert list(np.asarray(ok)) == [1, 3]
        assert list(np.asarray(oc)) == [3, 5]
        assert int(total) == 15 and int(kept) == 8  # 7 rows lost, exactly


class TestStateMergePaths:
    def test_split_fold_merge_equals_single_fold(self):
        """Two half-dataset table states merged == one whole-dataset state
        (the collective_merge_states semigroup contract)."""
        import jax.numpy as jnp

        from deequ_tpu.analyzers.grouping import DeviceFrequencyTableScan

        rng = np.random.default_rng(16)
        keys = rng.integers(0, 5_000, 16_384, dtype=np.uint64)
        scan = DeviceFrequencyTableScan(("k",), ("num",), 8192, 4096)
        z = jnp.zeros((), jnp.int64)

        def fold(arr):
            st = scan.init_state()
            from deequ_tpu.ops.hashing import splitmix64_jnp

            for at in range(0, len(arr), 4096):
                c = arr[at : at + 4096]
                hashed = splitmix64_jnp(jnp.asarray(c))
                st = st.append_keys(
                    hashed, z, jnp.asarray(len(c), jnp.int64)
                )
            return st

        def drained(st):
            return scan.fetch_summary(scan.drain(st))[0]

        whole = drained(fold(keys))
        merged = drained(scan.merge(fold(keys[:8192]), fold(keys[8192:])))
        from deequ_tpu.analyzers.grouping import _sum_c_ln_c

        _, counts = np.unique(keys, return_counts=True)
        assert whole.stream_summary() == (
            len(counts), int((counts == 1).sum()), len(keys),
            _sum_c_ln_c(counts),
        )
        assert whole.num_rows == merged.num_rows == len(keys)
        assert whole.stream_summary() == merged.stream_summary()

    def test_hashed_frequencies_refuses_value_keyed_merge(self):
        from deequ_tpu.analyzers.grouping import (
            FrequenciesAndNumRows,
            FrequencySummary,
        )

        hf = FrequencySummary(
            1, 0, 2, np.array([2]), np.array([1]), 2, ["k"]
        )
        with pytest.raises(TypeError, match="never mix"):
            hf.sum(FrequenciesAndNumRows.empty(["k"]))
        with pytest.raises(TypeError, match="never mix"):
            FrequenciesAndNumRows.empty(["k"]).sum(hf)


@pytest.mark.grouping
@pytest.mark.chaos
class TestGroupingChaos:
    """Satellite: the overflow tier under the existing fault-injection
    sites — a device fault mid-pass and an injected overflow both land on
    the host last-resort tier with exact metrics."""

    def _data(self, distinct=20_000, n=60_000, seed=20):
        rng = np.random.default_rng(seed)
        return Dataset.from_dict({"k": rng.integers(0, distinct, n)})

    def test_device_fault_during_table_pass_fails_over_exact(self):
        from deequ_tpu.reliability.faults import FaultSpec, inject

        data = self._data()
        battery = _battery(["k"])
        want = _values(_run(data, battery), battery)
        mon = RunMonitor()
        with inject(
            FaultSpec("device_update", "device", at=2), seed=7
        ) as inj:
            got = _values(_run(data, battery, monitor=mon, batch_size=8192), battery)
        assert inj.fired
        # whatever ladder rung caught it (failover, isolation of the table
        # scan, or the host fallback pass), the run must complete with the
        # exact metrics and a second pass must have served the set
        assert mon.passes >= 2
        assert got == want

    def test_overflow_tier_with_host_fault_still_terminates_typed(self, monkeypatch):
        """Overflow fallback pass + an injected analyzer fault in it: the
        grouping analyzers degrade TYPED, never hang or go silently
        wrong."""
        from deequ_tpu.reliability.faults import FaultSpec, inject

        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "1024")
        data = self._data()
        battery = _battery(["k"])
        mon = RunMonitor()
        with inject(
            FaultSpec("device_update", "device", at=3, count=None, every=1000),
            seed=11,
        ):
            ctx = _run(data, battery, monitor=mon, batch_size=8192)
        for a in battery:
            value = ctx.metric(a).value
            if value.is_failure:
                assert value.exception is not None  # typed, not swallowed
            else:
                assert np.isfinite(value.get())

    def test_overflow_chaos_metrics_exact_when_fallback_clean(self, monkeypatch):
        """Forced overflow (tiny table) with faults armed at unreached
        sites: the fallback path alone must reproduce exact metrics."""
        from deequ_tpu.reliability.faults import FaultSpec, inject

        monkeypatch.setenv("DEEQU_TPU_FREQ_BUFFER_ENTRIES", "8192")
        monkeypatch.setenv("DEEQU_TPU_FREQ_TABLE_SLOTS", "1024")
        data = self._data(seed=21)
        battery = _battery(["k"])
        monkeypatch.setenv("DEEQU_TPU_DEVICE_FREQ", "0")
        want = _values(_run(data, battery, batch_size=8192), battery)
        monkeypatch.delenv("DEEQU_TPU_DEVICE_FREQ")
        mon = RunMonitor()
        with inject(FaultSpec("checkpoint", "device", at=1), seed=13):
            got = _values(_run(data, battery, monitor=mon, batch_size=8192), battery)
        assert mon.freq_overflow_fallbacks >= 1
        assert got == want


class TestUniquenessReference:
    """Ported reference `UniquenessTest.scala` scenarios, run through the
    DEVICE frequency engine (the suite's fixtures are low-cardinality, so
    the reference behaviors must survive the hashed path too)."""

    def test_all_unique_column_is_one(self):
        data = Dataset.from_dict({"unique": ["a", "b", "c", "d", "e", "f"]})
        ctx = _run(data, [Uniqueness(["unique"])])
        assert ctx.metric(Uniqueness(["unique"])).value.get() == 1.0

    def test_non_unique_column(self):
        # reference fixture: att1 = a,b,a,a -> one singleton out of 4 rows
        data = Dataset.from_dict({"att1": ["a", "b", "a", "a"]})
        ctx = _run(data, [Uniqueness(["att1"])])
        assert ctx.metric(Uniqueness(["att1"])).value.get() == 0.25

    def test_unique_with_nulls(self):
        """Nulls leave the frequency table but stay in the denominator
        (reference: uniqueness counts null groups out)."""
        data = Dataset.from_dict({"c": pd.array([1, 2, 3, None, None], dtype="Int64")})
        ctx = _run(data, [Uniqueness(["c"]), Distinctness(["c"])])
        assert ctx.metric(Uniqueness(["c"])).value.get() == 3 / 5
        assert ctx.metric(Distinctness(["c"])).value.get() == 3 / 5

    def test_multi_column_uniqueness(self):
        """reference: (att1, att2) pairs — all pairs distinct -> 1.0 even
        though each column alone is not unique."""
        data = Dataset.from_dict({
            "att1": ["a", "a", "b", "b"], "att2": ["x", "y", "x", "y"],
        })
        single = Uniqueness(["att1"])
        pair = Uniqueness(["att1", "att2"])
        ctx = _run(data, [single, pair])
        assert ctx.metric(pair).value.get() == 1.0
        assert ctx.metric(single).value.get() == 0.0

    def test_all_null_column_yields_empty_metric(self):
        data = Dataset.from_dict({"c": pd.array([None, None], dtype="Int64")})
        ctx = _run(data, [Uniqueness(["c"])])
        value = ctx.metric(Uniqueness(["c"])).value
        assert value.is_failure  # EmptyStateException analog

    def test_unique_value_ratio(self):
        # reference: values a,a,b,c,d -> 3 singletons / 4 distinct
        data = Dataset.from_dict({"c": ["a", "a", "b", "c", "d"]})
        ctx = _run(data, [UniqueValueRatio(["c"])])
        assert ctx.metric(UniqueValueRatio(["c"])).value.get() == 0.75


@pytest.mark.grouping
class TestCardinalityPreRouting:
    """The pre-routing probe keeps confidently-low-cardinality sets on the
    host group-by (whose value_counts fast path wins below the sweep knee)
    while clustered layouts and genuine high cardinality stay on the
    device table. Perf-only routing: metrics stay bit-exact either way."""

    def _probe(self):
        from deequ_tpu.analyzers.grouping import probably_low_cardinality

        return probably_low_cardinality

    def _big(self, distinct, sort=False, rows=2_200_000, seed=21):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, distinct, rows)
        if sort:
            keys = np.sort(keys)
        return Dataset.from_dict({"k": keys}), keys

    def test_low_cardinality_at_scale_probes_true(self):
        data, _ = self._big(100)
        assert self._probe()(data, ("k",)) is True

    def test_high_cardinality_probes_false(self):
        data, _ = self._big(1_000_000)
        assert self._probe()(data, ("k",)) is False

    def test_clustered_layout_probes_false(self):
        # sorted by key: every slice is low-card but later slices keep
        # revealing NEW keys — total cardinality is unknowable from
        # slices, so the probe must NOT claim low-cardinality
        data, _ = self._big(500_000, sort=True)
        assert self._probe()(data, ("k",)) is False

    def test_small_runs_skip_the_probe(self):
        rng = np.random.default_rng(5)
        data = Dataset.from_dict({"k": rng.integers(0, 50, 100_000)})
        assert self._probe()(data, ("k",)) is False  # below the row floor

    def test_multi_column_product_estimate(self):
        rng = np.random.default_rng(9)
        n = 2_200_000
        data = Dataset.from_dict({
            "a": rng.integers(0, 300, n), "b": rng.integers(0, 300, n),
        })
        # 300 x 300 = 90k possible pairs > the 2^15 ceiling: not confident
        assert self._probe()(data, ("a", "b")) is False
        small = Dataset.from_dict({
            "a": rng.integers(0, 100, n), "b": rng.integers(0, 100, n),
        })
        assert self._probe()(small, ("a", "b")) is True

    def test_knob_zero_disables_probe(self, monkeypatch):
        monkeypatch.setenv("DEEQU_TPU_FREQ_HOST_ROUTE", "0")
        data, _ = self._big(100)
        assert self._probe()(data, ("k",)) is False

    def test_invalid_knob_warns_and_stays_enabled(self, monkeypatch, caplog):
        from deequ_tpu.analyzers import grouping

        monkeypatch.setattr(grouping, "_ENV_WARNED", set())
        monkeypatch.setenv("DEEQU_TPU_FREQ_HOST_ROUTE", "sometimes")
        data, _ = self._big(100)
        import logging

        with caplog.at_level(logging.WARNING):
            assert self._probe()(data, ("k",)) is True
        assert any("DEEQU_TPU_FREQ_HOST_ROUTE" in r.message for r in caplog.records)

    def test_end_to_end_low_card_routes_host_bit_exact(self, monkeypatch):
        data, _ = self._big(100)
        battery = _battery(["k"])
        mon = RunMonitor()
        routed = _values(_run(data, battery, monitor=mon, batch_size=1 << 20), battery)
        assert mon.device_freq_sets == 0  # probe kept it on the host path
        monkeypatch.setenv("DEEQU_TPU_FREQ_HOST_ROUTE", "0")
        mon2 = RunMonitor()
        forced = _values(_run(data, battery, monitor=mon2, batch_size=1 << 20), battery)
        assert mon2.device_freq_sets == 1
        assert routed == forced

"""Frequency/grouping analyzers.

The reference computes one `GROUP BY` per distinct grouping-column set and
shares the resulting frequency table between all analyzers on that set
(reference `analyzers/GroupingAnalyzers.scala:29-157`, scheduler sharing at
`analyzers/runners/AnalysisRunner.scala:259-287`). Here the frequency table
is an exact host-side group-by (pandas C kernels over the Arrow batch)
accumulated *in the same single pass* as the device scan — so a verification
run with grouping analyzers still touches the data once, beating the
reference's extra jobs.

State semantics (verified against the reference):
- frequencies exclude rows where any grouping column is null;
- ``num_rows`` counts ALL rows (`FrequencyBasedAnalyzer.computeFrequencies`,
  `GroupingAnalyzers.scala:53-80`: numRows = data.count());
- merge = outer join adding counts (`GroupingAnalyzers.scala:128-148`).
"""

from __future__ import annotations

import abc
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import pandas as pd

from ..data import Batch, ColumnKind, Schema
from ..metrics import (
    Distribution,
    DistributionValue,
    DoubleMetric,
    Entity,
    Failure,
    HistogramMetric,
    Success,
    metric_from_empty,
    metric_from_failure,
    metric_from_value,
)
from ..exceptions import (
    IllegalAnalyzerParameterException,
    wrap_if_necessary,
)
from ..utils import BoundedLRU
from .base import Analyzer, Preconditions, ScanShareableAnalyzer

COUNT_COL = "count"


#: flush the run buffer once it holds at least this many entries (and at
#: least as many as the merged table, so every flush is amortized against
#: fresh input — total merge work stays O(total entries appended), never
#: O(batches x distinct))
MIN_FLUSH_ENTRIES = 1 << 17

#: optional hard cap on the frequency table's resident entry count; a run
#: whose distinct-group count exceeds it fails with a clear resource error
#: (a failure METRIC via the runner, not an opaque OOM). 0 = unlimited.
FREQ_BUDGET_ENV = "DEEQU_TPU_MAX_FREQUENCY_ENTRIES"


class FrequencyBudgetExceeded(RuntimeError):
    """Distinct-group count crossed DEEQU_TPU_MAX_FREQUENCY_ENTRIES (with
    spilling disabled), or a spilled table was asked to fully materialize."""


#: set to "0" to disable spilling and restore the hard budget failure
FREQ_SPILL_ENV = "DEEQU_TPU_FREQUENCY_SPILL"
#: number of hash partitions a spilled table is scattered over
FREQ_SPILL_PARTITIONS_ENV = "DEEQU_TPU_FREQUENCY_SPILL_PARTITIONS"
_DEFAULT_SPILL_PARTITIONS = 64


class _SpillStore:
    """Hash-partitioned spill files for an over-budget frequency table —
    the analog of the Spark shuffle spill the reference leans on
    (`GroupingAnalyzers.scala:53-80` runs on Spark's hash aggregation,
    which spills sorted run files per hash partition when memory runs out).

    Every spill event scatters the resident table over P partitions by a
    stable row hash and appends one parquet run file per non-empty
    partition. A key lives in exactly ONE partition, so reading a
    partition's runs + one concat/groupby yields FINAL counts for its keys
    with peak memory ~ (total appended entries)/P — never the whole table.
    """

    #: sentinel column names inside spill parquet files — user key columns
    #: may be named anything (including "count"), so frames never use the
    #: user-visible names
    _COUNT = "__deequ_count__"

    def __init__(self, group_columns: Sequence[str]):
        import shutil
        import tempfile
        import weakref

        self.group_columns = list(group_columns)
        self._key_cols = [f"__deequ_key{i}__" for i in range(len(self.group_columns))]
        from ..utils import env_number

        self.partitions = env_number(
            FREQ_SPILL_PARTITIONS_ENV, _DEFAULT_SPILL_PARTITIONS, int,
            minimum=1,
        )
        self.dir = tempfile.mkdtemp(prefix="deequ-tpu-freq-spill-")
        self._runs = 0
        self.entries_spilled = 0
        self._finalizer = weakref.finalize(self, shutil.rmtree, self.dir, True)

    def close(self) -> None:
        """Remove the spill directory NOW (idempotent). The GC finalizer is
        only the backstop: a long-lived process whose collected states are
        kept alive by stray references would otherwise leak one
        ``deequ-tpu-freq-spill-*`` temp dir per spilled table until exit.
        The runner closes its pass-local grouping states as soon as their
        metrics are derived."""
        self._finalizer()

    def _partition_of(self, frame: pd.DataFrame) -> np.ndarray:
        """Stable per-row hash partition from the KEY COLUMNS (hashing the
        index directly trips pandas' Categorical factorization on NaN level
        values; plain columns hash NaN by bit pattern)."""
        codes = pd.util.hash_pandas_object(
            frame[self._key_cols], index=False
        ).to_numpy()
        return (codes % np.uint64(self.partitions)).astype(np.int64)

    def _to_frame(self, counts: pd.Series) -> pd.DataFrame:
        return counts.rename(self._COUNT).rename_axis(self._key_cols).reset_index()

    def _check_open(self) -> None:
        if not self._finalizer.alive:
            raise RuntimeError(
                "frequency spill store was closed; its partition files are "
                "gone — serving from it would silently drop counts"
            )

    def append(self, counts: pd.Series) -> None:
        """Scatter one resident table over the hash partitions."""
        import os

        self._check_open()
        if len(counts) == 0:
            return
        frame = self._to_frame(counts)
        part_of = self._partition_of(frame)
        run = self._runs
        self._runs += 1
        for p in np.unique(part_of):
            sub = frame.iloc[np.flatnonzero(part_of == p)]
            pdir = os.path.join(self.dir, f"part{p:05d}")
            os.makedirs(pdir, exist_ok=True)
            sub.to_parquet(os.path.join(pdir, f"run{run:05d}.parquet"), index=False)
        self.entries_spilled += len(counts)

    def iter_partition_counts(self, extra: Optional[pd.Series] = None):
        """Yield one FINAL count Series per partition (every key exactly
        once across all yields). ``extra`` is a not-yet-spilled resident
        table folded in (hashed with the same function)."""
        import os

        self._check_open()

        extra_parts: Dict[int, pd.Series] = {}
        if extra is not None and len(extra):
            part_of = self._partition_of(self._to_frame(extra))
            for p in np.unique(part_of):
                extra_parts[int(p)] = extra.iloc[np.flatnonzero(part_of == p)]
        for p in range(self.partitions):
            pdir = os.path.join(self.dir, f"part{p:05d}")
            pieces: List[pd.Series] = []
            if os.path.isdir(pdir):
                for name in sorted(os.listdir(pdir)):
                    frame = pd.read_parquet(os.path.join(pdir, name))
                    series = frame.set_index(self._key_cols)[self._COUNT]
                    if len(self._key_cols) == 1 and isinstance(
                        series.index, pd.MultiIndex
                    ):
                        series.index = series.index.get_level_values(0)
                    # restore the user-visible level names for consumers that
                    # read keys (Histogram, MutualInformation marginals)
                    series = series.rename_axis(
                        self.group_columns if len(self.group_columns) > 1
                        else self.group_columns[0]
                    )
                    pieces.append(series)
            if p in extra_parts:
                pieces.append(extra_parts[p])
            if not pieces:
                continue
            if len(pieces) == 1:
                yield pieces[0].astype(np.int64)
                continue
            cat = pd.concat(pieces)
            levels = (
                list(range(cat.index.nlevels))
                if isinstance(cat.index, pd.MultiIndex)
                else 0
            )
            yield (
                cat.groupby(level=levels, sort=False, dropna=False)
                .sum()
                .astype(np.int64)
            )


class FrequenciesAndNumRows:
    """Host state: group -> count plus total row count
    (reference `GroupingAnalyzers.scala:128-157`).

    Accumulation is amortized: per-batch count runs buffer in a list and are
    merged with ONE concat + groupby once the buffer outweighs the merged
    table (the reference leans on Spark's hash-aggregation shuffle for the
    same reason, `GroupingAnalyzers.scala:53-80`). The old per-batch
    ``Series.add`` outer join re-touched every distinct group per batch —
    quadratic over a run on high-cardinality columns.
    """

    #: total entries processed by flush merges across the process — tests
    #: assert this stays O(total entries appended), see tests/test_grouping_scale.py
    merge_work: int = 0

    def __init__(self, frequencies: pd.Series, num_rows: int, group_columns: Sequence[str]):
        self._merged = frequencies  # index = group keys (tuples for multi-col)
        self._runs: List[pd.Series] = []
        self._buffered = 0
        self._spill: Optional[_SpillStore] = None
        self._summary: Optional[Tuple[int, int, int, float]] = None
        self.num_rows = int(num_rows)
        self.group_columns = list(group_columns)

    @property
    def spilled(self) -> bool:
        """True once the table crossed the budget and lives (partly) in
        hash-partitioned spill files instead of RAM."""
        return self._spill is not None

    @property
    def frequencies(self) -> pd.Series:
        """The merged frequency table (forces a flush of buffered runs).

        A SPILLED table refuses to materialize: consumers that need the
        whole table at once (state persistence, incremental ``sum`` merge)
        fail with the same clean FrequencyBudgetExceeded the hard budget
        used to raise; streaming consumers use ``iter_merged_chunks``."""
        self._flush()
        if self._spill is not None:
            raise FrequencyBudgetExceeded(
                f"frequency table for {self.group_columns} spilled "
                f"{self._spill.entries_spilled} entries to disk under the "
                f"{FREQ_BUDGET_ENV} budget; full-table materialization is "
                "not available (set a larger budget, or use a streaming "
                "consumer)"
            )
        return self._merged

    @frequencies.setter
    def frequencies(self, value: pd.Series) -> None:
        self._merged = value
        self._runs = []
        self._buffered = 0
        self._spill = None
        self._summary = None

    def iter_merged_chunks(self):
        """Yield FINAL count Series chunks, each group exactly once across
        all chunks — the streaming read every frequency reduction uses.
        Unspilled tables yield themselves in one chunk; spilled tables
        k-way-merge their hash partitions at ~1/P of the table per step."""
        self._flush()
        if self._spill is None:
            if len(self._merged):
                yield self._merged
            return
        yield from self._spill.iter_partition_counts(
            self._merged if len(self._merged) else None
        )

    def num_distinct(self) -> int:
        """Number of distinct groups; streams when spilled."""
        self._flush()  # may create the spill store
        if self._spill is None:
            return len(self._merged)
        return self.stream_summary()[0]

    def stream_summary(self) -> Tuple[int, int, int, float]:
        """(num_distinct, singleton_count, sum(count), sum(count*ln(count)))
        computed in ONE streaming pass and cached — every scalar frequency
        reduction (Uniqueness, Distinctness, UniqueValueRatio,
        CountDistinct, Entropy) reads these, so a 5-analyzer battery over a
        spilled table costs one disk pass, not five. Invalidated whenever
        new counts are appended."""
        if self._summary is None:
            nd = 0
            singles = 0
            total = 0
            # count-of-counts histogram accumulated chunk-by-chunk: holds
            # one entry per distinct COUNT VALUE (O(sqrt(rows)) worst
            # case), never the table itself — the spill tier's chunks stay
            # bounded. Reduced through the same canonical function the
            # device path's _sum_c_ln_c uses, so Entropy stays
            # bit-identical across paths and chunkings.
            hist: dict = {}
            for chunk in self.iter_merged_chunks():
                c = chunk.to_numpy(dtype=np.int64)
                nd += len(c)
                singles += int((c == 1).sum())
                total += int(c.sum())
                uc, mult = np.unique(c[c > 0], return_counts=True)
                for v, m in zip(uc.tolist(), mult.tolist()):
                    hist[v] = hist.get(v, 0) + int(m)
            if hist:
                uc = np.fromiter(sorted(hist), np.int64, count=len(hist))
                mult = np.array([hist[int(v)] for v in uc], dtype=np.int64)
            else:
                uc = np.empty(0, np.int64)
                mult = np.empty(0, np.int64)
            self._summary = (
                nd, singles, total, _reduce_count_histogram(uc, mult)
            )
        return self._summary

    def is_empty(self) -> bool:
        self._flush()  # may create the spill store
        if self._spill is not None:
            return False  # a spilled table crossed the budget: never empty
        return len(self._merged) == 0

    def _budget(self) -> int:
        from ..utils import env_number

        return env_number(FREQ_BUDGET_ENV, 0, int, minimum=0)

    def _spill_enabled(self) -> bool:
        from ..utils import env_flag

        return env_flag(FREQ_SPILL_ENV, True)

    def _flush(self) -> None:
        if not self._runs:
            return
        parts = ([self._merged] if len(self._merged) else []) + self._runs
        FrequenciesAndNumRows.merge_work += sum(len(p) for p in parts)
        if len(parts) == 1:
            merged = parts[0].astype(np.int64)
        else:
            cat = pd.concat(parts)
            levels = (
                list(range(cat.index.nlevels))
                if isinstance(cat.index, pd.MultiIndex)
                else 0
            )
            # dropna=False: NaN is a real group key (update() groups with
            # dropna=False; a float column's NaN VALUES form a group, only
            # nulls are excluded)
            merged = (
                cat.groupby(level=levels, sort=False, dropna=False)
                .sum()
                .astype(np.int64)
            )
        budget = self._budget()
        if budget and len(merged) > budget:
            if not self._spill_enabled():
                raise FrequencyBudgetExceeded(
                    f"frequency table for {self.group_columns} holds {len(merged)} "
                    f"distinct groups, over the {FREQ_BUDGET_ENV}={budget} budget"
                )
            # over budget: scatter the resident table to the hash-partition
            # spill files and keep RAM bounded by ~budget entries (the Spark
            # shuffle-spill analog, `GroupingAnalyzers.scala:53-80`)
            if self._spill is None:
                self._spill = _SpillStore(self.group_columns)
            self._spill.append(merged)
            merged = pd.Series([], dtype=np.int64)
        self._merged = merged
        self._runs = []
        self._buffered = 0

    def _append_run(self, counts: pd.Series) -> None:
        if len(counts) == 0:
            return
        self._summary = None
        self._runs.append(counts)
        self._buffered += len(counts)
        if self._buffered >= max(len(self._merged), MIN_FLUSH_ENTRIES):
            self._flush()

    def close(self) -> None:
        """Release the hash-partition spill directory NOW (idempotent,
        no-op when nothing spilled). After closing, a spilled state refuses
        to serve (its partition files are gone); the runner closes its
        pass-local states once their metrics are derived, and the GC
        finalizer remains the backstop for everything else."""
        if self._spill is not None:
            self._spill.close()

    def sum(self, other: "FrequenciesAndNumRows") -> "FrequenciesAndNumRows":
        if not isinstance(other, FrequenciesAndNumRows):
            raise TypeError(
                f"cannot merge a value-keyed frequency table with "
                f"{type(other).__name__}: hashed device-frequency states "
                "and host group-by states never mix (the runner gates the "
                "device table engine off runs that persist or aggregate)"
            )
        merged = _add_series(self.frequencies, other.frequencies)
        return FrequenciesAndNumRows(merged, self.num_rows + other.num_rows, self.group_columns)

    @staticmethod
    def empty(group_columns: Sequence[str]) -> "FrequenciesAndNumRows":
        return FrequenciesAndNumRows(
            pd.Series([], dtype=np.int64), 0, group_columns
        )

    def update(self, batch: Batch) -> "FrequenciesAndNumRows":
        """Fold one batch of rows into the frequency table. O(batch) work per
        batch (the per-batch group-by); merges amortize via `_append_run`.
        Mutates and returns self — per-batch copies of a potentially huge
        table are exactly the cost this accumulator exists to avoid."""
        mask = batch.row_mask
        columns = {name: batch.column(name) for name in self.group_columns}
        for col in columns.values():
            mask = mask & col.mask  # validity masks only: values stay lazy
        self.num_rows += batch.num_rows
        if not mask.any():
            return self
        if len(self.group_columns) == 1:
            col = next(iter(columns.values()))
            if col.arrow is not None and batch.row_mask.all():
                # string keys kept as an Arrow array (its nulls ARE the
                # validity mask): C-speed value_counts, no python-object
                # materialization — touching col.values here would defeat it;
                # the null group is excluded inside _arrow_value_counts
                counts = _arrow_value_counts(col.arrow)
                if counts is not None:
                    self._append_run(counts)
                    return self
            vals = col.values
            if vals.dtype != object and np.issubdtype(vals.dtype, np.integer):
                sel = vals[mask]
                if sel.size:
                    smn, smx = sel.min(), sel.max()
                    if int(smx) - int(smn) < (1 << 16):
                        # small-range integer keys (flags, line numbers,
                        # ordinals): an offset bincount beats the sort
                        # inside np.unique ~5x. Dtype care: signed narrow
                        # dtypes wrap on in-dtype subtraction (int8:
                        # 127-(-128) -> -1) so they widen first; uint64
                        # values above 2^63 overflow int64 so unsigned
                        # subtracts in-dtype (exact — range < 2^16) and
                        # rebuilds keys in-dtype too.
                        if np.issubdtype(sel.dtype, np.signedinteger):
                            offs = sel.astype(np.int64) - int(smn)
                        else:
                            offs = (sel - smn).astype(np.int64)
                        cnts = np.bincount(offs, minlength=int(smx) - int(smn) + 1)
                        nz = np.flatnonzero(cnts)
                        if np.issubdtype(sel.dtype, np.signedinteger):
                            keys = (nz + int(smn)).astype(sel.dtype)
                        else:
                            keys = nz.astype(sel.dtype) + smn
                        self._append_run(
                            pd.Series(cnts[nz].astype(np.int64), index=keys)
                        )
                        return self
                # integer keys: np.unique sorts + counts ~6x faster than a
                # pandas groupby (floats stay on the groupby path — NaN
                # group-key identity is pandas' job)
                uniques, cnts = np.unique(sel, return_counts=True)
                self._append_run(pd.Series(cnts.astype(np.int64), index=uniques))
                return self
        frame = pd.DataFrame({n: c.values[mask] for n, c in columns.items()})
        counts = frame.groupby(self.group_columns, sort=False, dropna=False).size()
        if len(self.group_columns) == 1:
            counts.index = counts.index.get_level_values(0) if isinstance(
                counts.index, pd.MultiIndex
            ) else counts.index
        self._append_run(counts)
        return self


def _with_null_bin(counts: pd.Series, num_null: int) -> pd.Series:
    """Add the NullValue bin (reference `analyzers/Histogram.scala:108`:
    nulls count under the "NullValue" key) — the single definition all three
    Histogram accumulation paths share."""
    if not num_null:
        return counts
    return counts.add(
        pd.Series({NULL_FIELD_REPLACEMENT: num_null}), fill_value=0
    ).astype(np.int64)


def _arrow_value_counts(arr) -> Optional[pd.Series]:
    """Distinct-value counts of an Arrow array as an int64 Series (null
    entry dropped), or None when Arrow cannot count this type."""
    import pyarrow.compute as pc

    try:
        vc = pc.value_counts(arr)
    except Exception:  # noqa: BLE001 - unsupported type: caller falls back
        return None
    values = vc.field("values")
    keys = values.to_numpy(zero_copy_only=False)
    counts = vc.field("counts").to_numpy(zero_copy_only=False)
    if values.null_count:
        keep = np.asarray(pc.is_valid(values))
        keys, counts = keys[keep], counts[keep]
    return pd.Series(counts.astype(np.int64), index=keys)


def _sum_c_ln_c(counts: np.ndarray) -> float:
    """sum(count * ln(count)) over a count multiset in CANONICAL order: the
    count-of-counts histogram reduced in ascending count value. The device
    frequency engine surfaces counts keyed by 64-bit hashes, the host
    group-by keys them by value — same multiset, different array order, and
    float addition is not associative. The histogram form is a pure
    function of the multiset, so the two paths (and any chunking of the
    host spill — see ``FrequenciesAndNumRows.stream_summary``, which
    accumulates the same histogram chunk-by-chunk in bounded memory)
    produce the bit-identical Entropy."""
    counts = np.asarray(counts, dtype=np.int64)
    uc, mult = np.unique(counts[counts > 0], return_counts=True)
    return _reduce_count_histogram(uc, mult)


def _reduce_count_histogram(uc: np.ndarray, mult: np.ndarray) -> float:
    """The shared canonical reduction: ``uc`` ascending unique count
    values, ``mult`` their multiplicities. Every c*ln(c) consumer must
    reach this exact function for bit-identical results."""
    pos = uc.astype(np.float64)
    return float((mult.astype(np.float64) * (pos * np.log(pos))).sum())


def _add_series(a: pd.Series, b: pd.Series) -> pd.Series:
    """Outer-join add of two count series; tolerates empty operands whose
    index types don't match the other side's (Range vs MultiIndex)."""
    if len(a) == 0:
        return b.astype(np.int64)
    if len(b) == 0:
        return a.astype(np.int64)
    return a.add(b, fill_value=0).astype(np.int64)


#: dictionary sizes up to this ride the fused device scan (one-hot /
#: sort-based counting, see DeviceFrequencyScan.update); larger
#: dictionaries fall back to the device frequency TABLE engine (hashed
#: keys) or the amortized host group-by. Env-overridable via
#: DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY (read through
#: :func:`device_freq_max_cardinality`).
DEVICE_FREQ_MAX_CARDINALITY = 1 << 16

DEVICE_FREQ_MAX_CARDINALITY_ENV = "DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY"

#: env var switching the device frequency TABLE engine ("0" disables; the
#: dense dictionary path above stays on — it predates the table engine)
DEVICE_FREQ_ENV = "DEEQU_TPU_DEVICE_FREQ"

#: env var sizing the frequency table: distinct-group capacity per grouping
#: set (rounded up to a power of two; capped per run at the row count,
#: since distinct <= rows). Bigger tables push the overflow knee out at the
#: cost of HBM and per-compaction sort width.
FREQ_TABLE_SLOTS_ENV = "DEEQU_TPU_FREQ_TABLE_SLOTS"
DEFAULT_FREQ_TABLE_SLOTS = 1 << 22

#: env var capping the raw key buffer (entries, 8B each; rounded up to a
#: power of two). Runs whose padded row count fits under the cap ride the
#: RESIDENT trace: every per-row key stays buffered on device and the
#: drain aggregates once — no in-pass compaction sorts at all. Larger runs
#: fall back to the conditional-compaction trace (the sorted fixed-shape
#: table bounds drain work; the sort amortizes over buffer/batch batches),
#: whose buffer floor is one padded batch — the cap cannot shrink it below
#: that.
FREQ_BUFFER_ENTRIES_ENV = "DEEQU_TPU_FREQ_BUFFER_ENTRIES"
DEFAULT_FREQ_BUFFER_ENTRIES = 1 << 25  # 256MB of u64 keys

#: env var gating the pre-routing cardinality probe ("0" disables it, so
#: every eligible grouping set takes the device table no matter how small
#: it looks). With the probe on, sets that confidently look
#: low-cardinality stay on the host group-by, whose value_counts fast path
#: wins below the crossover.
FREQ_HOST_ROUTE_ENV = "DEEQU_TPU_FREQ_HOST_ROUTE"

#: warn-once latches for unparseable env overrides (the watchdog/trace
#: convention: never crash a run over a typo'd knob, never spam the log)
_ENV_WARNED: set = set()


def device_freq_max_cardinality() -> int:
    """The dense dictionary-path cardinality ceiling (registry-resolved:
    env override > tuned > static)."""
    from ..tuning import knobs

    return knobs.value("device_freq_max_cardinality")


def freq_table_slots() -> int:
    """Configured distinct-group capacity of the device frequency table."""
    from ..tuning import knobs

    return knobs.value("freq_table_slots")


def freq_buffer_entries() -> int:
    """Configured raw-key buffer cap (the resident-mode ceiling)."""
    from ..tuning import knobs

    return knobs.value("freq_buffer_entries")


def device_freq_enabled() -> bool:
    import logging
    import os

    raw = os.environ.get(DEVICE_FREQ_ENV)
    if raw is None or raw in ("0", "1"):
        return raw != "0"
    if DEVICE_FREQ_ENV not in _ENV_WARNED:
        _ENV_WARNED.add(DEVICE_FREQ_ENV)
        logging.getLogger(__name__).warning(
            "ignoring invalid %s=%r (expected 0 or 1); device frequency "
            "engine stays enabled", DEVICE_FREQ_ENV, raw,
        )
    return True


@dataclass(frozen=True)
class DeviceFrequencyScan(ScanShareableAnalyzer):
    """Frequency table of one dictionary-encoded column computed ON DEVICE:
    a scatter-free count over the column's codes joins the fused scan
    (chunked one-hot sum for small dictionaries, sort + boundary diffs for
    large ones — see ``update``), so low-cardinality grouping costs zero
    extra host work (SURVEY §7 step 6's hybrid; the reference instead runs
    a Spark groupBy shuffle per set, `GroupingAnalyzers.scala:53-80`).

    Runner-internal: `AnalysisRunner` instantiates it for eligible grouping
    sets and converts the state back into FrequenciesAndNumRows, so every
    grouping analyzer's metric code sees one state type."""

    column: str = ""
    num_categories: int = 0
    name: str = field(default="DeviceFrequencyScan", init=False)

    supports_host_partial = True

    @property
    def instance(self) -> str:
        return self.column

    def feature_specs(self):
        from .base import codes_feature, mask_feature, rows_feature

        return [rows_feature(), mask_feature(self.column), codes_feature(self.column)]

    def init_state(self):
        from .states import FrequencyCountsState

        return FrequencyCountsState.init(self.num_categories)

    def update(self, state, features):
        import jax.numpy as jnp

        from .base import codes_feature, mask_feature

        rows = features["rows"]
        mask = rows & features[mask_feature(self.column).key]
        codes = features[codes_feature(self.column).key]
        K = self.num_categories
        # No scatter-add: `segment_sum` lowers to a serialized loop on TPU
        # (measured 72-123ms per 1M-row batch). Small dictionaries count via
        # a chunked one-hot compare/sum scan (1.3ms — the VMEM-tile trick
        # the HLL register max uses); larger ones sort the codes and take
        # boundary differences (3-10ms, exact for any cardinality). Masked
        # rows map to the sentinel code K, which both paths drop.
        keys = jnp.where(mask, codes, K).astype(jnp.int32)
        if K <= 4096:
            from ..ops import chunked_key_fold

            cats = jnp.arange(K, dtype=jnp.int32)

            def fold_chunk(acc, row):
                hits = jnp.sum(
                    row[:, None] == cats[None, :], axis=0, dtype=acc.dtype
                )
                return acc + hits

            batch_counts = chunked_key_fold(
                keys, K, jnp.zeros(K, jnp.int32), fold_chunk
            )
        else:
            sorted_keys = jnp.sort(keys)
            bounds = jnp.searchsorted(
                sorted_keys, jnp.arange(K + 1, dtype=jnp.int32), side="left"
            )
            batch_counts = bounds[1:] - bounds[:-1]
        from .states import FrequencyCountsState

        return FrequencyCountsState(
            state.counts + batch_counts.astype(state.counts.dtype),
            state.num_rows + jnp.sum(rows, dtype=state.num_rows.dtype),
        )

    def host_partial(self, ctx):
        from .states import FrequencyCountsState

        col = ctx.batch.column(self.column)
        shared = ctx.dict_code_counts(self.column)
        if shared is not None:
            # the shared one-pass native count (also feeds DataType/HLL)
            counts = shared[: self.num_categories]
        else:
            mask = ctx.batch.row_mask & col.mask
            counts = np.bincount(
                col.codes[mask], minlength=self.num_categories + 1
            )[: self.num_categories]
        return FrequencyCountsState(
            counts.astype(np.int64), np.asarray(ctx.batch.num_rows, dtype=np.int64)
        )

    def merge(self, a, b):
        return a.merge(b)

    def to_frequencies(self, state, dictionary: np.ndarray) -> FrequenciesAndNumRows:
        counts = np.asarray(state.counts)
        nz = counts > 0
        series = pd.Series(
            counts[nz].astype(np.int64), index=pd.Index(np.asarray(dictionary)[nz])
        )
        return FrequenciesAndNumRows(series, int(state.num_rows), [self.column])

    def compute_metric_from(self, state):  # pragma: no cover - runner-internal
        raise NotImplementedError(
            "DeviceFrequencyScan states convert via to_frequencies; the "
            "grouping analyzers sharing the set own the metrics"
        )


class FrequencySummary:
    """The drained host view of a device frequency table
    (:class:`~..analyzers.states.FrequencyTableState`): the count multiset
    reduced on the device to what the scalar frequency reductions
    (Uniqueness, Distinctness, UniqueValueRatio, CountDistinct, Entropy)
    read, through the same ``stream_summary``/``num_distinct``/``is_empty``
    protocol as :class:`FrequenciesAndNumRows`, so the analyzers' metric
    code is state-type agnostic. sum(c ln c) comes from the count-of-counts
    histogram through the canonical reduction, bit-identical to the host
    group-by's. Key-reading consumers (Histogram bins, MutualInformation
    marginals) never receive one: runner eligibility keeps those on the
    dictionary or host paths.

    ``lost_rows > 0`` marks a table whose compactions dropped groups: its
    counts are incomplete and the runner re-runs the set through the host
    accumulator."""

    __slots__ = (
        "num_rows", "group_columns", "lost_groups", "lost_rows", "_summary",
    )

    def __init__(
        self, groups: int, singletons: int, rows: int,
        hist_values: np.ndarray, hist_mults: np.ndarray, num_rows: int,
        group_columns: Sequence[str], lost_groups: int = 0,
        lost_rows: int = 0,
    ):
        self.num_rows = int(num_rows)
        self.group_columns = list(group_columns)
        self.lost_groups = int(lost_groups)
        self.lost_rows = int(lost_rows)
        self._summary: Tuple[int, int, int, float] = (
            int(groups), int(singletons), int(rows),
            _reduce_count_histogram(
                np.asarray(hist_values, np.int64),
                np.asarray(hist_mults, np.int64),
            ),
        )

    def num_distinct(self) -> int:
        return self._summary[0]

    def is_empty(self) -> bool:
        return self._summary[0] == 0

    def stream_summary(self) -> Tuple[int, int, int, float]:
        """(num_distinct, singleton_count, sum(count), sum(count*ln(count)))
        — the quadruple FrequenciesAndNumRows serves."""
        return self._summary

    def close(self) -> None:  # protocol parity with FrequenciesAndNumRows
        pass

    def sum(self, other) -> "FrequencySummary":
        raise TypeError(
            f"cannot merge a drained device-frequency summary with "
            f"{type(other).__name__}: it holds no keys, and hashed "
            "device-frequency states and value-keyed host states never mix"
        )


#: the drain's scalar outputs, in order (one int64 vector, one transfer)
_DRAIN_SCALARS = (
    "groups", "singletons", "rows", "distinct_counts", "busiest_shard_rows",
    "sent_rows", "lost_groups", "lost_rows", "num_rows",
)

#: compiled drain programs keyed by (mesh devices when sharded, shards,
#: resident, histogram slots)
_DRAIN_PROGRAMS = BoundedLRU(64)


def _drain_program(mesh, shards: int, resident: bool, hist_slots: int):
    """The jitted device drain of one table layout (``jit_freq_drain_summary``
    in a device trace): the buffered keys (and, when the table compacts,
    its entries) sorted and run-length counted into the summary
    :func:`..ops.count_summary` gives. With ``shards > 1`` it runs on each
    device of ``mesh`` over its own buffer rows: one exchange
    (:func:`..parallel.exchange_by_key_range`) first brings every key to
    the device that owns its range, so the devices' groups are disjoint
    and their summaries add."""
    import jax.numpy as jnp

    from ..ops import count_summary, freq_compact
    from ..ops.hashing import FREQ_KEY_SENTINEL

    sharded = shards > 1
    key = (
        tuple(mesh.devices.flat) if sharded else None, shards, resident,
        hist_slots,
    )
    program = _DRAIN_PROGRAMS.get(key)
    if program is not None:
        return program
    from ..parallel import ROW_AXIS

    def summarize(st):
        sent = jnp.uint64(FREQ_KEY_SENTINEL)
        with jax.named_scope("freq_partition"):
            keys = st.buffered_keys(shards).reshape(-1)
            if resident:
                # the resident trace never compacts: its table is empty
                keys = jnp.sort(keys)
                if sharded:
                    from ..parallel import exchange_by_key_range

                    keys = jnp.sort(exchange_by_key_range(keys, sent))
                weights = (keys != sent).astype(jnp.int64)
            else:
                keys, weights = jax.lax.sort(
                    (
                        jnp.concatenate([st.sorted_keys, keys]),
                        jnp.concatenate([
                            st.sorted_counts,
                            (keys != sent).astype(jnp.int64),
                        ]),
                    ),
                    num_keys=1,
                )
        with jax.named_scope("freq_summary"):
            # a resident buffer holds fewer than 2^31 keys, so each count
            # fits 32 bits
            groups, singles, rows, values, mults, distinct = count_summary(
                keys, weights, hist_slots, sent,
                jnp.int32 if resident else jnp.int64,
            )
            busiest = rows
            if sharded:
                groups, singles, rows = (
                    jax.lax.psum(x, ROW_AXIS) for x in (groups, singles, rows)
                )
                # an s64 max all-reduce does not lower on TPU
                busiest = jnp.max(jax.lax.all_gather(busiest, ROW_AXIS))
                # the devices' histograms, merged by count value (a split
                # table is resident: 32-bit keys)
                top = jnp.int32(np.iinfo(np.int32).max)
                mults = jax.lax.all_gather(mults, ROW_AXIS).reshape(-1)
                values = jax.lax.all_gather(values, ROW_AXIS).reshape(-1)
                values, mults, distinct, _, _ = freq_compact(
                    jnp.where(mults > 0, values.astype(jnp.int32), top),
                    mults, hist_slots, top,
                )
            scalars = jnp.stack([
                x.astype(jnp.int64) for x in (
                    groups, singles, rows, distinct, busiest, st.sent_rows,
                    st.lost_groups, st.lost_rows, st.num_rows,
                )
            ])
            return scalars, jnp.stack([values.astype(jnp.int64), mults])

    if sharded:
        from jax.sharding import PartitionSpec as P

        from ..parallel import _shard_map
        from .states import FrequencyTableState

        specs = FrequencyTableState(
            P(), P(), P(), P(ROW_AXIS, None), P(), P(), P(), P(), P()
        )
        body = _shard_map(
            summarize, mesh=mesh, in_specs=(specs,), out_specs=(P(), P())
        )
    else:
        body = summarize

    def freq_drain_summary(state):
        return body(state)

    program = jax.jit(freq_drain_summary)
    _DRAIN_PROGRAMS[key] = program
    return program


@partial(jax.jit, static_argnums=1)
def _hist_prefix(hist, width: int):
    return hist[:, :width]


@dataclass(frozen=True)
class DeviceFrequencyTableScan(ScanShareableAnalyzer):
    """ARBITRARY-cardinality grouping frequencies computed ON DEVICE inside
    the fused pass (ROADMAP item 3: the refactor that kills the host
    ``value_counts`` + hash-partitioned spill default).

    Per batch, each grouping column contributes one 64-bit column key —
    integral/boolean columns mix their raw ``num`` feature through the
    bijective SplitMix64 avalanche ON DEVICE (zero host hashing);
    string/fractional columns ship per-row xxhash64 keys computed by the
    host feature frontend (dictionary columns gather cached per-entry
    hashes). Multi-column sets chain column keys with xxhash64, each key
    seeding the next (the Spark ``XxHash64`` chaining shape), so a combined
    key depends on every column and on column order — and multi-column
    grouping finally leaves the host path. The per-row keys append into the
    state's pow2 buffer at memcpy speed; a sort-merge compaction
    (:func:`deequ_tpu.ops.freq_compact`) folds the buffer into the sorted
    fixed-shape table only when it would overflow, keeping the trace
    shape-static and signature-bundleable.

    On a mesh of ``shards`` devices the buffer has one row per device and
    each device appends only its own rows' keys (``row_split_leaves``); at
    the pass end :meth:`drain` partitions the keys over the devices by
    key range and counts them where they land, so the table is split over
    the chips from the first batch to the summary.

    Tiering: groups beyond the table's ``slots`` capacity are dropped with
    EXACT loss accounting (``lost_rows``); the runner detects a lossy drain
    and re-runs just those grouping sets through the host accumulator
    (whose ``_SpillStore`` is thereby the LAST-RESORT tier instead of the
    default path).

    Runner-internal, like :class:`DeviceFrequencyScan`: the runner
    instantiates it for eligible sets, and the engine drains the state
    into a :class:`FrequencySummary` every member analyzer reads."""

    columns: Tuple[str, ...] = ()
    #: per-column key derivation, positionally parallel to ``columns``:
    #: "num" (device SplitMix64 over the shared numeric feature) or "hash"
    #: (host xxhash64 feature). Part of the frozen identity — it changes
    #: the traced update.
    column_kinds: Tuple[str, ...] = ()
    slots: int = 0
    #: key buffer entries per shard
    buffer_entries: int = 0
    #: RESIDENT mode: the planner proved ``buffer_entries`` covers every
    #: padded batch of the run, so the update emits NO compaction cond —
    #: the hot path is a pure donated-carry append (frozen identity: it
    #: changes the traced program)
    resident: bool = False
    #: devices the key buffer is split over (only a resident buffer is)
    shards: int = 1
    name: str = field(default="DeviceFrequencyTableScan", init=False)

    supports_host_partial = False  # raw keys must stream to the device;
    # on a feed-starved link the runner keeps the set on the host group-by

    @property
    def instance(self) -> str:
        return ",".join(self.columns)

    def scan_program_key(self) -> Tuple:
        # ``resident`` flips ``assume_fits`` inside ``update`` — a traced
        # control-flow difference invisible to state shapes and feature
        # kinds. Without this key a non-resident run whose (slots, buffer)
        # happen to match a cached resident program would execute the
        # cond-free trace and overflow the buffer silently.
        return (self.resident,)

    def row_split_leaves(self) -> Tuple[int, ...]:
        if self.shards == 1:
            return ()
        from dataclasses import fields

        from .states import FrequencyTableState

        return (
            [f.name for f in fields(FrequencyTableState)].index("buf"),
        )

    def feature_specs(self):
        from .base import (
            hash_feature,
            mask_feature,
            numeric_feature,
            rows_feature,
        )

        specs = [rows_feature()]
        for col, kind in zip(self.columns, self.column_kinds):
            specs.append(mask_feature(col))
            specs.append(
                numeric_feature(col) if kind == "num" else hash_feature(col)
            )
        return specs

    def init_state(self):
        from .states import FrequencyTableState

        return FrequencyTableState.init(
            self.slots, self.buffer_entries, self.shards
        )

    def update(self, state, features):
        import jax.numpy as jnp

        from ..ops.hashing import (
            FREQ_KEY_SENTINEL,
            splitmix64_jnp,
            xxhash64_u64_jnp,
        )
        from .base import hash_feature, mask_feature, numeric_feature

        rows = features["rows"]
        valid = rows
        for col in self.columns:
            valid = valid & features[mask_feature(col).key]
        key = None
        for col, kind in zip(self.columns, self.column_kinds):
            if kind == "num":
                # value conversion (not a bitcast — the TPU x64 emulation
                # implements no 64-bit bitcasts): int dtypes wrap modulo
                # 2^64 (bijective per dtype), boolean rides its f64 0/1
                # feature. Masked slots hold arbitrary bytes and are
                # sentinel-keyed below.
                ck = splitmix64_jnp(
                    features[numeric_feature(col).key].astype(jnp.uint64)
                )
            else:
                ck = features[hash_feature(col).key]
            key = ck if key is None else xxhash64_u64_jnp(ck, key)
        sent = jnp.uint64(FREQ_KEY_SENTINEL)
        # a real key colliding with the sentinel would read as a masked row:
        # count those rows exactly instead (they form one group per the
        # bijective single-column mixes; for hashed keys two such groups
        # colliding is a ~2^-64 event) and restore the group at drain time
        is_sent = valid & (key == sent)
        keys = jnp.where(valid & (key != sent), key, sent)
        return state.append_keys(
            keys,
            jnp.sum(is_sent, dtype=jnp.int64),
            jnp.sum(rows, dtype=jnp.int64),
            assume_fits=self.resident,
        )

    def merge(self, a, b):
        return a.merge(b)

    def hist_slots(self) -> int:
        """Slots of the drain's count-of-counts histogram: enough for every
        distinct count value. ``d`` distinct counts need d(d+1)/2 rows, so
        d < sqrt(2 rows), and a resident buffer holds at most one row per
        entry; a compacting table's counts are bounded only by its
        entries."""
        entries = self.shards * self.buffer_entries
        if self.resident:
            return _next_pow2(math.isqrt(2 * entries))
        return _next_pow2(self.slots + entries)

    def drain(self, state, mesh=None):
        """The device half of the drain: dispatch the summary program over
        the pass-end device state (a mesh run's state stays split over
        ``mesh``). Returns device arrays for :meth:`fetch_summary`."""
        return _drain_program(
            mesh, self.shards, self.resident, self.hist_slots()
        )(state)

    def fetch_summary(self, drained) -> Tuple["FrequencySummary", dict]:
        """The host half: the summary's scalars in one transfer, then the
        used part of the count-of-counts histogram in another. Rows whose
        real key collided with the sentinel (``sent_rows``) come back here
        as their own group. Returns the summary and what the drain read
        and moved: ``keys`` (valid keys counted, all devices),
        ``busiest_shard_keys`` and ``fetch_bytes``."""
        scalars, hist = drained
        host = np.asarray(scalars)
        s = dict(zip(_DRAIN_SCALARS, (int(v) for v in host)))
        fetched = host.nbytes
        distinct = s["distinct_counts"]
        values = np.zeros(0, np.int64)
        mults = np.zeros(0, np.int64)
        if distinct:
            part = np.asarray(_hist_prefix(hist, _next_pow2(distinct)))
            fetched += part.nbytes
            values, mults = part[0, :distinct], part[1, :distinct]
        groups, singles, rows = s["groups"], s["singletons"], s["rows"]
        sent = s["sent_rows"]
        if sent:
            groups, singles, rows = groups + 1, singles + (sent == 1), rows + sent
            at = int(np.searchsorted(values, sent))
            if at < len(values) and values[at] == sent:
                mults = mults.copy()
                mults[at] += 1
            else:
                values = np.insert(values, at, sent)
                mults = np.insert(mults, at, 1)
        summary = FrequencySummary(
            groups, singles, rows, values, mults, s["num_rows"],
            self.columns, s["lost_groups"], s["lost_rows"],
        )
        stats = {
            "keys": s["rows"], "busiest_shard_keys": s["busiest_shard_rows"],
            "fetch_bytes": fetched,
        }
        return summary, stats

    def compute_metric_from(self, state):  # pragma: no cover - runner-internal
        raise NotImplementedError(
            "DeviceFrequencyTableScan states convert via drain; the "
            "grouping analyzers sharing the set own the metrics"
        )


def _next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p <<= 1
    return p


# The probe's thresholds — the union-distinct ceiling for confidently
# routing host (~the PERF.md knee / 4: below ~100k distinct the host
# value_counts fast path wins ~3x, above it the device table wins up to
# ~13x), the rows per probe slice, and the row floor below which the
# probe never answers host — are registered tuning knobs
# (freq_host_route_max_distinct / freq_probe_rows /
# freq_host_route_min_rows in tuning/knobs.py) carrying the measured
# dev-box values as static defaults; boot-time calibration re-derives
# them per substrate.


def probably_low_cardinality(
    data, columns: Sequence[str], limit: Optional[int] = None
) -> bool:
    """Cheap pre-routing probe: True when EVERY column of the grouping set
    confidently looks low-cardinality, so the host group-by's
    ``value_counts`` fast path will beat the device frequency table (the
    sweep knee sits ~100k distinct; at 100 distinct the host path is ~3x
    faster). Mirrors the adaptive dictionary-encode probe in
    ``data._maybe_dictionary_encode``: head/mid/tail slices, and a
    clustered/sorted layout — whose later slices keep revealing NEW keys —
    is rejected via cross-slice novelty, because its low per-slice counts
    say nothing about total cardinality. Mis-detection is perf-only and
    asymmetric (a false "device" costs ~3x at tiny cardinalities, a false
    "host" forfeits up to ~13x at scale), so uncertainty answers False."""
    import logging
    import os

    import pyarrow as pa
    import pyarrow.compute as pc

    raw = os.environ.get(FREQ_HOST_ROUTE_ENV)
    if raw is not None and raw not in ("0", "1"):
        if FREQ_HOST_ROUTE_ENV not in _ENV_WARNED:
            _ENV_WARNED.add(FREQ_HOST_ROUTE_ENV)
            logging.getLogger(__name__).warning(
                "ignoring invalid %s=%r (expected 0 or 1); cardinality "
                "pre-routing stays enabled", FREQ_HOST_ROUTE_ENV, raw,
            )
        raw = None
    if raw == "0":
        return False
    from ..tuning import knobs

    if limit is None:
        limit = knobs.value("freq_host_route_max_distinct")
    probe_rows = knobs.value("freq_probe_rows")
    n = int(data.num_rows)
    if n <= knobs.value("freq_host_route_min_rows"):
        return False
    estimate = 1
    for col in columns:
        dictionary = data.dictionary_values(col)
        if dictionary is not None:
            card = len(dictionary)  # exact
        else:
            try:
                column = data.arrow.column(col)
                # disjoint head/mid/tail slices (n > MIN_ROWS >> 3 probes)
                slices = [
                    column.slice(start, probe_rows)
                    for start in (
                        0,
                        (n - probe_rows) // 2,
                        n - probe_rows,
                    )
                ]
                per_slice = [pc.count_distinct(s).as_py() for s in slices]
                union = pc.count_distinct(
                    pa.chunked_array([c for s in slices for c in s.chunks])
                ).as_py()
                if union > 1.5 * max(per_slice):
                    # later slices kept revealing new keys: clustered
                    # high-cardinality layout (or genuinely growing key
                    # space) — not confident, take the device table
                    return False
                card = union
            except Exception:  # noqa: BLE001 - exotic layout: stay on device
                return False
        estimate *= max(card, 1)
        if estimate > limit:
            return False
    return True


def plan_table_scan(
    schema, columns: Sequence[str], num_rows: int, batch_rows: int,
    shards: int = 1,
) -> Optional[DeviceFrequencyTableScan]:
    """Size a DeviceFrequencyTableScan for one grouping set, or None when a
    column's kind cannot derive a 64-bit key.

    Shapes are pow2-bucketed so the compiled-program space stays small and
    warm across runs. When every padded batch of the run fits the key
    buffer (cap :func:`freq_buffer_entries`, default 2^25), the scan runs
    RESIDENT: per-row keys append at memcpy speed with NO compaction cond
    in the trace, and the single drain-time aggregation is exact for ANY
    cardinality up to the buffer. On a mesh of ``shards`` devices
    (``batch_rows`` a multiple of it) a resident buffer splits over them,
    one row of a ``1/shards`` share per device. The cap holds for the rows taken together: the drain's
    exchange sends each device a block as long as a whole row from every
    device, so each device receives and sorts ``shards`` rows, which must
    fit what one device drains. A resident plan gets a minimal table:
    the trace never compacts into it, so full slots would be ~67MB of dead
    HBM per set. Larger runs get the conditional-compaction trace,
    replicated on a mesh: ``slots`` is the configured table capacity
    capped at the row count (distinct <= rows, so a table with slots >=
    rows can NEVER overflow) and the buffer covers at least one padded
    batch so the compaction sort amortizes."""
    from ..data import ColumnKind

    kinds: List[str] = []
    for col in columns:
        kind = schema[col].kind
        if kind in (ColumnKind.INTEGRAL, ColumnKind.BOOLEAN):
            kinds.append("num")
        elif kind in (ColumnKind.FRACTIONAL, ColumnKind.STRING):
            kinds.append("hash")
        else:
            return None
    slots = _next_pow2(
        min(freq_table_slots(), max(int(num_rows), 1024))
    )
    batch_rows = max(int(batch_rows), 1)
    # every batch appends its PADDED length (masked padding rows are
    # sentinel-keyed but still occupy buffer slots), so resident mode must
    # cover ceil(rows/batch) full batches
    padded_rows = -(-max(int(num_rows), 1) // batch_rows) * batch_rows
    # the knob is documented "rounded up to a power of two": compare
    # against the rounded cap so a non-pow2 setting admits exactly the
    # runs its allocated (pow2) buffer can hold
    buffer_cap = _next_pow2(freq_buffer_entries())
    per_shard = _next_pow2(max(padded_rows, batch_rows) // shards)
    if shards * per_shard <= buffer_cap:
        return DeviceFrequencyTableScan(
            tuple(columns), tuple(kinds), 8, per_shard,
            resident=True, shards=shards,
        )
    buffer_entries = _next_pow2(
        max(batch_rows, min(slots, 1 << 20, buffer_cap))
    )
    return DeviceFrequencyTableScan(
        tuple(columns), tuple(kinds), slots, buffer_entries
    )


class GroupingAnalyzer(Analyzer[FrequenciesAndNumRows, DoubleMetric]):
    """Analyzer computed from a shared frequency table."""

    columns: Sequence[str]

    def grouping_columns(self) -> List[str]:
        return list(self.columns)

    @property
    def instance(self) -> str:
        return ",".join(self.grouping_columns())

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN if len(self.grouping_columns()) == 1 else Entity.MULTICOLUMN

    def preconditions(self) -> List[Callable[[Schema], None]]:
        cols = self.grouping_columns()
        out: List[Callable[[Schema], None]] = [Preconditions.at_least_one(cols)]
        for c in cols:
            out.append(Preconditions.has_column(c))
            out.append(Preconditions.is_not_nested(c))
        return out

    def merge(self, a: FrequenciesAndNumRows, b: FrequenciesAndNumRows) -> FrequenciesAndNumRows:
        return a.sum(b)


class ScanShareableFrequencyBasedAnalyzer(GroupingAnalyzer):
    """Base for analyzers that reduce the frequency table to a double
    (reference `GroupingAnalyzers.scala:85-123`)."""

    #: an EMPTY frequency table (e.g. every grouping value null) yields an
    #: empty metric: the reference's SUM aggregation over an empty relation
    #: returns null -> EmptyStateException (`NullHandlingTests.scala`).
    #: CountDistinct overrides this — COUNT over an empty relation is 0.
    empty_frequencies_are_empty_metric: bool = True

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> DoubleMetric:
        if state is None:
            return metric_from_empty(self.name, self.instance, self.entity)
        if self.empty_frequencies_are_empty_metric and state.is_empty():
            return metric_from_empty(self.name, self.instance, self.entity)
        try:
            value = self.metric_from_frequencies(state)
        except Exception as exc:  # noqa: BLE001
            return metric_from_failure(wrap_if_necessary(exc), self.name, self.instance, self.entity)
        if value is None or (isinstance(value, float) and math.isnan(value)):
            return metric_from_empty(self.name, self.instance, self.entity)
        return metric_from_value(float(value), self.name, self.instance, self.entity)

    @abc.abstractmethod
    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        ...


@dataclass(frozen=True)
class Uniqueness(ScanShareableFrequencyBasedAnalyzer):
    """Fraction of rows whose group occurs exactly once: sum(count==1)/numRows
    (reference `analyzers/Uniqueness.scala:26-38`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="Uniqueness", init=False)

    def __init__(self, columns):
        object.__setattr__(self, "columns", _as_tuple(columns))

    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        if state.num_rows == 0:
            return float("nan")
        return float(state.stream_summary()[1]) / state.num_rows


@dataclass(frozen=True)
class Distinctness(ScanShareableFrequencyBasedAnalyzer):
    """Fraction of distinct groups over rows: sum(count>=1)/numRows
    (reference `analyzers/Distinctness.scala:29-41`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="Distinctness", init=False)

    def __init__(self, columns):
        object.__setattr__(self, "columns", _as_tuple(columns))

    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        if state.num_rows == 0:
            return float("nan")
        return float(state.num_distinct()) / state.num_rows


@dataclass(frozen=True)
class UniqueValueRatio(ScanShareableFrequencyBasedAnalyzer):
    """sum(count==1) / number of distinct groups
    (reference `analyzers/UniqueValueRatio.scala:25-44`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="UniqueValueRatio", init=False)

    def __init__(self, columns):
        object.__setattr__(self, "columns", _as_tuple(columns))

    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        num_groups, singletons, _, _ = state.stream_summary()
        if num_groups == 0:
            return float("nan")
        return float(singletons) / num_groups


@dataclass(frozen=True)
class CountDistinct(ScanShareableFrequencyBasedAnalyzer):
    """Number of distinct groups (reference `analyzers/CountDistinct.scala:24-40`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="CountDistinct", init=False)
    empty_frequencies_are_empty_metric = False  # COUNT of no groups is 0.0

    def __init__(self, columns):
        object.__setattr__(self, "columns", _as_tuple(columns))

    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        return float(state.num_distinct())


@dataclass(frozen=True)
class Entropy(ScanShareableFrequencyBasedAnalyzer):
    """Shannon entropy over the value distribution, with N = total row count:
    -sum (c/N) ln(c/N) (reference `analyzers/Entropy.scala:28-42`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="Entropy", init=False)

    def __init__(self, column):
        object.__setattr__(self, "columns", _as_tuple(column))

    def metric_from_frequencies(self, state: FrequenciesAndNumRows) -> float:
        n = state.num_rows
        if n == 0:
            return float("nan")
        # -sum (c/n) ln(c/n) = ln(n) * sum(c)/n - sum(c ln c)/n
        _, _, total, c_ln_c = state.stream_summary()
        return float(math.log(n) * total / n - c_ln_c / n)


@dataclass(frozen=True)
class MutualInformation(GroupingAnalyzer):
    """MI of two columns from the joint frequency table
    (reference `analyzers/MutualInformation.scala:35-103`)."""

    columns: Tuple[str, ...] = ()
    name: str = field(default="MutualInformation", init=False)

    def __init__(self, columns):
        object.__setattr__(self, "columns", _as_tuple(columns))

    @property
    def entity(self) -> Entity:
        return Entity.MULTICOLUMN

    def preconditions(self) -> List[Callable[[Schema], None]]:
        return [Preconditions.exactly_n_columns(self.columns, 2)] + super().preconditions()

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> DoubleMetric:
        if state is None or state.is_empty():
            return metric_from_empty(self.name, self.instance, self.entity)
        try:
            total = state.num_rows
            # two streaming passes over the joint table: marginals first,
            # then the MI sum. Memory = marginal cardinalities (always <=
            # the joint's), so a spilled joint still completes as long as
            # the per-column distinct counts fit in RAM.
            px: Optional[pd.Series] = None
            py: Optional[pd.Series] = None
            for joint in state.iter_merged_chunks():
                cx = joint.groupby(level=0).sum()
                cy = joint.groupby(level=1).sum()
                px = cx if px is None else px.add(cx, fill_value=0)
                py = cy if py is None else py.add(cy, fill_value=0)
            value = 0.0
            for joint in state.iter_merged_chunks():
                pxy = joint.to_numpy(dtype=np.float64) / total
                px_row = px.loc[joint.index.get_level_values(0)].to_numpy(dtype=np.float64) / total
                py_row = py.loc[joint.index.get_level_values(1)].to_numpy(dtype=np.float64) / total
                value += float((pxy * np.log(pxy / (px_row * py_row))).sum())
        except Exception as exc:  # noqa: BLE001
            return metric_from_failure(wrap_if_necessary(exc), self.name, self.instance, self.entity)
        return metric_from_value(value, self.name, self.instance, self.entity)


def _as_tuple(columns) -> Tuple[str, ...]:
    if isinstance(columns, str):
        return (columns,)
    return tuple(columns)


def _java_double_to_string(x: float) -> str:
    """Java ``Double.toString`` semantics: shortest round-trip digits,
    plain decimal for 1e-3 <= |x| < 1e7, otherwise computerized scientific
    notation ``d.dddEn`` (no '+', no leading exponent zeros). Spark's
    cast-to-string on DoubleType delegates to this, so Histogram bin keys
    and suggestion category lists must match it exactly (e.g. 1e7 keys as
    '1.0E7', not '10000000.0')."""
    import math

    if math.isnan(x):
        return "NaN"
    if math.isinf(x):
        return "Infinity" if x > 0 else "-Infinity"
    if x == 0.0:
        return "-0.0" if math.copysign(1.0, x) < 0 else "0.0"
    sign = "-" if x < 0 else ""
    a = abs(x)
    if 1e-3 <= a < 1e7:
        # Python repr is also shortest-round-trip and stays in plain
        # decimal over exactly this range (it switches to sci only below
        # 1e-4 or at/above 1e16), so the strings coincide digit for digit
        return sign + repr(a)
    # normalize shortest-round-trip digits to d.ddd * 10^dec_exp
    r = repr(a)
    if "e" in r:
        mant, _, exp_s = r.partition("e")
        digits = mant.replace(".", "")
        dec_exp = int(exp_s)
    else:
        int_part, _, frac = r.partition(".")
        if int_part != "0":
            digits = (int_part + frac).lstrip("0")
            dec_exp = len(int_part) - 1
        else:
            stripped = frac.lstrip("0")
            digits = stripped
            dec_exp = -(len(frac) - len(stripped) + 1)
    digits = digits.rstrip("0") or "0"
    mantissa = digits[0] + "." + (digits[1:] or "0")
    return f"{sign}{mantissa}E{dec_exp}"


def _spark_string_cast(value) -> str:
    """Format a value the way Spark's cast-to-string would (booleans
    lowercase, doubles via Java ``Double.toString``)."""
    if isinstance(value, (bool, np.bool_)):
        return "true" if value else "false"
    if isinstance(value, (float, np.floating)):
        return _java_double_to_string(float(value))
    if isinstance(value, (int, np.integer)):
        return str(int(value))
    return str(value)


def device_counts_to_histogram_frequencies(
    scan: "DeviceFrequencyScan", state, dictionary: np.ndarray
) -> FrequenciesAndNumRows:
    """Device frequency counts -> the Histogram state shape: keys become
    their Spark string casts and null rows land in the NullValue bin, so
    the resulting FrequenciesAndNumRows is indistinguishable from the host
    accumulator's (merge/persist/metric all behave identically)."""
    counts = np.asarray(state.counts)
    nz = np.flatnonzero(counts)
    keys = [_spark_string_cast(v) for v in np.asarray(dictionary)[nz]]
    series = pd.Series(counts[nz].astype(np.int64), index=keys)
    if series.index.has_duplicates:
        series = series.groupby(level=0, sort=False).sum()
    num_rows = int(state.num_rows)
    series = _with_null_bin(series, num_rows - int(counts.sum()))
    return FrequenciesAndNumRows(series.astype(np.int64), num_rows, [scan.column])


NULL_FIELD_REPLACEMENT = "NullValue"  # reference `analyzers/Histogram.scala:108`
MAXIMUM_ALLOWED_DETAIL_BINS = 1000  # reference `analyzers/Histogram.scala:109`


@dataclass(frozen=True)
class Histogram(Analyzer["FrequenciesAndNumRows", HistogramMetric]):
    """Exact value histogram of one column: values cast to string, nulls
    replaced by "NullValue", optional binning function, top-K detail bins by
    count (reference `analyzers/Histogram.scala:41-116`).

    ``binning_func`` MUST be a pure ``value -> bin`` mapping: it is applied
    once per DISTINCT value, not once per row (the engine counts raw values
    first and bins each distinct key once, turning an O(rows) Python loop
    into O(distinct)). A non-pure or row-position-dependent function would
    silently produce different counts than per-row application; the
    reference's binning UDF (`analyzers/Histogram.scala:63-66`) carries the
    same value-determinism assumption. Returning ``None`` buckets the value
    as "NullValue"."""

    column: str = ""
    binning_func: Optional[Callable] = None
    max_detail_bins: int = MAXIMUM_ALLOWED_DETAIL_BINS
    name: str = field(default="Histogram", init=False)

    @property
    def instance(self) -> str:
        return self.column

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    def preconditions(self) -> List[Callable[[Schema], None]]:
        def param_check(schema: Schema) -> None:
            if self.max_detail_bins > MAXIMUM_ALLOWED_DETAIL_BINS:
                raise IllegalAnalyzerParameterException(
                    f"Cannot return histogram values for more than "
                    f"{MAXIMUM_ALLOWED_DETAIL_BINS} values"
                )

        return [param_check, Preconditions.has_column(self.column)]

    # host accumulation protocol (driven by the runner's single pass)

    def host_init(self) -> FrequenciesAndNumRows:
        return FrequenciesAndNumRows.empty([self.column])

    def _dict_keys(self, col) -> np.ndarray:
        """Spark-string-cast of each DISTINCT dictionary entry, computed
        once per dataset (cached in col.aux across batches)."""
        keys = col.aux.get("hist_keys")
        if keys is None:
            keys = np.array(
                [_spark_string_cast(v) for v in col.dictionary], dtype=object
            )
            col.aux["hist_keys"] = keys
        return keys

    def host_update(self, state: FrequenciesAndNumRows, batch: Batch) -> FrequenciesAndNumRows:
        col = batch.column(self.column)
        mask = batch.row_mask
        if self.binning_func is None and col.has_dictionary and col.codes is not None:
            # dictionary column: one O(rows) code bincount; keys are the
            # cached per-entry Spark string casts — no per-row values at all
            from ..native import native_dict_masked_bincount

            num_cats = col.num_categories
            valid = mask & col.mask
            if native_dict_masked_bincount is not None:
                by_code = native_dict_masked_bincount(col.codes, valid, num_cats)[
                    :num_cats
                ]
            else:
                sel = col.codes[valid]
                by_code = np.bincount(
                    sel[(sel >= 0) & (sel < num_cats)], minlength=num_cats
                )
            n_null = int(np.count_nonzero(mask)) - int(by_code.sum())
            nz = np.flatnonzero(by_code)
            if len(nz):
                keys = self._dict_keys(col)
                counts = (
                    pd.Series(by_code[nz].astype(np.int64), index=keys[nz])
                    .groupby(level=0, sort=False)
                    .sum()
                )
            else:
                counts = pd.Series([], dtype=np.int64)
            counts = _with_null_bin(counts, n_null)
            state._append_run(counts.astype(np.int64))
            state.num_rows += batch.num_rows
            return state
        if (
            self.binning_func is None
            and col.arrow is not None
            and mask.all()
        ):
            # arrow-backed strings: count distincts C-speed without object
            # materialization; string keys are their own Spark-string-cast
            counts = _arrow_value_counts(col.arrow)
            if counts is not None:
                counts = _with_null_bin(counts, int(col.arrow.null_count))
                state._append_run(counts)
                state.num_rows += batch.num_rows
                return state
        values = col.values[mask]
        present = col.mask[mask]
        if self.binning_func is None:
            # vectorized: count raw PRESENT values first (cheap),
            # Spark-string-cast only the distinct keys; nullness comes from
            # the validity mask, never from the value (a genuine float NaN
            # keys as 'NaN' per Java Double.toString, a null as NullValue)
            present_values = values[present]
            if present_values.dtype == object:
                counts = pd.Series(present_values).value_counts(sort=False, dropna=False)
                distinct, cnts = list(counts.index), counts.to_numpy()
            else:
                distinct, cnts = np.unique(present_values, return_counts=True)
            counts = pd.Series(
                cnts, index=[_spark_string_cast(k) for k in distinct], dtype=np.int64
            )
            counts = counts.groupby(level=0, sort=False).sum()
            counts = _with_null_bin(counts, int(len(values) - present.sum()))
        else:
            # bin the DISTINCT values, not every row: the binning function is
            # a pure value->bin mapping (the reference's binning UDF carries
            # the same assumption), so counting raw values first and binning
            # each distinct once turns an O(rows) python loop into
            # O(distinct) — the no-binning path's cost profile
            present_values = values[present]
            if present_values.dtype == object:
                vc = pd.Series(present_values).value_counts(sort=False, dropna=False)
                distinct, cnts = list(vc.index), vc.to_numpy()
            else:
                distinct, cnts = np.unique(present_values, return_counts=True)
            keys = []
            for v in distinct:
                b = self.binning_func(v)
                keys.append(
                    _spark_string_cast(b) if b is not None else NULL_FIELD_REPLACEMENT
                )
            counts = (
                pd.Series(cnts, index=keys, dtype=np.int64)
                .groupby(level=0, sort=False)
                .sum()
            )
            counts = _with_null_bin(counts, int(len(values) - present.sum()))
        state._append_run(counts.astype(np.int64))
        state.num_rows += batch.num_rows
        return state

    def merge(self, a: FrequenciesAndNumRows, b: FrequenciesAndNumRows) -> FrequenciesAndNumRows:
        return a.sum(b)

    def compute_metric_from(self, state: Optional[FrequenciesAndNumRows]) -> HistogramMetric:
        if state is None:
            from ..exceptions import EmptyStateException

            return HistogramMetric(
                self.entity,
                self.name,
                self.instance,
                Failure(EmptyStateException(f"Empty state for analyzer {self}")),
                self.column,
            )
        try:
            bin_count = 0
            top: Optional[pd.Series] = None
            for chunk in state.iter_merged_chunks():
                bin_count += len(chunk)
                cand = chunk.nlargest(self.max_detail_bins)
                top = cand if top is None else pd.concat([top, cand]).nlargest(
                    self.max_detail_bins
                )
            if top is None:
                top = pd.Series([], dtype=np.int64)
            values = {
                str(k): DistributionValue(int(v), int(v) / state.num_rows)
                for k, v in top.items()
            }
            dist = Distribution(values, number_of_bins=bin_count)
            return HistogramMetric(self.entity, self.name, self.instance, Success(dist), self.column)
        except Exception as exc:  # noqa: BLE001
            return HistogramMetric(
                self.entity, self.name, self.instance, Failure(wrap_if_necessary(exc)), self.column
            )

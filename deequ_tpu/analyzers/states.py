"""Analyzer states: fixed-shape array pytrees with semigroup merge.

Each state mirrors a reference state class (`analyzers/*.scala`) but is a
flax.struct dataclass of jax scalars/arrays, so it is jit-able, donate-able,
collectively-mergeable over a mesh, and trivially serializable — the property
the reference gets from raw agg byte-buffers (`analyzers/StateProvider.scala:
187-241`).
"""

from __future__ import annotations

from typing import Any

import flax.struct
import jax.numpy as jnp
import numpy as np

from ..config import ACC_DTYPE, COUNT_DTYPE


def _f(x: float) -> jnp.ndarray:
    return jnp.asarray(x, dtype=ACC_DTYPE)


def _i(x: int) -> jnp.ndarray:
    return jnp.asarray(x, dtype=COUNT_DTYPE)


def min_nan_largest(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pairwise min under Spark's NaN-largest total order (reals < +inf <
    NaN): NaN never wins, making it the identity — and the init value — of
    MinState. The single definition serves both the device update path
    (analyzers/simple.py) and state merges, so the two cannot drift."""
    return jnp.where(jnp.isnan(a), b, jnp.where(jnp.isnan(b), a, jnp.minimum(a, b)))


@flax.struct.dataclass
class FrequencyCountsState:
    """Dense per-category counts for the device frequency path (dictionary-
    encoded grouping columns): counts[i] = rows whose code is i, plus the
    total row count the frequency semantics require (reference
    `GroupingAnalyzers.scala:53-80`: numRows counts ALL rows)."""

    counts: jnp.ndarray    # int64[num_categories]
    num_rows: jnp.ndarray  # int64

    @staticmethod
    def init(num_categories: int) -> "FrequencyCountsState":
        return FrequencyCountsState(
            jnp.zeros(num_categories, dtype=COUNT_DTYPE), _i(0)
        )

    def merge(self, other: "FrequencyCountsState") -> "FrequencyCountsState":
        return FrequencyCountsState(
            self.counts + other.counts, self.num_rows + other.num_rows
        )


@flax.struct.dataclass
class FrequencyTableState:
    """Device-resident frequency engine state for ARBITRARY-cardinality
    grouping sets (the dense ``FrequencyCountsState`` covers only small
    dictionary code spaces): a sorted fixed-shape (key, count) table plus a
    raw append buffer of per-row 64-bit group keys, all pow2-shaped so the
    trace stays shape-static and signature-bundleable.

    Tiering (ROADMAP item 3): per-batch folds APPEND hashed keys to ``buf``
    (a memcpy-speed ``dynamic_update_slice`` — no scatter, no sort on the
    hot path); when the buffer would overflow, an in-trace sort-merge
    compaction (:func:`deequ_tpu.ops.freq_compact`) folds it into the
    sorted table of ``slots`` uniques; groups that overflow even the table
    are counted exactly into ``lost_groups``/``lost_rows`` and the runner
    re-runs those grouping sets through the host accumulator (whose
    ``_SpillStore`` is thereby the LAST-RESORT tier instead of the default
    path). ``sent_rows`` counts rows whose mixed key collided with the
    sentinel — they form exactly one group, restored at drain time, so the
    bijective single-column mixes stay collision-free end to end.

    Merging (cross-batch, cross-device ``collective_merge_states``,
    cross-run) is the same compaction over both operands' tables and
    buffers — the frequency analog of the reference's outer-join merge
    (`GroupingAnalyzers.scala:128-148`) without ever leaving the device."""

    sorted_keys: jnp.ndarray    # uint64[slots], ascending, sentinel-padded
    sorted_counts: jnp.ndarray  # int64[slots]
    n_table: jnp.ndarray        # int64: occupied table entries
    buf: jnp.ndarray            # uint64[buffer_entries] raw per-row keys
    buf_fill: jnp.ndarray       # int64: appended entries (rows incl. masked)
    sent_rows: jnp.ndarray      # int64: rows whose key collided w/ sentinel
    lost_groups: jnp.ndarray    # int64: groups dropped at compactions (an
    #   upper bound: a group re-appearing after a drop counts again)
    lost_rows: jnp.ndarray      # int64: rows inside dropped groups (EXACT:
    #   any nonzero value routes the set to the host last-resort tier)
    num_rows: jnp.ndarray       # int64: ALL rows seen (grouping semantics)

    @staticmethod
    def init(slots: int, buffer_entries: int) -> "FrequencyTableState":
        from ..ops.hashing import FREQ_KEY_SENTINEL

        return FrequencyTableState(
            jnp.full(slots, FREQ_KEY_SENTINEL, dtype=jnp.uint64),
            jnp.zeros(slots, dtype=jnp.int64),
            jnp.zeros((), dtype=jnp.int64),
            jnp.zeros(buffer_entries, dtype=jnp.uint64),
            jnp.zeros((), dtype=jnp.int64),
            jnp.zeros((), dtype=jnp.int64),
            jnp.zeros((), dtype=jnp.int64),
            jnp.zeros((), dtype=jnp.int64),
            jnp.zeros((), dtype=jnp.int64),
        )

    def compacted(self) -> "FrequencyTableState":
        """Fold the raw buffer into the sorted table (buffer becomes
        empty); traced — both the in-pass overflow branch and ``merge``
        ride this."""
        from ..ops import freq_compact
        from ..ops.hashing import FREQ_KEY_SENTINEL

        sent = jnp.uint64(FREQ_KEY_SENTINEL)
        cap = self.buf.shape[0]
        slots = self.sorted_keys.shape[0]
        idx = jnp.arange(cap, dtype=jnp.int64)
        bkeys = jnp.where(idx < self.buf_fill, self.buf, sent)
        bcounts = (bkeys != sent).astype(jnp.int64)
        out_keys, out_counts, n_raw, kept, total = freq_compact(
            jnp.concatenate([self.sorted_keys, bkeys]),
            jnp.concatenate([self.sorted_counts, bcounts]),
            slots, sent,
        )
        return FrequencyTableState(
            out_keys, out_counts, jnp.minimum(n_raw, slots),
            jnp.zeros_like(self.buf), jnp.zeros_like(self.buf_fill),
            self.sent_rows,
            self.lost_groups + jnp.maximum(n_raw - slots, 0),
            self.lost_rows + (total - kept),
            self.num_rows,
        )

    def append_keys(
        self,
        keys: jnp.ndarray,
        n_sent: jnp.ndarray,
        n_rows: jnp.ndarray,
        assume_fits: bool = False,
    ) -> "FrequencyTableState":
        """Fold one batch of per-row group keys into the state (traced; the
        analyzer ``update``'s whole body). ``keys`` already carries the
        sentinel at masked/null positions AND at valid rows whose real key
        collided with it (those are counted via ``n_sent`` instead). The
        hot path is one memcpy-speed ``dynamic_update_slice`` append — no
        scatter, no sort.

        ``assume_fits=True`` is the RESIDENT trace: the planner proved the
        buffer covers every padded batch of the run, so no ``lax.cond`` is
        emitted at all — measured on CPU XLA the cond region forces the
        256MB buffer through region copies at ~0.4s/batch where the plain
        donated-carry append runs at memcpy speed (>250M rows/s). The
        conditional-compaction trace remains for runs whose rows exceed the
        buffer; its sort cost amortizes over ``buffer_entries / batch``
        batches."""
        import jax

        batch = keys.shape[0]
        cap = self.buf.shape[0]
        if batch > cap:
            raise ValueError(
                f"frequency-table buffer holds {cap} entries but the batch "
                f"carries {batch} rows; size buffer_entries >= the padded "
                "batch size (the runner guarantees this)"
            )

        def just_append(st: "FrequencyTableState") -> "FrequencyTableState":
            buf = jax.lax.dynamic_update_slice(st.buf, keys, (st.buf_fill,))
            return st.replace(buf=buf, buf_fill=st.buf_fill + batch)

        if assume_fits:
            appended = just_append(self)
        else:
            appended = jax.lax.cond(
                self.buf_fill + batch <= cap,
                just_append,
                lambda st: just_append(st.compacted()),
                self,
            )
        return appended.replace(
            sent_rows=appended.sent_rows + n_sent,
            num_rows=appended.num_rows + n_rows,
        )

    def merge(self, other: "FrequencyTableState") -> "FrequencyTableState":
        from ..ops import freq_compact
        from ..ops.hashing import FREQ_KEY_SENTINEL

        sent = jnp.uint64(FREQ_KEY_SENTINEL)
        a = self.compacted()
        b = other.compacted()
        slots = a.sorted_keys.shape[0]
        out_keys, out_counts, n_raw, kept, total = freq_compact(
            jnp.concatenate([a.sorted_keys, b.sorted_keys]),
            jnp.concatenate([a.sorted_counts, b.sorted_counts]),
            slots, sent,
        )
        return FrequencyTableState(
            out_keys, out_counts, jnp.minimum(n_raw, slots),
            jnp.zeros_like(a.buf), jnp.zeros_like(a.buf_fill),
            a.sent_rows + b.sent_rows,
            a.lost_groups + b.lost_groups + jnp.maximum(n_raw - slots, 0),
            a.lost_rows + b.lost_rows + (total - kept),
            a.num_rows + b.num_rows,
        )


@flax.struct.dataclass
class NumMatches:
    """Row-count state (reference `analyzers/Size.scala:23-29`)."""

    num_matches: jnp.ndarray

    @staticmethod
    def init() -> "NumMatches":
        return NumMatches(_i(0))

    def merge(self, other: "NumMatches") -> "NumMatches":
        return NumMatches(self.num_matches + other.num_matches)

    def metric_value(self) -> float:
        return float(self.num_matches)


@flax.struct.dataclass
class NumMatchesAndCount:
    """Ratio state (reference `analyzers/Analyzer.scala:438-449`)."""

    num_matches: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def init() -> "NumMatchesAndCount":
        return NumMatchesAndCount(_i(0), _i(0))

    def merge(self, other: "NumMatchesAndCount") -> "NumMatchesAndCount":
        return NumMatchesAndCount(
            self.num_matches + other.num_matches, self.count + other.count
        )

    def metric_value(self) -> float:
        count = float(self.count)
        if count == 0:
            return float("nan")
        return float(self.num_matches) / count


@flax.struct.dataclass
class MeanState:
    """(sum, count) (reference `analyzers/Mean.scala:25-35`)."""

    total: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def init() -> "MeanState":
        return MeanState(_f(0.0), _i(0))

    def merge(self, other: "MeanState") -> "MeanState":
        return MeanState(self.total + other.total, self.count + other.count)

    def metric_value(self) -> float:
        count = float(self.count)
        if count == 0:
            return float("nan")
        return float(self.total) / count


@flax.struct.dataclass
class SumState:
    """(sum) plus a count used only for emptiness detection
    (reference `analyzers/Sum.scala:25-33`)."""

    total: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def init() -> "SumState":
        return SumState(_f(0.0), _i(0))

    def merge(self, other: "SumState") -> "SumState":
        return SumState(self.total + other.total, self.count + other.count)

    def metric_value(self) -> float:
        return float(self.total)


@flax.struct.dataclass
class MinState:
    """(reference `analyzers/Minimum.scala:25-33`)."""

    min_value: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def init() -> "MinState":
        # NaN is the identity (top) element of the NaN-largest min order the
        # reference uses (Spark TypeUtils: reals < +inf < NaN); see
        # `min_nan_largest` below
        return MinState(_f(np.nan), _i(0))

    def merge(self, other: "MinState") -> "MinState":
        return MinState(
            min_nan_largest(self.min_value, other.min_value),
            self.count + other.count,
        )

    def metric_value(self) -> float:
        return float(self.min_value)


@flax.struct.dataclass
class MaxState:
    """(reference `analyzers/Maximum.scala:25-33`)."""

    max_value: jnp.ndarray
    count: jnp.ndarray

    @staticmethod
    def init() -> "MaxState":
        return MaxState(_f(-np.inf), _i(0))

    def merge(self, other: "MaxState") -> "MaxState":
        return MaxState(jnp.maximum(self.max_value, other.max_value), self.count + other.count)

    def metric_value(self) -> float:
        return float(self.max_value)


@flax.struct.dataclass
class StandardDeviationState:
    """Welford/Chan parallel-merge moments (n, avg, m2)
    (reference `analyzers/StandardDeviation.scala:25-50`)."""

    n: jnp.ndarray
    avg: jnp.ndarray
    m2: jnp.ndarray

    @staticmethod
    def init() -> "StandardDeviationState":
        return StandardDeviationState(_f(0.0), _f(0.0), _f(0.0))

    def merge(self, other: "StandardDeviationState") -> "StandardDeviationState":
        n = self.n + other.n
        safe_n = jnp.where(n == 0, 1.0, n)
        delta = other.avg - self.avg
        avg = jnp.where(n == 0, 0.0, (self.avg * self.n + other.avg * other.n) / safe_n)
        m2 = self.m2 + other.m2 + delta * delta * self.n * other.n / safe_n
        return StandardDeviationState(n, avg, jnp.where(n == 0, 0.0, m2))

    def metric_value(self) -> float:
        # host math only: a jnp op on a fetched numpy state would dispatch a
        # device program (a launch and a fetch per metric)
        n = float(self.n)
        if n == 0:
            return float("nan")
        return float(np.sqrt(float(self.m2) / n))


@flax.struct.dataclass
class CorrelationState:
    """Pairwise co-moment accumulators (n, xAvg, yAvg, ck, xMk, yMk)
    (reference `analyzers/Correlation.scala:26-60`)."""

    n: jnp.ndarray
    x_avg: jnp.ndarray
    y_avg: jnp.ndarray
    ck: jnp.ndarray
    x_mk: jnp.ndarray
    y_mk: jnp.ndarray

    @staticmethod
    def init() -> "CorrelationState":
        # distinct arrays: a shared buffer would be donated twice under jit
        return CorrelationState(_f(0.0), _f(0.0), _f(0.0), _f(0.0), _f(0.0), _f(0.0))

    def merge(self, other: "CorrelationState") -> "CorrelationState":
        n = self.n + other.n
        safe_n = jnp.where(n == 0, 1.0, n)
        dx = other.x_avg - self.x_avg
        dy = other.y_avg - self.y_avg
        frac = self.n * other.n / safe_n
        x_avg = jnp.where(n == 0, 0.0, (self.x_avg * self.n + other.x_avg * other.n) / safe_n)
        y_avg = jnp.where(n == 0, 0.0, (self.y_avg * self.n + other.y_avg * other.n) / safe_n)
        ck = self.ck + other.ck + dx * dy * frac
        x_mk = self.x_mk + other.x_mk + dx * dx * frac
        y_mk = self.y_mk + other.y_mk + dy * dy * frac
        return CorrelationState(
            n, x_avg, y_avg, jnp.where(n == 0, 0.0, ck), jnp.where(n == 0, 0.0, x_mk),
            jnp.where(n == 0, 0.0, y_mk)
        )

    def metric_value(self) -> float:
        if float(self.n) == 0:
            return float("nan")
        with np.errstate(divide="ignore", invalid="ignore"):
            return float(
                float(self.ck) / np.sqrt(float(self.x_mk) * float(self.y_mk))
            )


@flax.struct.dataclass
class DataTypeHistogram:
    """Counts of inferred value types [null, fractional, integral, boolean,
    string] (reference `analyzers/DataType.scala:32-96`)."""

    counts: jnp.ndarray  # int64[5]

    NULL_POS: int = flax.struct.field(pytree_node=False, default=0)

    @staticmethod
    def init() -> "DataTypeHistogram":
        return DataTypeHistogram(jnp.zeros(5, dtype=COUNT_DTYPE))

    def merge(self, other: "DataTypeHistogram") -> "DataTypeHistogram":
        return DataTypeHistogram(self.counts + other.counts)


@flax.struct.dataclass
class ApproxCountDistinctState:
    """HLL++ registers, unpacked int32[512] (reference packs them into 52
    longs, `analyzers/ApproxCountDistinct.scala:26-40`; see
    `deequ_tpu/ops/hll.py` for the packed-format converters)."""

    registers: jnp.ndarray  # int32[512]

    @staticmethod
    def init() -> "ApproxCountDistinctState":
        from ..ops.hll import M

        return ApproxCountDistinctState(jnp.zeros(M, dtype=jnp.int32))

    def merge(self, other: "ApproxCountDistinctState") -> "ApproxCountDistinctState":
        return ApproxCountDistinctState(jnp.maximum(self.registers, other.registers))

    def metric_value(self) -> float:
        from ..ops.hll import estimate_cardinality

        return estimate_cardinality(np.asarray(self.registers))


def to_host(state: Any) -> Any:
    """Bring a device state pytree back as numpy (for persistence/finalize).
    Uses device_get so all leaves copy in one batched round-trip."""
    import jax

    return jax.device_get(state)


#: State classes whose semigroup merge with the IDENTITY state is
#: bit-TRANSPARENT: ``merge(init(), s) == s`` leaf-for-leaf at the bit
#: level, by construction of the merge formula —
#:
#: - integer adds against 0 (NumMatches, NumMatchesAndCount,
#:   DataTypeHistogram, FrequencyCountsState counts/num_rows) are exact;
#: - float adds against +0.0 (MeanState/SumState totals) return the other
#:   operand's bits for every finite/NaN value;
#: - ``min_nan_largest(NaN, x) == x`` and ``max(-inf, x) == x`` exactly
#:   (MinState/MaxState);
#: - elementwise ``maximum(0, registers) == registers`` for the
#:   non-negative HLL registers (ApproxCountDistinctState).
#:
#: The streaming fast path (service.coalesce) relies on this: a
#: micro-batch's host-kernel partial IS the batch's folded state — no
#: identity fold needs to run, on host or device — and merging it into the
#: session's persisted states reproduces the engine host tier bit-exactly.
#: StandardDeviationState / CorrelationState are deliberately ABSENT: their
#: merges recompute ``avg = (avg*n)/n`` against the identity, which rounds
#: for ~10% of doubles (measured), so those states must fold through a real
#: program — the crossover router sends their batteries to the coalesced
#: device path instead.
IDENTITY_TRANSPARENT_STATES = frozenset({
    NumMatches,
    NumMatchesAndCount,
    MeanState,
    SumState,
    MinState,
    MaxState,
    DataTypeHistogram,
    ApproxCountDistinctState,
    FrequencyCountsState,
})


def identity_merge_transparent(state_cls: type) -> bool:
    """Whether ``merge(init(), s)`` provably returns ``s``'s exact bits for
    this state class (see :data:`IDENTITY_TRANSPARENT_STATES`)."""
    return state_cls in IDENTITY_TRANSPARENT_STATES


def _np(x) -> np.ndarray:
    # np.asarray is zero-copy for numpy leaves and completes the transfer
    # for the occasional device-resident leaf a mixed history left behind
    return np.asarray(x)


def host_merge(a: Any, b: Any) -> Any:
    """Device-free semigroup merge for the IDENTITY-TRANSPARENT state
    classes: the same formulas as each class's jnp ``merge``, evaluated
    with numpy on host leaves — every operation is a single IEEE scalar
    (or elementwise integer) op, so the result is bit-identical to the
    compiled merge, with ZERO device dispatches. This is the streaming
    fast path's merge: at thousands of folds per second the jit-dispatch
    + device_get round trip of `merge_states_batched` was ~40% of the
    whole fold (measured), for states that are a handful of scalars.

    Raises ``TypeError`` for classes outside the transparent set — their
    merges (Welford/co-moment recombinations) are only bit-reproducible
    through the one compiled program every path shares."""
    cls = type(a)
    if cls is not type(b):
        raise TypeError(f"cannot host-merge {cls.__name__} with {type(b).__name__}")
    if cls is NumMatches:
        return NumMatches(_np(a.num_matches) + _np(b.num_matches))
    if cls is NumMatchesAndCount:
        return NumMatchesAndCount(
            _np(a.num_matches) + _np(b.num_matches),
            _np(a.count) + _np(b.count),
        )
    if cls is MeanState:
        return MeanState(
            _np(a.total) + _np(b.total), _np(a.count) + _np(b.count)
        )
    if cls is SumState:
        return SumState(
            _np(a.total) + _np(b.total), _np(a.count) + _np(b.count)
        )
    if cls is MinState:
        av, bv = _np(a.min_value), _np(b.min_value)
        # NaN-largest order, the same branch structure as min_nan_largest
        mn = bv if np.isnan(av) else (av if np.isnan(bv) else np.minimum(av, bv))
        return MinState(mn, _np(a.count) + _np(b.count))
    if cls is MaxState:
        return MaxState(
            np.maximum(_np(a.max_value), _np(b.max_value)),
            _np(a.count) + _np(b.count),
        )
    if cls is DataTypeHistogram:
        return DataTypeHistogram(_np(a.counts) + _np(b.counts))
    if cls is ApproxCountDistinctState:
        return ApproxCountDistinctState(
            np.maximum(_np(a.registers), _np(b.registers))
        )
    if cls is FrequencyCountsState:
        return FrequencyCountsState(
            _np(a.counts) + _np(b.counts),
            _np(a.num_rows) + _np(b.num_rows),
        )
    raise TypeError(
        f"{cls.__name__} is not identity-merge transparent; merge it "
        "through the compiled path"
    )

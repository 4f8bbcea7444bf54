"""Analyzer protocol: the core algebra of the engine.

An analyzer is a pair of functions ``computeStateFrom: Data -> S`` and
``computeMetricFrom: S -> M`` where ``S`` is a commutative-semigroup state
(reference `analyzers/Analyzer.scala:34-53`). On TPU a state is a pytree of
fixed-shape jax arrays; ``update`` consumes a whole column *batch* (vectorized,
never per-row) and ``merge`` is the semigroup sum used for cross-batch,
cross-device (psum-style collectives) and cross-run (incremental) merges.

Scan-sharing (reference `ScanShareableAnalyzer`, `analyzers/Analyzer.scala:
169-197`): N analyzers contribute their feature requirements; the runner
computes the union of features once per batch and calls one fused jit'd update
for all analyzers — fusion is done by XLA instead of Spark aggregate offsets.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Generic, List, Optional, Sequence, Tuple, TypeVar

import jax.numpy as jnp
import numpy as np

from ..data import ColumnKind, Schema
from ..expr import Predicate
from ..metrics import (
    DoubleMetric,
    Entity,
    Failure,
    Metric,
    metric_from_empty,
    metric_from_failure,
    metric_from_value,
)
from ..exceptions import (
    MetricCalculationException,
    NoColumnsSpecifiedException,
    NoSuchColumnException,
    NumberOfSpecifiedColumnsException,
    WrongColumnTypeException,
    wrap_if_necessary,
)

S = TypeVar("S")
M = TypeVar("M", bound=Metric)


# ---------------------------------------------------------------------------
# Feature specs: what a scan-shareable analyzer needs per batch on device.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FeatureSpec:
    """A named, device-resident numeric array derived from the batch.

    ``kind`` selects the host computation (see `runners/features.py`);
    ``payload`` carries a predicate (str or callable) or regex pattern.
    ``key`` is the stable string under which the array appears in the
    features dict handed to the fused jit'd update.
    """

    kind: str
    column: Optional[str] = None
    payload: Any = None

    @property
    def key(self) -> str:
        parts = [self.kind]
        if self.column is not None:
            parts.append(self.column)
        if self.payload is not None:
            parts.append(
                self.payload if isinstance(self.payload, str) else f"callable:{id(self.payload)}"
            )
        return ":".join(parts)


def rows_feature() -> FeatureSpec:
    return FeatureSpec("rows")


def numeric_feature(column: str) -> FeatureSpec:
    return FeatureSpec("num", column)


def mask_feature(column: str) -> FeatureSpec:
    return FeatureSpec("mask", column)


def length_feature(column: str) -> FeatureSpec:
    return FeatureSpec("len", column)


def predicate_feature(predicate: Predicate) -> FeatureSpec:
    return FeatureSpec("pred", None, predicate)


def regex_feature(column: str, pattern: str) -> FeatureSpec:
    return FeatureSpec("match", column, pattern)


def hash_feature(column: str) -> FeatureSpec:
    return FeatureSpec("hash", column)


def hll_feature(column: str) -> FeatureSpec:
    """(2, B) int32 (register index, leading-zero count) pairs for HLL++."""
    return FeatureSpec("hll", column)


def typeclass_feature(column: str) -> FeatureSpec:
    return FeatureSpec("type", column)


def codes_feature(column: str) -> FeatureSpec:
    """int32 dictionary codes of an encoded column (nulls/padding coded
    out-of-range) — the device frequency path's input."""
    return FeatureSpec("codes", column)


# ---------------------------------------------------------------------------
# Preconditions (reference `analyzers/Analyzer.scala:285-359`)
# ---------------------------------------------------------------------------


class Preconditions:
    @staticmethod
    def has_column(column: str) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            if column not in schema:
                raise NoSuchColumnException(f"Input data does not include column {column}!")

        return check

    @staticmethod
    def is_numeric(column: str) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            kind = schema[column].kind
            if not (kind.is_numeric or kind == ColumnKind.BOOLEAN):
                raise WrongColumnTypeException(
                    f"Expected type of column {column} to be numeric, but found {kind.value}!"
                )

        return check

    @staticmethod
    def is_string(column: str) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            if schema[column].kind != ColumnKind.STRING:
                raise WrongColumnTypeException(
                    f"Expected type of column {column} to be string, but found "
                    f"{schema[column].kind.value}!"
                )

        return check

    @staticmethod
    def is_not_nested(column: str) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            if schema[column].kind == ColumnKind.UNKNOWN:
                raise WrongColumnTypeException(
                    f"Unsupported nested column type of column {column}!"
                )

        return check

    @staticmethod
    def at_least_one(columns: Sequence[str]) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            if len(columns) == 0:
                raise NoColumnsSpecifiedException("At least one column needs to be specified!")

        return check

    @staticmethod
    def exactly_n_columns(columns: Sequence[str], n: int) -> Callable[[Schema], None]:
        def check(schema: Schema) -> None:
            if len(columns) != n:
                raise NumberOfSpecifiedColumnsException(
                    f"{n} columns have to be specified! Currently, columns contains only "
                    f"{len(columns)} column(s): {','.join(columns)}!"
                )

        return check

    @staticmethod
    def find_first_failing(
        schema: Schema, conditions: Sequence[Callable[[Schema], None]]
    ) -> Optional[MetricCalculationException]:
        for condition in conditions:
            try:
                condition(schema)
            except MetricCalculationException as exc:
                return exc
            except Exception as exc:  # noqa: BLE001
                return wrap_if_necessary(exc)
        return None


# ---------------------------------------------------------------------------
# Analyzer base classes
# ---------------------------------------------------------------------------


class Analyzer(abc.ABC, Generic[S, M]):
    """Base analyzer. Subclasses are frozen dataclasses, hashable for dedupe
    (reference dedupes analyzers against repository results,
    `AnalysisRunner.scala:116-134`)."""

    name: str = "Analyzer"

    @property
    def instance(self) -> str:
        return "*"

    @property
    def entity(self) -> Entity:
        return Entity.DATASET

    def preconditions(self) -> List[Callable[[Schema], None]]:
        return []

    @abc.abstractmethod
    def compute_metric_from(self, state: Optional[S]) -> M:
        ...

    def to_failure_metric(self, exception: BaseException) -> DoubleMetric:
        return metric_from_failure(
            wrap_if_necessary(exception), self.name, self.instance, self.entity
        )

    # semigroup ops on host-side states -------------------------------------

    def merge_states(self, a: Optional[S], b: Optional[S]) -> Optional[S]:
        """None-tolerant semigroup sum (reference `Analyzers.merge`,
        `analyzers/Analyzer.scala:361-372`)."""
        if a is None:
            return b
        if b is None:
            return a
        return self.merge(a, b)

    def merge(self, a: S, b: S) -> S:  # pragma: no cover - overridden
        raise NotImplementedError

    # slim state fetch -------------------------------------------------------

    def metric_leaves(self) -> Optional[Sequence[int]]:
        """Indices (into the flattened state pytree, ``tree_flatten`` order)
        of the leaves ``compute_metric_from`` actually reads, or ``None``
        when every leaf is metric-bearing (the safe default).

        The engine's slim fetch uses this on runs that neither persist nor
        aggregate states: only the named leaves cross the device feed link;
        the rest are reconstructed host-side from ``init_state`` identity
        values the metric never touches. An analyzer overriding this
        GUARANTEES its metric (and ``is_empty``) never read an excluded
        leaf."""
        return None


#: jit'd per-analyzer state-fold programs, keyed by (analyzer, shard count);
#: bounded LRU so a long-lived service cycling through many analyzer
#: identities / partition counts cannot grow it without limit, while hot
#: keys stay resident
from ..utils import BoundedLRU

_MERGE_FOLD_CACHE = BoundedLRU(256)


def merge_states_batched(analyzer: "Analyzer", states: Sequence[Any]) -> Optional[Any]:
    """Fold many states with the analyzer's semigroup ``merge`` in ONE
    compiled program (a lax.scan over the stacked state pytrees) instead of
    dispatching each merge's ops eagerly — an eager KLL merge alone costs
    ~100 dispatches, each with its own launch latency. States that are not
    array pytrees (e.g. frequency tables) fold sequentially on the host.
    Result order equals the left-to-right sequential fold. (A log-depth
    tree of VMAPPED pairwise merges was measured 4x SLOWER for KLL states
    on a v5e chip — the compaction cascade's dynamic_update_slices lower to
    gathers under vmap — so the sequential scan stays; see PERF.md.)"""
    states = [s for s in states if s is not None]
    if not states:
        return None
    if len(states) == 1:
        return states[0]
    import jax

    def _leaf_sig(leaf):
        # metadata only — np.asarray on an ARRAY leaf would force a blocking
        # D2H copy of every leaf of every state before the fold dispatches;
        # python-scalar leaves (no .dtype) are host values, cheap to probe
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            a = np.asarray(leaf)
            return (a.shape, a.dtype)
        return (getattr(leaf, "shape", ()), np.dtype(dt))

    leaves, treedef = jax.tree_util.tree_flatten(states[0])
    array_like = bool(leaves) and all(
        hasattr(leaf, "dtype") and getattr(leaf, "dtype", None) != object
        for leaf in leaves
    )
    if array_like:
        # States persisted under different layouts (e.g. KLL sketches saved
        # before a capacity widening, or differing level counts) share a
        # treedef but not leaf shapes; np.stack would raise mid-fold. Require
        # identical leaf shapes AND dtypes, else fall back to the sequential
        # analyzer.merge fold, which handles heterogeneous states.
        sig = [_leaf_sig(leaf) for leaf in leaves]
        for s in states[1:]:
            other_leaves, other_treedef = jax.tree_util.tree_flatten(s)
            if other_treedef != treedef or [
                _leaf_sig(leaf) for leaf in other_leaves
            ] != sig:
                array_like = False
                break
    if not array_like:
        merged = states[0]
        for s in states[1:]:
            merged = analyzer.merge(merged, s)
        return merged

    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *states
    )
    key = (analyzer, len(states))
    program = _MERGE_FOLD_CACHE.get(key)
    if program is None:
        def fold(stacked_states):
            first = jax.tree_util.tree_map(lambda x: x[0], stacked_states)
            rest = jax.tree_util.tree_map(lambda x: x[1:], stacked_states)

            def body(acc, s):
                return analyzer.merge(acc, s), None

            out, _ = jax.lax.scan(body, first, rest)
            return out

        # donate the stacked input: it is a freshly built host stack (never
        # re-read), so the fold's working buffers alias the transferred
        # copy instead of duplicating it — one fewer state-sized copy per
        # fold on the streaming plane's load->merge->persist cycle
        program = jax.jit(fold, donate_argnums=0)
        _MERGE_FOLD_CACHE[key] = program
        import warnings

        with warnings.catch_warnings():
            # first call traces+compiles: leaves whose scan carry changes
            # layout report their donated buffer as unusable — expected
            # (the donation exists for the large array leaves)
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return jax.device_get(program(stacked))
    return jax.device_get(program(stacked))


class HostBatchContext:
    """Per-batch helper for the host ingest tier: caches predicate masks so
    N analyzers sharing a `where` filter evaluate it once (the
    `conditionalSelection` analog on the host side).

    ``run_token`` identifies the enclosing PASS (one ScanEngine run): host
    partials whose cross-batch skip caches live in the per-dataset
    ``Column.aux`` dict key their entries on it, so a second pass over the
    same dataset never reuses skip state from an earlier pass (which would
    silently drop its contribution). ``None`` disables such caches."""

    def __init__(self, batch, batch_index: int = 0, run_token=None):
        self.batch = batch
        self.batch_index = batch_index
        self.run_token = run_token
        self._pred_cache: Dict[str, np.ndarray] = {}
        self._pred_columns = None

    def pred_mask(self, predicate) -> np.ndarray:
        key = str(predicate)
        cached = self._pred_cache.get(key)
        if cached is None:
            from ..expr import evaluate_predicate
            from ..runners.features import _predicate_columns

            if self._pred_columns is None:
                self._pred_columns = _predicate_columns(self.batch)
            cached = evaluate_predicate(
                predicate, self._pred_columns, len(self.batch.row_mask)
            ) & self.batch.row_mask
            self._pred_cache[key] = cached
        return cached

    def row_mask(self, analyzer) -> np.ndarray:
        """batch row mask & the analyzer's where-filter."""
        where = getattr(analyzer, "where", None)
        if where is None:
            return self.batch.row_mask
        return self.pred_mask(where)

    def row_mask_all(self) -> bool:
        """Whether every row of the batch is valid (no padding) — gates the
        shared dictionary fast paths; cached per batch."""
        cached = self._pred_cache.get(("row_mask_all",))
        if cached is None:
            cached = bool(self.batch.row_mask.all())
            self._pred_cache[("row_mask_all",)] = cached
        return cached

    def dict_code_counts(self, column: str) -> "Optional[np.ndarray]":
        """int64[num_cats + 1] count per dictionary code over valid rows
        (masked-out/null rows in the sentinel slot) — ONE native pass per
        batch-column shared by the type-class histogram, the HLL
        present-entry fold, and the device-frequency host partial. None when
        the native kernel is unavailable (callers keep their numpy path)."""
        from ..native import native_dict_masked_bincount

        if native_dict_masked_bincount is None:
            return None
        key = ("dict_counts", column)
        cached = self._pred_cache.get(key)
        if cached is None:
            col = self.batch.column(column)
            mask = self.batch.row_mask & col.mask
            cached = native_dict_masked_bincount(
                col.codes, mask, col.num_categories
            )
            self._pred_cache[key] = cached
        return cached

    def column_mask(self, analyzer, column: str) -> np.ndarray:
        return self.row_mask(analyzer) & self.batch.column(column).mask

    def block_stats(self, analyzer, column: str) -> np.ndarray:
        """[count, sum, min, max, m2, nonnan, max_nonnan] over the
        analyzer-masked column — ONE native pass shared by
        Mean/Sum/Min/Max/StdDev (and the KLL sampler's counting half) on the
        same column (the host-tier analog of their fused device updates)."""
        where = getattr(analyzer, "where", None)
        key = ("stats", column, None if where is None else str(where))
        cached = self._pred_cache.get(key)
        if cached is None:
            col = self.batch.column(column)
            mask = self.column_mask(analyzer, column)
            vals = col.values
            if not np.issubdtype(vals.dtype, np.number):
                vals = col.numeric_f64()
            from ..native import native_block_stats

            if native_block_stats is not None:
                cached = native_block_stats(vals, mask)
            else:
                v = vals[mask].astype(np.float64)
                if v.size == 0:
                    cached = np.array([0.0, 0.0, np.nan, np.nan, 0.0, 0.0, np.nan])
                else:
                    # NaN-largest order, matching the native kernel and the
                    # device update: NaN never wins the min (no non-NaN
                    # values -> identity NaN); any NaN wins the max
                    nonnan = v[~np.isnan(v)]
                    mn = nonnan.min() if nonnan.size else np.nan
                    mx = np.nan if nonnan.size < v.size else v.max()
                    mx_nonnan = nonnan.max() if nonnan.size else np.nan
                    cached = np.array(
                        [v.size, v.sum(), mn, mx, ((v - v.mean()) ** 2).sum(),
                         float(nonnan.size), mx_nonnan]
                    )
            self._pred_cache[key] = cached
        return cached

    def peek_block_stats(self, analyzer, column: str):
        """The cached block_stats row, or None if no stats analyzer has
        computed it for this (column, where) yet — lets the KLL sampler skip
        its counting pass without forcing an extra stats pass when running
        alone."""
        where = getattr(analyzer, "where", None)
        return self._pred_cache.get(
            ("stats", column, None if where is None else str(where))
        )

    def string_lengths(self, column: str) -> np.ndarray:
        key = ("len", column)
        cached = self._pred_cache.get(key)
        if cached is None:
            from ..runners.features import (
                _is_string_dict,
                dict_string_lengths,
                string_lengths,
            )

            col = self.batch.column(column)
            if _is_string_dict(col):
                cached = dict_string_lengths(col)
            else:
                cached = string_lengths(col.string_source, col.mask)
            self._pred_cache[key] = cached
        return cached

    def type_codes(self, column: str) -> np.ndarray:
        key = ("type", column)
        cached = self._pred_cache.get(key)
        if cached is None:
            from ..runners.features import (
                _is_string_dict,
                classify_type_codes,
                dict_type_codes,
            )

            from ..data import ColumnKind

            col = self.batch.column(column)
            if _is_string_dict(col):
                cached = dict_type_codes(col)
            else:
                source = col.string_source if col.kind == ColumnKind.STRING else col.values
                cached = classify_type_codes(source, col.mask, col.kind)
            self._pred_cache[key] = cached
        return cached


class ScanShareableAnalyzer(Analyzer[S, M]):
    """Analyzer whose state updates fuse into the shared single-pass scan."""

    @abc.abstractmethod
    def feature_specs(self) -> List[FeatureSpec]:
        ...

    def scan_program_key(self) -> Tuple:
        """Extra program-identity key for the bundled device scan. Two
        analyzers sharing (class, feature-spec kinds, state shapes) AND this
        tuple run through ONE compiled update program with their feature
        arrays remapped positionally — so any instance parameter that alters
        the TRACED update logic beyond what state shapes and feature values
        express MUST appear here. Column names, where-filters, predicates,
        regexes and quantile points all act host-side (feature computation)
        or at metric time, so the default is empty."""
        return ()

    @abc.abstractmethod
    def init_state(self) -> S:
        ...

    @abc.abstractmethod
    def update(self, state: S, features: Dict[str, jnp.ndarray]) -> S:
        """Fold one batch into the state. Traced under jit; must be pure,
        fixed-shape jax ops only."""

    #: whether `host_partial` is implemented (the engine streams raw columns
    #: to the device when any requested analyzer lacks the host tier)
    supports_host_partial: bool = False

    def host_partial(self, ctx: "HostBatchContext") -> Any:
        """Per-batch partial state computed host-side by the native ingest
        tier (one C pass per block). Used when the accelerator feed link
        cannot sustain raw column streaming: the device then folds the tiny
        partials with `ingest_partial` — the same partial-aggregate-near-
        the-data + algebraic-merge split Spark executes executor-side
        (reference `AnalysisRunner.scala:303-318`, SURVEY.md §2.9)."""
        raise NotImplementedError

    def ingest_partial(self, state: S, partial: Any) -> S:
        """Fold one host partial into the device state (traced under jit).
        Default: the partial IS a state — semigroup merge."""
        return self.merge(state, partial)

    def _row_mask(self, features: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Valid-row mask combined with this analyzer's where-filter
        (the `conditionalSelection` analog, reference
        `analyzers/Analyzer.scala:409-432`)."""
        mask = features["rows"]
        where = getattr(self, "where", None)
        if where is not None:
            mask = mask & features[predicate_feature(where).key]
        return mask


class StandardScanShareableAnalyzer(ScanShareableAnalyzer[S, DoubleMetric]):
    """Adds the success/empty/failure DoubleMetric mapping
    (reference `analyzers/Analyzer.scala:200-226`)."""

    def compute_metric_from(self, state: Optional[S]) -> DoubleMetric:
        if state is None or self.is_empty(state):
            return metric_from_empty(self.name, self.instance, self.entity)
        try:
            value = self.metric_value(state)
        except Exception as exc:  # noqa: BLE001
            return metric_from_failure(wrap_if_necessary(exc), self.name, self.instance, self.entity)
        if value is None:
            return metric_from_empty(self.name, self.instance, self.entity)
        # a NaN from a NON-empty state is a real result (Spark: max/sum/avg
        # over data containing NaN is NaN; corr with zero variance is NaN)
        # and surfaces as Success(NaN), exactly as the reference's agg row
        # does — emptiness is decided solely by `is_empty`/None
        return metric_from_value(float(value), self.name, self.instance, self.entity)

    @abc.abstractmethod
    def metric_value(self, state: S) -> float:
        ...

    def is_empty(self, state: S) -> bool:
        """Whether the folded state saw no values at all."""
        return False

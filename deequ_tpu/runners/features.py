"""Host feature frontend: turns column batches into device-ready numeric
arrays (the union of all analyzers' FeatureSpecs, computed once per batch).

This is the scan-sharing mechanism: deequ shares one Spark scan between N
analyzers via fused aggregation columns with row offsets (reference
`analyzers/runners/AnalysisRunner.scala:303-318`); here N analyzers share one
host pass + one fused XLA program, and the features dict is their shared
input. String-typed work (regex, lengths, type inference, hashing) happens
here, vectorized on host, so the device program stays pure fixed-shape
numerics.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Sequence

import numpy as np

from ..analyzers.base import FeatureSpec
from ..data import Batch, ColumnKind
from ..expr import evaluate_predicate
from ..ops.hashing import hash_column
from ..ops.hll import hll_features, hll_pack_features, hll_pack_pairs


def _hll_packed(col) -> np.ndarray:
    """uint16 HLL ingest feature for a column that is not a dictionary
    STRING column (those gather ``dict_hll_packed``) — native C++ single
    pass (hash + clz + pack) when built, numpy fallback otherwise."""
    from ..native import native_hll_pack_numeric, native_hll_pack_strings
    from ..ops.hashing import DEFAULT_SEED

    if col.kind == ColumnKind.STRING:
        if native_hll_pack_strings is not None:
            src = col.string_source
            if not isinstance(src, np.ndarray) or src.dtype == object:
                return native_hll_pack_strings(src, col.mask, DEFAULT_SEED)
    elif col.kind == ColumnKind.BOOLEAN or col.kind.is_numeric:
        if native_hll_pack_numeric is not None:
            vals = col.values
            if vals.dtype == np.bool_ or (
                np.issubdtype(vals.dtype, np.integer) and vals.dtype != np.int64
            ):
                vals = vals.astype(np.int64)
            if np.issubdtype(vals.dtype, np.number):
                return native_hll_pack_numeric(vals, col.mask, DEFAULT_SEED)
    hashes = hash_column(col.values, col.mask, col.kind)
    return hll_pack_features(hashes, col.mask)

# reference regexes (`analyzers/catalyst/StatefulDataType.scala:36-38`);
# decision order: null -> fractional -> integral -> boolean -> string
# (`StatefulDataType.update`, same file). re.ASCII + fullmatch reproduce the
# Java Matcher semantics (ASCII \d, whole-string match incl. no trailing
# newline) and keep the native C++ kernel bit-identical.
_FRACTIONAL_RE = re.compile(r"(-|\+)? ?\d*\.\d*", re.ASCII)
_INTEGRAL_RE = re.compile(r"(-|\+)? ?\d*", re.ASCII)
_BOOLEAN_RE = re.compile(r"true|false")

TYPE_NULL, TYPE_FRACTIONAL, TYPE_INTEGRAL, TYPE_BOOLEAN, TYPE_STRING = range(5)


def classify_type_codes(values, mask: np.ndarray, kind: ColumnKind) -> np.ndarray:
    """Per-value inferred-type codes 0..4 (Unknown/Fractional/Integral/
    Boolean/String). Non-string columns map directly from their kind, which
    matches the reference's behavior of casting values to strings first
    (e.g. 1.5 -> "1.5" matches FRACTIONAL). ``values`` may be a pyarrow
    string array (buffer-direct native path, no object materialization)."""
    n = len(values)
    if kind == ColumnKind.STRING:
        from ..native import native_classify_types

        if native_classify_types is not None:
            return native_classify_types(values, mask)
        values = _as_object_array(values)
        out = np.full(n, TYPE_NULL, dtype=np.int32)
        for i in range(n):
            if not mask[i]:
                continue
            v = values[i]
            if v is None:
                continue
            if _FRACTIONAL_RE.fullmatch(v):
                out[i] = TYPE_FRACTIONAL
            elif _INTEGRAL_RE.fullmatch(v):
                out[i] = TYPE_INTEGRAL
            elif _BOOLEAN_RE.fullmatch(v):
                out[i] = TYPE_BOOLEAN
            else:
                out[i] = TYPE_STRING
        return out
    if kind == ColumnKind.FRACTIONAL:
        code = TYPE_FRACTIONAL
    elif kind == ColumnKind.INTEGRAL:
        code = TYPE_INTEGRAL
    elif kind == ColumnKind.BOOLEAN:
        code = TYPE_BOOLEAN
    else:
        code = TYPE_STRING
    return np.where(mask, np.int32(code), np.int32(TYPE_NULL)).astype(np.int32)


from ..ops.hashing import as_object_array as _as_object_array  # noqa: E402


def string_lengths(values, mask: np.ndarray) -> np.ndarray:
    from ..native import native_string_lengths

    if native_string_lengths is not None:
        return native_string_lengths(values, mask)
    values = _as_object_array(values)
    out = np.zeros(len(values), dtype=np.int32)
    for i in np.flatnonzero(mask):
        v = values[i]
        if v is not None:
            out[i] = len(v)
    return out


def regex_matches(values, mask: np.ndarray, pattern: str) -> np.ndarray:
    """Unanchored regex search per value, nulls -> False (the reference uses
    `regexp_extract(col, pattern, 0) != ""`, `analyzers/PatternMatch.scala:
    46-52` — note a successful empty-string match also counts as False there,
    which we reproduce). ``values`` may be a pyarrow string array, in which
    case the GIL-free PCRE2 kernel runs over the Arrow buffers directly
    (undecidable rows are re-checked under Python `re`)."""
    from ..native import native_pattern_match

    if native_pattern_match is not None and (
        not isinstance(values, np.ndarray) or values.dtype == object
    ):
        try:
            out = native_pattern_match(values, mask, pattern)
        except Exception:  # noqa: BLE001 - e.g. non-UTF-8-able objects
            out = None
        if out is not None:
            return out
    values = _as_object_array(values)
    compiled = re.compile(pattern)
    out = np.zeros(len(values), dtype=bool)
    for i in np.flatnonzero(mask):
        v = values[i]
        if v is None:
            continue
        m = compiled.search(str(v))
        out[i] = bool(m) and m.group(0) != ""
    return out


def dict_regex_matches(col, pattern: str) -> np.ndarray:
    """Per-row regex matches for a dictionary STRING column: each DISTINCT
    entry is matched once per dataset (cached in col.aux, keyed by
    pattern) under Python `re` — exact semantics at O(distinct) cost —
    then gathered by code. Null/padding rows -> False."""
    def per_entry():
        ones = np.ones(col.num_categories, dtype=bool)
        return regex_matches(col.dictionary_source, ones, pattern)

    return _gather_by_code(col, _entry_table(col, ("regex", pattern), per_entry, bool))


def column_regex_matches(col, pattern: str) -> np.ndarray:
    """The one regex entry point for a Column: dictionary fast path when
    possible, else buffer-direct native / Python fallback."""
    if _is_string_dict(col):
        return dict_regex_matches(col, pattern)
    if col.kind == ColumnKind.STRING and col.arrow is not None:
        return regex_matches(col.arrow, col.mask, pattern)
    return regex_matches(col.values, col.mask, pattern)


def _entry_table(col, key, per_entry, dtype) -> np.ndarray:
    """A dictionary column's per-entry table, cached in ``col.aux[key]``
    once per dataset: ``per_entry()`` for each DISTINCT value, then one
    sentinel slot at index ``num_categories`` holding 0 — the code that
    null and padding rows carry, so a gather needs no bounds pass."""
    table = col.aux.get(key)
    if table is None:
        table = np.zeros(col.num_categories + 1, dtype=dtype)
        table[:-1] = per_entry()
        col.aux[key] = table  # published whole: concurrent feature builds race benignly
    return table


def _gather_by_code(col, table: np.ndarray) -> np.ndarray:
    """Per-row values of a sentinel-slot ``_entry_table``; rows where
    ``col.mask`` is False read the sentinel's 0."""
    codes = col.codes
    if not col.mask.all():
        codes = np.where(col.mask, codes, len(table) - 1)
    return np.take(table, codes)  # ~1.6x faster than table[codes]


def _dict_type_table(col) -> np.ndarray:
    def per_entry():
        ones = np.ones(col.num_categories, dtype=bool)
        return classify_type_codes(col.dictionary_source, ones, ColumnKind.STRING)

    return _entry_table(col, "type_codes", per_entry, np.int32)


def dict_entry_type_codes(col) -> np.ndarray:
    """Type codes of each DISTINCT dictionary value, classified once per
    dataset (cached in col.aux across batches)."""
    return _dict_type_table(col)[:-1]


def dict_type_codes(col) -> np.ndarray:
    """Per-row type codes for a dictionary STRING column: classify the
    DISTINCT values once, gather by code. Null/padding rows read the
    sentinel's 0, which is TYPE_NULL."""
    return _gather_by_code(col, _dict_type_table(col))


def dict_string_lengths(col) -> np.ndarray:
    """Per-row string lengths for a dictionary STRING column: measure the
    DISTINCT values once, gather by code. Null/padding rows -> 0."""
    def per_entry():
        ones = np.ones(col.num_categories, dtype=bool)
        return string_lengths(col.dictionary_source, ones)

    return _gather_by_code(col, _entry_table(col, "lengths", per_entry, np.int32))


def dict_entry_hll_pairs(col) -> np.ndarray:
    """(2, num_categories) HLL register index and leading-zero count of each
    DISTINCT dictionary value, cached per dataset — the one table both the
    host tier's partials and the device feature (``dict_hll_packed``)
    read."""
    pairs = col.aux.get("hll_pairs")
    if pairs is None:
        pairs = hll_features(dict_entry_hashes(col))
        col.aux["hll_pairs"] = pairs
    return pairs


def dict_hll_packed(col) -> np.ndarray:
    """Per-row uint16 HLL feature for a dictionary STRING column: each
    DISTINCT value's ``(idx << 6) | pw`` packed once per dataset, gathered
    by code. Null/padding rows -> 0, which never wins a register max."""
    def per_entry():
        return hll_pack_pairs(dict_entry_hll_pairs(col))

    return _gather_by_code(col, _entry_table(col, "hll_packed", per_entry, np.uint16))


def dict_entry_hashes(col) -> np.ndarray:
    """xxhash64 of each DISTINCT dictionary value, cached per dataset —
    the one hash pass every dictionary consumer (per-row hashes, HLL
    register pairs) derives from."""
    hd = col.aux.get("hashes")
    if hd is None:
        ones = np.ones(col.num_categories, dtype=bool)
        hd = hash_column(col.dictionary_source, ones, col.kind)
        col.aux["hashes"] = hd
    return hd


def dict_hashes(col) -> np.ndarray:
    """Per-row xxhash64 via the cached distinct-value hashes + a gather.
    Masked rows carry arbitrary hashes — every consumer masks before use."""
    hd = dict_entry_hashes(col)
    num_cats = col.num_categories
    if not num_cats:
        return np.zeros(len(col.codes), dtype=np.uint64)
    safe = np.where(col.codes < num_cats, col.codes, 0)
    return hd[safe]


def _is_string_dict(col) -> bool:
    return (
        col.has_dictionary
        and col.codes is not None
        and col.kind == ColumnKind.STRING
    )


class FeatureBuilder:
    """Computes the union of requested features for each batch."""

    def __init__(self, specs: Iterable[FeatureSpec]):
        # dedupe by key, keep spec objects (payload needed for predicates)
        self.specs: Dict[str, FeatureSpec] = {}
        for s in specs:
            self.specs.setdefault(s.key, s)

    @property
    def required_columns(self) -> List[str]:
        # predicates may reference any column — the runner accounts for that
        # in `_columns_needed`, not here
        return sorted({s.column for s in self.specs.values() if s.column is not None})

    def build(self, batch: Batch, monitor=None) -> Dict[str, np.ndarray]:
        """The features of one batch. ``monitor`` (a ``RunMonitor``) counts
        the HLL features served from a dictionary's per-entry table
        (``dict_hll_gathers``)."""
        features: Dict[str, np.ndarray] = {}
        pred_columns: Dict[str, np.ndarray] | None = None
        dict_hll_gathers = 0
        for key, spec in self.specs.items():
            if spec.kind == "rows":
                features[key] = batch.row_mask
            elif spec.kind == "num":
                col = batch.column(spec.column)
                if np.issubdtype(col.values.dtype, np.number):
                    # zero-copy passthrough: masked-out positions may carry
                    # arbitrary bytes (Arrow leaves null slots undefined) —
                    # every device consumer masks before use, so no host
                    # copy is needed; genuine NaN/inf at valid positions
                    # propagate (Spark semantics)
                    features[key] = col.values
                else:
                    features[key] = col.numeric_f64()
            elif spec.kind == "mask":
                col = batch.column(spec.column)
                features[key] = col.mask
            elif spec.kind == "len":
                col = batch.column(spec.column)
                if _is_string_dict(col):
                    features[key] = dict_string_lengths(col)
                else:
                    features[key] = string_lengths(col.string_source, col.mask)
            elif spec.kind == "match":
                features[key] = column_regex_matches(
                    batch.column(spec.column), spec.payload
                )
            elif spec.kind == "type":
                col = batch.column(spec.column)
                if _is_string_dict(col):
                    features[key] = dict_type_codes(col)
                else:
                    features[key] = classify_type_codes(
                        col.string_source if col.kind == ColumnKind.STRING else col.values,
                        col.mask,
                        col.kind,
                    )
            elif spec.kind == "hash":
                col = batch.column(spec.column)
                if _is_string_dict(col):
                    # gather from the per-dataset cached DISTINCT-value
                    # hashes (masked rows carry arbitrary hashes — the
                    # frequency engine sentinel-keys them before use)
                    features[key] = dict_hashes(col)
                elif col.kind == ColumnKind.STRING:
                    features[key] = hash_column(
                        col.string_source, col.mask, col.kind
                    )
                else:
                    features[key] = hash_column(col.values, col.mask, col.kind)
            elif spec.kind == "hll":
                col = batch.column(spec.column)
                if _is_string_dict(col):
                    features[key] = dict_hll_packed(col)
                    dict_hll_gathers += 1
                else:
                    features[key] = _hll_packed(col)
            elif spec.kind == "codes":
                col = batch.column(spec.column)
                if col.codes is None:
                    raise ValueError(
                        f"column {spec.column} is not dictionary-encoded; the "
                        "codes feature is only valid on dictionary sources"
                    )
                features[key] = col.codes
            elif spec.kind == "pred":
                if pred_columns is None:
                    pred_columns = _predicate_columns(batch)
                mask = evaluate_predicate(spec.payload, pred_columns, len(batch.row_mask))
                features[key] = mask & batch.row_mask
            else:
                raise ValueError(f"unknown feature kind {spec.kind}")
        if monitor is not None and dict_hll_gathers:
            monitor.bump("dict_hll_gathers", dict_hll_gathers)
        return features


def dry_run_batch(schema) -> Batch:
    """A synthetic all-null 1-row batch used to validate an analyzer's
    features (predicate syntax, column refs, regex compilation) before the
    real pass, so a bad analyzer yields a failure metric instead of killing
    the shared scan."""
    from ..data import Column

    columns = {}
    for cs in schema.columns:
        mask = np.zeros(1, dtype=bool)
        if cs.kind.is_numeric or cs.kind == ColumnKind.BOOLEAN:
            values = np.zeros(1, dtype=np.float64)
        else:
            values = np.array([None], dtype=object)
        columns[cs.name] = Column(cs.name, cs.kind, values, mask)
    return Batch(columns, np.zeros(1, dtype=bool), 0)


class _LazyPredicateColumns:
    """Mapping of column name -> predicate operand, materialized ON ACCESS
    and cached: a predicate battery only touches the columns it references,
    so untouched columns (e.g. high-cardinality strings during a
    constraint-evaluation pass) never pay object conversion."""

    def __init__(self, batch: Batch):
        self._batch = batch
        self._cache: Dict[str, Any] = {}

    def __contains__(self, name: str) -> bool:
        return name in self._batch.columns

    def keys(self):
        return self._batch.columns.keys()

    def items(self):
        return ((name, self[name]) for name in self.keys())

    def __getitem__(self, name: str):
        cached = self._cache.get(name)
        if cached is None:
            cached = self._cache[name] = _predicate_column(
                self._batch.column(name)
            )
        return cached


def _predicate_column(col):
    from ..expr import DictColumn

    if col.kind.is_numeric or col.kind == ColumnKind.BOOLEAN:
        return col.numeric_f64()
    if col.has_dictionary and col.codes is not None:
        # lazy dictionary operand: membership/comparisons/functions
        # evaluate on the DISTINCT entries and gather by code; the
        # entry table (with its None sentinel) caches per dataset
        num_cats = col.num_categories
        entries = col.aux.get("pred_entries")
        if entries is None or len(entries) != num_cats + 1:
            entries = np.empty(num_cats + 1, dtype=object)
            if num_cats:
                entries[:num_cats] = col.dictionary
            entries[num_cats] = None
            col.aux["pred_entries"] = entries
        codes = np.where(
            col.mask & (col.codes >= 0) & (col.codes < num_cats),
            col.codes,
            num_cats,
        ).astype(np.int32)
        return DictColumn(entries, codes)
    vals = col.values
    if vals.dtype != object:
        vals = vals.astype(object)
    vals = vals.copy()
    vals[~col.mask] = None
    return vals


def _predicate_columns(batch: Batch) -> "_LazyPredicateColumns":
    return _LazyPredicateColumns(batch)

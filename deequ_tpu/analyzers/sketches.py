"""Sketch-backed analyzers: approximate distinct counts and quantiles.

The reference implements these as Spark ImperativeAggregate/UDAF kernels with
per-row imperative buffer updates (`analyzers/catalyst/*.scala`); here the
sketch updates are vectorized fixed-shape device ops that join the same fused
single-pass scan as every other analyzer.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data import Schema
from ..exceptions import EmptyStateException, IllegalAnalyzerParameterException
from ..expr import Predicate
from ..metrics import (
    BucketDistribution,
    BucketValue,
    Entity,
    Failure,
    KeyedDoubleMetric,
    KLLMetric,
    Success,
    metric_from_empty,
)
from ..ops.kll import (
    DEFAULT_SHRINKING_FACTOR,
    DEFAULT_SKETCH_SIZE,
    KLLSketchState,
    MAXIMUM_ALLOWED_DETAIL_BINS,
    compactor_buffers,
    kll_init,
    kll_merge,
    kll_update,
)
from ..ops.kll_host import HostKLL
from .base import (
    FeatureSpec,
    Preconditions,
    ScanShareableAnalyzer,
    StandardScanShareableAnalyzer,
    hll_feature,
    mask_feature,
    numeric_feature,
    predicate_feature,
    rows_feature,
)
from .states import ApproxCountDistinctState


@dataclass(frozen=True)
class ApproxCountDistinct(StandardScanShareableAnalyzer[ApproxCountDistinctState]):
    """Approximate distinct count via HLL++ (relativeSD=0.05, p=9, 512
    registers), matching the reference's accuracy envelope and hash (xxhash64
    seed 42) bit-for-bit (reference `analyzers/ApproxCountDistinct.scala:
    26-64`, kernel `analyzers/catalyst/StatefulHyperloglogPlus.scala:89-139`).

    Device work per batch: a chunked one-hot compare/max scan over the 512
    registers (see ``update`` — TPU scatters and sorts both lose to it);
    merge is an elementwise register max (pmax-compatible over a mesh axis).
    """

    column: str = ""
    where: Optional[Predicate] = None
    name: str = field(default="ApproxCountDistinct", init=False)

    @property
    def instance(self) -> str:
        return self.column

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    def preconditions(self) -> List[Callable[[Schema], None]]:
        return [Preconditions.has_column(self.column)]

    def feature_specs(self) -> List[FeatureSpec]:
        specs = [rows_feature(), mask_feature(self.column), hll_feature(self.column)]
        if self.where is not None:
            specs.append(predicate_feature(self.where))
        return specs

    def init_state(self) -> ApproxCountDistinctState:
        return ApproxCountDistinctState.init()

    supports_host_partial = True

    def host_partial(self, ctx) -> ApproxCountDistinctState:
        from ..data import ColumnKind
        from ..native import native_block_hll, native_block_hll_strings
        from ..ops.hashing import DEFAULT_SEED

        col = ctx.batch.column(self.column)
        mask = ctx.column_mask(self, self.column)
        if col.has_dictionary and col.codes is not None:
            # dictionary column: hash the DISTINCT values once (cached in
            # col.aux across batches), then max-scatter only the entries
            # present in this batch — O(rows) code counting + O(dict) scatter
            from ..ops.hll import M
            from ..runners.features import dict_entry_hll_pairs

            # the same per-entry pairs the device feature packs
            pairs = dict_entry_hll_pairs(col)
            num_cats = col.num_categories
            if not num_cats:
                return ApproxCountDistinctState(np.zeros(M, dtype=np.int32))
            aux = col.aux
            regs_full = aux.get("hll_regs_full")
            if regs_full is None:
                # per-DATASET artifacts: registers over the whole
                # dictionary, plus a register-sorted view of the (idx, pw)
                # pairs so per-batch folds are a vectorized reduceat, not a
                # serialized np.maximum.at ufunc loop (~2.5x at 200k
                # categories)
                idx, pw = pairs[0][:num_cats], pairs[1][:num_cats]
                regs_full = np.zeros(M, dtype=np.int32)
                np.maximum.at(regs_full, idx, pw)
                perm = np.argsort(idx, kind="stable")
                aux["hll_perm"] = perm
                aux["hll_pw_sorted"] = pw[perm]
                aux["hll_starts"] = np.searchsorted(idx[perm], np.arange(M))
                # published last: a concurrent fold of the same column
                # that finds it finds the sorted view too
                aux["hll_regs_full"] = regs_full
            if self.where is None and ctx.run_token is not None:
                # cross-batch skip: within one pass, registers are a MAX
                # fold over batch partials, so an entry only needs to reach
                # the fold through the FIRST batch that sees it — later
                # batches contribute registers of NEW entries only, and once
                # every dictionary entry has been seen the partial is the
                # O(1) "saturated" zero state (a 1M-entry comment dictionary
                # used to cost O(dict) per batch FOREVER; small dictionaries
                # saturate after one batch). The token keys the seen-set to
                # the enclosing pass. The lock only guards the epoch swap:
                # concurrent workers marking entries can at worst duplicate
                # a contribution (max-fold idempotent), never drop one — a
                # batch only SKIPS an entry another batch of the same epoch
                # already marked, and that batch contributed it.
                import threading

                lock = aux.setdefault("_hll_lock", threading.Lock())
                with lock:
                    if aux.get("hll_seen_full") is ctx.run_token:
                        return ApproxCountDistinctState(np.zeros(M, dtype=np.int32))
                    tok, seen = aux.get("hll_seen", (None, None))
                    if tok is not ctx.run_token:
                        seen = np.zeros(num_cats + 1, dtype=bool)
                        seen[num_cats] = True
                        aux["hll_seen"] = (ctx.run_token, seen)
                idx, pw = pairs[0][:num_cats], pairs[1][:num_cats]
                if num_cats > (1 << 16):
                    # large dictionary: an O(rows) seen-lookup decides
                    # cheaper than an O(rows + cats) presence bincount
                    codes = np.where(col.codes < num_cats, col.codes, num_cats)
                    unseen = ~seen[codes]
                    n_unseen = int(np.count_nonzero(unseen))
                    if n_unseen == 0:
                        return ApproxCountDistinctState(
                            np.zeros(M, dtype=np.int32)
                        )
                    if n_unseen <= len(codes) // 64:
                        # near-saturation: tiny unique + sparse scatter-max
                        new_codes = np.unique(codes[unseen])
                        seen[new_codes] = True
                        if seen.all():
                            aux["hll_seen_full"] = ctx.run_token
                        regs = np.zeros(M, dtype=np.int32)
                        np.maximum.at(regs, idx[new_codes], pw[new_codes])
                        return ApproxCountDistinctState(regs)
                # warm-up shape: presence bincount, fold only NEW entries
                counts = (
                    ctx.dict_code_counts(self.column) if ctx.row_mask_all() else None
                )
                if counts is None:
                    safe = np.where(col.codes < num_cats, col.codes, num_cats)
                    counts = np.bincount(safe[mask], minlength=num_cats + 1)
                present = counts[:num_cats] > 0
                target = present & ~seen[:num_cats]
                seen[:num_cats] |= present
                if seen.all():
                    aux["hll_seen_full"] = ctx.run_token
                if not target.any():
                    return ApproxCountDistinctState(np.zeros(M, dtype=np.int32))
                if target.all():
                    return ApproxCountDistinctState(regs_full.copy())
                return ApproxCountDistinctState(
                    self._regs_for_target(aux, pairs, target, num_cats)
                )
            shared = (
                ctx.dict_code_counts(self.column) if self.where is None else None
            )
            if shared is not None:
                # the shared one-pass native count (sentinel slot = masked)
                counts = shared[:num_cats]
            else:
                counts = np.bincount(
                    col.codes[mask], minlength=num_cats + 1
                )[:num_cats]
            present = counts > 0
            if present.all():
                # every dictionary entry occurs in this batch: the cached
                # full-dictionary registers ARE the answer (copied — states
                # must stay immutable downstream)
                return ApproxCountDistinctState(regs_full.copy())
            return ApproxCountDistinctState(
                self._regs_for_target(aux, pairs, present, num_cats)
            )
        return self._host_partial_plain(col, mask)

    def _regs_for_target(self, aux, pairs, target: np.ndarray, num_cats: int):
        """Registers over the dictionary entries selected by ``target`` —
        sparse scatter-max for few entries, register-sorted reduceat (the
        cached per-dataset view) otherwise."""
        from ..ops.hll import M

        idx, pw = pairs[0][:num_cats], pairs[1][:num_cats]
        n_target = int(np.count_nonzero(target))
        if n_target * 8 < num_cats:
            ti = np.flatnonzero(target)
            regs = np.zeros(M, dtype=np.int32)
            np.maximum.at(regs, idx[ti], pw[ti])
            return regs
        perm = aux["hll_perm"]
        pw_eff = np.where(target[perm], aux["hll_pw_sorted"], -1)
        starts = aux["hll_starts"]
        nexts = np.append(starts[1:], num_cats)
        # a trailing -1 sentinel keeps every starts value (up to
        # num_cats inclusive, for empty trailing registers) a valid
        # reduceat index WITHOUT clamping — clamping to num_cats-1
        # silently cut the last pair out of the topmost occupied
        # register's segment whenever any register above it was empty
        pw_ext = np.append(pw_eff, np.int32(-1))
        seg = np.maximum.reduceat(pw_ext, starts)
        seg = np.where(nexts > starts, seg, -1)
        return np.maximum(seg, 0).astype(np.int32)

    def _host_partial_plain(self, col, mask) -> ApproxCountDistinctState:
        from ..data import ColumnKind
        from ..native import native_block_hll, native_block_hll_strings
        from ..ops.hashing import DEFAULT_SEED

        if col.kind == ColumnKind.STRING:
            src = col.string_source
            if native_block_hll_strings is not None and (
                not isinstance(src, np.ndarray) or src.dtype == object
            ):
                regs = native_block_hll_strings(src, mask, DEFAULT_SEED)
                return ApproxCountDistinctState(regs.astype(np.int32))
        elif native_block_hll is not None and (
            col.kind.is_numeric or col.kind == ColumnKind.BOOLEAN
        ):
            vals = col.values
            if vals.dtype == np.bool_ or (
                np.issubdtype(vals.dtype, np.integer) and vals.dtype != np.int64
            ):
                vals = vals.astype(np.int64)
            if np.issubdtype(vals.dtype, np.number):
                regs = native_block_hll(vals, mask, DEFAULT_SEED)
                return ApproxCountDistinctState(regs.astype(np.int32))
        # numpy fallback: hash + scatter-max
        from ..ops.hashing import hash_column
        from ..ops.hll import M, hll_features

        pairs = hll_features(hash_column(col.values, col.mask, col.kind))
        regs = np.zeros(M, dtype=np.int32)
        np.maximum.at(regs, pairs[0][mask], pairs[1][mask])
        return ApproxCountDistinctState(regs)

    @jax.named_scope("hll_update")
    def update(self, state, features):
        from ..ops.hll import M

        packed = features[hll_feature(self.column).key]
        # wire format: uint16 (idx << 6) | pw — 2 bytes/row on the host feed
        # (see ops/hll.hll_pack_features); nulls arrive pre-packed as 0
        mask = self._row_mask(features) & features[mask_feature(self.column).key]
        # Per-register max via a CHUNKED ONE-HOT compare/max scan — neither
        # a scatter (segment_max lowers to a serialized loop on TPU, ~11ms
        # per 1M-row batch) nor a sort (~1.3ms): each scan step broadcasts a
        # (chunk, 1) key column against the (1, 512) register ids and
        # max-reduces the chunk axis, keeping the (chunk x 512) compare tile
        # in VMEM — measured 0.34ms per 1M rows, identical registers.
        # Within one register group the key max IS (idx<<6 | max pw), so
        # the masked-out rows' key 0 (idx 0, pw 0) never wins a max.
        from ..ops import chunked_key_fold

        keys = jnp.where(mask, packed, 0).astype(jnp.int32)
        regs = jnp.arange(M, dtype=jnp.int32)

        def fold_chunk(acc, row):
            hit = (row[:, None] >> 6) == regs[None, :]
            return jnp.maximum(acc, jnp.max(jnp.where(hit, row[:, None], 0), axis=0))

        acc = chunked_key_fold(keys, 0, jnp.zeros(M, jnp.int32), fold_chunk)
        batch_regs = (acc & 63).astype(jnp.int32)
        return ApproxCountDistinctState(jnp.maximum(state.registers, batch_regs))

    def merge(self, a, b):
        return a.merge(b)

    def metric_value(self, state) -> float:
        # on empty data the estimate is 0.0, matching the reference where the
        # HLL agg buffer always exists (`ApproxCountDistinct.scala:49-56`)
        return state.metric_value()


# ---------------------------------------------------------------------------
# KLL-backed quantile analyzers
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KLLParameters:
    """(reference `analyzers/KLLSketch.scala:82`)."""

    sketch_size: int = DEFAULT_SKETCH_SIZE
    shrinking_factor: float = DEFAULT_SHRINKING_FACTOR
    number_of_buckets: int = MAXIMUM_ALLOWED_DETAIL_BINS


class _KLLBackedAnalyzer(ScanShareableAnalyzer[KLLSketchState, KLLMetric]):
    """Shared plumbing for analyzers folding a column into a KLL sketch.
    Subclasses define ``_sketch_size`` and the metric finalization."""

    @property
    def instance(self) -> str:
        return self.column

    @property
    def entity(self) -> Entity:
        return Entity.COLUMN

    def _sketch_size(self) -> int:
        raise NotImplementedError

    def preconditions(self) -> List[Callable[[Schema], None]]:
        return [
            Preconditions.has_column(self.column),
            Preconditions.is_numeric(self.column),
        ]

    def feature_specs(self) -> List[FeatureSpec]:
        specs = [rows_feature(), numeric_feature(self.column), mask_feature(self.column)]
        where = getattr(self, "where", None)
        if where is not None:
            specs.append(predicate_feature(where))
        return specs

    def init_state(self) -> KLLSketchState:
        return kll_init(self._sketch_size())

    def update(self, state, features):
        v = features[numeric_feature(self.column).key]
        mask = self._row_mask(features) & features[mask_feature(self.column).key]
        return kll_update(state, v, mask)

    def merge(self, a, b):
        return kll_merge(a, b)

    def metric_leaves(self):
        # KLLSketchState flattens as (items, sizes, parity, ticks, count,
        # g_min, g_max); the metric (HostKLL ranks/quantiles + the
        # compactor-buffer payload) reads everything EXCEPT the compaction
        # parity offsets and the update tick counter, which only steer
        # FUTURE folds/merges — the slim fetch drops them.
        return (0, 1, 4, 5, 6)

    supports_host_partial = True

    def host_partial(self, ctx):
        from ..config import ACC_DTYPE, COUNT_DTYPE
        from ..native import native_block_kll_pick, native_block_kll_sample

        col = ctx.batch.column(self.column)
        mask = ctx.column_mask(self, self.column)
        vals = col.values if np.issubdtype(col.values.dtype, np.number) else col.numeric_f64()
        k = self._sketch_size()
        stats = ctx.peek_block_stats(self, self.column)
        if stats is not None and native_block_kll_pick is not None:
            # a stats analyzer on the same column+mask already counted the
            # non-NaN values and found min/max: skip the sampler's counting
            # sweep (one less pass over the column's memory)
            nv = int(stats[5])
            if nv == 0:
                # identity partial — same (4k,) width as every sampler path
                items, m, h, mn, mx = (
                    np.full(4 * k, np.inf), 0, 0, np.inf, -np.inf
                )
            else:
                items, m, h = native_block_kll_pick(
                    vals, mask, k, ctx.batch_index, nv
                )
                mn, mx = float(stats[2]), float(stats[6])
        elif native_block_kll_sample is not None:
            items, m, h, nv, mn, mx = native_block_kll_sample(
                vals, mask, k, ctx.batch_index
            )
        else:
            items, m, h, nv, mn, mx = _np_kll_sample(vals, mask, k, ctx.batch_index)
        return (
            items.astype(np.float64),
            np.int32(m),
            np.int32(h),
            np.asarray(nv, dtype=COUNT_DTYPE),
            np.asarray(mn, dtype=ACC_DTYPE),
            np.asarray(mx, dtype=ACC_DTYPE),
        )

    def ingest_partial(self, state, partial):
        from ..ops.kll import kll_ingest_sampled

        items, m, h, nv, mn, mx = partial
        return kll_ingest_sampled(state, items, m, h, nv, mn, mx)


def _np_kll_sample(values: np.ndarray, mask: np.ndarray, k: int, tick: int):
    """numpy fallback for native block_kll_sample (same sampler semantics,
    incl. the up-to-two-levels-denser stride policy — compaction reduces the
    extra items with deterministic error instead of sampling variance)."""
    k = max(int(k), 1)  # non-positive sketch size must not hang the stride loop
    v = np.asarray(values, dtype=np.float64)
    ok = np.asarray(mask, dtype=bool) & ~np.isnan(v)
    vv = v[ok]
    nv = int(vv.size)
    items = np.full(4 * k, np.inf, dtype=np.float64)
    if nv == 0:
        return items, 0, 0, 0, np.inf, -np.inf
    h = 0
    stride = 1
    while stride * k < nv:
        stride <<= 1
        h += 1
    dense = 2 if h >= 2 else h
    h -= dense
    stride >>= dense
    cap = k << dense
    # batch index XOR valid-count mixing, bit-identical to the native
    # block_kll_sample_f64 (periodic streams must not phase-lock the stride;
    # uint32 wraparound is the intended mixing, hence the errstate guard)
    with np.errstate(over="ignore"):
        r = (
            (np.uint32(tick) * np.uint32(2654435761))
            ^ (np.uint32(nv) * np.uint32(2246822519))
        ) >> np.uint32(7)
    offset = int(r % np.uint32(stride))
    picked = np.sort(vv[offset::stride])[:cap]
    if dense == 2 and picked.size > 1:
        # one in-sampler compaction: every 2nd of the sorted dense pick,
        # weight doubles — keeps the dense sample's rank accuracy while
        # emitting <= 2k items (the state-buffer occupancy bound)
        parity = int((r >> np.uint32(8)) & np.uint32(1))
        picked = picked[parity::2]
        h += 1
    items[: picked.size] = picked
    return items, int(picked.size), h, nv, float(vv.min()), float(vv.max())


@dataclass(frozen=True)
class KLLSketch(_KLLBackedAnalyzer):
    """Quantile sketch of a numeric column, reported as an equi-width
    BucketDistribution over [globalMin, globalMax]
    (reference `analyzers/KLLSketch.scala:42-176`)."""

    column: str = ""
    kll_parameters: Optional[KLLParameters] = None
    where: Optional[Predicate] = None
    name: str = field(default="KLLSketch", init=False)

    @property
    def params(self) -> KLLParameters:
        return self.kll_parameters or KLLParameters()

    def _sketch_size(self) -> int:
        return self.params.sketch_size

    def preconditions(self) -> List[Callable[[Schema], None]]:
        def param_check(schema: Schema) -> None:
            if self.params.number_of_buckets > MAXIMUM_ALLOWED_DETAIL_BINS:
                raise IllegalAnalyzerParameterException(
                    f"Cannot return KLL Sketch related values for more than "
                    f"{MAXIMUM_ALLOWED_DETAIL_BINS} values"
                )
            if self.params.sketch_size < 1:
                raise IllegalAnalyzerParameterException(
                    f"KLL sketch size must be positive, got {self.params.sketch_size}"
                )

        return [param_check] + super().preconditions()

    def compute_metric_from(self, state: Optional[KLLSketchState]) -> KLLMetric:
        if state is None or int(state.count) == 0:
            return KLLMetric(
                Entity.COLUMN,
                self.name,
                self.column,
                Failure(
                    EmptyStateException(
                        f"Empty state for analyzer {self.name} on {self.column}, "
                        "all input values were NULL."
                    )
                ),
            )
        try:
            sketch = HostKLL.from_state(state)
            start = float(state.g_min)
            end = float(state.g_max)
            nb = self.params.number_of_buckets
            count = int(state.count)
            # bucket i covers (low_i, high_i]; the last bucket includes its
            # upper bound (reference `analyzers/KLLSketch.scala:136-146`).
            # The batch pre-collapse drops remainder items (n mod stride), so
            # the sketch's total weight can drift slightly below the exact
            # value count; scale the cumulative ranks so bucket counts
            # telescope to EXACTLY `count`, like the reference sketch whose
            # compactions preserve total weight (`NonSampleCompactor.scala:
            # 29-69`).
            bounds = [start + (end - start) * i / nb for i in range(nb + 1)]
            raw = [sketch.rank_exclusive(b) for b in bounds[:-1]]
            # anchor the ends at 0 and the FULL sketch weight, not at
            # rank(g_min)/rank(g_max): f32-quantized items can round a hair
            # past either f64 extreme and must still land in the end buckets
            raw[0] = 0
            raw.append(sketch.total_weight)
            tw = sketch.total_weight
            scale = (count / tw) if tw else 0.0
            cum = [int(np.floor(r * scale + 0.5)) for r in raw]
            buckets = [
                BucketValue(bounds[i], bounds[i + 1], cum[i + 1] - cum[i])
                for i in range(nb)
            ]
            dist = BucketDistribution(
                buckets,
                [self.params.shrinking_factor, float(self._sketch_size())],
                compactor_buffers(state),
            )
            return KLLMetric(Entity.COLUMN, self.name, self.column, Success(dist))
        except Exception as exc:  # noqa: BLE001
            return self.to_failure_metric(exc)

    def to_failure_metric(self, exception: BaseException) -> KLLMetric:
        from ..exceptions import wrap_if_necessary

        return KLLMetric(
            Entity.COLUMN, self.name, self.column, Failure(wrap_if_necessary(exception))
        )


def _sketch_size_for_error(relative_error: float) -> int:
    """Sketch size giving (empirically validated) rank error well inside
    ``relative_error``. The reference uses a Greenwald-Khanna digest with
    accuracy 1/relativeError (`analyzers/catalyst/DeequFunctions.scala:
    65-77`); KLL-backed needs O(1/eps) space for the same bound."""

    return max(256, int(math.ceil(4.0 / max(relative_error, 1e-4))))


def _check_quantile(q: float) -> None:
    if not 0.0 <= q <= 1.0:
        raise IllegalAnalyzerParameterException(
            "Quantile parameter must be in the closed interval [0, 1]. "
            f"Currently, the value is: {q}!"
        )


def _check_relative_error(relative_error: float) -> None:
    """The reference admits relativeError=0 as 'exact' GK mode
    (`ApproxQuantiles.scala:30`); a KLL sketch cannot be exact in bounded
    memory, so ``relative_error=0.0`` here routes the analyzer to a HOST
    full-sort accumulator (see :class:`ExactQuantileState`) whose result
    matches ``numpy.quantile`` exactly at O(n) host memory. Errors in
    (0, 1] stay KLL-backed, with 1e-4 as the smallest honored error."""
    if not 0.0 <= relative_error <= 1.0:
        raise IllegalAnalyzerParameterException(
            "Relative error parameter must be in the interval [0, 1]. "
            f"Currently, the value is: {relative_error}!"
        )


@dataclass(frozen=True)
class ExactQuantileState:
    """Host accumulator for ``relative_error=0.0`` (the reference's "exact"
    GK mode, `ApproxQuantiles.scala:30`): chunks of the column's non-null,
    non-NaN values, concatenated and full-sorted at metric time so the
    result is bit-identical to ``numpy.quantile`` (linear interpolation).
    Memory is O(values retained) — the documented price of exactness; the
    merge is chunk-list concatenation, so in-memory partition states
    aggregate like any other semigroup state. NOT registered with the
    state-persistence codec: persisting raw column values as "state"
    defeats the sketch contract, and ``save_states_with`` on an exact
    analyzer degrades to a typed failure metric naming the unregistered
    type."""

    chunks: Tuple[np.ndarray, ...] = ()

    def add(self, values: np.ndarray) -> "ExactQuantileState":
        return ExactQuantileState(
            self.chunks + (np.asarray(values, dtype=np.float64),)
        )

    def merge(self, other: "ExactQuantileState") -> "ExactQuantileState":
        return ExactQuantileState(self.chunks + other.chunks)

    @property
    def count(self) -> int:
        return int(sum(c.size for c in self.chunks))

    def values(self) -> np.ndarray:
        if not self.chunks:
            return np.empty(0, dtype=np.float64)
        return np.concatenate(self.chunks)


class _ExactQuantileMode:
    """Exact-mode plumbing shared by ApproxQuantile(+s): with
    ``relative_error == 0.0`` the analyzer leaves the fused scan
    (``host_exclusive``) and accumulates raw values host-side through the
    shared pass — still ONE pass over the data, like every other
    accumulator."""

    @property
    def host_exclusive(self) -> bool:
        return self.relative_error == 0.0

    def host_init(self) -> ExactQuantileState:
        return ExactQuantileState()

    def host_update(self, state: ExactQuantileState, batch) -> ExactQuantileState:
        col = batch.column(self.column)
        mask = batch.row_mask & col.mask
        if self.where is not None:
            from ..expr import evaluate_predicate
            from ..runners.features import _predicate_columns

            mask = mask & evaluate_predicate(
                self.where, _predicate_columns(batch), len(batch.row_mask)
            )
        vals = (
            col.values
            if np.issubdtype(col.values.dtype, np.number)
            else col.numeric_f64()
        )
        v = np.asarray(vals, dtype=np.float64)[mask]
        v = v[~np.isnan(v)]
        return state.add(v) if v.size else state

    def merge(self, a, b):
        if isinstance(a, ExactQuantileState) or isinstance(b, ExactQuantileState):
            return a.merge(b)
        return kll_merge(a, b)


@dataclass(frozen=True)
class ApproxQuantile(
    _ExactQuantileMode, _KLLBackedAnalyzer, StandardScanShareableAnalyzer[KLLSketchState]
):
    """Approximate single quantile (reference `analyzers/ApproxQuantile.scala:
    28-103`, default relativeError 0.01 at `:49`), KLL-backed;
    ``relative_error=0.0`` selects the exact host full-sort mode."""

    column: str = ""
    quantile: float = 0.5
    relative_error: float = 0.01
    where: Optional[Predicate] = None
    name: str = field(default="ApproxQuantile", init=False)

    def __post_init__(self):
        # metric name carries the quantile so several quantiles of one column
        # stay distinguishable (reference `ApproxQuantile.scala:90-97`)
        object.__setattr__(self, "name", f"ApproxQuantile-{self.quantile}")

    def _sketch_size(self) -> int:
        return _sketch_size_for_error(self.relative_error)

    def preconditions(self) -> List[Callable[[Schema], None]]:
        def param_checks(schema: Schema) -> None:
            _check_quantile(self.quantile)
            _check_relative_error(self.relative_error)

        return [param_checks] + super().preconditions()

    def metric_value(self, state) -> float:
        if isinstance(state, ExactQuantileState):
            return float(np.quantile(state.values(), self.quantile))
        return HostKLL.from_state(state).quantile(self.quantile)

    def is_empty(self, state) -> bool:
        return int(state.count) == 0


@dataclass(frozen=True)
class ApproxQuantiles(_ExactQuantileMode, _KLLBackedAnalyzer):
    """Several quantiles from one sketch -> KeyedDoubleMetric
    (reference `analyzers/ApproxQuantiles.scala:39-101`);
    ``relative_error=0.0`` selects the exact host full-sort mode."""

    column: str = ""
    quantiles: Tuple[float, ...] = ()
    relative_error: float = 0.01
    name: str = field(default="ApproxQuantiles", init=False)
    where: Optional[Predicate] = None

    def __post_init__(self):
        if not isinstance(self.quantiles, tuple):
            object.__setattr__(self, "quantiles", tuple(self.quantiles))

    def _sketch_size(self) -> int:
        return _sketch_size_for_error(self.relative_error)

    def preconditions(self) -> List[Callable[[Schema], None]]:
        def param_checks(schema: Schema) -> None:
            for q in self.quantiles:
                _check_quantile(q)
            _check_relative_error(self.relative_error)

        return [param_checks] + super().preconditions()

    def compute_metric_from(self, state) -> KeyedDoubleMetric:
        if state is None or int(state.count) == 0:
            empty = metric_from_empty(self.name, self.column, Entity.COLUMN)
            return KeyedDoubleMetric(Entity.COLUMN, self.name, self.column, empty.value)
        try:
            if isinstance(state, ExactQuantileState):
                vals = state.values()
                values = {
                    str(q): float(np.quantile(vals, q)) for q in self.quantiles
                }
                return KeyedDoubleMetric(
                    Entity.COLUMN, self.name, self.column, Success(values)
                )
            sketch = HostKLL.from_state(state)
            values = {str(q): sketch.quantile(q) for q in self.quantiles}
            return KeyedDoubleMetric(Entity.COLUMN, self.name, self.column, Success(values))
        except Exception as exc:  # noqa: BLE001
            from ..exceptions import wrap_if_necessary

            return KeyedDoubleMetric(
                Entity.COLUMN, self.name, self.column, Failure(wrap_if_necessary(exc))
            )

    def to_failure_metric(self, exception: BaseException) -> KeyedDoubleMetric:
        from ..exceptions import wrap_if_necessary

        return KeyedDoubleMetric(
            Entity.COLUMN, self.name, self.column, Failure(wrap_if_necessary(exception))
        )

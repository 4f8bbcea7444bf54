"""Batched KLL quantile sketch as a fixed-shape TPU kernel.

The reference's KLL (`analyzers/QuantileNonSample.scala:25-305`) is a per-row
imperative update: append one item to a dynamically-sized level-0 buffer and
run a compaction cascade when full — hostile to SIMD and to XLA's static
shapes. This redesign keeps the KLL *algebra* (levelled compactors, every-2nd
subsampling with alternating offsets, weight doubling per level) but makes
every step a fixed-shape vector op:

- the level buffers are one ``float64[L, 4k]`` array padded with ``+inf``
  plus an ``int32[L]`` size vector — jit-able, donate-able, mergeable
  (4k is the fixed point of the worst-case occupancy recurrence
  ``M = 2k + M/2``: a merge appends up to 2k before the cascade runs, and a
  compaction of a 4k-full level promotes at most 2k upward);
- a whole batch is folded at once: sort the batch, stride-subsample it down
  to ≤ k items of weight ``2^h`` (equivalent to ``h`` perfect pairwise
  compactions in one step), and scatter-append at level ``h``;
- the compaction cascade is an unrolled loop over levels with masked
  ``where`` selects instead of data-dependent control flow.

Levels use uniform capacity ``k`` (the reference shrinks lower-level
capacities by ``shrinkingFactor``, `QuantileNonSample.scala:78-80`; uniform
capacity strictly dominates it in rank error at a modest constant-factor
space cost, and keeps one static shape). ``shrinking_factor`` is retained in
the API and serde for compatibility.

Rank-error behaviour is validated probabilistically in
``tests/test_kll.py`` (the `KLL/KLLProbTest.scala` analog).
"""

from __future__ import annotations

from typing import Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ..config import ACC_DTYPE, COUNT_DTYPE

#: sketch items are f32: quantile VALUE precision (1e-7 relative) is orders
#: of magnitude finer than the sketch's RANK error, and f32 sorts run on the
#: TPU's native path instead of emulated f64. min/max/count stay ACC/COUNT
#: dtype for exact parity.
#:
#: DOCUMENTED CAVEAT (persistence): the reference persists KLL items as
#: doubles (`analyzers/catalyst/KLLSketchSerializer.scala:26-121`); here the
#: state's item buffers are f32 on device and persist as f32, so a persisted
#: sketch's item VALUES can differ from a double-precision payload by up to
#: 1 ulp of f32 (~1.2e-7 relative). Round-trips through the state providers
#: are bit-exact with respect to the sketch's own contents (asserted in
#: tests/test_state_serde.py); the f32 quantisation happens once, at update
#: time, and is far inside the sketch's rank-error envelope. g_min/g_max and
#: the exact count persist at full f64/i64 precision.
ITEM_DTYPE = jnp.float32

#: defaults matching the reference (`analyzers/KLLSketch.scala:172-176`)
DEFAULT_SKETCH_SIZE = 2048
DEFAULT_SHRINKING_FACTOR = 0.64
MAXIMUM_ALLOWED_DETAIL_BINS = 100

#: number of levels: level l holds items of weight 2^l, so 32 levels cover
#: k * 2^31 ~ 4e12 rows at the default sketch size before the top level can
#: saturate
MAX_LEVELS = 32

_INF = jnp.inf


@flax.struct.dataclass
class KLLSketchState:
    """Mergeable sketch state (+ global min/max + exact count), the analog of
    the reference `KLLState` (`analyzers/KLLSketch.scala:42-55`)."""

    items: jnp.ndarray   # float64[L, 4k], +inf beyond sizes[l]
    sizes: jnp.ndarray   # int32[L]
    parity: jnp.ndarray  # int32[L], alternating compaction offsets
    ticks: jnp.ndarray   # int32, update counter (drives subsample offsets)
    count: jnp.ndarray   # int64, exact number of folded values
    g_min: jnp.ndarray   # float64
    g_max: jnp.ndarray   # float64

    sketch_size: int = flax.struct.field(pytree_node=False, default=DEFAULT_SKETCH_SIZE)

    @property
    def capacity(self) -> int:
        return self.sketch_size

    def merge(self, other: "KLLSketchState") -> "KLLSketchState":
        """Semigroup merge (delegates to :func:`kll_merge`): every *State
        class exposes the algebra uniformly so generic fold/merge paths —
        and the state-algebra invariant check — can rely on it."""
        return kll_merge(self, other)


def kll_init(sketch_size: int = DEFAULT_SKETCH_SIZE, levels: int = MAX_LEVELS) -> KLLSketchState:
    k = int(sketch_size)
    return KLLSketchState(
        items=jnp.full((levels, 4 * k), _INF, dtype=ITEM_DTYPE),
        sizes=jnp.zeros(levels, dtype=jnp.int32),
        parity=jnp.zeros(levels, dtype=jnp.int32),
        ticks=jnp.zeros((), dtype=jnp.int32),
        count=jnp.zeros((), dtype=COUNT_DTYPE),
        g_min=jnp.asarray(jnp.inf, dtype=ACC_DTYPE),
        g_max=jnp.asarray(-jnp.inf, dtype=ACC_DTYPE),
        sketch_size=k,
    )


def _append_level(
    items: jnp.ndarray, sizes: jnp.ndarray, level, values: jnp.ndarray, num_valid
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append the valid prefix of ``values`` to ``items[level]``. Writes past
    capacity drop AND are excluded from the size accounting, so a saturated
    top level (only reachable past ~1e13 rows) loses weight instead of
    corrupting the buffer with counted padding.

    Implemented as a GATHER-based row rebuild + one dynamic row update: a
    TPU scatter of even 2k elements lowers to a serialized loop measured at
    ~9ms per call — the single hottest op in the old KLL update — while the
    equivalent ``values[j - size]`` gather + full-row select runs in the
    same fused elementwise pass as everything else."""
    buf_len = items.shape[1]
    level = jnp.asarray(level, jnp.int32)
    size = sizes[level]
    written = jnp.clip(num_valid.astype(jnp.int32), 0, buf_len - size)
    row = items[level]
    # shifted[j] = values[j - size]: a dynamic_slice of the padded values
    # (contiguous window), not a gather — indices are consecutive. INF pads
    # BOTH sides so the start index (buf_len - size, in [0, buf_len]) is
    # never clamped regardless of len(values); out-of-range reads yield INF
    # and are masked by ``take`` anyway.
    pad = jnp.full(buf_len, _INF, values.dtype)
    padded = jnp.concatenate([pad, values, pad])
    shifted = jax.lax.dynamic_slice(padded, (buf_len - size,), (buf_len,))
    rel = jnp.arange(buf_len, dtype=jnp.int32) - size
    take = (rel >= 0) & (rel < written)
    new_row = jnp.where(take, shifted, row)
    items = jax.lax.dynamic_update_slice(
        items, new_row[None, :], (level, jnp.zeros((), jnp.int32))
    )
    sizes = sizes.at[level].add(written)
    return items, sizes


def _make_compact_level(shape: Tuple[int, int]):
    """The single-level compactor: sort the level, promote every-2nd item of
    its even-length prefix to the next level with doubled weight, keep the
    odd tail (the batched analog of the reference compactor,
    `analyzers/NonSampleCompactor.scala:29-69`). Untouched levels keep their
    insertion order; every consumer (compaction itself, HostKLL,
    compactor_buffers) sorts, so only the multiset per level matters."""
    levels, buf_len = shape
    half = buf_len // 2  # max items a compaction can emit
    slots = jnp.arange(half, dtype=jnp.int32)
    buf_slots = jnp.arange(buf_len, dtype=jnp.int32)

    def compact_level(lvl, carry):
        items, sizes, parity = carry
        n = sizes[lvl]
        buf = jnp.sort(items[lvl])
        n2 = n - (n & 1)
        m_emit = n2 // 2
        off = parity[lvl]
        # promoted items: buf[off + 2j] for j < m_emit (a sorted prefix)
        emit_idx = jnp.clip(off + 2 * slots, 0, buf_len - 1)
        emitted = jnp.where(slots < m_emit, buf[emit_idx], _INF)
        # tail kept at this level: buf[n2:n] (0 or 1 items)
        tail_count = n - n2
        tail_idx = jnp.clip(n2 + buf_slots, 0, buf_len - 1)
        new_row = jnp.where(buf_slots < tail_count, buf[tail_idx], _INF)
        items = items.at[lvl].set(new_row)
        sizes = sizes.at[lvl].set(tail_count.astype(jnp.int32))
        parity = parity.at[lvl].set(1 - off)
        items, sizes = _append_level(items, sizes, lvl + 1, emitted, m_emit)
        return items, sizes, parity

    return compact_level


def _compact_cascade(items: jnp.ndarray, sizes: jnp.ndarray, parity: jnp.ndarray, k: int):
    """Full upward sweep over every level — needed after a MERGE, where all
    levels receive appends. Each level is wrapped in a ``lax.cond`` so
    levels within capacity skip their sort."""
    levels, _ = items.shape
    compact_level = _make_compact_level(items.shape)

    def body(lvl, carry):
        _items, _sizes, _parity = carry
        return jax.lax.cond(
            _sizes[lvl] > k,
            lambda c: compact_level(lvl, c),
            lambda c: c,
            carry,
        )

    # one compiled level-step instead of L-1 unrolled copies; a single
    # upward sweep suffices because level l+1 is processed after receiving
    # level l's promotions
    return jax.lax.fori_loop(0, levels - 1, body, (items, sizes, parity))


def _compact_cascade_from(
    items: jnp.ndarray, sizes: jnp.ndarray, parity: jnp.ndarray, k: int, start_level
):
    """Early-terminating cascade for a SINGLE-LEVEL append (batch update /
    sampled ingest): only ``start_level`` can overflow, each compaction can
    only overflow the level above, and the cascade dies the moment a level
    fits — so a ``while_loop`` starting at ``start_level`` visits the one or
    two levels that actually changed instead of sweeping all ~32 (measured
    ~3x faster per 1M-row fold than the full sweep on TPU; the sweep's 31
    ``cond``s each carry the 1MB item buffer through an iteration even when
    they skip)."""
    levels, _ = items.shape
    compact_level = _make_compact_level(items.shape)

    def cond(carry):
        _items, _sizes, _parity, lvl = carry
        return (lvl < levels - 1) & (_sizes[lvl] > k)

    def body(carry):
        _items, _sizes, _parity, lvl = carry
        _items, _sizes, _parity = compact_level(lvl, (_items, _sizes, _parity))
        return _items, _sizes, _parity, lvl + 1

    items, sizes, parity, _ = jax.lax.while_loop(
        cond, body, (items, sizes, parity, jnp.asarray(start_level, jnp.int32))
    )
    return items, sizes, parity


@jax.named_scope("kll_update")
def kll_update(state: KLLSketchState, values: jnp.ndarray, valid: jnp.ndarray) -> KLLSketchState:
    """Fold one batch (fixed shape, masked) into the sketch. Pure jax; safe
    under jit/shard_map. NaNs are excluded from the sketch."""
    k = state.sketch_size
    v = values.astype(ACC_DTYPE)
    ok = valid & ~jnp.isnan(v)
    n = jnp.sum(ok).astype(jnp.int32)

    count = state.count + n.astype(COUNT_DTYPE)
    g_min = jnp.minimum(state.g_min, jnp.min(jnp.where(ok, v, jnp.inf)))
    g_max = jnp.maximum(state.g_max, jnp.max(jnp.where(ok, v, -jnp.inf)))

    # clamp to the finite ITEM_DTYPE range before the cast: a legitimate
    # |value| > 3.4e38 must saturate, not become inf and collide with the
    # padding sentinel (quantiles at such magnitudes saturate; min/max/count
    # stay exact in ACC_DTYPE)
    finfo_max = jnp.asarray(jnp.finfo(ITEM_DTYPE).max, dtype=v.dtype)
    clamped = jnp.clip(v, -finfo_max, finfo_max)
    sv = jnp.sort(jnp.where(ok, clamped, _INF).astype(ITEM_DTYPE))

    # pre-collapse the batch: stride 2^h subsampling of the sorted batch is
    # equivalent to h perfect pairwise compactions, landing ≤ k items of
    # weight 2^h directly at level h
    m_needed = jnp.maximum((n + k - 1) // k, 1)
    h = jnp.ceil(jnp.log2(m_needed.astype(jnp.float32))).astype(jnp.int32)
    stride = (1 << h).astype(jnp.int32)
    # cheap deterministic rotation of the subsample offset across updates
    r = (state.ticks.astype(jnp.uint32) * jnp.uint32(2654435761)) >> jnp.uint32(7)
    offset = (r % stride.astype(jnp.uint32)).astype(jnp.int32)

    slots = jnp.arange(k, dtype=jnp.int32)
    pos = offset + slots * stride
    sample_valid = pos < n
    samples = jnp.where(sample_valid, sv[jnp.clip(pos, 0, sv.shape[0] - 1)], _INF)
    m = jnp.sum(sample_valid).astype(jnp.int32)

    items, sizes = _append_level(state.items, state.sizes, h, samples, m)
    items, sizes, parity = _compact_cascade_from(items, sizes, state.parity, k, h)

    return KLLSketchState(
        items=items,
        sizes=sizes,
        parity=parity,
        ticks=state.ticks + 1,
        count=count,
        g_min=g_min,
        g_max=g_max,
        sketch_size=k,
    )


def kll_ingest_sampled(
    state: KLLSketchState,
    samples: jnp.ndarray,
    m: jnp.ndarray,
    h: jnp.ndarray,
    nv: jnp.ndarray,
    g_min: jnp.ndarray,
    g_max: jnp.ndarray,
) -> KLLSketchState:
    """Fold a host-side pre-sampled block into the sketch: ``samples`` is a
    sorted, +inf-padded (<=4k,) vector of ``m`` items carrying weight
    ``2^h`` each, covering ``nv`` underlying values with the given block
    min/max (the native ingest tier's `block_kll_sample` output — the
    bottom-sampler form of kll_update's batch pre-collapse, sampled up to
    two levels denser than strictly fits so compaction absorbs the surplus).
    Pure jax; runs inside the jit'd partial-fold program."""
    k = state.sketch_size
    # clamp like kll_update: legitimate huge/-inf values saturate to the
    # finite ITEM range (a -inf must stay minimum-side). Padding beyond the
    # first m slots never enters the sketch (_append_level writes m items),
    # so the +inf padding needs no special casing.
    finfo_max = jnp.asarray(jnp.finfo(ITEM_DTYPE).max, dtype=jnp.float64)
    sv = jnp.clip(
        jnp.asarray(samples, dtype=jnp.float64), -finfo_max, finfo_max
    ).astype(ITEM_DTYPE)

    items, sizes = _append_level(
        state.items, state.sizes, jnp.asarray(h, dtype=jnp.int32), sv,
        jnp.asarray(m, dtype=jnp.int32),
    )
    items, sizes, parity = _compact_cascade_from(items, sizes, state.parity, k, h)
    return KLLSketchState(
        items=items,
        sizes=sizes,
        parity=parity,
        ticks=state.ticks + 1,
        count=state.count + jnp.asarray(nv, dtype=COUNT_DTYPE),
        g_min=jnp.minimum(state.g_min, jnp.asarray(g_min, dtype=ACC_DTYPE)),
        g_max=jnp.maximum(state.g_max, jnp.asarray(g_max, dtype=ACC_DTYPE)),
        sketch_size=k,
    )


@jax.named_scope("kll_merge")
def kll_merge(a: KLLSketchState, b: KLLSketchState) -> KLLSketchState:
    """Semigroup sum: concatenate per-level buffers and re-compact
    (reference `QuantileNonSample.merge`, `analyzers/QuantileNonSample.scala:
    215-230`). Pure jax, usable inside collective tree merges."""
    assert a.sketch_size == b.sketch_size, "cannot merge sketches of different size"
    # persisted states come back as numpy pytrees; coerce for .at[] scatters
    items, sizes = jnp.asarray(a.items), jnp.asarray(a.sizes)
    for lvl in range(items.shape[0]):
        items, sizes = _append_level(items, sizes, lvl, b.items[lvl], b.sizes[lvl])
    items, sizes, parity = _compact_cascade(
        items, sizes, jnp.asarray(a.parity) ^ jnp.asarray(b.parity), a.sketch_size
    )
    return KLLSketchState(
        items=items,
        sizes=sizes,
        parity=parity,
        ticks=a.ticks + b.ticks,
        count=a.count + b.count,
        g_min=jnp.minimum(a.g_min, b.g_min),
        g_max=jnp.maximum(a.g_max, b.g_max),
        sketch_size=a.sketch_size,
    )


# ---------------------------------------------------------------------------
# host-side views
# ---------------------------------------------------------------------------


def compactor_buffers(state: KLLSketchState) -> list:
    """Per-level item lists (weights 2^level) — the `getCompactorItems`
    payload stored in BucketDistribution.data (reference
    `analyzers/KLLSketch.scala:150`)."""
    items = np.asarray(state.items)
    sizes = np.asarray(state.sizes)
    out = []
    top = 0
    for lvl in range(items.shape[0]):
        if sizes[lvl] > 0:
            top = lvl + 1
    for lvl in range(max(top, 1)):
        out.append(sorted(items[lvl][: sizes[lvl]].tolist()))
    return out

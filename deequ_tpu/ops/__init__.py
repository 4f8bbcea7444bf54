"""Device kernels (HLL, KLL, hashing) and shared TPU op scaffolding."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunked_key_fold(keys, pad_value, init, fold_chunk, chunk: int = 4096):
    """Fold a 1-D key array through ``fold_chunk`` in fixed-size chunks via
    ``lax.scan``: the per-chunk broadcast tile (e.g. a ``(chunk, K)``
    compare against category/register ids) stays in VMEM instead of
    materializing a ``(rows, K)`` intermediate — the pattern both the HLL
    register max and the device frequency count use, and the reason neither
    needs a TPU scatter (which lowers to a serialized loop) or a sort.

    ``keys`` is padded to a chunk multiple with ``pad_value``; callers pick
    a sentinel their fold ignores. ``fold_chunk(acc, row) -> acc`` folds one
    ``(chunk,)`` slice.
    """
    if keys.shape[0] == 0:
        return init
    c = min(chunk, keys.shape[0])
    pad = (-keys.shape[0]) % c
    if pad:
        keys = jnp.concatenate([keys, jnp.full(pad, pad_value, keys.dtype)])
    acc, _ = jax.lax.scan(
        lambda a, row: (fold_chunk(a, row), None), init, keys.reshape(-1, c)
    )
    return acc


def _prefix_sum(x, block: int = 1024):
    """Inclusive prefix sum of a 1-D array in two levels: within blocks of
    ``block`` entries, then over the block totals. A flat ``jnp.cumsum``
    of the int64 (u32-pair emulated) counts at the default table sizes
    (2^22 slots + 2^20 buffer) lowers on TPU to a reduce-window the v5e
    compiler refuses inside the compaction cond (19.1M of its 16M scoped
    VMEM); the blocked form compiles. Integer sums are exact in any order,
    so the result is bit-identical."""
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, x.dtype)])
    inner = jnp.cumsum(x.reshape(-1, block), axis=1)
    totals = inner[:, -1]
    offsets = jnp.cumsum(totals) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


def _prefix_max(x, block: int = 1024):
    """Inclusive running maximum of a non-negative 1-D integer array, in
    the same two levels as :func:`_prefix_sum` and for the same reason."""
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, x.dtype)])
    inner = jax.lax.cummax(x.reshape(-1, block), axis=1)
    before = jax.lax.cummax(inner[:, -1])
    before = jnp.concatenate([jnp.zeros(1, x.dtype), before[:-1]])
    return jnp.maximum(inner, before[:, None]).reshape(-1)[:n]


def freq_compact(keys, counts, out_size: int, sentinel):
    """Sort-merge compaction of (key, count) pairs into at most ``out_size``
    sorted uniques — the device frequency engine's table maintenance, shared
    by the in-pass buffer compaction and the semigroup state merge so the
    two cannot drift.

    Scatter-free by construction (XLA scatters serialize on TPU, see
    DeviceFrequencyScan.update): one pair-sort brings equal keys adjacent,
    a cumsum over the sorted counts turns segment sums into two gathers,
    and the compaction gather indices come from searchsorted over the
    running unique rank — every step is a sort, scan or gather the TPU
    vectorizes. Entries with ``key == sentinel`` (masked rows, structural
    padding) contribute nothing and sort last.

    Returns ``(out_keys, out_counts, n_unique, kept_rows, total_rows)``:
    ``out_size`` sorted unique keys (sentinel-padded past ``n_unique``)
    with summed counts. ``n_unique`` is the RAW distinct count of the
    input, which may exceed ``out_size``: the smallest ``out_size`` uniques
    are kept, the rest are dropped, and the caller accounts
    ``max(n_unique - out_size, 0)`` groups / ``total_rows - kept_rows``
    rows as lost (the overflow tier's exact loss ledger).
    """
    import jax.numpy as jnp

    k, c = jax.lax.sort((keys, counts), num_keys=1)
    n = k.shape[0]
    # caller contract: sentinel-keyed entries carry count 0 and real keys
    # carry counts >= 1, so segment sums need no per-entry validity test
    is_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), k[1:] != k[:-1]]
    ) & (k != sentinel)
    ranks = _prefix_sum(is_start.astype(jnp.int64))
    n_unique = ranks[-1]
    tot = _prefix_sum(c)
    target = jnp.arange(1, out_size + 1, dtype=jnp.int64)
    pos = jnp.clip(jnp.searchsorted(ranks, target, side="left"), 0, n - 1)
    pos_next = jnp.searchsorted(ranks, target + 1, side="left")
    valid = target <= n_unique
    out_keys = jnp.where(valid, k[pos], sentinel)
    seg_end = tot[jnp.clip(pos_next - 1, 0, n - 1)]
    seg_end = jnp.where(pos_next >= n, tot[n - 1], seg_end)
    seg_begin = jnp.where(pos > 0, tot[pos - 1], 0)
    out_counts = jnp.where(valid, seg_end - seg_begin, 0)
    total_rows = tot[n - 1]
    kept_rows = jnp.sum(out_counts)
    return out_keys, out_counts, n_unique, kept_rows, total_rows


def count_summary(keys, weights, hist_slots: int, sentinel, count_dtype):
    """The count multiset of sorted (key, weight) entries, reduced to what
    every scalar frequency metric reads: ``(groups, singletons, rows,
    hist_values, hist_mults, distinct_counts)``. Keys are ascending with
    ``sentinel`` entries (weight 0) last; a group is a run of equal keys
    and its count the sum of its weights.

    The count-of-counts histogram (each distinct count value with the
    number of groups that have it, ascending, ``distinct_counts`` of its
    ``hist_slots`` entries used) is kept whole rather than reduced to
    sum(c ln c) here: the host reduces it through the one canonical
    function, so Entropy stays bit-identical across the device and host
    paths. ``hist_slots`` must cover the distinct count values; ``d`` of
    them need at least d(d+1)/2 rows. ``count_dtype`` holds every group's
    count: int32 where the entries are fewer than 2^31 rows (32-bit sorts
    and scans are native on the TPU). Scatter-free: prefix scans, one sort
    of the counts and gathers."""
    valid = keys != sentinel
    same = keys[1:] == keys[:-1]
    is_start = valid & jnp.concatenate([jnp.ones(1, bool), ~same])
    is_end = valid & jnp.concatenate([~same, jnp.ones(1, bool)])
    tot = _prefix_sum(weights)
    before = _prefix_max(jnp.where(is_start, tot - weights, 0))
    counts = jnp.where(is_end, tot - before, 0)
    groups = jnp.sum(is_end, dtype=jnp.int64)
    singletons = jnp.sum(counts == 1, dtype=jnp.int64)
    # the entries that end no group read 0 and sort first
    c = jnp.sort(counts.astype(count_dtype))
    n = c.shape[0]
    last = (c != 0) & jnp.concatenate([c[1:] != c[:-1], jnp.ones(1, bool)])
    rank = _prefix_sum(last.astype(count_dtype))
    distinct = rank[-1]
    # the r-th distinct count's last entry, and the one before it
    pos = jnp.searchsorted(
        rank, jnp.arange(1, hist_slots + 1, dtype=rank.dtype), side="left"
    )
    prev = jnp.concatenate([(n - groups - 1)[None].astype(pos.dtype), pos[:-1]])
    used = jnp.arange(hist_slots) < distinct
    values = jnp.where(used, c[jnp.clip(pos, 0, n - 1)], 0).astype(jnp.int64)
    mults = jnp.where(used, pos - prev, 0).astype(jnp.int64)
    return groups, singletons, tot[-1], values, mults, distinct.astype(jnp.int64)

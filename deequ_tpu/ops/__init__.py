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

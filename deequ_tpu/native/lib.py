"""ctypes bindings for the native host kernels.

Object arrays of python strings are converted ONCE per batch to Arrow
large-string layout (concatenated UTF-8 buffer + int64 offsets — a C-speed
conversion via pyarrow), then each kernel runs a single C++ pass over the
buffers. Falls back to pure Python upstream if anything here fails to load.
"""

from __future__ import annotations

import ctypes

import numpy as np
import pyarrow as pa

from ..utils import env_flag
from .build import build

#: env var: set to 1 to disable the native kernels (pure-Python fallback)
NO_NATIVE_ENV = "DEEQU_TPU_NO_NATIVE"

if env_flag(NO_NATIVE_ENV, False):
    raise ImportError("native kernels disabled via DEEQU_TPU_NO_NATIVE")

_lib = ctypes.CDLL(build())

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i64p = ctypes.POINTER(ctypes.c_int64)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_i32p = ctypes.POINTER(ctypes.c_int32)

_u16p = ctypes.POINTER(ctypes.c_uint16)
_f64p = ctypes.POINTER(ctypes.c_double)

_lib.xxhash64_batch.argtypes = [_u8p, _i64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u64p]
_lib.classify_types_batch.argtypes = [_u8p, _i64p, _u8p, ctypes.c_int64, _i32p]
_lib.string_lengths_batch.argtypes = [_u8p, _i64p, _u8p, ctypes.c_int64, _i32p]
_lib.hll_pack_f64.argtypes = [_f64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u16p]
_lib.hll_pack_i64.argtypes = [_i64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u16p]
_lib.hll_pack_strings.argtypes = [_u8p, _i64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u16p]
_f32p = ctypes.POINTER(ctypes.c_float)
for _name, _vp in (
    ("block_stats_f64", _f64p), ("block_stats_f32", _f32p),
    ("block_stats_i64", _i64p), ("block_stats_i32", _i32p),
):
    getattr(_lib, _name).argtypes = [_vp, _u8p, ctypes.c_int64, _f64p]
_lib.block_comoments_f64.argtypes = [_f64p, _f64p, _u8p, ctypes.c_int64, _f64p]
_lib.block_hll_f64.argtypes = [_f64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u8p]
_lib.block_hll_i64.argtypes = [_i64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u8p]
_lib.block_hll_strings.argtypes = [_u8p, _i64p, _u8p, ctypes.c_int64, ctypes.c_uint64, _u8p]
_lib.block_kll_sample_f64.argtypes = [
    _f64p, _u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint32,
    _f64p, _i64p, _f64p,
]
_lib.dict_masked_bincount.argtypes = [
    _i32p, _u8p, ctypes.c_int64, ctypes.c_int64, _i64p,
]
_lib.block_kll_pick_f64.argtypes = [
    _f64p, _u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint32,
    ctypes.c_int64, _f64p, _i64p,
]
_lib.block_kll_pick_i64.argtypes = [
    _i64p, _u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint32,
    ctypes.c_int64, _f64p, _i64p,
]
_lib.pattern_match_batch.argtypes = [
    _u8p, _i64p, _u8p, ctypes.c_int64, ctypes.c_char_p, _u8p,
]
_lib.pattern_match_batch.restype = ctypes.c_int


def _arrow_layout(values):
    """(data u8[:], offsets i64[n+1], valid u8[n]) from an object array of
    str/None OR directly from a pyarrow string array (no per-value python
    object materialization — the fast path for lazy string columns)."""
    if isinstance(values, pa.Array):
        arr = values
        if not pa.types.is_large_string(arr.type):
            arr = arr.cast(pa.large_string())  # widens offsets only
    else:
        arr = pa.array(values, type=pa.large_string(), from_pandas=True)
    buffers = arr.buffers()  # [validity, offsets, data]
    n = len(arr)
    offsets = np.frombuffer(buffers[1], dtype=np.int64, count=n + 1 + arr.offset)
    if arr.offset:
        offsets = offsets[arr.offset:]
    data_buf = buffers[2]
    data = (
        np.frombuffer(data_buf, dtype=np.uint8)
        if data_buf is not None and len(data_buf) > 0
        else np.zeros(1, dtype=np.uint8)
    )
    if arr.null_count:
        valid = np.asarray(arr.is_valid()).astype(np.uint8)
    else:
        valid = np.ones(n, dtype=np.uint8)
    return data, np.ascontiguousarray(offsets), valid


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctype)


def native_xxhash64_strings(values: np.ndarray, seed: int) -> np.ndarray:
    data, offsets, valid = _arrow_layout(values)
    n = len(values)
    out = np.empty(n, dtype=np.uint64)
    _lib.xxhash64_batch(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p),
        n, ctypes.c_uint64(seed), _ptr(out, _u64p),
    )
    return out


def native_classify_types(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    data, offsets, valid = _arrow_layout(values)
    valid = valid & np.asarray(mask, dtype=np.uint8)
    n = len(values)
    out = np.empty(n, dtype=np.int32)
    _lib.classify_types_batch(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p), n, _ptr(out, _i32p)
    )
    return out


def native_string_lengths(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    data, offsets, valid = _arrow_layout(values)
    valid = valid & np.asarray(mask, dtype=np.uint8)
    n = len(values)
    out = np.empty(n, dtype=np.int32)
    _lib.string_lengths_batch(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p), n, _ptr(out, _i32p)
    )
    return out


def native_hll_pack_numeric(values: np.ndarray, mask: np.ndarray, seed: int) -> np.ndarray:
    """uint16 (idx<<6)|pw HLL feature per row from a numeric array; nulls -> 0.
    Doubles hash as IEEE754 bits (-0.0 normalized), integrals/booleans as
    int64 — matching Spark's per-type layout (see ops/hashing.hash_column)."""
    n = len(values)
    out = np.empty(n, dtype=np.uint16)
    valid = None if mask is None else np.ascontiguousarray(mask, dtype=np.uint8)
    vp = _ptr(valid, _u8p) if valid is not None else None
    if np.issubdtype(values.dtype, np.floating):
        vals = np.ascontiguousarray(values, dtype=np.float64)
        _lib.hll_pack_f64(_ptr(vals, _f64p), vp, n, ctypes.c_uint64(seed), _ptr(out, _u16p))
    else:
        vals = np.ascontiguousarray(values, dtype=np.int64)
        _lib.hll_pack_i64(_ptr(vals, _i64p), vp, n, ctypes.c_uint64(seed), _ptr(out, _u16p))
    return out


def native_hll_pack_strings(values: np.ndarray, mask: np.ndarray, seed: int) -> np.ndarray:
    data, offsets, valid = _arrow_layout(values)
    if mask is not None:
        valid = valid & np.asarray(mask, dtype=np.uint8)
    n = len(values)
    out = np.empty(n, dtype=np.uint16)
    _lib.hll_pack_strings(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p),
        n, ctypes.c_uint64(seed), _ptr(out, _u16p),
    )
    return out


# -- block-partial reduction kernels (ingest tier) ---------------------------

_BLOCK_STATS = {
    np.dtype(np.float64): ("block_stats_f64", _f64p),
    np.dtype(np.float32): ("block_stats_f32", _f32p),
    np.dtype(np.int64): ("block_stats_i64", _i64p),
    np.dtype(np.int32): ("block_stats_i32", _i32p),
}


def _mask_u8(mask):
    if mask is None:
        return None, None
    m = np.ascontiguousarray(mask, dtype=np.uint8)
    return m, _ptr(m, _u8p)


def native_block_stats(values: np.ndarray, mask) -> np.ndarray:
    """One C pass -> [count, sum, min, max, m2, nonnan, max_nonnan] over the
    masked block (min/max follow the NaN-largest order; slots 5-6 let the
    KLL sampler skip its counting pass)."""
    entry = _BLOCK_STATS.get(values.dtype)
    if entry is None:
        values = np.ascontiguousarray(values, dtype=np.float64)
        entry = _BLOCK_STATS[values.dtype]
    else:
        values = np.ascontiguousarray(values)
    name, vp = entry
    out = np.empty(7, dtype=np.float64)
    _m, mp = _mask_u8(mask)
    getattr(_lib, name)(_ptr(values, vp), mp, len(values), _ptr(out, _f64p))
    return out


def native_block_kll_pick(values: np.ndarray, mask, k: int, tick: int, nv: int):
    """(items f64[k] sorted asc with +inf padding, m, h) — the pick-only KLL
    sampler for callers that already know the non-NaN valid count ``nv``
    from a shared block_stats pass (one less memory sweep). int64 columns
    dispatch to the i64 kernel, which converts per PICKED item instead of
    paying a full-column f64 conversion copy."""
    k = max(int(k), 1)  # keep the buffer in step with the kernel's k clamp
    # 4k wide: the kernel's stride policy picks up to two levels denser
    items = np.full(4 * k, np.inf, dtype=np.float64)
    meta = np.zeros(2, dtype=np.int64)
    _m, mp = _mask_u8(mask)
    if values.dtype == np.int64 and values.flags.c_contiguous:
        _lib.block_kll_pick_i64(
            _ptr(values, _i64p), mp, len(values), ctypes.c_int32(k),
            ctypes.c_uint32(tick & 0xFFFFFFFF), ctypes.c_int64(nv),
            _ptr(items, _f64p), _ptr(meta, _i64p),
        )
    else:
        vals = np.ascontiguousarray(values, dtype=np.float64)
        _lib.block_kll_pick_f64(
            _ptr(vals, _f64p), mp, len(vals), ctypes.c_int32(k),
            ctypes.c_uint32(tick & 0xFFFFFFFF), ctypes.c_int64(nv),
            _ptr(items, _f64p), _ptr(meta, _i64p),
        )
    m = int(meta[0])
    items[m:] = np.inf
    return items, m, int(meta[1])


def native_block_comoments(x: np.ndarray, y: np.ndarray, mask) -> np.ndarray:
    """[n, xsum, ysum, ck, xmk, ymk] co-moments over the jointly-masked block."""
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    out = np.empty(6, dtype=np.float64)
    _m, mp = _mask_u8(mask)
    _lib.block_comoments_f64(_ptr(x, _f64p), _ptr(y, _f64p), mp, len(x), _ptr(out, _f64p))
    return out


def native_block_hll(values: np.ndarray, mask, seed: int,
                     regs: np.ndarray | None = None) -> np.ndarray:
    """Update (or create) a uint8[512] HLL register block from numeric values."""
    if regs is None:
        regs = np.zeros(512, dtype=np.uint8)
    _m, mp = _mask_u8(mask)
    if np.issubdtype(values.dtype, np.floating):
        vals = np.ascontiguousarray(values, dtype=np.float64)
        _lib.block_hll_f64(_ptr(vals, _f64p), mp, len(vals), ctypes.c_uint64(seed), _ptr(regs, _u8p))
    else:
        vals = np.ascontiguousarray(values, dtype=np.int64)
        _lib.block_hll_i64(_ptr(vals, _i64p), mp, len(vals), ctypes.c_uint64(seed), _ptr(regs, _u8p))
    return regs


def native_block_hll_strings(values: np.ndarray, mask, seed: int,
                             regs: np.ndarray | None = None) -> np.ndarray:
    if regs is None:
        regs = np.zeros(512, dtype=np.uint8)
    data, offsets, valid = _arrow_layout(values)
    if mask is not None:
        valid = valid & np.asarray(mask, dtype=np.uint8)
    _lib.block_hll_strings(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p),
        len(values), ctypes.c_uint64(seed), _ptr(regs, _u8p),
    )
    return regs


def native_block_kll_sample(values: np.ndarray, mask, k: int, tick: int):
    """(items f64[4k] sorted asc with +inf padding beyond m, m, h, nv,
    min, max) — m <= 2k after the in-kernel dense-pick compaction."""
    k = max(int(k), 1)  # keep the buffer in step with the kernel's k clamp
    vals = np.ascontiguousarray(values, dtype=np.float64)
    # 4k wide: the kernel's stride policy picks up to two levels denser
    items = np.full(4 * k, np.inf, dtype=np.float64)
    meta = np.zeros(3, dtype=np.int64)
    minmax = np.zeros(2, dtype=np.float64)
    _m, mp = _mask_u8(mask)
    _lib.block_kll_sample_f64(
        _ptr(vals, _f64p), mp, len(vals), ctypes.c_int32(k),
        ctypes.c_uint32(tick & 0xFFFFFFFF),
        _ptr(items, _f64p), _ptr(meta, _i64p), _ptr(minmax, _f64p),
    )
    m, h, nv = int(meta[0]), int(meta[1]), int(meta[2])
    items[m:] = np.inf
    if nv == 0:
        # identity element: no items, min/max at the fold identities
        return items, 0, 0, 0, np.inf, -np.inf
    return items, m, h, nv, float(minmax[0]), float(minmax[1])


def native_pattern_match(values, mask, pattern: str):
    """bool[n] unanchored non-empty regex match per row, computed GIL-free
    by PCRE2 over the Arrow string buffers. Returns None when PCRE2 is
    unavailable or refuses the pattern (caller falls back to Python `re`).
    Rows PCRE2 cannot judge (sentinel 2, e.g. invalid UTF-8) are re-checked
    under Python `re` so the result is always `re`-exact."""
    data, offsets, valid = _arrow_layout(values)
    if mask is not None:
        valid = valid & np.asarray(mask, dtype=np.uint8)
    n = len(valid)
    out = np.zeros(n, dtype=np.uint8)
    rc = _lib.pattern_match_batch(
        _ptr(data, _u8p), _ptr(offsets, _i64p), _ptr(valid, _u8p),
        ctypes.c_int64(n), pattern.encode("utf-8"), _ptr(out, _u8p),
    )
    if rc != 0:
        return None
    result = out == 1
    undecided = np.flatnonzero(out == 2)
    if undecided.size:
        import re as _re

        compiled = _re.compile(pattern)
        for i in undecided:
            s = int(offsets[i])
            e = int(offsets[i + 1])
            try:
                text = bytes(data[s:e]).decode("utf-8", errors="surrogateescape")
            except Exception:  # noqa: BLE001
                result[i] = False
                continue
            m = compiled.search(text)
            result[i] = bool(m) and m.group(0) != ""
    return result


def native_dict_masked_bincount(
    codes: np.ndarray, mask, num_cats: int
) -> np.ndarray:
    """int64[num_cats + 1] counts of each dictionary code among masked rows;
    masked-out or out-of-range rows land in the final sentinel slot. ONE
    memory pass shared by every per-batch dictionary consumer (type-class
    histogram, HLL present-entry fold, frequency counts)."""
    codes = np.ascontiguousarray(codes, dtype=np.int32)
    out = np.zeros(int(num_cats) + 1, dtype=np.int64)
    _m, mp = _mask_u8(mask)
    _lib.dict_masked_bincount(
        _ptr(codes, _i32p), mp, len(codes), ctypes.c_int64(int(num_cats)),
        _ptr(out, _i64p),
    )
    return out

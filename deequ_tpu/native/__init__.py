"""Native (C++) runtime kernels for host-side hot loops.

The reference's "native tier" is its set of Catalyst ImperativeAggregate /
UDAF kernels injected into Spark internals (reference
`analyzers/catalyst/*.scala`). Here the device tier is XLA/Pallas; this
package holds the *host* native tier: batch string hashing, regex/type
classification, HLL ingest packing and group-by keying over Arrow buffers,
compiled from C++ (`deequ_tpu/native/src/`) and loaded via ctypes.

Falls back to pure Python (exports = None) when the shared library has not
been built; build with `python -m deequ_tpu.native.build`.
"""

from __future__ import annotations

native_xxhash64_strings = None
native_classify_types = None
native_string_lengths = None
native_hll_pack_numeric = None
native_hll_pack_strings = None
native_block_stats = None
native_block_comoments = None
native_block_hll = None
native_block_hll_strings = None
native_block_kll_sample = None
native_dict_masked_bincount = None
native_block_kll_pick = None
native_pattern_match = None

try:  # pragma: no cover - exercised when the native lib is built
    from .lib import (  # noqa: F401
        native_block_comoments,
        native_block_hll,
        native_block_hll_strings,
        native_block_kll_pick,
        native_block_kll_sample,
        native_dict_masked_bincount,
        native_block_stats,
        native_classify_types,
        native_hll_pack_numeric,
        native_hll_pack_strings,
        native_pattern_match,
        native_string_lengths,
        native_xxhash64_strings,
    )
except Exception:  # noqa: BLE001
    pass

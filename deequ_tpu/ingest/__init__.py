"""High-throughput ingestion plane: socket to device.

The engine scans faster than a naive feed link can serve it. This
package is the frontend between the two:

- :mod:`.columnar` — in-process coercion: dict-of-numpy / Arrow tables /
  record batches become :class:`~deequ_tpu.data.Dataset` with no pandas
  hop (`as_dataset`);
- :mod:`.arrow_stream` — the zero-copy Arrow IPC wire format: typed,
  checksummed, fault-injectable frame decode (`iter_frames`) and the
  per-frame atomic fold into a streaming session (`fold_stream`);
- :mod:`.endpoint` — the HTTP frontend riding the MetricsExporter plane
  (``POST /ingest/v1/<tenant>/<dataset>``);
- :mod:`.rowgate` — row-level ingest gating: one vectorized conformance
  mask per frame BEFORE the fold, clean rows fold bit-exact, rejects go
  to a typed, bounded, content-addressed Arrow quarantine sidecar
  (`RowGate`, `QuarantineSidecar`);
- :mod:`.prefetch` — the double-buffered host->device feed pipeline the
  engine's device pass pulls batches through
  (`PrefetchingBatchIterator`, ``DEEQU_TPU_PREFETCH_DEPTH``).
"""

from ..exceptions import (
    FeedDisconnectError,
    FeedStallError,
    MalformedFrameError,
)
from .arrow_stream import (
    CHECKSUM_HEADER,
    BoundedReader,
    IngestReport,
    encode_ipc_stream,
    fold_stream,
    fold_stream_reader,
    iter_frames,
)
from .columnar import as_dataset, payload_bytes
from .endpoint import INGEST_PREFIX, IngestEndpoint
from .rowgate import (
    DEFAULT_QUARANTINE_MAX_ROWS,
    QUARANTINE_MAX_ROWS_ENV,
    FrameQuarantinedError,
    QuarantineSidecar,
    RowGate,
    quarantine_max_rows,
)
from .prefetch import (
    DEFAULT_FEED_STALL_S,
    DEFAULT_PREFETCH_DEPTH,
    FEED_STALL_ENV,
    PREFETCH_DEPTH_ENV,
    PrefetchingBatchIterator,
    feed_stall_s,
    prefetch_depth,
)

__all__ = [
    "as_dataset", "payload_bytes",
    "encode_ipc_stream", "iter_frames", "fold_stream", "IngestReport",
    "fold_stream_reader", "BoundedReader",
    "CHECKSUM_HEADER", "INGEST_PREFIX", "IngestEndpoint",
    "PrefetchingBatchIterator", "prefetch_depth", "feed_stall_s",
    "PREFETCH_DEPTH_ENV", "DEFAULT_PREFETCH_DEPTH",
    "FEED_STALL_ENV", "DEFAULT_FEED_STALL_S",
    "MalformedFrameError", "FeedDisconnectError", "FeedStallError",
    "RowGate", "QuarantineSidecar", "FrameQuarantinedError",
    "quarantine_max_rows", "QUARANTINE_MAX_ROWS_ENV",
    "DEFAULT_QUARANTINE_MAX_ROWS",
]

"""Failure taxonomy for metric computation.

Mirrors the reference's typed exception hierarchy
(`analyzers/runners/MetricCalculationException.scala:19-78`): every analyzer
error is captured as a Failure *metric*, never an aborted run — partial
results are a feature (`analyzers/Analyzer.scala:94-103`).
"""

from __future__ import annotations


class MetricCalculationException(Exception):
    """Base for all metric-calculation failures."""


class MetricCalculationPreconditionException(MetricCalculationException):
    """Schema precondition failed before any data was scanned."""


class MetricCalculationRuntimeException(MetricCalculationException):
    """Failure while computing the metric from data."""


class NoSuchColumnException(MetricCalculationPreconditionException):
    pass


class WrongColumnTypeException(MetricCalculationPreconditionException):
    pass


class NoColumnsSpecifiedException(MetricCalculationPreconditionException):
    pass


class NumberOfSpecifiedColumnsException(MetricCalculationPreconditionException):
    pass


class IllegalAnalyzerParameterException(MetricCalculationPreconditionException):
    pass


class EmptyStateException(MetricCalculationRuntimeException):
    """All input values were null/filtered — no state to finalize."""


class DeviceFailureException(MetricCalculationRuntimeException):
    """The accelerator tier failed for INFRASTRUCTURE reasons (XLA runtime
    error, lost device, host-link fault) rather than anything about the
    data or the analyzer. The reliability layer treats this class as
    tier-recoverable: the same battery re-runs on the host ingest tier,
    which shares no device state with the failed pass."""


class DeviceOOMException(DeviceFailureException):
    """The device ran out of memory executing a pass. Recoverable by batch
    bisection (smaller padded batches shrink the live feature set) before
    the general host-tier failover applies."""


class PoisonedBatchException(MetricCalculationRuntimeException):
    """A specific input batch cannot be processed (corrupt encoding,
    malformed values past the dry-run validation). Carries the batch index
    so operators can quarantine the slice."""

    def __init__(self, batch_index: int, message: str = ""):
        self.batch_index = batch_index
        super().__init__(
            f"batch {batch_index} is poisoned{': ' + message if message else ''}"
        )


class AnalyzerFaultException(MetricCalculationRuntimeException):
    """A fault attributable to ONE analyzer inside a fused battery. The
    isolation machinery bisects the battery until the faulty analyzer is
    alone in its partition, degrades it to a typed Failure metric, and
    completes the rest."""


class CorruptStateError(MetricCalculationRuntimeException, ValueError):
    """A persisted payload (state blob, repository entry, checkpoint) failed
    its integrity check: the stored xxhash64 content checksum does not match
    the bytes on disk, or the payload is structurally torn. The data plane
    treats this as RECOVERABLE, never fatal: corrupt checkpoints fall back
    to a fresh fold (the resume point is lost, the results are not), corrupt
    repository entries are quarantined to a ``.quarantine/`` sidecar instead
    of poisoning query loaders, and corrupt state blobs degrade exactly the
    analyzers that needed them to typed ``Failure`` metrics. The reference
    assumes torn/garbled state rather than hoping against it — its per-type
    binary codecs pin byte layouts precisely (`StateProvider.scala:187-311`);
    the checksum is our equivalent tripwire."""

    def __init__(self, kind: str, source: str, detail: str = ""):
        self.kind = kind
        self.source = source
        super().__init__(
            f"corrupt {kind} at {source}"
            + (f": {detail}" if detail else "")
        )


class SchemaDriftError(MetricCalculationRuntimeException):
    """A streaming micro-batch's schema drifted from the session's
    :class:`~deequ_tpu.service.drift.SchemaContract` (column added/dropped/
    retyped beyond a compatible widening). Raised BEFORE the batch folds,
    so persisted algebraic states are never contaminated by mixed-schema
    merges. Carries the structured drift list for operator triage."""

    def __init__(self, session: str, drifts):
        self.session = session
        self.drifts = list(drifts)
        super().__init__(
            f"schema drift in session {session}: " + "; ".join(self.drifts)
        )


class ShardLossError(DeviceFailureException):
    """A shard of a multi-device mesh was lost mid-pass: a dead device, a
    dead ``jax.distributed`` process, or a heartbeat-declared stall. Unlike
    a plain :class:`DeviceFailureException` (one sick accelerator, recover
    on the host), a shard loss is MESH-recoverable: the surviving shards'
    algebraic states are mergeable by construction, so the elastic layer
    (`deequ_tpu.parallel.elastic`) salvages them, rebuilds the mesh over
    the surviving devices one ladder rung down, and resumes the fold —
    ``classify_failure`` maps this class to ``"mesh"`` so an escaped loss
    re-shards BEFORE the host-tier failover applies.

    ``lost`` holds the mesh positions (indices into ``mesh.devices.flat``)
    declared dead; ``survivors`` optionally carries the surviving device
    objects so a pass-level retry can rebuild a mesh without re-probing."""

    def __init__(self, lost, site: str = "", survivors=None, detail: str = ""):
        self.lost = tuple(int(i) for i in lost)
        self.site = site
        self.survivors = None if survivors is None else list(survivors)
        super().__init__(
            f"mesh shard loss at {site or '<mesh>'}: shard(s) "
            f"{list(self.lost)} lost"
            + (f": {detail}" if detail else "")
        )


class ShardStallError(ShardLossError):
    """A shard stopped making progress (heartbeat probe exceeded
    ``DEEQU_TPU_SHARD_HEARTBEAT_S``) without raising. Declared lost after
    the probe deadline — the hang-not-crash failure mode on a mesh, handled
    exactly like a thrown shard loss (salvage + re-shard), mirroring how
    :class:`ScanStallError` piggybacks on the device-failover path."""


class MalformedFrameError(MetricCalculationRuntimeException, ValueError):
    """A frame on the ingestion plane failed to decode: torn Arrow IPC
    bytes, a schema message that is not a schema, or a payload whose
    declared checksum does not match the bytes received. Raised BEFORE
    anything folds, so a corrupt producer can never contaminate a
    session's persisted states — the frame is rejected typed and the
    stream position it occupied is reported for operator triage."""

    def __init__(self, source: str, detail: str = "", frame_index: int = -1):
        self.source = source
        self.frame_index = int(frame_index)
        where = f" (frame {frame_index})" if frame_index >= 0 else ""
        super().__init__(
            f"malformed ingest frame from {source}{where}"
            + (f": {detail}" if detail else "")
        )


class FeedDisconnectError(MetricCalculationRuntimeException):
    """An ingest stream ended mid-frame: the producer disconnected, the
    socket died, or the payload was truncated below its declared length.
    Frames that decoded COMPLETELY before the disconnect have already
    folded (each is one atomic micro-batch merge); the torn tail frame
    never touches state. Carries how far the stream got so a resuming
    producer knows what committed."""

    def __init__(self, source: str, frames_decoded: int = 0,
                 bytes_read: int = 0, detail: str = ""):
        self.source = source
        self.frames_decoded = int(frames_decoded)
        self.bytes_read = int(bytes_read)
        super().__init__(
            f"ingest feed from {source} disconnected mid-frame after "
            f"{frames_decoded} complete frame(s), {bytes_read} byte(s)"
            + (f": {detail}" if detail else "")
        )


class FeedStallError(DeviceFailureException):
    """The prefetching feed pipeline that stages host->device transfers
    stopped delivering batches (a wedged transfer thread, a starved
    source). Deliberately a ``DeviceFailureException`` subclass: the
    pipeline only exists on the device tier, so ``classify_failure``
    routes the pass to the host tier — whose chunk iteration shares none
    of the stalled machinery — exactly like a thrown device fault."""

    def __init__(self, site: str, detail: str = ""):
        self.site = site
        super().__init__(
            f"ingest feed pipeline stalled at {site}"
            + (f": {detail}" if detail else "")
        )


class ScanStallError(DeviceFailureException):
    """A device or host-tier pass exceeded its watchdog deadline without
    finishing OR failing — the hang-not-crash failure mode the exception-
    driven reliability layer cannot see. Deliberately a
    ``DeviceFailureException`` subclass: ``classify_failure`` then maps it
    to the tier-failover path (the battery re-runs on the other tier with
    fresh states) and the service's placement router puts the battery on
    probation, exactly like a thrown device fault."""

    def __init__(self, site: str, deadline_s: float, waited_s: float):
        self.site = site
        self.deadline_s = deadline_s
        self.waited_s = waited_s
        super().__init__(
            f"scan watchdog: {site} pass exceeded its {deadline_s:.1f}s "
            f"deadline (waited {waited_s:.1f}s); cancelling and failing over"
        )


class UnsupportedFormatVersionError(Exception):
    """A persisted payload (metrics-history JSON or .npz state blob) carries
    a format version this build does not understand. Raised INSTEAD of
    silently misreading a layout from a newer build (SURVEY §7 hard part 5:
    incremental-state serialization stability across versions)."""

    def __init__(self, kind: str, found: int, supported: int):
        self.kind = kind
        self.found = found
        self.supported = supported
        super().__init__(
            f"{kind} format version {found} is not supported by this build "
            f"(max supported: {supported}). Upgrade deequ_tpu to read this "
            f"payload, or re-materialize it with the current build."
        )


def wrap_if_necessary(exception: BaseException) -> MetricCalculationException:
    """Wrap arbitrary errors into the taxonomy
    (reference `MetricCalculationException.scala:70-78`)."""
    if isinstance(exception, MetricCalculationException):
        return exception
    wrapped = MetricCalculationRuntimeException(str(exception))
    wrapped.__cause__ = exception
    return wrapped


#: Typed exceptions that LIVE next to their subsystem (import cycles or
#: cohesion keep them out of this module) but are part of the package's
#: failure taxonomy: each is importable from here lazily, and the invariant
#: linter (tools/statlint, failure-registry check) requires every exception
#: class defined outside the registry modules (this file, service/errors.py,
#: runners/exceptions.py, reliability/faults.py) to be listed in this
#: mapping — a typed failure nobody can discover is not typed.
_SUBSYSTEM_EXCEPTIONS = {
    "SerializationError": "deequ_tpu.repository.serde",
    "ExpressionError": "deequ_tpu.expr",
    "FrequencyBudgetExceeded": "deequ_tpu.analyzers.grouping",
    "MeshExhaustedError": "deequ_tpu.parallel.elastic",
    "HostLossError": "deequ_tpu.cluster.membership",
    "CatalogError": "deequ_tpu.service.catalog",
    "FrameQuarantinedError": "deequ_tpu.ingest.rowgate",
}


def __getattr__(name: str):
    """PEP 562 lazy re-export of the subsystem exceptions (eager imports
    here would cycle: every subsystem imports this module)."""
    target = _SUBSYSTEM_EXCEPTIONS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(target), name)

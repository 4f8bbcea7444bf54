"""Double-buffered host->device feed pipeline.

The engine's device pass is a chain of async XLA dispatches; what gates it
is the HOST side of each batch — feature build plus the ``jax.device_put``
host->device copy. This module is the reusable seam: a dedicated feed
thread stages batch k+1 (and with depth 2, k+2) — building features and
starting its device transfer — while batch k's fold executes, so the
transfer time hides under device compute instead of serializing with it.
Batch shapes stay pow2-bucketed upstream (`service.streaming` /
`runners.engine.effective_batch_size`), so staging ahead never provokes a
recompile — every staged batch reuses the one compiled program shape.

``DEEQU_TPU_PREFETCH_DEPTH`` sizes the pipeline (default 2 = classic
double buffering: one batch in flight on device, one staged, one being
built). ``0`` disables the feed thread entirely — batches produce inline
on the consumer thread — which is the measured "serial" baseline the
PERF.md overlap numbers compare against. Unparseable values warn once and
keep the default (the watchdog env convention).

Failure contract: an exception inside the feed thread (a poisoned batch,
an injected ``feed_stall``, a device_put infrastructure error) propagates
to the consumer on its next pull — same semantics as the inline path —
and the pipeline shuts down; a feed thread that goes SILENT (a hung
transfer that neither returns nor raises) trips the consumer's stall
deadline (``DEEQU_TPU_FEED_STALL_S``, default 120s, <=0 disables) as a
typed ``FeedStallError``, which is a ``DeviceFailureException`` — the
pass fails over to the host tier exactly like a thrown device fault. The
``prefetch`` fault site fires before each staged batch so chaos tests
can wedge or kill the feed on demand.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator, Optional

#: env var sizing the staged-batch pipeline (0 = serial, no feed thread)
PREFETCH_DEPTH_ENV = "DEEQU_TPU_PREFETCH_DEPTH"
DEFAULT_PREFETCH_DEPTH = 2

#: env var: seconds the consumer waits on a silent feed thread before
#: declaring it wedged with a typed FeedStallError (<= 0 disables).
#: Generous by default — a healthy produce is sub-second per batch — so
#: only a genuinely hung device_put / wedged source trips it.
FEED_STALL_ENV = "DEEQU_TPU_FEED_STALL_S"
DEFAULT_FEED_STALL_S = 120.0

def prefetch_depth() -> int:
    """The configured pipeline depth (env override > tuned > static 2);
    warn-and-fallback on bad values."""
    from ..tuning import knobs

    return knobs.value("prefetch_depth")


def feed_stall_s() -> float:
    """The configured feed-stall deadline (<= 0 = disabled);
    warn-and-fallback on bad values."""
    from ..utils import env_number

    return env_number(FEED_STALL_ENV, DEFAULT_FEED_STALL_S, float)


def staging_depth(n_batches: int):
    """The pipeline depth for a pass of ``n_batches``: ``None`` (the
    configured depth) for multi-batch passes, ``0`` (inline, no feed
    thread) for a single-batch pass. Double-buffering a one-batch fold
    has nothing to overlap with, so the feed thread's spawn/teardown is
    pure fixed cost — measurable on the streaming plane, where every
    micro-batch fold is a one-batch pass (the ~50ms/fold knee diet). The
    inline path keeps the ``prefetch`` fault site and identical ordering,
    so semantics are unchanged — this is the documented "serial" mode
    applied exactly where serial is optimal."""
    return 0 if n_batches <= 1 else None


#: queue sentinel kinds
_ITEM, _DONE, _ERROR = 0, 1, 2


class PrefetchingBatchIterator:
    """Iterate ``produce()`` results through a bounded staging pipeline.

    ``produce`` is called repeatedly on the feed thread; it returns the
    next staged item or ``None`` at end of input (the engine's existing
    producer contract). Up to ``depth`` finished items wait in the stage
    queue while the consumer folds; ``depth=0`` degenerates to calling
    ``produce`` inline (no thread, bit-identical ordering).

    The iterator is a context manager; exiting (or ``close()``) tears the
    feed thread down even when the consumer stopped early."""

    def __init__(
        self,
        produce: Callable[[], Optional[Any]],
        *,
        depth: Optional[int] = None,
        stall_timeout_s: Optional[float] = None,
        name: str = "deequ-ingest-prefetch",
    ):
        self._produce = produce
        self.depth = prefetch_depth() if depth is None else max(0, int(depth))
        #: how long the consumer tolerates a SILENT feed thread before
        #: raising typed FeedStallError (<= 0 disables the deadline)
        self.stall_timeout_s = (
            feed_stall_s() if stall_timeout_s is None else float(stall_timeout_s)
        )
        self._closed = threading.Event()
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._index = 0
        if self.depth > 0:
            self._queue = queue.Queue(maxsize=self.depth)
            self._thread = threading.Thread(
                target=self._feed_loop, name=name, daemon=True
            )
            self._thread.start()

    # -- feed thread ---------------------------------------------------------

    def _feed_loop(self) -> None:
        from ..reliability.faults import fault_point

        index = 0
        while not self._closed.is_set():
            try:
                # chaos site: an injected feed_stall wedges/kills the feed
                # exactly where a real transfer thread would
                fault_point("prefetch", tag=str(index))
                item = self._produce()
            except BaseException as exc:  # noqa: BLE001 - propagate to
                # the consumer: KeyboardInterrupt-class injections must
                # ride out exactly like on the inline path
                self._put((_ERROR, exc))
                return
            if item is None:
                self._put((_DONE, None))
                return
            if not self._put((_ITEM, item)):
                return  # consumer closed while we were staging
            index += 1

    def _put(self, entry) -> bool:
        """Bounded put that aborts when the consumer closed the pipeline
        (a consumer that stopped early must not leave this thread parked
        on a full queue forever)."""
        while not self._closed.is_set():
            try:
                self._queue.put(entry, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side -------------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self.depth == 0:
            from ..reliability.faults import fault_point

            fault_point("prefetch", tag=str(self._index))
            self._index += 1
            item = self._produce()
            if item is None:
                raise StopIteration
            return item
        if self._closed.is_set():
            raise StopIteration
        deadline = self.stall_timeout_s
        try:
            if deadline and deadline > 0:
                kind, value = self._queue.get(timeout=deadline)
            else:
                kind, value = self._queue.get()
        except queue.Empty:
            # the feed thread went SILENT past the stall deadline (a hung
            # device_put, a wedged source): declare it typed — a
            # DeviceFailureException, so the pass fails over to the host
            # tier, whose chunk iteration shares none of this machinery
            from ..exceptions import FeedStallError

            self.close()
            raise FeedStallError(
                "prefetch",
                f"feed thread produced nothing for {deadline:.0f}s",
            ) from None
        if kind == _ITEM:
            return value
        self._closed.set()
        if kind == _ERROR:
            raise value
        raise StopIteration

    def close(self) -> None:
        """Tear the pipeline down (idempotent): wakes a feed thread parked
        on a full queue and joins it. Staged-but-unconsumed items are
        dropped — the consumer abandoning a pass does exactly that."""
        self._closed.set()
        if self._thread is not None:
            # drain so a blocked put's retry loop sees closed immediately
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> "PrefetchingBatchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

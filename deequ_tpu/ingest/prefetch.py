"""Staged host->device feed pipeline.

The engine's device pass is a chain of async XLA dispatches; what gates it
is the HOST side of each batch — slicing, feature build and the
``jax.device_put`` host->device copy. This module is the reusable seam: a
pool of feed workers stages the batches ahead of the fold — each worker
takes the next batch index, builds its features and starts its device
transfer — while the dispatching thread folds the staged batches strictly
in batch order, so the states fold exactly as a serial pass folds them.
The staging work releases the GIL (numpy over whole batches, the native
packers, pyarrow slicing), so several workers overlap on the host's cores.
Batch shapes stay pow2-bucketed upstream (`service.streaming` /
`runners.engine.effective_batch_size`), so staging ahead never provokes a
recompile — every staged batch reuses the one compiled program shape.

``DEEQU_TPU_PREFETCH_DEPTH`` sizes the pipeline (default 2): at most
``workers + depth`` batches are staged, or being staged, ahead of the one
the consumer takes next, which bounds host and device memory. ``0``
disables the feed threads entirely — batches produce inline on the
consumer thread — which is the measured "serial" baseline. Unparseable
values warn once and keep the default (the watchdog env convention).
``feed_workers`` sizes the pool from what the process can observe: the
CPUs it may use and the pass's batch count.

Failure contract: an exception while staging batch k (a poisoned batch,
an injected ``feed_stall``, a device_put infrastructure error) reaches
the consumer when it reaches batch k — after every earlier batch, same
semantics as the inline path — and the pool shuts down; a pool that goes
SILENT (a hung transfer that neither returns nor raises) trips the
consumer's stall deadline (``DEEQU_TPU_FEED_STALL_S``, default 120s, <=0
disables) as a typed ``FeedStallError``, which is a
``DeviceFailureException`` — the pass fails over to the host tier exactly
like a thrown device fault. The ``prefetch`` fault site fires once before
each staged batch, in batch order, tagged with its index, so chaos tests
can wedge or kill the feed on demand.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

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
    thread) for a single-batch pass. Staging a one-batch fold ahead
    has nothing to overlap with, so the feed thread's spawn/teardown is
    pure fixed cost — measurable on the streaming plane, where every
    micro-batch fold is a one-batch pass (the ~50ms/fold knee diet). The
    inline path keeps the ``prefetch`` fault site and identical ordering,
    so semantics are unchanged — this is the documented "serial" mode
    applied exactly where serial is optimal."""
    return 0 if n_batches <= 1 else None


def feed_workers(n_batches: int) -> int:
    """The feed pool's size for a pass of ``n_batches``: half the CPUs
    this process may use, which leaves cores to the dispatching thread and
    XLA's host threads, and no more workers than batches. One usable CPU,
    or one batch, gives the one feed thread."""
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - platforms without affinity
        cpus = os.cpu_count() or 1
    return max(1, min(int(n_batches), cpus // 2))


class PrefetchingBatchIterator:
    """Iterate ``produce(index)`` for ``index = start, ..., count - 1``
    through a bounded staging pool, in index order.

    ``produce(index)`` runs on a feed worker and returns the staged item
    of batch ``index``. ``workers`` threads stage concurrently; a worker
    takes index ``i`` only while ``i < next + workers + depth``, where
    ``next`` is the index the consumer takes next. ``depth=0`` degenerates
    to calling ``produce`` inline (no thread, bit-identical ordering).

    ``inflight_peak`` is the most items that were being staged at once.
    The iterator is a context manager; exiting (or ``close()``) tears
    every worker down even when the consumer stopped early."""

    def __init__(
        self,
        produce: Callable[[int], Any],
        count: int,
        *,
        start: int = 0,
        depth: Optional[int] = None,
        workers: int = 1,
        stall_timeout_s: Optional[float] = None,
        name: str = "deequ-ingest-prefetch",
    ):
        self._produce = produce
        self.depth = prefetch_depth() if depth is None else max(0, int(depth))
        #: feed threads staging items (0: inline on the consumer thread)
        self.workers = max(1, int(workers)) if self.depth > 0 else 0
        #: how long the consumer tolerates a SILENT pool before raising
        #: typed FeedStallError (<= 0 disables the deadline)
        self.stall_timeout_s = (
            feed_stall_s() if stall_timeout_s is None else float(stall_timeout_s)
        )
        self.inflight_peak = 0
        self._inflight = 0
        self._next = start  # the index the consumer takes next
        self._claim = start  # the index a worker stages next
        #: first index no worker stages: ``count``, or a failed item's
        self._end = count
        #: finished items by index: (value, None) or (None, exception)
        self._slots: Dict[int, Tuple[Any, Optional[BaseException]]] = {}
        self._progress = time.monotonic()  # when a worker last finished
        self._closed = False
        self._cond = threading.Condition()
        #: held from a claim through its fault probe: probes fire in index
        #: order, so a plan's ``at=k`` hits batch k - 1 whatever the timing
        self._claim_lock = threading.Lock()
        self._threads: List[threading.Thread] = [
            threading.Thread(
                target=self._work, name=f"{name}-{k}", daemon=True
            )
            for k in range(self.workers)
        ]
        for thread in self._threads:
            thread.start()

    # -- feed workers --------------------------------------------------------

    def _work(self) -> None:
        from ..reliability.faults import fault_point

        while True:
            with self._claim_lock:
                with self._cond:
                    while not self._closed and self._claim < self._end and (
                        self._claim >= self._next + self.workers + self.depth
                    ):
                        self._cond.wait()
                    if self._closed or self._claim >= self._end:
                        return
                    index = self._claim
                    self._claim += 1
                    self._inflight += 1
                    self.inflight_peak = max(self.inflight_peak, self._inflight)
                try:
                    # chaos site: an injected feed_stall wedges/kills the
                    # feed exactly where a real transfer thread would
                    fault_point("prefetch", tag=str(index))
                except BaseException as exc:  # noqa: BLE001
                    self._finish(index, None, exc)
                    return
            try:
                item = self._produce(index)
            except BaseException as exc:  # noqa: BLE001 - propagate to
                # the consumer: KeyboardInterrupt-class injections must
                # ride out exactly like on the inline path
                self._finish(index, None, exc)
                return
            self._finish(index, item, None)

    def _finish(self, index: int, item: Any, error: Optional[BaseException]) -> None:
        with self._cond:
            self._inflight -= 1
            self._progress = time.monotonic()
            if not self._closed:
                self._slots[index] = (item, error)
                if error is not None:
                    self._end = min(self._end, index)
            self._cond.notify_all()

    # -- consumer side -------------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        if self.depth == 0:
            return self._next_inline()
        deadline = self.stall_timeout_s
        with self._cond:
            if self._closed or (
                self._next >= self._end and self._next not in self._slots
            ):
                self._closed = True
                raise StopIteration
            waited_from = time.monotonic()
            while self._next not in self._slots:
                if deadline and deadline > 0:
                    silent = time.monotonic() - max(waited_from, self._progress)
                    if silent >= deadline:
                        break
                    self._cond.wait(deadline - silent)
                else:
                    self._cond.wait()
            entry = self._slots.pop(self._next, None)
            if entry is not None:
                self._next += 1
                self._cond.notify_all()  # a staging slot opened
        if entry is None:
            # the pool went SILENT past the stall deadline (a hung
            # device_put, a wedged source): declare it typed — a
            # DeviceFailureException, so the pass fails over to the host
            # tier, whose chunk iteration shares none of this machinery
            from ..exceptions import FeedStallError

            self.close()
            raise FeedStallError(
                "prefetch",
                f"feed workers produced nothing for {deadline:.0f}s",
            )
        item, error = entry
        if error is not None:
            self.close()
            raise error
        return item

    def _next_inline(self) -> Any:
        from ..reliability.faults import fault_point

        with self._cond:  # uncontended: no worker shares this state
            if self._next >= self._end:
                raise StopIteration
            index = self._next
            self._next += 1
            self.inflight_peak = 1
        fault_point("prefetch", tag=str(index))
        return self._produce(index)

    def close(self) -> None:
        """Tear the pool down (idempotent): wakes every worker waiting for
        a staging slot and joins them all. Staged-but-unconsumed items are
        dropped — the consumer abandoning a pass does exactly that."""
        with self._cond:
            self._closed = True
            self._slots.clear()
            self._cond.notify_all()
        # one deadline for the whole pool: a worker wedged inside produce
        # cannot be joined, and must not hold the consumer for 5s apiece
        join_by = time.monotonic() + 5.0
        for thread in self._threads:
            thread.join(timeout=max(0.0, join_by - time.monotonic()))
        self._threads = []

    def __enter__(self) -> "PrefetchingBatchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

"""Scan watchdog: deadline monitoring for device and host-tier passes.

The PR-2 reliability layer reacts to RAISED exceptions — isolation,
failover, retry all begin when something throws. A pass that HANGS (a
wedged device link, a collective waiting on a peer that died, a kernel
spinning on a poisoned shape) defeats all of it: the worker blocks
forever, the battery never degrades, the scheduler queue backs up behind
a job that will never finish. This module closes that gap with the
hang-detection analog of a thrown fault:

- every engine pass runs under a DEADLINE derived from the measured
  per-ROW rate of previous passes on the same tier (a generous
  multiple, so normal variance never trips; per-row so micro-batch and
  full-batch passes share one honest rate), extended by the longest
  compile seen so far for each program the pass has never run (a rate
  learned from warm passes says nothing about a cold TPU compile),
  overridable with
  ``DEEQU_TPU_SCAN_DEADLINE_S`` (<= 0 disables);
- a pass exceeding its deadline is cancelled — the caller gets a typed
  :class:`~deequ_tpu.exceptions.ScanStallError`, which classifies as a
  ``"device"`` fault and takes the EXISTING tier-failover +
  placement-probation path (`isolation.classify_failure`); the
  ``RunMonitor.stalls`` counter records it;
- the service scheduler treats an escaped stall as retryable, so a
  watchdog-flagged job is requeued instead of failing outright
  (`scheduler._maybe_retry`).

Division of labor with the mesh heartbeat: this watchdog guards the WHOLE
pass (one deadline around the fold); `parallel/health.py`'s per-shard
heartbeat guards individual mesh shards DURING the fold, declaring a
wedged shard lost (typed ``ShardStallError``, a ``ShardLossError``) so
the elastic layer salvages and re-shards instead of abandoning the whole
pass — the pass-level deadline stays as the backstop when the entire mesh
(or the host tier) hangs.

Cancellation semantics: Python cannot kill a thread, so the stalled pass
is ABANDONED on a daemon thread while the caller proceeds with recovery.
The zombie's side effects are bounded by design — engine passes fold into
pass-local state and only publish by RETURNING (which the abandoned
caller discards); the one durable side channel, a checkpoint save, writes
a self-consistent resume point that a later run may legitimately use.
Before the first measured rate exists, derived deadlines are disabled
(there is nothing honest to derive from); the env override always
applies.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, Optional

from ..exceptions import ScanStallError

#: env var: per-pass deadline in seconds. Overrides the derived deadline;
#: "0" (or any value <= 0) disables the watchdog entirely.
SCAN_DEADLINE_ENV = "DEEQU_TPU_SCAN_DEADLINE_S"

#: multiple of the measured per-row time a pass may take before it is
#: declared stalled — generous, because the cost of a false trip (a
#: spurious failover) is far higher than a few extra seconds of waiting
DEADLINE_RATE_MULTIPLE = 10.0

#: floor on any derived deadline: feed warmup and probe costs amortize
#: into the first batches, so short passes get slack
DEADLINE_FLOOR_S = 30.0

#: longest XLA backend compile this process has seen (seconds); a pass
#: about to compile gets this much extra per program it has never run
_LONGEST_COMPILE_S = 0.0
_COMPILE_LISTENER = False


def _note_compile(event: str, duration: float, **_) -> None:
    global _LONGEST_COMPILE_S
    if event == "/jax/core/compile/backend_compile_duration":
        _LONGEST_COMPILE_S = max(_LONGEST_COMPILE_S, float(duration))


def watch_compiles() -> None:
    """Start recording compile durations (idempotent)."""
    global _COMPILE_LISTENER
    if not _COMPILE_LISTENER:
        import jax

        _COMPILE_LISTENER = True
        jax.monitoring.register_event_duration_secs_listener(_note_compile)


def compile_allowance_s(cold_programs: int) -> float:
    """Deadline extension for a pass that will compile ``cold_programs``
    programs: a rate learned from warm passes says nothing about compile
    time, and a first TPU compile takes tens of seconds to minutes."""
    if cold_programs <= 0:
        return 0.0
    return cold_programs * max(DEADLINE_FLOOR_S, _LONGEST_COMPILE_S)


class RateTracker:
    """EWMA of measured per-ROW wall seconds, per tier. Fed by successful
    engine passes; consulted to derive the next pass's deadline.
    Per-row, not per-batch: one tier serves both 512-row streaming
    micro-batches and 1M-row verification batches, and a per-batch rate
    learned from the small ones would derive deadlines no healthy
    large-batch pass can meet. Thread-safe (service workers run passes
    concurrently)."""

    ALPHA = 0.3

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._per_row_s: Dict[str, float] = {}

    def observe(self, tier: str, rows: int, seconds: float) -> None:
        if rows <= 0 or seconds <= 0:
            return
        per_row = seconds / rows
        with self._lock:
            prev = self._per_row_s.get(tier)
            self._per_row_s[tier] = (
                per_row if prev is None
                else self.ALPHA * per_row + (1 - self.ALPHA) * prev
            )

    def per_row_s(self, tier: str) -> Optional[float]:
        with self._lock:
            return self._per_row_s.get(tier)

    def clear(self) -> None:
        with self._lock:
            self._per_row_s.clear()


#: the process-wide rate ledger (deadlines derive from what THIS process
#: measured; rates do not survive restarts — the first pass of a process
#: runs unguarded unless the env override is set)
_TRACKER = RateTracker()


def rate_tracker() -> RateTracker:
    return _TRACKER


#: warn-once latch for an unparseable env override
_ENV_WARNED = False


def scan_deadline_s(
    n_rows: int, tier: str, cold_programs: int = 0
) -> Optional[float]:
    """The deadline for a pass over ``n_rows`` on ``tier`` that will
    compile ``cold_programs`` programs, or None (watchdog disabled: no
    override and no measured rate yet)."""
    watch_compiles()
    env = os.environ.get(SCAN_DEADLINE_ENV)
    if env is not None:
        try:
            value = float(env)
        except ValueError:
            # an operator who set "60s"/"1m" believes hang detection is
            # armed — falling back to the derived deadline (instead of
            # silently disabling BOTH paths) keeps some guard up, and the
            # warning says why the pinned value was ignored
            global _ENV_WARNED
            if not _ENV_WARNED:
                _ENV_WARNED = True
                import logging

                logging.getLogger(__name__).warning(
                    "ignoring unparseable %s=%r (expected seconds as a "
                    "number); falling back to the measured-rate deadline",
                    SCAN_DEADLINE_ENV, env,
                )
        else:
            return value if value > 0 else None
    per_row = _TRACKER.per_row_s(tier)
    if per_row is None:
        return None
    return max(
        DEADLINE_FLOOR_S,
        DEADLINE_RATE_MULTIPLE * per_row * max(int(n_rows), 1),
    ) + compile_allowance_s(cold_programs)


def run_with_deadline(
    fn: Callable[[], "object"],
    deadline_s: float,
    monitor,
    site: str,
):
    """Run ``fn`` to completion or to the deadline, whichever first.

    On deadline: bump ``monitor.stalls``, abandon the worker thread (it
    stays a daemon; its eventual return value is discarded) and raise
    :class:`ScanStallError`. On completion: return/raise exactly what
    ``fn`` did."""
    from ..observability import record_failure
    from ..observability import trace as _trace

    box: Dict[str, object] = {}
    done = threading.Event()
    # the pass body runs on a daemon thread: carry the caller's trace
    # context over so the pass's spans stay in the caller's tree (an
    # abandoned zombie keeps appending to the SAME trace, which is exactly
    # what a post-mortem wants to see)
    ctx = _trace.capture()

    def body() -> None:
        try:
            with _trace.attach(ctx):
                box["value"] = fn()
        except BaseException as exc:  # noqa: BLE001 - re-raised in caller
            box["error"] = exc
        finally:
            done.set()

    t0 = time.perf_counter()
    worker = threading.Thread(
        target=body, name=f"scan-watchdog-{site}", daemon=True
    )
    worker.start()
    if not done.wait(deadline_s):
        waited = time.perf_counter() - t0
        if monitor is not None:
            monitor.bump("stalls")
            if site == "device":
                # tier-attributed: only DEVICE stalls should teach the
                # placement router to avoid the device tier — pinning a
                # battery to the host tier because the HOST hung would
                # probation it onto the sick tier
                monitor.bump("device_stalls")
        stall = ScanStallError(site, deadline_s, waited)
        _trace.add_event(
            "scan_stall", site=site, deadline_s=deadline_s, waited_s=waited
        )
        record_failure(stall)
        raise stall
    if "error" in box:
        raise box["error"]
    return box["value"]

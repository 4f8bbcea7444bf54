"""Ingestion-plane soak: concurrency knee + sustained in-process MB/s.

Two measurements back the ROADMAP item-4 acceptance bar ("thousands of
concurrent sessions under bounded admission", "saturate the feed link"):

1. **Concurrency soak** (``run_concurrency_soak``): N streaming sessions
   ingest B micro-batches each through the service scheduler under
   bounded admission with backpressure (``block_s``) — feeder threads park
   when the queue fills instead of dropping. Reports sessions/s and MB/s
   sustained, jobs shed, and the per-batch fold results. ``--sweep`` runs
   a doubling ladder of session counts so the knee (where sessions/s
   stops scaling) is visible in one invocation.

2. **Stream throughput** (``run_stream_throughput``): ONE session fed
   Arrow IPC payloads through `deequ_tpu.ingest.fold_stream` — the same
   decode + atomic-fold path the HTTP endpoint runs — at production batch
   shapes. Reports sustained MB/s and rows/s including decode, checksum
   (optional) and the full verification fold.

Usage::

    python -m tools.ingest_soak --sessions 1000 --batches 2 --rows 4096
    python -m tools.ingest_soak --stream-mb 512            # throughput only
    python -m tools.ingest_soak --sweep                    # knee ladder

Exit code 0 iff every fold terminated (result or typed shed) and the
stream-throughput parity check held. JSON summary on stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional


def _checks():
    from deequ_tpu.checks import Check, CheckLevel

    return [
        Check(CheckLevel.ERROR, "ingest battery")
        .has_size(lambda n: n > 0)
        .is_complete("x")
        .has_mean("y", lambda m: -100.0 < m < 100.0),
    ]


def _build_table(rows: int, seed: int = 7):
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    return pa.table({
        "x": rng.normal(size=rows),
        "y": rng.normal(10.0, 2.0, size=rows),
        "k": rng.integers(0, 1000, size=rows),
        "v": rng.uniform(0, 1, size=rows),
    })


# ---------------------------------------------------------------------------
# measurement 1: concurrency soak under bounded admission
# ---------------------------------------------------------------------------


def run_concurrency_soak(
    sessions: int = 1000,
    batches: int = 2,
    rows: int = 4096,
    workers: int = 8,
    queue_depth: int = 256,
    block_s: float = 30.0,
    feeders: int = 32,
    service=None,
) -> Dict:
    """Drive ``sessions`` concurrent streaming sessions, ``batches``
    micro-batches each, through bounded admission with backpressure.
    Every session shares one table's slices (zero-copy record batches) so
    the measurement is the SERVICE's, not the data generator's."""
    import threading

    from deequ_tpu.service import ServiceError, VerificationService

    table = _build_table(rows * batches)
    slices = [table.slice(b * rows, rows) for b in range(batches)]
    payload_mb = sum(s.nbytes for s in slices) / 1e6
    checks = _checks()
    own_service = service is None
    if own_service:
        service = VerificationService(
            workers=workers, max_queue_depth=queue_depth,
            background_warm=False,
        )
    summary: Dict = {
        "sessions": sessions, "batches_per_session": batches,
        "rows_per_batch": rows, "workers": workers,
        "queue_depth": queue_depth,
    }
    try:
        # pre-create the sessions (registration is not the measurement)
        sess = [
            service.session(f"soak-{i}", "stream", checks,
                            admission_block_s=block_s)
            for i in range(sessions)
        ]
        # one tiny warm fold compiles the (shared) bucketed program shape
        # so the soak measures the service, not one XLA compile
        warm = service.session("soak-warm", "stream", checks,
                               admission_block_s=block_s)
        warm.ingest(slices[0])

        shed_before = service.metrics.counter_value(
            "deequ_service_jobs_shed_total"
        )
        errors: List[str] = []
        handles_lock = threading.Lock()
        all_handles = []

        def feed(lo: int, hi: int) -> None:
            mine = []
            for i in range(lo, hi):
                for b in range(batches):
                    try:
                        mine.append(sess[i].ingest(slices[b], wait=False))
                    except ServiceError as exc:
                        with handles_lock:
                            errors.append(type(exc).__name__)
            with handles_lock:
                all_handles.extend(mine)

        n_feeders = max(1, min(feeders, sessions))
        per = -(-sessions // n_feeders)
        threads = [
            threading.Thread(
                target=feed, args=(f * per, min((f + 1) * per, sessions)),
                daemon=True,
            )
            for f in range(n_feeders)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        failed = 0
        for h in all_handles:
            try:
                h.result(timeout=300)
            except Exception:  # noqa: BLE001 - counted, soak verdict below
                failed += 1
        wall = time.perf_counter() - t0
        done_sessions = sum(
            1 for s in sess if s.batches_ingested == batches
        )
        total_mb = sum(s.bytes_ingested for s in sess) / 1e6
        summary.update({
            "wall_s": round(wall, 3),
            "sessions_completed": done_sessions,
            "sessions_per_s": round(done_sessions / wall, 1),
            "folds_per_s": round(len(all_handles) / wall, 1),
            "mb_per_s": round(total_mb / wall, 1),
            "ingested_mb": round(total_mb, 1),
            "payload_mb_per_session": round(payload_mb, 3),
            "shed": int(
                service.metrics.counter_value("deequ_service_jobs_shed_total")
                - shed_before
            ),
            "feeder_errors": len(errors),
            "failed_folds": failed,
            "ok": failed == 0 and done_sessions == sessions,
        })
        # tail latency from the service's own SLO histograms (merged
        # across tenants): what fleetwatch burn rates are computed from
        from deequ_tpu.service.metrics import histogram_quantile

        for slug, series in (
            ("fold_latency", "deequ_service_fold_latency_seconds"),
            ("admission_wait", "deequ_service_admission_wait_seconds"),
        ):
            state = service.metrics.histogram_merged(series)
            for q, tag in ((0.5, "p50"), (0.99, "p99")):
                value = histogram_quantile(state, q)
                if value is not None and value != float("inf"):
                    summary[f"{slug}_{tag}_s"] = round(value, 6)
    finally:
        if own_service:
            service.close()
    return summary


# ---------------------------------------------------------------------------
# measurement 2: sustained in-process Arrow stream throughput
# ---------------------------------------------------------------------------


def run_stream_throughput(
    target_mb: float = 512.0,
    rows_per_batch: int = 1 << 20,
    checksum: bool = False,
    workers: int = 4,
) -> Dict:
    """Feed ONE session Arrow IPC payloads through ``fold_stream`` until
    ``target_mb`` of wire bytes have folded; report sustained MB/s and
    rows/s (decode + optional checksum + the full verification fold), and
    parity-check the folded metrics against a direct in-process run of
    the same battery over the same concatenated data."""
    import numpy as np

    from deequ_tpu.ingest import encode_ipc_stream, fold_stream
    from deequ_tpu.integrity import checksum_bytes
    from deequ_tpu.service import VerificationService

    table = _build_table(rows_per_batch, seed=11)
    payload = encode_ipc_stream(table)
    digest = checksum_bytes(payload) if checksum else None
    n_streams = max(1, int(target_mb * 1e6 / len(payload)))
    checks = _checks()
    with VerificationService(
        workers=workers, max_queue_depth=64, background_warm=False
    ) as service:
        session = service.session("tput", "stream", checks,
                                  admission_block_s=60.0)
        # warm fold: compile the bucketed batch shape outside the timing
        warm = service.session("tput-warm", "stream", checks)
        warm.ingest(table.slice(0, rows_per_batch))

        t0 = time.perf_counter()
        frames = 0
        for _ in range(n_streams):
            report = fold_stream(session, payload, checksum=digest,
                                 source="soak")
            frames += report.frames
        wall = time.perf_counter() - t0
        total_mb = n_streams * len(payload) / 1e6
        total_rows = n_streams * rows_per_batch

        # parity: cumulative session metrics == one direct run over the
        # same data repeated n_streams times (algebraic states make the
        # mean/completeness identical; size is n_streams * rows)
        cum = session.current()
        from deequ_tpu.checks import CheckStatus

        parity_ok = cum.status == CheckStatus.SUCCESS
        mean_direct = float(np.mean(table["y"].to_numpy()))
        mean_stream = None
        for a, m in cum.metrics.items():
            if a.name == "Mean" and a.instance == "y" and m.value.is_success:
                mean_stream = m.value.get()
        if mean_stream is not None:
            parity_ok = parity_ok and abs(mean_stream - mean_direct) <= 1e-9
    return {
        "streams": n_streams,
        "frames": frames,
        "rows_per_batch": rows_per_batch,
        "checksum": bool(checksum),
        "wall_s": round(wall, 3),
        "mb_per_s": round(total_mb / wall, 1),
        "rows_per_s": round(total_rows / wall, 1),
        "ingested_mb": round(total_mb, 1),
        "parity_ok": parity_ok,
        "ok": parity_ok,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--sessions", type=int, default=1000)
    parser.add_argument("--batches", type=int, default=2)
    parser.add_argument("--rows", type=int, default=4096)
    parser.add_argument("--workers", type=int, default=8)
    parser.add_argument("--queue-depth", type=int, default=256)
    parser.add_argument("--block-s", type=float, default=30.0)
    parser.add_argument("--stream-mb", type=float, default=0.0,
                        help="run ONLY the stream-throughput measurement "
                        "at this many MB")
    parser.add_argument("--checksum", action="store_true",
                        help="verify xxhash64 on every stream payload")
    parser.add_argument("--sweep", action="store_true",
                        help="double session counts up to --sessions to "
                        "expose the concurrency knee")
    args = parser.parse_args(argv)
    if args.stream_mb > 0:
        summary = run_stream_throughput(
            target_mb=args.stream_mb, checksum=args.checksum,
            workers=args.workers,
        )
    elif args.sweep:
        points = []
        n = max(args.sessions // 8, 8)
        while n <= args.sessions:
            points.append(run_concurrency_soak(
                sessions=n, batches=args.batches, rows=args.rows,
                workers=args.workers, queue_depth=args.queue_depth,
                block_s=args.block_s,
            ))
            n *= 2
        summary = {
            "sweep": [
                {k: p[k] for k in ("sessions", "sessions_per_s", "mb_per_s",
                                   "shed", "ok")}
                for p in points
            ],
            "ok": all(p["ok"] for p in points),
        }
    else:
        summary = run_concurrency_soak(
            sessions=args.sessions, batches=args.batches, rows=args.rows,
            workers=args.workers, queue_depth=args.queue_depth,
            block_s=args.block_s,
        )
    print(json.dumps(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

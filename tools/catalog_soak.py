"""Tenant-catalog soak: registered >> active tiering + live-edit drills
+ gated-vs-ungated throughput.

The ISSUE 17 acceptance drills, one invocation, one JSON line:

1. **Tiering soak** (``--registered R --active A``, R >> A): R tenants
   register suite documents in the catalog (cold tier: one versioned
   file each, no live state), then A of them go hot — sessions
   materialize from their documents on first ingest and stream
   micro-batches. The verdict pins that hot-tier cost tracks ACTIVE
   tenants (hot_count == A) while the registry holds all R, and that
   every fold succeeded.
2. **Mid-soak edit drill**: while the hot tenants stream, one tenant's
   document is re-registered with a different priority and a looser row
   gate. The next fold boundary must pick it up — no restart — pinned by
   the session's live priority, the reloads counter, and a frame that
   the OLD gate would have quarantined folding cleanly.
3. **Corrupt-edit drill**: a torn write lands as the same tenant's next
   version. The tenant must keep serving LAST-GOOD (folds keep
   succeeding, config unchanged) with EXACTLY one quarantine counter
   bump and the bad bytes preserved content-addressed in the
   ``.quarantine`` sidecar.
4. **Gated vs ungated throughput**: the same Arrow stream is folded
   through a session WITH a row gate (all rows conforming — the
   production steady state) and one WITHOUT; reports
   ``gated_throughput_fraction`` (gated MB/s / ungated MB/s; acceptance
   floor 0.8) and pins the two sessions' cumulative metrics BIT-EXACT.

Exit code 0 iff every verdict holds, 1 on a failed verdict. The JSON
summary is always printed.

Usage::

    python -m tools.catalog_soak                     # CI-scaled defaults
    python -m tools.catalog_soak --registered 10000 --active 500
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict

DEFAULT_REGISTERED = 400
DEFAULT_ACTIVE = 24
DEFAULT_BATCHES = 3
DEFAULT_ROWS = 2048


def _doc(priority: str = "normal", max_len: int = 8) -> Dict:
    return {
        "checks": [{"name": "soak", "constraints": [
            {"kind": "complete", "column": "id"},
            {"kind": "min", "column": "v", "min": 0},
            {"kind": "size", "min": 1},
        ]}],
        "row_gate": {"columns": [
            {"name": "id", "type": "int", "nullable": False},
            {"name": "s", "type": "string", "max_length": max_len},
        ]},
        "priority": priority,
        "session": {"admission_block_s": 10.0},
    }


def _frame(rows: int, start: int = 0, s: str = "ok"):
    import numpy as np

    return {
        "id": np.arange(start, start + rows),
        "s": np.array([s] * rows),
        "v": np.ones(rows, dtype=np.float64),
    }


# ---------------------------------------------------------------------------
# drills 1-3: tiering + live edits over one service
# ---------------------------------------------------------------------------

def run_tiering_soak(
    registered: int = DEFAULT_REGISTERED,
    active: int = DEFAULT_ACTIVE,
    batches: int = DEFAULT_BATCHES,
    rows: int = DEFAULT_ROWS,
    workers: int = 4,
) -> Dict:
    import os
    import tempfile

    from deequ_tpu.service import TenantCatalog, VerificationService

    run_dir = tempfile.mkdtemp(prefix="catalog-soak-")
    catalog = TenantCatalog(os.path.join(run_dir, "catalog"))

    t0 = time.perf_counter()
    for i in range(registered):
        catalog.register(f"tenant-{i:06d}", _doc())
    register_s = time.perf_counter() - t0

    out: Dict = {
        "registered": registered,
        "active": active,
        "registers_per_s": round(registered / max(register_s, 1e-9), 1),
    }
    with VerificationService(
        workers=workers, max_queue_depth=max(64, active * 2),
        background_warm=False, catalog=catalog,
    ) as service:
        plane = service.catalog_plane
        plane.poll_s = 0.0  # fold boundaries poll every time: the edit
        #                     drills must not wait out a debounce window
        hot = [f"tenant-{i:06d}" for i in range(active)]
        t0 = time.perf_counter()
        sessions = {t: plane.ensure_session(t, "stream") for t in hot}
        folds_ok = 0
        for b in range(batches):
            for t in hot:
                r = sessions[t].ingest(_frame(rows, start=b * rows))
                folds_ok += r.status.name == "SUCCESS"
        soak_s = time.perf_counter() - t0
        out["sessions_per_s"] = round(
            active * batches / max(soak_s, 1e-9), 1
        )
        out["folds_ok"] = folds_ok
        out["hot_count"] = plane.hot_count()
        out["registered_count"] = catalog.registered_count()

        # -- drill 2: mid-soak edit, effective without restart ----------
        victim = hot[0]
        catalog.register(victim, _doc(priority="low", max_len=64))
        plane.on_fold_boundary(sessions[victim])
        long_frame = _frame(rows, start=batches * rows, s="x" * 32)
        edit_result = sessions[victim].ingest(long_frame)
        from deequ_tpu.service.scheduler import Priority

        out["edit_drill"] = {
            "priority_live": sessions[victim].priority is Priority.LOW,
            "loosened_gate_live": edit_result.status.name == "SUCCESS"
            and sessions[victim].rows_ingested
            == (batches + 1) * rows,
            "reloads": service.metrics.counter_value(
                "deequ_service_catalog_reloads_total", tenant=victim
            ),
        }
        out["edit_drill"]["ok"] = (
            out["edit_drill"]["priority_live"]
            and out["edit_drill"]["loosened_gate_live"]
            and out["edit_drill"]["reloads"] == 1
        )

        # -- drill 3: corrupt edit -> last-good, one quarantine bump ----
        tdir = os.path.join(
            catalog.path, f"t-{victim}"
        )
        torn = os.path.join(tdir, "v00000099.json")
        with open(torn, "w") as fh:
            fh.write('{"torn": tru')
        before = service.metrics.counter_value(
            "deequ_service_catalog_quarantined_total", tenant=victim
        )
        for _ in range(3):  # repeated boundaries must not re-quarantine
            plane.on_fold_boundary(sessions[victim])
        corrupt_result = sessions[victim].ingest(
            _frame(rows, start=(batches + 1) * rows, s="x" * 32)
        )
        bumps = service.metrics.counter_value(
            "deequ_service_catalog_quarantined_total", tenant=victim
        ) - before
        qdir = catalog.path + ".quarantine"
        preserved = [
            n for n in (os.listdir(qdir) if os.path.isdir(qdir) else [])
            if n.startswith("v00000099.json-")
        ]
        out["corrupt_drill"] = {
            "still_serving": corrupt_result.status.name == "SUCCESS",
            "config_kept": sessions[victim].priority is Priority.LOW,
            "quarantine_bumps": bumps,
            "preserved": len(preserved),
        }
        out["corrupt_drill"]["ok"] = (
            out["corrupt_drill"]["still_serving"]
            and out["corrupt_drill"]["config_kept"]
            and bumps == 1 and len(preserved) == 1
        )
    out["tiering_ok"] = (
        out["folds_ok"] == active * batches
        and out["hot_count"] == active
        and out["registered_count"] == registered
    )
    out["ok"] = bool(
        out["tiering_ok"] and out["edit_drill"]["ok"]
        and out["corrupt_drill"]["ok"]
    )
    return out


# ---------------------------------------------------------------------------
# drill 4: gated vs ungated throughput, bit-exact
# ---------------------------------------------------------------------------

def run_gate_throughput(
    batches: int = 24, rows: int = 65_536,
) -> Dict:
    """Fold the SAME clean Arrow stream through a gated and an ungated
    session; the fraction is the row gate's steady-state cost (every row
    conforming — the mask always runs, the split never does), and the
    cumulative metrics must be bit-exact between the two."""
    import numpy as np
    import pyarrow as pa

    from deequ_tpu.checks import Check, CheckLevel
    from deequ_tpu.ingest import RowGate, fold_stream, encode_ipc_stream
    from deequ_tpu.schema import RowLevelSchema
    from deequ_tpu.service import VerificationService

    rng = np.random.default_rng(11)
    payloads = [
        encode_ipc_stream(pa.table({
            "id": pa.array(np.arange(b * rows, (b + 1) * rows)),
            "v": pa.array(rng.normal(10.0, 2.0, size=rows)),
        }))
        for b in range(batches)
    ]
    total_mb = sum(len(p) for p in payloads) / 2**20

    def checks():
        return [Check(CheckLevel.ERROR, "gate-throughput")
                .has_size(lambda n: n > 0)
                .is_complete("id")
                .has_mean("v", lambda m: 0.0 < m < 20.0)
                .has_sum("v", lambda s: s > 0)]

    schema = RowLevelSchema().with_int_column("id", is_nullable=False)
    out: Dict = {"mb": round(total_mb, 1), "batches": batches}
    with VerificationService(workers=2, background_warm=False) as svc:
        gate = RowGate(schema, metrics=svc.metrics)
        timings = {}
        metrics = {}
        for name, kw in (
            ("ungated", {}), ("gated", {"row_gate": gate}),
        ):
            session = svc.session("tp", name, checks(), **kw)
            t0 = time.perf_counter()
            for payload in payloads:
                fold_stream(session, payload, source=name)
            timings[name] = time.perf_counter() - t0
            metrics[name] = {
                repr(a): m.value.get()
                for a, m in session.current().metrics.items()
                if m.value.is_success
            }
        out["ungated_mb_per_s"] = round(total_mb / timings["ungated"], 1)
        out["gated_mb_per_s"] = round(total_mb / timings["gated"], 1)
        out["gated_throughput_fraction"] = round(
            timings["ungated"] / timings["gated"], 3
        )
        out["bit_exact"] = metrics["gated"] == metrics["ungated"]
        out["gate_rows"] = svc.metrics.counter_value(
            "deequ_service_rowgate_rows_total", tenant="tp", dataset="gated"
        )
    out["ok"] = bool(
        out["bit_exact"] and out["gate_rows"] == batches * rows
    )
    return out


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--registered", type=int, default=DEFAULT_REGISTERED)
    parser.add_argument("--active", type=int, default=DEFAULT_ACTIVE)
    parser.add_argument("--batches", type=int, default=DEFAULT_BATCHES)
    parser.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    parser.add_argument("--gate-batches", type=int, default=24)
    parser.add_argument("--gate-rows", type=int, default=65_536)
    parser.add_argument("--fraction-floor", type=float, default=0.8,
                        help="acceptance floor for gated/ungated MB/s "
                             "(0 disables; timing floors are meaningless "
                             "at toy sizes)")
    args = parser.parse_args(argv)

    t0 = time.perf_counter()
    summary: Dict = {
        "soak": run_tiering_soak(
            registered=args.registered, active=args.active,
            batches=args.batches, rows=args.rows,
        ),
        "gate": run_gate_throughput(
            batches=args.gate_batches, rows=args.gate_rows,
        ),
    }
    summary["gated_throughput_fraction"] = (
        summary["gate"]["gated_throughput_fraction"]
    )
    summary["seconds"] = round(time.perf_counter() - t0, 2)
    summary["ok"] = bool(
        summary["soak"]["ok"] and summary["gate"]["ok"]
        and summary["gated_throughput_fraction"] >= args.fraction_floor
    )
    print(json.dumps(summary, sort_keys=True))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

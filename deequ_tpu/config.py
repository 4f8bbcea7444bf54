"""Global configuration for the TPU data-quality engine.

The reference (deequ) relies on JVM doubles everywhere; to hold the +-1e-6
metric-parity target we default to float64 accumulators, which requires
jax_enable_x64. Set DEEQU_TPU_NO_X64=1 before import to opt out (accumulators
then fall back to float32 + compensated summation where implemented).
"""

from __future__ import annotations

import os

import jax

if not os.environ.get("DEEQU_TPU_NO_X64"):
    jax.config.update("jax_enable_x64", True)

#: directory for what the program caches at run time, fixed inside the
#: checkout (gitignored): a cache whose path moves never hits
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache"
)

#: env var: "1" turns the persistent XLA compilation cache off
NO_COMPILE_CACHE_ENV = "DEEQU_TPU_NO_COMPILE_CACHE"


def compile_cache_dir():
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` when
    set (jax reads it itself), else ``<checkout>/.cache/xla``; None when
    the cache is off."""
    if os.environ.get(NO_COMPILE_CACHE_ENV):
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CACHE_ROOT, "xla"
    )


# persistent XLA compilation cache: fused analyzer programs are large (tens
# of seconds to compile) and identical across processes/runs
_cache_dir = compile_cache_dir()
if _cache_dir is None:
    jax.config.update("jax_enable_compilation_cache", False)
else:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import jax.numpy as jnp  # noqa: E402  (after x64 setup)

#: dtype used for floating-point accumulator states (sums, moments, ...)
ACC_DTYPE = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
#: dtype used for integer counters
COUNT_DTYPE = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32

#: default number of rows per device batch fed to the fused update program
DEFAULT_BATCH_SIZE = 1 << 20

# ---------------------------------------------------------------------------
# Device scan-program bundling + slim state fetch (read per call, not at
# import, so tests and operators can flip them without re-importing jax)
# ---------------------------------------------------------------------------

#: env var sizing the signature-keyed device scan bundles: a battery is
#: partitioned into (analyzer-class, state-shape) bundles of at most this
#: many analyzers, each compiled as ONE small PackedScanProgram that is
#: REUSED across columns, batteries and runs (a 50-column profile compiles
#: ~10 small programs instead of one monolithic one). "0" restores the
#: monolithic one-program-per-battery behavior (maximum fusion, maximum
#: cold-compile stall).
SCAN_BUNDLE_ENV = "DEEQU_TPU_SCAN_BUNDLE"
DEFAULT_SCAN_BUNDLE = 8


def scan_bundle_size() -> int:
    raw = os.environ.get(SCAN_BUNDLE_ENV)
    if raw is None:
        return DEFAULT_SCAN_BUNDLE
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_SCAN_BUNDLE


#: env var disabling the slim state fetch ("0" = always fetch full states).
#: When enabled (default), a run that neither persists nor aggregates
#: states ships only each analyzer's METRIC-BEARING state leaves over the
#: device feed link (see Analyzer.metric_leaves); the remaining leaves are
#: reconstructed host-side from identity values the metric never reads.
SLIM_FETCH_ENV = "DEEQU_TPU_SLIM_FETCH"


def slim_fetch_enabled() -> bool:
    return os.environ.get(SLIM_FETCH_ENV, "1") != "0"


# ---------------------------------------------------------------------------
# Device frequency engine (implemented in deequ_tpu.analyzers.grouping; the
# env knobs are documented here with the other operator-facing switches and
# re-exported below). All three follow the warn-and-fallback convention:
# an unparseable value warns once and keeps the default, never crashes.
#
# - DEEQU_TPU_DEVICE_FREQ: "0" disables the device-resident frequency
#   TABLE engine (hashed fixed-shape count tables for arbitrary-cardinality
#   grouping sets); grouping then accumulates through the host group-by.
#   The dense dictionary path is unaffected.
# - DEEQU_TPU_FREQ_TABLE_SLOTS: distinct-group capacity per grouping set
#   (default 2^22; rounded up to a power of two, capped per run at the row
#   count). Sets whose cardinality exceeds it overflow EXACTLY and re-run
#   on the host last-resort tier.
# - DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY: dictionary-size ceiling of the
#   dense per-code device counting path (default 2^16).
# - DEEQU_TPU_FREQ_BUFFER_ENTRIES: raw key-buffer cap (default 2^25 = 256MB
#   of u64 keys; rounded up to a power of two). Runs whose padded row count
#   fits ride the RESIDENT trace (memcpy-speed appends, zero in-pass
#   compactions, exact at any cardinality); larger runs use the
#   conditional-compaction trace.
# - DEEQU_TPU_FREQ_HOST_ROUTE: "0" disables the cardinality pre-routing
#   probe — every eligible grouping set takes the device table even when a
#   cheap probe says the host group-by's value_counts fast path would win
#   (confidently-low-cardinality sets at >2M rows).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Ingestion plane (implemented in deequ_tpu.ingest; the env knob is
# documented here with the other operator-facing switches and re-exported
# below). Follows the warn-and-fallback convention: an unparseable value
# warns once and keeps the default.
#
# - DEEQU_TPU_PREFETCH_DEPTH: staged batches in the double-buffered
#   host->device feed pipeline (default 2: one batch folding on device,
#   one staged with its transfer in flight, one being built). "0" removes
#   the feed thread entirely — batches build and transfer inline on the
#   consumer thread, the measured "serial" baseline of PERF.md's overlap
#   numbers. Batch shapes stay pow2-bucketed upstream, so a deeper
#   pipeline never provokes a recompile.
# - DEEQU_TPU_FEED_STALL_S: seconds the fold tolerates a SILENT feed
#   thread before declaring it wedged with a typed FeedStallError
#   (default 120; <= 0 disables). A tripped deadline fails the pass over
#   to the host tier exactly like a thrown device fault.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Cross-session fold coalescing + tiny-delta host fast path (implemented in
# deequ_tpu.service.coalesce; the env knobs are documented here with the
# other operator-facing switches and re-exported below). All follow the
# warn-and-fallback convention: an unparseable value warns once and keeps
# the default.
#
# - DEEQU_TPU_COALESCE: "0" disables the whole coalescing plane — every
#   streaming ingest takes exactly the pre-coalescing serial path (the
#   true escape hatch; default on).
# - DEEQU_TPU_COALESCE_MAX_WIDTH: max sessions stacked into one coalesced
#   device launch (default 16; launches bucket their width to powers of
#   two so the compiled-shape space stays log-bounded).
# - DEEQU_TPU_FAST_PATH_MAX_ROWS: fixed row ceiling for the host fast
#   path. Default -1 = route from the MEASURED per-analyzer-class
#   crossover (host-kernel rates observed on every fast fold vs the
#   device fixed cost observed on every coalesced launch); 0 forces every
#   eligible fold onto the coalesced device path.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Fleet scheduler (implemented in deequ_tpu.service.fleet; the env knobs
# are documented here with the other operator-facing switches and
# re-exported below). Both follow the warn-and-fallback convention.
#
# - DEEQU_TPU_FLEET: "0" disables fleet scheduling entirely — single-chip
#   routing, byte-for-byte the pre-fleet service path (the escape hatch);
#   "1" forces it on even on the CPU backend (virtual-device drills and
#   tests); unset = ON exactly when the backend is a real accelerator
#   with more than one chip. When on, every tenant's batch scans shard
#   across that tenant's DISJOINT sub-mesh slice of the device mesh, and
#   fleet-sized streaming deltas fold shard-local + butterfly-merge at
#   coalesce-drain boundaries.
# - DEEQU_TPU_FLEET_STREAM_MIN_ROWS: minimum micro-batch rows before a
#   streaming fold shards over the tenant's sub-mesh (default 65536 —
#   below it the single-chip coalesced/fast paths beat the collective's
#   latency; 0 shards every eligible fold, the fleet drills use it).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Partition-aware incremental verification (implemented in
# deequ_tpu.repository.partition_store + deequ_tpu.runners.incremental;
# the env knobs are documented here with the other operator-facing
# switches and re-exported below). Both follow the warn-and-fallback
# convention where numeric.
#
# - DEEQU_TPU_PARTITION_STORE: root path (local or any deequ_tpu.io URI —
#   s3://, gs://, memory://) of the service-default PartitionStateStore.
#   When set, VerificationService plans incremental runs against it and
#   streaming sessions flush their cumulative states into it as a
#   partition on close. Unset = no default store (pass one explicitly).
# - DEEQU_TPU_PARTITION_WINDOW_MONTHS: default listing window, in month
#   buckets, for partition listings with no explicit window (0 =
#   unlimited). The store's directory layout is time-partitioned
#   (YYYY-MM buckets for date-named partitions), so a year of daily
#   partitions lists in O(window) directory walks; this knob bounds the
#   default walk for dropped-partition detection on very old stores.
#   Unparseable values warn once and keep the default (0).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Fleet watch — the standing fleet-scale anomaly plane (implemented in
# deequ_tpu.service.fleetwatch; the env knobs are documented here with the
# other operator-facing switches and re-exported below). All three follow
# the warn-and-fallback convention via the shared utils parsers.
#
# - DEEQU_TPU_FLEETWATCH: "0" detaches the standing watch from scheduler
#   harvests (explicit FleetWatch.harvest_now() still scores); default on.
#   When attached, every completed job of a WATCHED tenant triggers one
#   debounced scoring pass over every watched tenant's metric history.
# - DEEQU_TPU_FLEETWATCH_WINDOW_MONTHS: metric-history window each
#   harvest scores, in month buckets (default 12; 0 = unbounded). Rides
#   the PartitionedMetricsRepository's O(queried window) loads, so a year
#   of per-run history never costs a full-history deserialize per score.
# - DEEQU_TPU_FLEETWATCH_BUNDLE: maximum series stacked into one batched
#   detect_batch call (default 16384 — a 10k-tenant fleet scores in ONE
#   call per strategy bundle; larger fleets chunk).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Engine placement / host tier / profiling (implemented in
# deequ_tpu.runners.engine + .analysis_runner; documented here with the
# other operator-facing switches — the invariant linter's env-knob check
# (tools/statlint) requires every DEEQU_TPU_* knob read anywhere in the
# package to be discoverable from this file).
#
# - DEEQU_TPU_PLACEMENT: default ingest-tier placement when a run passes
#   none — "auto" (probe the feed link), "host", or "device".
# - DEEQU_TPU_HOST_TIER_WORKERS: host ingest tier partial-worker pool
#   size (default: all cores; 0/unset = default; warn-and-fallback).
# - DEEQU_TPU_DEVICE_FEATURE_CACHE: HBM budget in GB for the
#   device-resident feature cache; unset/"0" disables (warn-and-fallback).
# - DEEQU_TPU_NO_NATIVE: "1" disables the native host kernels entirely
#   (pure-Python fallbacks); read at deequ_tpu.native.lib import.
# - DEEQU_TPU_ADAPTIVE_DICT_ENCODE: "0" disables ingest-time adaptive
#   dictionary encoding of low-cardinality string columns (data module).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Host group-by spill tier (implemented in deequ_tpu.analyzers.grouping's
# host accumulator; documented here for discoverability). All three follow
# the warn-and-fallback convention via utils.env_number/env_flag.
#
# - DEEQU_TPU_MAX_FREQUENCY_ENTRIES: host frequency-table entry budget
#   before the accumulator spills to disk (0 = unbounded, the default).
# - DEEQU_TPU_FREQUENCY_SPILL: "0" disables the disk spill tier (the
#   budget then degrades the analyzer instead of spilling).
# - DEEQU_TPU_FREQUENCY_SPILL_PARTITIONS: hash partitions of the spill
#   store's disk layout (default 64; minimum 1).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Deterministic fault injection (implemented in deequ_tpu.reliability.faults;
# documented here for discoverability — tools/chaos_soak.py drives these).
#
# - DEEQU_TPU_FAULTS: JSON list of FaultSpec dicts arming a process-wide
#   fault plan. Deliberately NOT warn-and-fallback: a chaos plan that does
#   not parse must raise, not silently run the drill fault-free.
# - DEEQU_TPU_FAULT_SEED: rng seed for p-based fault specs (default 0).
#   Same raise-loudly contract as the plan: a bad seed would silently
#   change the drill's deterministic fault sequence.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Scan watchdog (implemented in deequ_tpu.reliability.watchdog; the env
# knob is documented here with the other operator-facing switches)
# ---------------------------------------------------------------------------

#: env var: per-pass watchdog deadline in seconds. Unset = derive from the
#: measured per-batch rate of completed passes on the same tier (a 10x
#: multiple with a 30s floor; disabled until a first rate exists). Any
#: value <= 0 disables the watchdog. A pass exceeding its deadline is
#: cancelled with a typed ScanStallError and fails over to the other tier
#: exactly like a thrown device fault.
SCAN_DEADLINE_ENV = "DEEQU_TPU_SCAN_DEADLINE_S"


# ---------------------------------------------------------------------------
# Elastic mesh fault tolerance (implemented in deequ_tpu.parallel.elastic /
# .health; the env knobs are documented here with the other operator-facing
# switches and re-exported below). Both follow the warn-and-fallback
# convention: an unparseable value warns once and keeps the default.
#
# - DEEQU_TPU_MESH_LADDER: comma-separated descending device counts the
#   re-shard ladder walks after a shard loss (default "8,4,2,1"). When no
#   rung fits the survivors, the fold drops to the host tier with the
#   salvaged canonical states — folded work is never lost.
# - DEEQU_TPU_SHARD_HEARTBEAT_S: seconds between heartbeat probes of a live
#   mesh fold, and each probe's per-shard deadline (default 5.0; <= 0
#   disables the periodic heartbeat). A shard missing its heartbeat is
#   declared lost exactly like a thrown ShardLossError.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Tracing / flight recorder (implemented in deequ_tpu.observability; the env
# knobs are documented here with the other operator-facing switches)
# ---------------------------------------------------------------------------

# Single source of truth lives where the values are READ (the modules
# below); re-exported here so every operator-facing knob is discoverable
# from config:
#
# - DEEQU_TPU_TRACE: span tracing. Default ON ("1"/unset); "0" disables
#   entirely; a float in (0, 1) samples that fraction of root traces
#   deterministically (unparseable values warn once and keep the default).
# - DEEQU_TPU_TRACE_RING: capacity of the flight-recorder ring of recent
#   finished spans (default 4096) — what /trace serves and what
#   typed-failure post-mortem dumps snapshot.
# - DEEQU_TPU_TRACE_JOURNAL: directory receiving this process's span
#   JOURNAL (``spans-<host>.jsonl``, line-buffered, one span per line as
#   it finishes) — the per-host half of a cross-process merged trace
#   (observability.export.merge_journals). Unset = no journal.
# - DEEQU_TPU_TRACE_HOST: the host label stamped on this process's
#   journal filename and header (default ``pid<pid>``); what the merged
#   Perfetto artifact names the process track.
# - DEEQU_TPU_FLIGHT_DIR: directory receiving flight-record JSONL
#   artifacts dumped on typed failures (DeviceFailure / ScanStallError /
#   CorruptStateError / SchemaDriftError). Unset = per-process temp dir.
from .ingest.prefetch import (  # noqa: E402,F401
    FEED_STALL_ENV,
    PREFETCH_DEPTH_ENV,
)
from .service.coalesce import (  # noqa: E402,F401
    COALESCE_ENV,
    COALESCE_MAX_WIDTH_ENV,
    FAST_PATH_MAX_ROWS_ENV,
)
from .service.fleet import (  # noqa: E402,F401
    FLEET_ENV,
    FLEET_STREAM_MIN_ROWS_ENV,
)
from .repository.partition_store import (  # noqa: E402,F401
    PARTITION_STORE_ENV,
    PARTITION_WINDOW_ENV,
)
from .service.fleetwatch import (  # noqa: E402,F401
    FLEETWATCH_BUNDLE_ENV,
    FLEETWATCH_ENV,
    FLEETWATCH_WINDOW_ENV,
)
from .observability.recorder import FLIGHT_DIR_ENV  # noqa: E402,F401
from .parallel.elastic import MESH_LADDER_ENV  # noqa: E402,F401
from .parallel.health import HEARTBEAT_ENV as SHARD_HEARTBEAT_ENV  # noqa: E402,F401
from .observability.trace import TRACE_ENV, TRACE_RING_ENV  # noqa: E402,F401
from .analyzers.grouping import (  # noqa: E402,F401
    DEVICE_FREQ_ENV,
    DEVICE_FREQ_MAX_CARDINALITY_ENV,
    FREQ_BUFFER_ENTRIES_ENV,
    FREQ_HOST_ROUTE_ENV,
    FREQ_TABLE_SLOTS_ENV,
)

# ---------------------------------------------------------------------------
# Cluster tier (implemented in deequ_tpu.cluster + repository/lease.py; the
# env knobs are documented here with the other operator-facing switches)
# ---------------------------------------------------------------------------
#
# - DEEQU_TPU_CLUSTER_VNODES: virtual nodes per host on the front tier's
#   consistent-hash ring (default 64; minimum 1). More points smooth the
#   per-host key distribution at slightly larger ring rebuild cost; a
#   membership change always re-homes only ~1/N of the key space.
# - DEEQU_TPU_CLUSTER_HEARTBEAT_S: seconds between a worker's heartbeat
#   writes into the shared membership directory (default 0.5; minimum
#   0.05). Heartbeats are atomic tmp+rename file writes on the same
#   shared filesystem the partition store uses.
# - DEEQU_TPU_CLUSTER_HOST_TTL_S: seconds without a beat before the front
#   tier declares a host LOST (default 3.0; minimum 0.1) and runs
#   recovery: ring re-hash to survivors, session adoption from the
#   partition store, journal replay of the folds the last flush missed.
#   Size it to several heartbeat periods to ride out scheduler hiccups.
# - DEEQU_TPU_CLUSTER_LEASE_TTL_S: seconds a compaction lease on a
#   PartitionedMetricsRepository stays valid without renewal (default
#   30.0; minimum 0.1). The lease elects ONE compactor among concurrent
#   writers (atomic create + epoch-fenced takeover of stale holders); a
#   refused or lost lease leaves loose entries readable — never deleted.
#
# All four parse via the shared warn-once utils.env_* readers:
# unparseable or out-of-range values log once and keep the default.
from .cluster.membership import (  # noqa: E402,F401
    HEARTBEAT_ENV as CLUSTER_HEARTBEAT_ENV,
    HOST_TTL_ENV as CLUSTER_HOST_TTL_ENV,
)
from .cluster.ring import VNODES_ENV as CLUSTER_VNODES_ENV  # noqa: E402,F401
from .repository.lease import (  # noqa: E402,F401
    LEASE_TTL_ENV as CLUSTER_LEASE_TTL_ENV,
)

# ---------------------------------------------------------------------------
# Tenant isolation plane (deequ_tpu.service.catalog + deequ_tpu.ingest.
# rowgate + the cluster front tier's journal bound)
# ---------------------------------------------------------------------------
#
# - DEEQU_TPU_CLUSTER_JOURNAL_MAX_FOLDS: payloads a session's loss-replay
#   journal may hold before the front tier force-flushes the session to
#   the partition store and clears it (default 256; minimum 1). The
#   journal replays the window since the last flush after a host loss; a
#   producer that never calls flush() would otherwise grow it one
#   payload per fold, unbounded, for the session's whole life.
# - DEEQU_TPU_CATALOG_HOT_TTL_S: seconds a catalog-opened session may sit
#   idle in the HOT tier before the plane's sweep() closes it back to
#   COLD (default 300.0; minimum 1.0). Cold tenants cost one registry
#   row, not a session — registration scales past active capacity.
# - DEEQU_TPU_CATALOG_POLL_S: debounce on the fold-boundary version poll
#   of a hot tenant's catalog document (default 2.0; minimum 0.0). A
#   catalog edit becomes effective within one poll interval at the next
#   fold boundary — no restart; 0 polls every fold.
# - DEEQU_TPU_ROWGATE_QUARANTINE_MAX_ROWS: total rows a quarantine
#   sidecar retains per (tenant, dataset) before further rejects are
#   counted but dropped (default 100000; minimum 0). Bounds the disk a
#   misbehaving producer can consume with nonconforming rows.
#
# All four parse via the shared warn-once utils.env_* readers:
# unparseable or out-of-range values log once and keep the default.
from .cluster.front import (  # noqa: E402,F401
    CLUSTER_JOURNAL_MAX_FOLDS_ENV,
)
from .ingest.rowgate import (  # noqa: E402,F401
    QUARANTINE_MAX_ROWS_ENV as ROWGATE_QUARANTINE_MAX_ROWS_ENV,
)
from .service.catalog import (  # noqa: E402,F401
    CATALOG_HOT_TTL_ENV,
    CATALOG_POLL_ENV,
)

# ---------------------------------------------------------------------------
# Self-tuning control plane (deequ_tpu.tuning: boot-time calibration,
# per-substrate profiles, online shadow-route re-fitting)
# ---------------------------------------------------------------------------
#
# - DEEQU_TPU_AUTOTUNE: "0" disables the whole tuning plane — no profile
#   load at service start, no online controller, and every registered
#   knob resolves to its static default, byte-for-byte the untuned
#   routing behavior (the escape hatch; pinned by tests/test_tuning.py).
#   Default on.
# - DEEQU_TPU_TUNING_PROFILE_DIR: directory holding the checksummed
#   per-substrate calibration profiles (default: <checkout>/.cache/
#   tuning, beside the in-checkout XLA compile cache). One file
#   per substrate fingerprint; corrupt or stale files are quarantined
#   into .quarantine/ and the service boots on static defaults.
# - DEEQU_TPU_TUNING_SHADOW_FRACTION: fraction of eligible folds the
#   online controller routes under a CANDIDATE knob setting while an
#   experiment runs (default 0.05; clamped to [0, 0.5] — the incumbent
#   always keeps majority traffic; 0 starves candidates of evidence, so
#   nothing is ever promoted).
# - DEEQU_TPU_TUNING_MIN_SAMPLES: measured folds each experiment arm
#   needs before a promotion/demotion verdict (default 32; minimum 1).
# - DEEQU_TPU_TUNING_BAND: the tolerance band — a candidate promotes
#   only when its measured rows/s beats the incumbent by MORE than this
#   fraction, and the floor guardrail demotes tuned knobs when the live
#   rate falls this far below the measured static-default rate
#   (default 0.25).
#
# Every tunable routing constant (fast-path ceiling, coalesce width,
# fleet sharding floor, prefetch depth, frequency-engine capacities, the
# probably_low_cardinality probe thresholds, the CrossoverRouter cost
# seeds) is registered in deequ_tpu/tuning/knobs.py; the env vars above
# and each knob's own DEEQU_TPU_* override parse via the shared
# warn-once utils.env_* readers, and operator env ALWAYS outranks tuned
# values. New DEEQU_TPU_FREQ_* overrides registered there:
#
# - DEEQU_TPU_FREQ_HOST_ROUTE_MAX_DISTINCT: union-distinct ceiling for
#   probably_low_cardinality to answer "host" (default 32768; min 1).
# - DEEQU_TPU_FREQ_PROBE_ROWS: rows per head/mid/tail probe slice
#   (default 65536; minimum 1).
# - DEEQU_TPU_FREQ_HOST_ROUTE_MIN_ROWS: row floor below which the probe
#   never routes host (default 2097152; minimum 0).
from .tuning.knobs import (  # noqa: E402,F401
    AUTOTUNE_ENV,
    TUNING_BAND_ENV,
    TUNING_MIN_SAMPLES_ENV,
    TUNING_PROFILE_DIR_ENV,
    TUNING_SHADOW_FRACTION_ENV,
)

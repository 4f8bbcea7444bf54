"""ScanEngine: the fused single-pass executor.

Replaces the reference's `runScanningAnalyzers` fused `data.agg(...)` scan
(reference `analyzers/runners/AnalysisRunner.scala:289-336`): all requested
scan-shareable analyzers fold each padded batch into their states inside ONE
jit'd XLA program (fusion by the compiler, not row offsets), while grouping /
host-accumulated analyzers consume the same batch on the host — so the whole
run makes exactly one pass over the data.

``RunMonitor`` is the SparkMonitor analog (reference test fixture
`SparkMonitor.scala:39-76`): pass/batch/program counts are first-class
observables so tests can assert scan-sharing invariants, not just values.
"""

from __future__ import annotations

import logging
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analyzers.base import ScanShareableAnalyzer
from ..analyzers.grouping import FrequenciesAndNumRows, GroupingAnalyzer
from ..config import DEFAULT_BATCH_SIZE
from ..data import Dataset
from ..observability import trace as _trace
from ..reliability.faults import fault_point
from .features import FeatureBuilder

_logger = logging.getLogger(__name__)

#: env var overriding the default ingest-tier placement ("auto" when unset;
#: "host"/"device" pin the tier). Read through `utils.env_str` so the
#: env-knob convention check (tools/statlint) can see every read site.
PLACEMENT_ENV = "DEEQU_TPU_PLACEMENT"

#: env var: directory receiving a `jax.profiler` trace of every pass
PROFILE_DIR_ENV = "DEEQU_TPU_PROFILE_DIR"


@dataclass
class RunMonitor:
    """Counts execution events for scan-sharing assertions. Also records
    which ingest tier a run executed on (``placement``), the probed feed
    bandwidth that drove the decision, and per-phase wall time
    (``phase_seconds``) so a run's cost is attributable without external
    tooling (SURVEY §5: lightweight phase timers).

    The reliability fields are the engine-side ledger the service's
    placement router learns from: ``device_failovers`` counts device→host
    tier hops, ``batch_bisections`` OOM-driven batch halvings,
    ``isolation_reruns`` battery-bisection re-passes, and ``degraded``
    names what was knocked out (analyzer reprs, host accumulator keys,
    tier hops). ``checkpoint_saves``/``resumed_at_batch`` trace the
    resumable-ingest path."""

    passes: int = 0
    batches: int = 0
    device_updates: int = 0
    jit_compiles: int = 0
    #: XLA program traces NEWLY paid during this monitor's runs (a DELTA,
    #: unlike ``jit_compiles`` which mirrors the absolute program-cache
    #: occupancy): a warm re-run of the same battery records 0 here. The
    #: compile-budget regression test and the bench's per-stage artifact
    #: key on this.
    program_compiles: int = 0
    placement: Optional[str] = None
    feed_bandwidth_mbps: Optional[float] = None
    phase_seconds: Dict[str, float] = field(default_factory=dict)
    device_failovers: int = 0
    batch_bisections: int = 0
    isolation_reruns: int = 0
    degraded: List[str] = field(default_factory=list)
    checkpoint_saves: int = 0
    resumed_at_batch: Optional[int] = None
    #: corrupt persisted payloads (repository entries, checkpoint states)
    #: this run's loaders quarantined or discarded instead of crashing on
    corrupt_quarantined: int = 0
    #: passes the scan watchdog cancelled for exceeding their deadline
    stalls: int = 0
    #: the subset of ``stalls`` that happened on the DEVICE tier — the
    #: placement router's probation signal (a host-tier hang must not pin
    #: the battery onto the tier that hung)
    device_stalls: int = 0
    #: per-analyzer cost attribution (seconds, keyed by repr(analyzer)):
    #: each signature bundle's measured compile+dispatch wall time split
    #: evenly across its REAL slots (pad slots re-fold a duplicate and
    #: charge nothing). Shares sum to ``bundle_dispatch_seconds`` exactly,
    #: so "what did analyzer X cost this run" is answerable even though
    #: bundling makes individual programs invisible. Dispatch is async:
    #: what a share measures is enqueue time plus, on a bundle's FIRST
    #: dispatch, the synchronous trace+XLA-compile it pays — the periodic
    #: solo-timing probe (``cost_probes``) adds synchronized samples where
    #: the bundle's true per-batch execution time is captured too.
    cost_by_analyzer: Dict[str, float] = field(default_factory=dict)
    #: total measured per-bundle dispatch wall seconds (the attribution
    #: denominator: sum(cost_by_analyzer.values()) == this, within float
    #: rounding)
    bundle_dispatch_seconds: float = 0.0
    #: synchronized solo-timing probes taken (every _COST_PROBE_EVERY
    #: batches a bundle dispatch is bracketed by block_until_ready, so its
    #: measured time is true execution, not enqueue)
    cost_probes: int = 0
    #: grouping sets that rode the device frequency TABLE engine this run
    #: (hashed fixed-shape count tables in the fused pass; ROADMAP item 3)
    device_freq_sets: int = 0
    #: device frequency tables whose compactions dropped groups — those
    #: sets re-ran through the host accumulator last-resort tier
    freq_overflow_fallbacks: int = 0
    #: mesh shards (devices/processes) declared lost mid-pass — dead
    #: collectives, injected mesh_loss faults, heartbeat-declared stalls
    shard_losses: int = 0
    #: times a degraded mesh was rebuilt over the surviving devices (the
    #: 8→4→2→1→host ladder; the terminal host drop counts too)
    mesh_reshards: int = 0
    #: surviving per-shard states salvaged into a canonical merge after a
    #: shard loss (what the elastic layer kept instead of recomputing)
    salvaged_states: int = 0
    #: streaming folds served by the tiny-delta HOST fast path (delta state
    #: computed with the host kernels, merged algebraically — no engine
    #: pass, no device dispatch; service.coalesce routes these)
    fast_path_folds: int = 0
    #: streaming folds executed inside a cross-session COALESCED device
    #: launch (stacked along a leading session axis, one vmapped program)
    coalesced_folds: int = 0
    #: streaming folds sharded over a FLEET sub-mesh: per-slice host
    #: partials fold shard-local states, butterfly-merged at the coalesce
    #: drain boundary (service.coalesce._execute_mesh_fold)
    fleet_mesh_folds: int = 0
    #: incremental verification (runners.incremental): partitions the
    #: delta planner scheduled a scan for this run (new + invalidated)
    partitions_scanned: int = 0
    #: partitions whose stored states were loaded with ZERO data touched
    partitions_reused: int = 0
    #: partitions whose stored states went stale (content change,
    #: fingerprint mismatch, battery growth, corruption) and re-scanned
    partitions_invalidated: int = 0
    #: stored partitions absent from the incoming set — excluded from the
    #: merge (retention deletions show up here)
    partitions_dropped: int = 0
    #: partitions whose states were served by the ROLLUP cache (the
    #: persisted left-fold prefix) — neither their data nor their state
    #: blobs were touched
    partitions_rolled_up: int = 0

    def reset(self) -> None:
        self.passes = 0
        self.batches = 0
        self.device_updates = 0
        self.jit_compiles = 0
        self.program_compiles = 0
        self.placement = None
        self.feed_bandwidth_mbps = None
        self.phase_seconds = {}
        self.device_failovers = 0
        self.batch_bisections = 0
        self.isolation_reruns = 0
        self.degraded = []
        self.checkpoint_saves = 0
        self.resumed_at_batch = None
        self.corrupt_quarantined = 0
        self.stalls = 0
        self.device_stalls = 0
        self.cost_by_analyzer = {}
        self.bundle_dispatch_seconds = 0.0
        self.cost_probes = 0
        self.device_freq_sets = 0
        self.freq_overflow_fallbacks = 0
        self.shard_losses = 0
        self.mesh_reshards = 0
        self.salvaged_states = 0
        self.fast_path_folds = 0
        self.coalesced_folds = 0
        self.fleet_mesh_folds = 0
        self.partitions_scanned = 0
        self.partitions_reused = 0
        self.partitions_invalidated = 0
        self.partitions_dropped = 0
        self.partitions_rolled_up = 0

    def merge_from(self, other: "RunMonitor") -> None:
        """Absorb another monitor's counters and phase times (locked).
        The coalescer records each fold's costs into a fold-local monitor
        while the fold executes inside ANOTHER job's launch; the fold's
        own job absorbs them here exactly once, so the export-plane
        harvest attributes the work to the tenant that submitted it."""
        with _MONITOR_LOCK:
            for name in (
                "passes", "batches", "device_updates", "program_compiles",
                "device_failovers", "batch_bisections", "isolation_reruns",
                "checkpoint_saves", "corrupt_quarantined", "stalls",
                "device_stalls", "device_freq_sets",
                "freq_overflow_fallbacks", "shard_losses", "mesh_reshards",
                "salvaged_states", "fast_path_folds", "coalesced_folds",
                "fleet_mesh_folds", "cost_probes", "partitions_scanned",
                "partitions_reused", "partitions_invalidated",
                "partitions_dropped", "partitions_rolled_up",
            ):
                setattr(self, name, getattr(self, name) + getattr(other, name))
            self.bundle_dispatch_seconds += other.bundle_dispatch_seconds
            for phase, seconds in other.phase_seconds.items():
                self.phase_seconds[phase] = (
                    self.phase_seconds.get(phase, 0.0) + seconds
                )
            for key, seconds in other.cost_by_analyzer.items():
                self.cost_by_analyzer[key] = (
                    self.cost_by_analyzer.get(key, 0.0) + seconds
                )
            self.degraded.extend(other.degraded)
            if other.placement is not None:
                self.placement = other.placement

    def note_degraded(self, tag: str) -> None:
        with _MONITOR_LOCK:
            self.degraded.append(tag)

    def bump(self, field_name: str, by: int = 1) -> None:
        """Locked counter increment: overlapped profile passes share one
        monitor across threads, and `+=` on a dataclass int is not
        atomic."""
        with _MONITOR_LOCK:
            setattr(self, field_name, getattr(self, field_name) + by)

    def add_phase_time(self, phase: str, seconds: float) -> None:
        with _MONITOR_LOCK:
            self.phase_seconds[phase] = self.phase_seconds.get(phase, 0.0) + seconds

    def timed(self, phase: str):
        """Context manager accumulating wall time under ``phase``; safe to
        use from the prefetch/ingest worker threads."""
        return _PhaseTimer(self, phase)


import threading as _threading  # noqa: E402

_MONITOR_LOCK = _threading.Lock()

#: guards _PROGRAM_CACHE's check-then-insert: service workers and the
#: placement warmer race on the same battery, and a losing duplicate
#: (executed=False) overwriting the winner would make the battery read as
#: cold forever after a completed warm
_PROGRAM_CACHE_LOCK = _threading.Lock()

#: per-thread device-feature-cache bypass: warm runs execute a throwaway
#: 1-row sample whose padded features must not occupy (or evict from) the
#: production cache budget
_CACHE_BYPASS = _threading.local()


class _PhaseTimer:
    """Span-backed phase timer: the measured interval both accumulates into
    ``phase_seconds`` (unchanged numbers, now derived from the same ns
    clock) and, when the calling thread carries a trace context, publishes
    as a finished child span — so a trace's phase durations can never
    disagree with the monitor's."""

    __slots__ = ("monitor", "phase", "t0_ns")

    def __init__(self, monitor: RunMonitor, phase: str):
        self.monitor = monitor
        self.phase = phase

    def __enter__(self):
        import time

        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        import time

        end_ns = time.perf_counter_ns()
        self.monitor.add_phase_time(self.phase, (end_ns - self.t0_ns) / 1e9)
        _trace.record_phase(self.phase, self.t0_ns, end_ns)
        return False


#: battery-level scan orchestrators keyed by (analyzer battery, mesh) —
#: analyzers are frozen dataclasses, so identical batteries across runs
#: reuse the SAME BundledScanProgram (whose `executed` flag carries the
#: service's warmth semantics). The COMPILED units live one level down in
#: _BUNDLE_PROGRAM_CACHE, keyed by signature so different batteries share
#: them. LRU-bounded so a long-lived multi-tenant service cycling through
#: many distinct batteries cannot grow program/device memory monotonically;
#: an evicted battery simply reads as cold again and re-warms through the
#: placement router.
from ..utils import BoundedLRU as _BoundedLRU  # noqa: E402

_PROGRAM_CACHE = _BoundedLRU(256)


class PackedScanProgram:
    """The fused per-batch update over a PACKED carry: every scalar state
    leaf rides in one stacked float vector + one stacked int vector; array
    leaves (HLL registers, KLL buffers, ...) stay separate.

    Why: XLA's fusion groups form around OUTPUT roots. With the naive carry
    — a tuple of per-analyzer states holding ~dozens of independent scalar
    leaves — every reduction becomes its own fusion root and the TPU runs
    one full pass over the batch PER REDUCTION: measured 138ms per 1M-row
    batch for 24 reductions over 4 f64 columns (~6ms per analyzer,
    perfectly additive, zero sharing). Stacking the scalar results into one
    vector gives the sibling reduces a single root, and XLA fuses them into
    one pass over each column: the same 24 reductions measure 3.6ms — a
    ~38x speedup with bit-identical results. Floats and ints pack into
    SEPARATE vectors so int32/int64 counters round-trip exactly even in
    32-bit mode (f32 slots would corrupt counts beyond 2^24).

    The packed carry lives on device across the whole pass; ``unpack``
    (jit'd slices + casts, negligible) restores the ordinary state pytrees
    for the fetch/merge paths, so everything outside the hot loop keeps the
    plain-state protocol.

    COLUMN-AGNOSTIC TRACE: the jit'd update consumes per-slot POSITIONAL
    feature tuples, and the traced body rebuilds each slot's features dict
    from this program's own analyzers' spec keys. Feature arrays are thereby
    remapped positionally, so one compiled program serves EVERY battery
    whose per-slot (class, feature kinds, state shapes) signatures match —
    ``Mean("a")`` and ``Mean("z")`` run the same XLA executable. This is
    what lets the signature-keyed bundle cache share programs across
    columns, batteries and the suggestion stage (the device-tier analog of
    the host ingest tier's signature bundling)."""

    def __init__(self, analyzers: Tuple[ScanShareableAnalyzer, ...], mesh):
        self.analyzers = analyzers
        self.mesh = mesh
        #: True once the fused update has DISPATCHED at least once: jax.jit
        #: compiles lazily, so mere construction leaves the program cold —
        #: warmth claims (the service's cache-aware placement) key on this
        self.executed = False
        #: per-slot feature keys of the TEMPLATE analyzers this program was
        #: traced with; callers with same-signature batteries feed arrays
        #: positionally and the trace rebinds them under these keys
        self._spec_keys = [
            tuple(spec.key for spec in a.feature_specs()) for a in analyzers
        ]

        init_shapes = jax.eval_shape(
            lambda: tuple(a.init_state() for a in analyzers)
        )
        leaves, treedef = jax.tree_util.tree_flatten(init_shapes)
        self._treedef = treedef
        self._float_idx = [
            i for i, l in enumerate(leaves)
            if l.ndim == 0 and jnp.issubdtype(l.dtype, jnp.floating)
        ]
        self._int_idx = [
            i for i, l in enumerate(leaves)
            if l.ndim == 0 and not jnp.issubdtype(l.dtype, jnp.floating)
        ]
        self._aux_idx = [i for i, l in enumerate(leaves) if l.ndim != 0]
        self._leaf_dtypes = [l.dtype for l in leaves]
        from ..config import ACC_DTYPE, COUNT_DTYPE

        self._fvec_dtype = ACC_DTYPE
        self._ivec_dtype = COUNT_DTYPE

        pack, unpack = self._pack, self._unpack
        spec_keys = self._spec_keys

        def fused_update(carry, slot_features):
            states = unpack(carry)
            return pack(
                tuple(
                    a.update(s, dict(zip(keys, feats)))
                    for a, keys, s, feats in zip(
                        analyzers, spec_keys, states, slot_features
                    )
                )
            )

        if mesh is None:
            self._update = jax.jit(fused_update, donate_argnums=0)
        else:
            from ..parallel import replicated

            self._update = jax.jit(
                fused_update,
                in_shardings=(replicated(mesh), None),
                out_shardings=replicated(mesh),
                donate_argnums=0,
            )
        #: the raw traced bodies, kept so the cross-session COALESCED path
        #: can lift the SAME update/unpack over a leading session axis
        #: (jax.vmap) — one fused launch folds W sessions' batches; built
        #: lazily on first coalesced use so ordinary runs pay nothing
        self._fused_update_fn = fused_update
        self._update_stacked = None
        self._unpack_stacked_jit = None
        self._init_stacked_jit = None
        self._unpack_jit = jax.jit(unpack)
        # pass-END unpack: the carry is dead afterwards, so donating it
        # lets the pass-through (aux) leaves alias instead of copy — a
        # resident frequency buffer is hundreds of MB, and the identity
        # copy was measurable (~0.26s at 256MB on CPU). NEVER use for the
        # mid-pass checkpoint unpack, whose carry keeps folding.
        self._unpack_final_jit = jax.jit(unpack, donate_argnums=0)
        # a mesh program's carry is born on the mesh: left to jax it lands
        # on the default device (device 0), outside a sub-mesh that does
        # not hold it, and the donated update then reads it cross-chip
        self._carry_sharding = None if mesh is None else replicated(mesh)
        self._init_jit = jax.jit(
            lambda: pack(tuple(a.init_state() for a in analyzers)),
            out_shardings=self._carry_sharding,
        )

    def _pack(self, states: Tuple):
        leaves = jax.tree_util.tree_flatten(states)[0]
        fvec = (
            jnp.stack([leaves[i].astype(self._fvec_dtype) for i in self._float_idx])
            if self._float_idx
            else jnp.zeros((0,), self._fvec_dtype)
        )
        ivec = (
            jnp.stack([leaves[i].astype(self._ivec_dtype) for i in self._int_idx])
            if self._int_idx
            else jnp.zeros((0,), self._ivec_dtype)
        )
        return fvec, ivec, tuple(leaves[i] for i in self._aux_idx)

    def _unpack(self, carry) -> Tuple:
        fvec, ivec, aux = carry
        leaves: List[Any] = [None] * len(self._leaf_dtypes)
        for j, i in enumerate(self._float_idx):
            leaves[i] = fvec[j].astype(self._leaf_dtypes[i])
        for j, i in enumerate(self._int_idx):
            leaves[i] = ivec[j].astype(self._leaf_dtypes[i])
        for j, i in enumerate(self._aux_idx):
            leaves[i] = aux[j]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def init_carry(self):
        """Packed identity states, built ON DEVICE (one dispatch): pulling
        init scalars to host first would cost a feed-link round trip per
        leaf."""
        return self._init_jit()

    def __call__(self, carry, features: Dict[str, jax.Array]):
        """Dispatch one batch with a GLOBAL features dict (keys = this
        program's own analyzers' spec keys — the monolithic/bench entry)."""
        slots = tuple(
            tuple(features[k] for k in keys) for keys in self._spec_keys
        )
        return self.call_with_slots(carry, slots)

    def call_with_slots(self, carry, slot_features):
        """Dispatch one batch with PRE-GATHERED per-slot feature tuples (the
        bundled entry: the caller gathered them via its OWN analyzers' spec
        keys, positionally parallel to this program's template specs)."""
        out = self._update(carry, slot_features)
        self.executed = True  # the jit call above traced + compiled
        return out

    def cold_programs(self) -> int:
        """1 until the fused update has dispatched (it compiles then)."""
        return 0 if self.executed else 1

    def unpack(self, carry) -> Tuple:
        """Packed carry -> ordinary per-analyzer state pytrees (on device)."""
        return self._unpack_jit(carry)

    def unpack_final(self, carry) -> Tuple:
        """Like :meth:`unpack` but DONATES the carry (pass-end only: the
        carry must not be dispatched again)."""
        import warnings

        with warnings.catch_warnings():
            # the stacked fvec/ivec leaves change dtype on unpack, so jax
            # reports their donated buffers as unusable — expected; the
            # donation exists for the pass-through aux leaves (a resident
            # frequency buffer is hundreds of MB)
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return self._unpack_final_jit(carry)

    def pack_states(self, states: Tuple):
        """Ordinary per-analyzer state pytrees -> packed carry; the inverse
        of :meth:`unpack`, used to re-enter the fused loop from
        checkpointed (host numpy) states. Lossless: every scalar leaf's
        dtype is ACC_DTYPE/COUNT_DTYPE, the packed vectors' own dtypes."""
        carry = self._pack(tuple(states))
        if self._carry_sharding is None:
            return carry
        return jax.device_put(carry, self._carry_sharding)

    # -- coalesced (stacked-over-sessions) entry points ----------------------
    #
    # The cross-session fold coalescer (service.coalesce) stacks W
    # same-signature sessions' single padded batches along a leading axis
    # and folds them as ONE device program: jax.vmap of the identical
    # fused_update, so per-slot semantics — and the compiled reduction
    # bits — match the serial dispatch exactly (pinned by the coalesce
    # parity tests). jit re-specializes per W, and the coalescer buckets W
    # to powers of two, so the compiled-shape space stays log-bounded.

    def init_carry_stacked(self, width: int):
        """W stacked identity carries, built on device in one dispatch."""
        if self._init_stacked_jit is None:
            pack, analyzers = self._pack, self.analyzers
            self._init_stacked_jit = jax.jit(
                lambda d: jax.vmap(
                    lambda _: pack(tuple(a.init_state() for a in analyzers))
                )(d)
            )
        return self._init_stacked_jit(jnp.zeros((width,), jnp.int32))

    def call_with_slots_stacked(self, carry, slot_features):
        """One coalesced dispatch: ``slot_features`` mirror
        :meth:`call_with_slots` but every array carries a leading session
        axis of the carry's width. The carry is DONATED (fold programs
        never re-read it), so per-launch state copies disappear."""
        if self._update_stacked is None:
            self._update_stacked = jax.jit(
                jax.vmap(self._fused_update_fn), donate_argnums=0
            )
        out = self._update_stacked(carry, slot_features)
        self.executed = True
        return out

    def unpack_stacked_final(self, carry) -> Tuple:
        """Stacked packed carry -> per-analyzer state pytrees whose leaves
        keep the leading session axis (the caller splits per session after
        ONE packed fetch). Donates the carry — launch-end only."""
        if self._unpack_stacked_jit is None:
            self._unpack_stacked_jit = jax.jit(
                jax.vmap(self._unpack), donate_argnums=0
            )
        import warnings

        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return self._unpack_stacked_jit(carry)

    def _cache_size(self) -> int:
        return self._update._cache_size()


#: signature-keyed bundle programs: the compiled-XLA sharing layer. Keys are
#: tuples of per-slot scan signatures + mesh, NOT analyzer identities, so
#: ``(Mean("a"), Mean("b"))`` and ``(Mean("x"), Mean("y"))`` — and the same
#: classes inside a different battery, or the suggestion stage's evaluation
#: batteries — all resolve to ONE PackedScanProgram. Sized above the
#: battery-level cache: bundles are the scarcer, more reusable resource.
_BUNDLE_PROGRAM_CACHE = _BoundedLRU(512)

_SCAN_SIG_CACHE = _BoundedLRU(4096)

#: batches between synchronized cost-attribution probes: the probed batch's
#: bundle dispatches are bracketed with block_until_ready so their measured
#: time is true execution (async dispatch otherwise measures enqueue). The
#: first probe lands on batch index 1 — batch 0 pays any cold compile and
#: would conflate compile with execution.
_COST_PROBE_EVERY = 64


class _CostLedger:
    """PASS-LOCAL per-analyzer cost accumulation. Two reasons it exists
    instead of writing straight to the RunMonitor:

    - **Hot-path cost.** Attribution runs per bundle per batch; a local
      dict accumulate is lock-free and uses the bundle programs'
      PRECOMPUTED repr strings, with ONE locked flush per pass.
    - **Zombie-pass hygiene.** A watchdog-abandoned pass keeps dispatching
      on its daemon thread while the failover re-pass runs against the
      SAME monitor; flushing only at pass completion — and only when the
      engine has not marked the pass cancelled — keeps an abandoned pass's
      costs out of ``cost_by_analyzer`` (the attribution analog of the
      rate tracker's contamination guard)."""

    __slots__ = ("by_key", "total", "probes")

    def __init__(self):
        self.by_key: Dict[str, float] = {}
        self.total = 0.0
        self.probes = 0

    def add_bundle(self, slot_reprs, seconds: float) -> None:
        self.total += seconds
        share = seconds / len(slot_reprs)
        by_key = self.by_key
        for key in slot_reprs:
            by_key[key] = by_key.get(key, 0.0) + share

    def flush(self, monitor: RunMonitor) -> None:
        if not self.by_key and not self.probes:
            return
        with _MONITOR_LOCK:
            costs = monitor.cost_by_analyzer
            for key, seconds in self.by_key.items():
                costs[key] = costs.get(key, 0.0) + seconds
            monitor.bundle_dispatch_seconds += self.total
            monitor.cost_probes += self.probes


def _scan_signature(a: ScanShareableAnalyzer) -> Tuple:
    """Program-identity key of an analyzer's fused-scan update: the ingest
    signature (class + state tree structure + leaf shapes/dtypes) extended
    with the feature-spec KIND tuple (a where-filter adds a predicate
    feature, changing the traced update) and the analyzer's own
    ``scan_program_key`` escape hatch. Valid because every ``update`` is a
    pure function of the state and feature VALUES given that key: columns,
    predicates, regexes and quantile points act host-side (feature
    computation) or at metric time, never inside the trace."""
    sig = _SCAN_SIG_CACHE.get(a)
    if sig is None:
        keys = [spec.key for spec in a.feature_specs()]
        sig = _ingest_signature(a) + (
            tuple(spec.kind for spec in a.feature_specs()),
            # the key-DUPLICATION pattern: the traced update rebinds slot
            # arrays under the template's keys via dict(zip(keys, feats)),
            # so an analyzer whose specs repeat a key (e.g. where ==
            # predicate) collapses positions a distinct-key analyzer keeps
            # separate — they must not share a program
            tuple(keys.index(k) for k in keys),
            a.scan_program_key(),
        )
        _SCAN_SIG_CACHE[a] = sig
    return sig


def _signature_bundles(analyzers, sig_fn, bundle_size: int):
    """Partition analyzer indices into signature-homogeneous bundles of at
    most ``bundle_size``, preserving relative order within a signature;
    returns (indices, n_real) pairs. Pad positions (j >= n_real) re-fold a
    REPEAT of the bundle's first index and their outputs MUST be discarded
    by the caller. Two padding rules bound the compiled-shape space per
    signature to log2(bundle_size)+1 variants while keeping pad waste < 2x:

    - a signature spanning MORE than one bundle pads its tail to the full
      ``bundle_size`` so the tail reuses the full-size compiled program
      instead of compiling a second length variant;
    - a LONE small group pads to the next power of two, so batteries with
      nearby same-class counts (pass-2 numeric batteries, suggestion
      evaluation subsets) converge on the same program shapes instead of
      compiling one program per exact count.

    Shared by the host ingest tier and the device scan bundling so the two
    partitioning policies cannot drift."""
    by_sig: Dict[Tuple, List[int]] = {}
    for i, a in enumerate(analyzers):
        by_sig.setdefault(sig_fn(a), []).append(i)
    bundles: List[Tuple[List[int], int]] = []
    for idxs in by_sig.values():
        for j in range(0, len(idxs), bundle_size):
            part = idxs[j : j + bundle_size]
            n_real = len(part)
            if j > 0 and n_real < bundle_size:
                part = part + [idxs[0]] * (bundle_size - n_real)
            elif j == 0 and n_real < bundle_size:
                slots = 1
                while slots < n_real:
                    slots *= 2
                part = part + [idxs[0]] * (slots - n_real)
            bundles.append((part, n_real))
    return bundles


def _bundle_program(
    bundle_analyzers: Tuple[ScanShareableAnalyzer, ...], mesh
) -> PackedScanProgram:
    """The signature-cached PackedScanProgram for one bundle. The stored
    program was traced with the FIRST battery's analyzers that materialized
    this key (the templates); every later same-signature bundle feeds its
    feature arrays positionally through ``call_with_slots``. Callers hold
    _PROGRAM_CACHE_LOCK."""
    key = (
        tuple(_scan_signature(a) for a in bundle_analyzers),
        None if mesh is None else tuple(mesh.devices.flat),
    )
    cached = _BUNDLE_PROGRAM_CACHE.get(key)
    if cached is None:
        fault_point("compile", tag=str(len(bundle_analyzers)))
        cached = PackedScanProgram(bundle_analyzers, mesh)
        _BUNDLE_PROGRAM_CACHE[key] = cached
    return cached


class BundledScanProgram:
    """Battery-level orchestrator over signature-keyed bundle programs.

    The monolithic PackedScanProgram keys its compile on the full analyzer
    tuple, so a cold 50-column profile battery pays one giant XLA compile
    (measured 1140.6s staging vs 1.98s warm — 575x, July chip round) that
    nothing else can reuse. This splits the battery into (class,
    state-shape) signature bundles of at most ``config.scan_bundle_size()``
    analyzers: each bundle compiles a SMALL program cached by signature, so
    a 50-column profile compiles ~10 programs that are shared across its
    own columns, across batteries, across the profiler's passes and the
    suggestion stage — and, via jax's persistent compilation cache, across
    processes. The packed-carry fusion win survives WITHIN each bundle
    (same-class sibling reductions share one output root); what is traded
    away is cross-class fusion over one column, bought back many times over
    in compile time.

    ``DEEQU_TPU_SCAN_BUNDLE=0`` restores the monolithic single-bundle
    behavior (the parity baseline the bundled path is tested bit-identical
    against).

    Presents the same interface the engine drives (`init_carry` /
    ``__call__`` / `unpack` / `pack_states` / `_cache_size`); the carry is a
    tuple of per-bundle packed carries."""

    def __init__(self, analyzers: Tuple[ScanShareableAnalyzer, ...], mesh):
        from ..config import scan_bundle_size

        self.analyzers = analyzers
        self.mesh = mesh
        #: battery-level warmth: True once THIS battery dispatched. Shared
        #: bundle programs may already be compiled (that is the point), but
        #: warmth introspection stays conservative at battery granularity so
        #: the service's placement probes keep their lazy-compile semantics.
        self.executed = False
        bundle_size = scan_bundle_size()
        if bundle_size <= 0:
            self._bundles = [(list(range(len(analyzers))), len(analyzers))]
        else:
            self._bundles = _signature_bundles(
                analyzers, _scan_signature, bundle_size
            )
        self._programs = [
            _bundle_program(tuple(analyzers[i] for i in idxs), mesh)
            for idxs, _ in self._bundles
        ]
        #: per-bundle, per-slot feature keys of the ACTUAL analyzers —
        #: gathered from the global features dict at dispatch and fed
        #: positionally to the (possibly template-traced) bundle program
        self._slot_keys = [
            [
                tuple(spec.key for spec in analyzers[i].feature_specs())
                for i in idxs
            ]
            for idxs, _ in self._bundles
        ]
        #: per-bundle repr strings of the REAL slots — precomputed so cost
        #: attribution never builds repr() on the dispatch hot path
        self._slot_reprs = [
            [repr(analyzers[i]) for i in idxs[:n_real]]
            for idxs, n_real in self._bundles
        ]

    def init_carry(self):
        return tuple(prog.init_carry() for prog in self._programs)

    def __call__(
        self,
        carry,
        features: Dict[str, jax.Array],
        ledger: Optional[_CostLedger] = None,
        probe: bool = False,
    ):
        """Dispatch one batch. With ``ledger`` (a pass-local
        :class:`_CostLedger`), each bundle's dispatch wall time is measured
        and attributed evenly across its REAL slots; async dispatch means
        the share normally measures enqueue + (on the first dispatch) the
        synchronous trace/XLA compile. ``probe=True`` brackets each bundle
        with ``block_until_ready`` so this batch's measurement is TRUE
        execution time — the engine schedules one probe every
        ``_COST_PROBE_EVERY`` batches, bounding the sync overhead."""
        import time as _time

        out = []
        for c, prog, keys, reprs in zip(
            carry, self._programs, self._slot_keys, self._slot_reprs
        ):
            slots = tuple(tuple(features[k] for k in slot) for slot in keys)
            if ledger is None:
                out.append(prog.call_with_slots(c, slots))
                continue
            if probe:
                jax.block_until_ready(jax.tree_util.tree_leaves(c))
            t0 = _time.perf_counter()
            result = prog.call_with_slots(c, slots)
            if probe:
                jax.block_until_ready(jax.tree_util.tree_leaves(result))
            out.append(result)
            ledger.add_bundle(reprs, _time.perf_counter() - t0)
        if probe and ledger is not None and self._programs:
            # one probe per probed BATCH (the documented unit), however
            # many bundles the battery spans
            ledger.probes += 1
        self.executed = True
        return tuple(out)

    def cold_programs(self) -> int:
        """Bundle programs that have never dispatched (each compiles on
        its first batch)."""
        return sum(not p.executed for p in self._programs)

    def unpack(self, carry) -> Tuple:
        """Per-analyzer state pytrees in battery order (pad slots, which
        re-folded a duplicate of their bundle's first analyzer, are
        discarded)."""
        return self._unpack(carry, final=False)

    def unpack_final(self, carry) -> Tuple:
        """Pass-end variant: donates each bundle's carry (it must not be
        dispatched again) so pass-through leaves alias instead of copy."""
        return self._unpack(carry, final=True)

    def _unpack(self, carry, final: bool) -> Tuple:
        out: List[Any] = [None] * len(self.analyzers)
        for (idxs, n_real), prog, c in zip(self._bundles, self._programs, carry):
            states = prog.unpack_final(c) if final else prog.unpack(c)
            for j in range(n_real):
                out[idxs[j]] = states[j]
        return tuple(out)

    def pack_states(self, states: Tuple):
        """Inverse of :meth:`unpack` (checkpoint resume): pad slots are
        refilled with their bundle's first state, mirroring what the fold
        would have computed for them."""
        states = tuple(states)
        return tuple(
            prog.pack_states(tuple(states[i] for i in idxs))
            for (idxs, _), prog in zip(self._bundles, self._programs)
        )

    def _distinct_programs(self) -> List[PackedScanProgram]:
        seen: Dict[int, PackedScanProgram] = {}
        for prog in self._programs:
            seen.setdefault(id(prog), prog)
        return list(seen.values())

    def _cache_size(self) -> int:
        return sum(p._cache_size() for p in self._distinct_programs())


def fold_sessions_coalesced(
    orchestrators: Sequence[BundledScanProgram],
    features_list: Sequence[Dict[str, np.ndarray]],
) -> List[Tuple]:
    """Fold W same-signature sessions' single padded batches as ONE device
    launch per signature bundle (the cross-session coalescer's device arm).

    ``orchestrators[i]`` is session i's own battery orchestrator — its
    ``_slot_keys`` gather ``features_list[i]`` under that battery's spec
    keys; every battery in the group shares the template's per-position
    scan signatures (the coalesce key guarantees it), so the gathered
    arrays feed the TEMPLATE's bundle programs positionally, exactly like
    a single-session bundled dispatch. The group pads to the next power of
    two with duplicates of session 0 (bounding compiled widths to
    log2(max_width) variants); pad outputs are discarded.

    One vmapped dispatch per bundle + ONE packed state fetch for the whole
    group — the per-session fixed cost this path exists to amortize.
    Returns per REAL session a tuple of host state pytrees in battery
    order. Mesh-free only (service streaming sessions coalesce; GSPMD
    passes keep the serial path)."""
    template = orchestrators[0]
    if template.mesh is not None:
        raise ValueError("coalesced folds are mesh-free by design")
    n_real = len(features_list)
    width = 1
    while width < n_real:
        width *= 2
    gathered = [
        [
            tuple(tuple(feats[k] for k in slot) for slot in keys)
            for keys in prog._slot_keys
        ]
        for prog, feats in zip(orchestrators, features_list)
    ]
    gathered.extend([gathered[0]] * (width - n_real))
    stacked_states: List[Any] = [None] * len(template.analyzers)
    for j, ((idxs, n_real_slots), bprog) in enumerate(
        zip(template._bundles, template._programs)
    ):
        stacked_slots = tuple(
            tuple(
                np.stack([gathered[w][j][s][f] for w in range(width)])
                for f in range(len(gathered[0][j][s]))
            )
            for s in range(len(template._slot_keys[j]))
        )
        carry = bprog.init_carry_stacked(width)
        out = bprog.call_with_slots_stacked(carry, stacked_slots)
        states = bprog.unpack_stacked_final(out)
        for k in range(n_real_slots):
            stacked_states[idxs[k]] = states[k]
    fetched = _fetch_states_packed(tuple(stacked_states))
    return [
        tuple(
            jax.tree_util.tree_map(lambda x, _w=w: x[_w], st)
            for st in fetched
        )
        for w in range(n_real)
    ]


def _program_cache_key(analyzers: Tuple[ScanShareableAnalyzer, ...], mesh) -> Tuple:
    from ..config import scan_bundle_size

    # bundle size joins the key: an orchestrator bakes its partitioning in
    # __init__, so a DEEQU_TPU_SCAN_BUNDLE flip mid-process must MISS the
    # battery cache and re-partition instead of silently serving the old
    # layout (config.py promises the knob is honored without re-import,
    # and the bundled-vs-monolithic parity tests depend on it)
    return (
        analyzers,
        None if mesh is None else tuple(mesh.devices.flat),
        scan_bundle_size(),
    )


def _fused_program(analyzers: Tuple[ScanShareableAnalyzer, ...], mesh):
    key = _program_cache_key(analyzers, mesh)
    # construction is cheap (eval_shape + lazy jit wrappers, no compile),
    # so holding the lock across it guarantees ONE instance per key — the
    # instance whose `executed` flag warmth decisions read
    with _PROGRAM_CACHE_LOCK:
        cached = _PROGRAM_CACHE.get(key)
        if cached is None:
            cached = BundledScanProgram(analyzers, mesh)
            _PROGRAM_CACHE[key] = cached
        return cached


def _deduped_battery(analyzers) -> Tuple[ScanShareableAnalyzer, ...]:
    """Scan-shareable subset, deduped in first-encounter order — the same
    normalization do_analysis_run applies before building its battery, so
    warm registrations and cache probes key consistently with real runs."""
    return tuple(
        dict.fromkeys(
            a for a in analyzers if isinstance(a, ScanShareableAnalyzer)
        )
    )


def fused_program_is_cached(
    analyzers: Sequence[ScanShareableAnalyzer], mesh=None
) -> bool:
    """Whether the fused scan program for this exact battery has already
    EXECUTED in this process (jit compiles lazily, so a merely-constructed
    program would still pay the full XLA compile on its first dispatch —
    warmth means "a dispatch already happened", not "an object exists").
    The service's cache-aware placement keys its routing on this."""
    program = _PROGRAM_CACHE.get(
        _program_cache_key(_deduped_battery(analyzers), mesh)
    )
    return program is not None and program.executed


def effective_batch_size(data: Dataset, batch_size: Optional[int] = None) -> int:
    """The batch size a run over ``data`` will actually use when the
    caller leaves it unset. (The service plane always passes an EXPLICIT
    batch size — the bucketed `_session_batch_size` — so its warmth keys
    key on the shape it dispatches, not on this default.)"""
    return batch_size or min(DEFAULT_BATCH_SIZE, max(int(data.num_rows), 1))


def detached_warm_sample(data: Dataset) -> Dataset:
    """A 1-row DEEP copy of the dataset for background warming. A zero-copy
    ``slice(0, 1)`` would keep the parent table's buffers alive for as long
    as the warm sits queued — with a backlog of multi-second compiles, that
    pins whole datasets in memory after their jobs finished. The IPC round
    trip copies only the one row plus each dictionary column's dictionary
    (which warm battery planning needs)."""
    import pyarrow as pa

    head = data.arrow.slice(0, 1)
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, head.schema) as writer:
        writer.write_table(head)
    table = pa.ipc.open_stream(sink.getvalue()).read_all()
    return Dataset(table, probe_encoding=False)


def warm_fused_program(
    analyzers: Sequence[ScanShareableAnalyzer],
    mesh=None,
    data: Optional[Dataset] = None,
    batch_size: Optional[int] = None,
) -> None:
    """Compile the fused scan program for a battery ahead of its first
    production run. Cold compiles stall a request for tens of seconds (the
    575x cold-compile gap); the service calls this from a background warmer
    so queued jobs fall back to the host tier instead of blocking.

    With ``data``, runs the REAL pipeline over a 1-row slice padded to the
    production batch size, with the FULL analyzer list — grouping analyzers
    included, so run-time battery augmentations (DeviceFrequencyScan over
    the dict columns; a slice shares its parent's table-wide dictionary)
    compile exactly as production will dispatch them. Without ``data`` only
    the program object is built (registration; the compile stays lazy)."""
    if data is None:
        battery = _deduped_battery(analyzers)
        if battery:
            _fused_program(battery, mesh)
        return
    from .analysis_runner import AnalysisRunner

    sample = Dataset(data.arrow.slice(0, 1), probe_encoding=False)
    # default to the PRODUCTION batch size: deriving it from ``data`` would
    # compile a shape-1 program when handed a detached 1-row warm sample,
    # falsely marking the battery warm at a shape no real run dispatches
    bs = batch_size or DEFAULT_BATCH_SIZE
    _CACHE_BYPASS.active = True
    try:
        AnalysisRunner.do_analysis_run(
            sample, list(analyzers), batch_size=bs, sharding=mesh,
            placement="device",
        )
    finally:
        _CACHE_BYPASS.active = False


def _group_leaves(leaves, idx=None) -> Dict[Tuple, List[int]]:
    """Leaf indices (all, or the subset ``idx``) grouped by (shape, dtype)
    in first-encounter order. A battery fetch packs hundreds of leaves;
    grouping same-shaped leaves into one ``stack`` before the final concat
    compiles ~6x faster than a 600-operand concat (cold fetch was paying
    seconds of XLA compile) and produces the same bytes in the GROUPED
    leaf order, which the unpackers walk via _grouped_leaf_order — both
    derive from this one grouping so the byte-order contract cannot
    drift."""
    groups: Dict[Tuple, List[int]] = {}
    for i in range(len(leaves)) if idx is None else idx:
        leaf = leaves[i]
        groups.setdefault((tuple(leaf.shape), str(leaf.dtype)), []).append(i)
    return groups


def _grouped_leaf_order(leaves, idx=None) -> List[int]:
    return [i for grp in _group_leaves(leaves, idx).values() for i in grp]


@jax.jit
def _pack_leaves_f64(leaves):
    """Concatenate every state leaf into ONE f64 device buffer (in GROUPED
    leaf order, see _group_leaves). Fetching a state pytree leaf-by-leaf
    costs a device round trip per buffer, hundreds of them for a wide
    battery; one packed fetch costs a single round trip regardless of
    battery size. f64 represents every state dtype in use exactly
    (f32/f16 subsets; bool / (u)int8/16/32 exactly; int64 counters exactly
    up to 2^53 — counters are row counts, far below that). 64-bit
    *bitcasts* would be bit-perfect but the TPU x64-emulation rewriter does
    not implement them."""
    parts = []
    for idxs in _group_leaves(leaves).values():
        if len(idxs) == 1:
            parts.append(jnp.ravel(leaves[idxs[0]]).astype(jnp.float64))
        else:
            parts.append(
                jnp.ravel(jnp.stack([leaves[i] for i in idxs]).astype(jnp.float64))
            )
    return jnp.concatenate(parts)


@jax.jit
def _pack_leaves_u64_u8(leaves):
    """x64-mode packing of 8-byte UNSIGNED leaves — the frequency engine's
    full-range u64 hash keys, which the f64 upcast path would corrupt above
    2^53. Each leaf splits into (lo, hi) uint32 halves and ships through
    the bit-exact u8 bitcast (the TPU x64-emulation rewriter implements no
    64-bit bitcasts; 32-bit ones it does). Per group the layout is one
    lo-block then one hi-block, grouped leaf order."""
    parts = []
    for idxs in _group_leaves(leaves).values():
        grp = [leaves[i] for i in idxs]
        stacked = grp[0] if len(grp) == 1 else jnp.stack(grp)
        lo = (stacked & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (stacked >> jnp.uint64(32)).astype(jnp.uint32)
        parts.append(
            jnp.ravel(
                jax.lax.bitcast_convert_type(jnp.stack([lo, hi]), jnp.uint8)
            )
        )
    return jnp.concatenate(parts)


@jax.jit
def _pack_leaves_u8(leaves):
    """32-bit-mode packing (grouped leaf order): bitcast each (<=32-bit)
    leaf to raw bytes — bit-exact, and int32 values above f32's 2^24
    integer range survive."""
    parts = []
    for idxs in _group_leaves(leaves).values():
        grp = [leaves[i] for i in idxs]
        if grp[0].dtype == jnp.bool_:
            grp = [g.astype(jnp.uint8) for g in grp]
        stacked = grp[0] if len(grp) == 1 else jnp.stack(grp)
        parts.append(jnp.ravel(jax.lax.bitcast_convert_type(stacked, jnp.uint8)))
    return jnp.concatenate(parts)


def _empty_batch_like(data: Dataset, columns):
    """A 0-valid-row batch with the dataset's schema (identity partials)."""
    names = list(columns) if columns is not None else data.schema.names
    empty = data.arrow.slice(0, 0)
    for b in Dataset(empty, probe_encoding=False).batches(1, columns=names):
        return b
    raise AssertionError("batches() always yields at least one batch")


#: below this many narrow bytes the second transfer's round trip costs more
#: than the f64 upcast wastes
_NARROW_SPLIT_BYTES = 1 << 15

#: leaves at least this big skip the packed-transfer paths and transfer
#: directly (one leaf = one transfer; the repack's extra full-buffer
#: copies dominate at resident-key-buffer sizes)
_DIRECT_LEAF_BYTES = 4 << 20


@partial(jax.jit, static_argnums=1)
def _split_kll_items(items, sketch_size: int):
    """(non-top levels cut to ``sketch_size`` columns, the top level), in
    one program: eager indexing would stage its index constants on the
    default device, outside a sub-mesh that holds ``items``."""
    return items[:-1, :sketch_size], items[-1:, :]


def _slim_kll_for_fetch(states: Tuple) -> Tuple[Tuple, List[Optional[int]]]:
    """Shrink each KLL state's item buffer before fetching: after every
    fold/merge the compaction cascade leaves <= k items in every level it
    processes, so columns beyond k are structural +inf padding — 3/4 of the
    buffer's bytes. The TOP level is the one level the cascade never
    compacts and can legitimately exceed k, so it ships FULL width; the
    transform is lossless. Returns (slimmed states, original widths)."""
    from ..ops.kll import KLLSketchState

    widths: List[Optional[int]] = []
    slim: List[Any] = []
    for s in states:
        if (
            isinstance(s, KLLSketchState)
            and s.items.ndim == 2
            and s.items.shape[1] > s.sketch_size
        ):
            widths.append(int(s.items.shape[1]))
            low_items, top = _split_kll_items(s.items, int(s.sketch_size))
            slim.append((s.replace(items=low_items), top))
        else:
            widths.append(None)
            slim.append(s)
    return tuple(slim), widths


def _assert_kll_slim_invariant(sizes: np.ndarray, sketch_size: int) -> None:
    """Losslessness of every slim-for-fetch variant rests on each non-top
    level holding <= sketch_size items at fetch time (guaranteed because
    every update/ingest/merge ends in a compaction cascade). A future code
    path fetching mid-append would otherwise silently truncate items; the
    shipped ``sizes`` let us fail loudly instead."""
    if (sizes[:-1] > sketch_size).any():
        raise AssertionError(
            "KLL slim-for-fetch invariant violated: non-top level holds "
            f"{int(sizes[:-1].max())} items > sketch_size "
            f"{sketch_size}; state was fetched mid-append"
        )


def _restore_kll_width(fetched: List[Any], widths: List[Optional[int]]) -> List[Any]:
    for i, width in enumerate(widths):
        if width is None:
            continue
        low_state, top = fetched[i]
        low = np.asarray(low_state.items)
        _assert_kll_slim_invariant(np.asarray(low_state.sizes), low_state.sketch_size)
        pad = np.full((low.shape[0], width - low.shape[1]), np.inf, dtype=low.dtype)
        items = np.concatenate(
            [np.concatenate([low, pad], axis=1), np.asarray(top)], axis=0
        )
        fetched[i] = low_state.replace(items=items)
    return fetched


#: host-side identity leaf values per scan signature: the slim fetch
#: reconstructs non-metric-bearing leaves from these instead of hauling
#: them over the feed link. One device round trip per SIGNATURE per
#: process (not per analyzer per pass).
_HOST_INIT_LEAVES = _BoundedLRU(1024)


def _host_init_leaf_values(a) -> List[np.ndarray]:
    key = _scan_signature(a)
    cached = _HOST_INIT_LEAVES.get(key)
    if cached is None:
        cached = [
            np.asarray(leaf)
            for leaf in jax.tree_util.tree_leaves(a.init_state())
        ]
        _HOST_INIT_LEAVES[key] = cached
    return cached


def _slim_metric_leaves(analyzers, states: Tuple):
    """Replace each analyzer's NON-metric-bearing state leaves (per
    ``Analyzer.metric_leaves``) with zero-size placeholders so they cost
    nothing on the feed link; returns (slimmed states, restore plan). Only
    called on runs that neither persist nor aggregate states — the metric
    never reads the dropped leaves, so reconstructing them from identity
    values (:func:`_restore_slim_leaves`) is observationally lossless."""
    plan: List[Tuple[int, List[int]]] = []
    out = list(states)
    for i, a in enumerate(analyzers):
        idx = a.metric_leaves()
        if idx is None:
            continue
        leaves, treedef = jax.tree_util.tree_flatten(out[i])
        keep = {int(j) for j in idx}
        dropped = [j for j in range(len(leaves)) if j not in keep]
        if not dropped:
            continue
        for j in dropped:
            # a host placeholder: a device one would sit on the default
            # device, outside a sub-mesh holding the other leaves
            leaves[j] = np.zeros((0,), np.dtype(leaves[j].dtype))
        out[i] = jax.tree_util.tree_unflatten(treedef, leaves)
        plan.append((i, dropped))
    return tuple(out), plan


def _restore_slim_leaves(analyzers, fetched: List[Any], plan) -> List[Any]:
    for i, dropped in plan:
        init_leaves = _host_init_leaf_values(analyzers[i])
        leaves, treedef = jax.tree_util.tree_flatten(fetched[i])
        for j in dropped:
            leaves[j] = init_leaves[j]
        fetched[i] = jax.tree_util.tree_unflatten(treedef, leaves)
    return fetched


#: floor on statically-slimmed KLL item bytes below which the two-phase
#: fetch is never considered (the economic gate below also weighs the
#: probed link bandwidth/latency)
_TWO_PHASE_KLL_BYTES = 1 << 20

#: fraction of the slimmed bytes the occupied-levels slice typically drops
#: (~log2(rows/k) of 32 levels occupied)
_TWO_PHASE_EXPECTED_SAVING = 0.6


def _fetch_states_packed(states: Tuple, analyzers=None) -> List[Any]:
    """Device states -> host numpy pytrees via packed D2H transfers.

    In x64 mode, leaves that are natively <= 32-bit (KLL item buffers are
    f32[levels, 4k] — by far the largest states) ship bit-exact through the
    u8-bitcast buffer instead of being upcast to f64, halving the bytes on
    the feed link; 64-bit leaves ride the f64 buffer as before. Both packs
    dispatch before either blocks, so the link sees back-to-back transfers.
    KLL item buffers additionally ship only their occupied column range
    (see _slim_kll_for_fetch) and are re-padded host-side; when the
    battery carries enough sketch bytes, the two-phase variant also drops
    every level row above the deepest occupied one.

    With ``analyzers`` (the SLIM fetch — runs that neither persist nor
    aggregate states), each analyzer's non-metric-bearing leaves are
    dropped from the transfer entirely and reconstructed host-side from
    identity values (see ``Analyzer.metric_leaves``); everything above
    composes on top."""
    from ..ops.kll import KLLSketchState

    fault_point("state_fetch")
    slim_plan = None
    if analyzers is not None:
        from ..config import slim_fetch_enabled

        if slim_fetch_enabled() and len(analyzers) == len(states):
            states, slim_plan = _slim_metric_leaves(analyzers, states)

    def finish(fetched: List[Any]) -> List[Any]:
        if slim_plan:
            fetched = _restore_slim_leaves(analyzers, fetched, slim_plan)
        return fetched

    kll_idx = [
        i for i, s in enumerate(states)
        if isinstance(s, KLLSketchState)
        and s.items.ndim == 2
        and s.items.shape[1] > s.sketch_size
    ]
    slim_bytes = sum(
        ((states[i].items.shape[0] - 1) * states[i].sketch_size
         + states[i].items.shape[1]) * states[i].items.dtype.itemsize
        for i in kll_idx
    )
    if slim_bytes > _TWO_PHASE_KLL_BYTES:
        # economic gate: splitting the fetch serializes one extra link
        # round trip, so it must buy more transfer time than it costs —
        # on a fast-but-latent link a few MB is cheaper in one shot
        bw_bytes_per_s = probe_feed_bandwidth() * 1e6
        expected_saving_s = _TWO_PHASE_EXPECTED_SAVING * slim_bytes / bw_bytes_per_s
        if expected_saving_s > probe_feed_latency():
            return finish(_fetch_states_two_phase(states, kll_idx))
    states, kll_widths = _slim_kll_for_fetch(states)
    if any(w is not None for w in kll_widths):
        return finish(
            _restore_kll_width(_fetch_states_packed_raw(states), kll_widths)
        )
    return finish(_fetch_states_packed_raw(states))


def _fetch_states_two_phase(states: Tuple, kll_idx: List[int]) -> List[Any]:
    """Two feed-link transfers instead of one, but only the OCCUPIED slice
    of each KLL item buffer crosses the link: phase A ships every state
    leaf except the item buffers (including the per-level ``sizes``), the
    host derives each sketch's deepest occupied level, and phase B ships
    rows ``[0..T]`` at sketch_size width (typical occupancy is ~log2(rows/k)
    of the 32 levels, so this cuts the dominant fetch bytes another ~2-4x
    on top of the width slim). The reconstruction re-pads with the +inf
    structural padding; the non-top <= k occupancy invariant is asserted
    exactly like the one-phase slim. Shipped row counts round up to the
    next power of two so the packed-fetch program shapes stay stable
    across runs with different occupancy depths (no recompile per
    signature)."""
    placeholders = {i: states[i].items for i in kll_idx}
    stripped = list(states)
    for i in kll_idx:
        stripped[i] = states[i].replace(
            items=np.zeros((0, 0), np.dtype(states[i].items.dtype))
        )
    fetched = _fetch_states_packed_raw(tuple(stripped))

    slices: List[Any] = []
    metas: List[Tuple[int, int, bool]] = []
    for i in kll_idx:
        st = fetched[i]
        sizes = np.asarray(st.sizes)
        _assert_kll_slim_invariant(sizes, st.sketch_size)
        items = placeholders[i]
        levels = items.shape[0]
        k = st.sketch_size
        occupied = np.nonzero(sizes > 0)[0]
        top_level = int(occupied.max()) if occupied.size else -1
        if top_level == levels - 1:
            # the uncompacted top level can exceed k: ship it full width
            slices.append((items[: levels - 1, :k], items[levels - 1 :, :]))
            metas.append((i, 0, True))
        else:
            # power-of-two row count: stable packed-program shapes (at most
            # log2(levels) variants) at <= 2x the minimal bytes; rows above
            # the deepest occupied level are structural +inf padding
            rows = 1
            while rows < top_level + 1:
                rows *= 2
            rows = min(rows, levels - 1)
            slices.append(items[:rows, :k])
            metas.append((i, rows, False))
    fetched_items = _fetch_states_packed_raw(tuple(slices))

    for (i, rows, has_top), item in zip(metas, fetched_items):
        st = fetched[i]
        levels, width = placeholders[i].shape
        k = st.sketch_size
        full = np.full(
            (levels, width), np.inf, dtype=np.dtype(placeholders[i].dtype.name)
        )
        if has_top:
            low, top = item
            full[: levels - 1, :k] = np.asarray(low)
            full[levels - 1, :] = np.asarray(top)
        elif rows:
            full[:rows, :k] = np.asarray(item)
        fetched[i] = st.replace(items=full)
    return fetched


def _as_leaf(leaf):
    """A device array as is; anything else as a host array of the dtype
    jax would give it. Host leaves stay on the host: staged on the default
    device they would be copied chip to chip into a sub-mesh."""
    if isinstance(leaf, jax.Array):
        return leaf
    host = np.asarray(leaf)
    return host.astype(jax.dtypes.canonicalize_dtype(host.dtype), copy=False)


def _fetch_states_packed_raw(states: Tuple) -> List[Any]:
    leaves, treedef = jax.tree_util.tree_flatten(states)
    if not leaves:
        return list(states)
    leaves = [_as_leaf(l) for l in leaves]
    x64 = jax.config.jax_enable_x64
    out_leaves: List[Any] = [None] * len(leaves)

    def unpack_f64(idx: List[int], flat: np.ndarray) -> None:
        offset = 0
        for i in idx:
            leaf = leaves[i]
            part = flat[offset:offset + leaf.size]
            out_leaves[i] = part.reshape(leaf.shape).astype(np.dtype(leaf.dtype.name))
            offset += leaf.size

    def unpack_u8(idx: List[int], raw: bytes) -> None:
        offset = 0
        for i in idx:
            leaf = leaves[i]
            dtype = np.dtype(leaf.dtype.name)
            host = np.frombuffer(raw, dtype=dtype, count=leaf.size, offset=offset)
            out_leaves[i] = host.reshape(leaf.shape).copy()
            offset += leaf.size * dtype.itemsize

    def unpack_u64(idx: List[int], raw: bytes) -> None:
        # inverse of _pack_leaves_u64_u8: per (shape, dtype) group, one
        # lo-u32 block then one hi-u32 block covering the whole group
        offset = 0
        for grp in _group_leaves(leaves, idx).values():
            n = sum(leaves[i].size for i in grp)
            lo = np.frombuffer(raw, dtype=np.uint32, count=n, offset=offset)
            offset += 4 * n
            hi = np.frombuffer(raw, dtype=np.uint32, count=n, offset=offset)
            offset += 4 * n
            vals = lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))
            at = 0
            for i in grp:
                leaf = leaves[i]
                out_leaves[i] = (
                    vals[at : at + leaf.size]
                    .astype(np.dtype(leaf.dtype.name))
                    .reshape(leaf.shape)
                )
                at += leaf.size

    def start_d2h(arr):
        # kick off the device->host copy without blocking, so a second
        # packed buffer's transfer (and any remaining host work) overlaps
        # it; np.asarray then completes an already-in-flight copy
        if hasattr(arr, "copy_to_host_async"):
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 - overlap is best-effort
                pass
        return arr

    if not x64:
        unpack_u8(_grouped_leaf_order(leaves), np.asarray(start_d2h(_pack_leaves_u8(leaves))).tobytes())
        return list(jax.tree_util.tree_unflatten(treedef, out_leaves))

    # HUGE leaves (a resident frequency key buffer is hundreds of MB, its
    # count table tens) transfer DIRECTLY: one leaf is one transfer anyway,
    # and skipping the stack/convert/repack round-trips saves several
    # full-buffer copies per side (on the CPU backend np.asarray of the
    # leaf is zero-copy: measured 2.3s packed -> ~0s direct for a 256MB
    # buffer). The packed paths exist to batch MANY SMALL leaves into few
    # transfers — past _DIRECT_LEAF_BYTES a leaf is its own bulk transfer.
    direct = [
        i for i, l in enumerate(leaves)
        if l.size * l.dtype.itemsize >= _DIRECT_LEAF_BYTES
    ]
    for i in direct:
        start_d2h(leaves[i])  # kick the D2H copy early; harvested below
    # remaining 8-byte UNSIGNED leaves (u64 hash keys) must never ride the
    # f64 upcast — values above 2^53 would round; they get the split-to-u32
    # bit-exact transfer. (int64 counters stay on the f64 path: they hold
    # row counts, far below 2^53 — the documented contract.)
    wide_u64 = [
        i for i, l in enumerate(leaves)
        if i not in set(direct)
        and l.dtype.itemsize == 8
        and np.dtype(l.dtype.name).kind == "u"
    ]
    packed_u64 = (
        start_d2h(_pack_leaves_u64_u8([leaves[i] for i in wide_u64]))
        if wide_u64
        else None
    )
    rest = [
        i for i in range(len(leaves))
        if i not in set(direct) and i not in set(wide_u64)
    ]

    def unpack_direct() -> None:
        for i in direct:
            out_leaves[i] = np.asarray(leaves[i])

    narrow = [i for i in rest if leaves[i].dtype.itemsize <= 4]
    narrow_bytes = sum(leaves[i].size * leaves[i].dtype.itemsize for i in narrow)
    if narrow_bytes < _NARROW_SPLIT_BYTES:
        if rest:
            unpack_f64(
                _grouped_leaf_order(leaves, rest),
                np.asarray(start_d2h(_pack_leaves_f64([leaves[i] for i in rest]))),
            )
        if packed_u64 is not None:
            unpack_u64(wide_u64, np.asarray(packed_u64).tobytes())
        unpack_direct()
        return list(jax.tree_util.tree_unflatten(treedef, out_leaves))

    wide = [i for i in rest if i not in set(narrow)]
    packed_narrow = start_d2h(_pack_leaves_u8([leaves[i] for i in narrow]))
    packed_wide = (
        start_d2h(_pack_leaves_f64([leaves[i] for i in wide])) if wide else None
    )
    # subset packs reindex their leaf lists, so group over the SUBSET in
    # its original positions — same keys, same encounter order
    unpack_u8(_grouped_leaf_order(leaves, narrow), np.asarray(packed_narrow).tobytes())
    if packed_wide is not None:
        unpack_f64(_grouped_leaf_order(leaves, wide), np.asarray(packed_wide))
    if packed_u64 is not None:
        unpack_u64(wide_u64, np.asarray(packed_u64).tobytes())
    unpack_direct()
    return list(jax.tree_util.tree_unflatten(treedef, out_leaves))


#: cached result of the device-feed bandwidth probe (MB/s), per process
_FEED_BANDWIDTH_MBPS: Optional[float] = None
_FEED_LATENCY_S: Optional[float] = None

#: feed bandwidth below which raw column streaming to the device loses to
#: host-side partial aggregation. The probe reads 516-586 MB/s on a local
#: v5e host (PR 21) and read 6-35 MB/s on a remote link; the threshold
#: sits between the two regimes, not at the edge of the local one, so
#: probe noise cannot flip a local chip to the host tier
_FEED_BANDWIDTH_THRESHOLD_MBPS = 100.0


def probe_feed_bandwidth() -> float:
    """Measured round-trip bandwidth (MB/s) of the default-device feed link,
    cached per process. A put+get round trip forces a REAL transfer — put
    alone can report completion before the bytes have moved.

    The first transfer of a process can pay one-time backend
    initialization; an untimed warm-up plus best-of-3 keeps a transient
    stall from silently flipping every later auto-placement decision."""
    global _FEED_BANDWIDTH_MBPS, _FEED_LATENCY_S
    if _FEED_BANDWIDTH_MBPS is None:
        # 1MB payload: a slow link is probed in about a second; fixed
        # round-trip LATENCY is measured separately with a tiny transfer and
        # subtracted, so a fast-but-latent link (e.g. 1GB/s at 4ms RTT, which
        # a raw 1MB timing would score at ~300MB/s) is not misclassified to
        # the host tier
        arr = np.zeros(1 << 17, dtype=np.float64)
        tiny = np.zeros(512, dtype=np.float64)  # 4KB: pure-latency proxy
        import time

        np.asarray(jax.device_put(arr))  # untimed warm-up
        latency = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(jax.device_put(tiny))
            latency = min(latency, time.perf_counter() - t0)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            d = jax.device_put(arr)
            np.asarray(d)
            elapsed = time.perf_counter() - t0
            transfer = max(elapsed - latency, 1e-9)
            best = max(best, 2 * arr.nbytes / transfer / 1e6)
        _FEED_BANDWIDTH_MBPS = best
        _FEED_LATENCY_S = latency
    return _FEED_BANDWIDTH_MBPS


def probe_feed_latency() -> float:
    """Round-trip latency (seconds) of the feed link; probes on first use."""
    probe_feed_bandwidth()
    return _FEED_LATENCY_S if _FEED_LATENCY_S is not None else 0.0


def resolve_scan_placement(scan_analyzers, placement, monitor=None) -> str:
    """THE ingest-tier decision for a fused scan pass: "device" streams
    batches to the accelerator, "host" folds per-analyzer partials in a
    thread pool. Module-level (not a method) because the runner's
    device-frequency eligibility gate must ask the same question BEFORE
    an engine exists — one copy means the two can never drift.

    - a battery with any device-only analyzer (no host partial) streams
      to the device regardless of the requested placement
    - explicit "host"/"device" placements are honored otherwise
    - "auto" probes the feed link: below the bandwidth threshold, host
      partials win (composes with a mesh: _run_host_tier shards the fold
      over the devices — streaming raw columns over a slow feed would
      starve ALL chips at once)
    """
    from ..utils import env_str

    effective = placement or env_str(PLACEMENT_ENV, "auto")
    if not scan_analyzers:
        return "device"
    if not all(a.supports_host_partial for a in scan_analyzers):
        return "device"
    if effective == "host":
        return "host"
    if effective == "auto":
        bw = probe_feed_bandwidth()
        if monitor is not None:
            monitor.feed_bandwidth_mbps = bw
        if bw < _FEED_BANDWIDTH_THRESHOLD_MBPS:
            return "host"
    return "device"


class _DeviceFeatureCache:
    """Device-RESIDENT feature cache (opt-in): per-(table, batching,
    battery) feature arrays stay in HBM across passes and runs, so a warm
    run over the same dataset streams nothing over the feed link — the
    device-placement analog of a cached columnar scan. Strong table refs
    pin the id()-based keys.

    Entries group by their source TABLE; when the byte budget is exhausted,
    whole least-recently-used table groups are evicted — dropping the Arrow
    table pin with them — so a long-lived service rotating across datasets
    cannot grow host + HBM footprint monotonically. The group currently
    being admitted is never evicted to make room for itself (evicting batch
    0 to admit batch N of the same table would thrash every pass); when no
    other group can be freed, admission stops and that is logged once."""

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self.bytes = 0
        self.store: Dict[Tuple, Dict[str, Any]] = {}
        self.tables: Dict[int, Any] = {}
        self.evictions = 0
        #: table-id groups in least-recently-USED-first order
        self._group_order: "OrderedDict[int, None]" = OrderedDict()
        self._group_keys: Dict[int, List[Tuple]] = {}
        self._group_bytes: Dict[int, int] = {}
        self._admission_stop_logged = False
        self._lock = _threading.Lock()

    def get(self, key: Tuple) -> Optional[Dict[str, Any]]:
        with self._lock:
            features = self.store.get(key)
            if features is not None:
                self._group_order.move_to_end(key[0])
            return features

    def admit(
        self, key: Tuple, table: Any, features: Dict[str, Any], nbytes: int
    ) -> bool:
        """Insert ``features`` under ``key`` (whose first element is the
        source table's id), evicting LRU table groups as needed. Returns
        False when the entry cannot fit without evicting its own group."""
        table_id = key[0]
        with self._lock:
            if key in self.store:
                # two workers prepared the same batch concurrently: keep the
                # first insert (double-inserting would double-count bytes
                # and leave a duplicate group key that breaks eviction)
                self._group_order.move_to_end(table_id)
                return True
            if nbytes + self._group_bytes.get(table_id, 0) > self.budget:
                # no amount of eviction can ever fit this entry (its OWN
                # group is never evicted for it) — refuse UP FRONT instead
                # of flushing every other warm group for nothing
                self._log_admission_stop(nbytes)
                return False
            while (
                self.bytes + nbytes > self.budget
                and self._evict_lru_group(exclude=table_id)
            ):
                pass
            if self.bytes + nbytes > self.budget:
                self._log_admission_stop(nbytes)
                return False
            self.store[key] = features
            self.bytes += nbytes
            self.tables[table_id] = table
            self._group_keys.setdefault(table_id, []).append(key)
            self._group_bytes[table_id] = (
                self._group_bytes.get(table_id, 0) + nbytes
            )
            if table_id in self._group_order:
                self._group_order.move_to_end(table_id)
            else:
                self._group_order[table_id] = None
            return True

    def _log_admission_stop(self, nbytes: int) -> None:
        if not self._admission_stop_logged:
            self._admission_stop_logged = True
            _logger.warning(
                "device feature cache stopped admitting: entry of %d bytes "
                "does not fit the %d-byte budget (%d bytes in use by "
                "unevictable entries); raise %s or expect cold feeds for "
                "the overflow batches",
                nbytes, self.budget, self.bytes, DEVICE_FEATURE_CACHE_ENV,
            )

    def _evict_lru_group(self, exclude: int) -> bool:
        for table_id in self._group_order:
            if table_id == exclude:
                continue
            del self._group_order[table_id]
            for key in self._group_keys.pop(table_id):
                del self.store[key]
            freed = self._group_bytes.pop(table_id)
            self.bytes -= freed
            self.tables.pop(table_id, None)
            self.evictions += 1
            _logger.info(
                "device feature cache evicted table group %d (%d bytes)",
                table_id, freed,
            )
            return True
        return False

    def clear(self) -> None:
        with self._lock:
            self.store.clear()
            self.tables.clear()
            self.bytes = 0
            self._group_order.clear()
            self._group_keys.clear()
            self._group_bytes.clear()
            self._admission_stop_logged = False


#: env var overriding the host ingest tier's partial-worker pool size
#: (default: all cores). The `tools/host_tier_sweep.py` scaling sweep
#: drives this; PERF.md records the measured workers -> rows/s curve.
HOST_TIER_WORKERS_ENV = "DEEQU_TPU_HOST_TIER_WORKERS"

#: env var enabling the device feature cache; value = HBM budget in GB
DEVICE_FEATURE_CACHE_ENV = "DEEQU_TPU_DEVICE_FEATURE_CACHE"
_DEVICE_FEATURE_CACHE: Optional[_DeviceFeatureCache] = None


def device_feature_cache() -> Optional[_DeviceFeatureCache]:
    from ..utils import env_number

    global _DEVICE_FEATURE_CACHE
    if getattr(_CACHE_BYPASS, "active", False):
        return None  # warm-run sample features must not enter the budget
    budget_gb = env_number(DEVICE_FEATURE_CACHE_ENV, 0.0, float, minimum=0.0)
    if not budget_gb:
        return None
    if _DEVICE_FEATURE_CACHE is None:
        _DEVICE_FEATURE_CACHE = _DeviceFeatureCache(int(budget_gb * 1e9))
    return _DEVICE_FEATURE_CACHE


def clear_device_feature_cache() -> None:
    global _DEVICE_FEATURE_CACHE
    if _DEVICE_FEATURE_CACHE is not None:
        _DEVICE_FEATURE_CACHE.clear()
    _DEVICE_FEATURE_CACHE = None


_INGEST_CACHE: Dict[Tuple, Any] = {}

#: batches folded per ingest-program call; fixed so the program shape (and
#: therefore the compile) is independent of the run's batch count
_INGEST_CHUNK = 32

#: analyzers per ingest sub-program: bundles of same-SIGNATURE analyzers
#: share one compiled program (a 50-column battery folds through ~3 small
#: compiles instead of one mega-program; signatures repeat across runs and
#: datasets, so cold runs converge on warm)
_INGEST_BUNDLE = 8

from ..utils import BoundedLRU

_INGEST_SIG_CACHE = BoundedLRU(4096)


def _ingest_signature(a: ScanShareableAnalyzer) -> Tuple:
    """Program-identity key of an analyzer's ingest fold: class + state
    tree structure + leaf shapes/dtypes. Valid because every
    ``ingest_partial`` implementation is a pure function of the state and
    partial VALUES given the class and state shapes — column names,
    predicates, regexes and where-filters act host-side (feature
    computation), never inside the fold — so two same-class analyzers over
    different columns share one compiled program."""
    sig = _INGEST_SIG_CACHE.get(a)
    if sig is None:
        shapes = jax.eval_shape(a.init_state)
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        sig = (
            type(a),
            str(treedef),
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
        )
        _INGEST_SIG_CACHE[a] = sig
    return sig


def _ingest_bundles(analyzers: Tuple[ScanShareableAnalyzer, ...]):
    """Signature-homogeneous ingest bundles (see :func:`_signature_bundles`
    for the partitioning/padding policy, shared with the device scan)."""
    return _signature_bundles(analyzers, _ingest_signature, _INGEST_BUNDLE)


_INGEST_INIT_CACHE: Dict[Tuple, Any] = {}


def _ingest_init_program(bundle: Tuple[ScanShareableAnalyzer, ...]):
    """jit'd identity-state constructor for one bundle (signature-cached,
    same validity argument as _ingest_program: init values depend only on
    class + shapes)."""
    key = tuple(_ingest_signature(a) for a in bundle)
    prog = _INGEST_INIT_CACHE.get(key)
    if prog is None:
        prog = jax.jit(lambda: tuple(a.init_state() for a in bundle))
        _INGEST_INIT_CACHE[key] = prog
    return prog


def _ingest_program(bundle: Tuple[ScanShareableAnalyzer, ...]):
    """jit'd fold of stacked host partials into device states via lax.scan —
    the device-side half of the host ingest tier (the merge tree the TPU
    owns; batch count appears only as the scan length). Padding steps in
    the tail chunk compute-then-select (see make_flagged_ingest_body): the
    wasted work is a few identity folds once per run, bought against ~35%
    of the fold's compile time. Cached by SIGNATURE: all bundles of
    same-class/same-shape analyzers reuse one program."""
    key = tuple(_ingest_signature(a) for a in bundle)
    cached = _INGEST_CACHE.get(key)
    if cached is not None:
        return cached

    body = make_flagged_ingest_body(bundle)

    def fold(states, flags, stacked):
        out, _ = jax.lax.scan(body, states, (flags, stacked))
        return out

    # no donation: a tail-padded bundle passes one state buffer twice (the
    # pad slots), and per-analyzer states are small enough that the copy is
    # noise at chunk granularity
    program = jax.jit(fold)
    _INGEST_CACHE[key] = program
    return program


def make_flagged_ingest_body(analyzers: Tuple[ScanShareableAnalyzer, ...]):
    """The scan body folding one (flag, partial) step into the states;
    identity when the flag marks a padding entry. Shared by the
    single-device ingest program and the sharded mesh fold
    (parallel.sharded_ingest_fold) so the two paths cannot drift.

    Padding steps compute-then-SELECT rather than `lax.cond`-branch: only
    the tail chunk ever carries padding, so the skipped work is negligible,
    while a cond would compile BOTH branches (measured ~35% of the ingest
    fold's compile time, which dominates cold runs)."""

    def body(states, xs):
        flag, partial_slice = xs
        applied = tuple(
            a.ingest_partial(s, p)
            for a, s, p in zip(analyzers, states, partial_slice)
        )
        kept = jax.tree_util.tree_map(
            lambda new, old: jnp.where(flag, new, old), applied, states
        )
        return kept, None

    return body


class ScanEngine:
    """One shared pass: device-fused scan analyzers + host accumulators.

    ``placement`` decides where the per-row work happens:

    - ``"device"``: stream raw column batches to the accelerator; the fused
      XLA program does everything (the default on TPU-VM-class feed links).
    - ``"host"``: the native C ingest tier computes per-batch partial states
      next to the data and the device folds the tiny partials — the same
      partial-aggregate/merge split Spark runs executor-side (reference
      `AnalysisRunner.scala:303-318`). Chosen when raw streaming would be
      feed-bandwidth-bound.
    - ``"auto"`` (default, or env DEEQU_TPU_PLACEMENT): probe the feed link
      once per process and pick.
    """

    def __init__(
        self,
        scan_analyzers: Sequence[ScanShareableAnalyzer],
        monitor: Optional[RunMonitor] = None,
        sharding: Optional[Any] = None,
        placement: Optional[str] = None,
    ):
        from ..utils import env_str

        self.scan_analyzers = list(scan_analyzers)
        self.monitor = monitor or RunMonitor()
        #: set when the watchdog abandons this engine's pass: the zombie
        #: thread checks it before flushing its cost ledger, so an
        #: abandoned pass's attribution never contaminates the monitor the
        #: failover re-pass (a NEW engine) is reporting into
        self._cancelled = _threading.Event()
        self.mesh = sharding  # a jax.sharding.Mesh -> row-sharded GSPMD scan
        self.placement = placement or env_str(PLACEMENT_ENV, "auto")
        self.builder = FeatureBuilder(
            [s for a in self.scan_analyzers for s in a.feature_specs()]
        )
        analyzers = self.scan_analyzers

        if not analyzers:
            self._update = None
        elif self._resolve_placement_inner() == "host":
            # the host tier never dispatches the fused device program;
            # building it here would register the battery in the program
            # cache while leaving it uncompiled (jit is lazy), which the
            # service's cache-aware placement would misread as warm
            self._update = None
        else:
            self._update = _fused_program(tuple(analyzers), self.mesh)

    def _resolve_placement(self) -> str:
        placement = self._resolve_placement_inner()
        self.monitor.placement = placement
        return placement

    def _resolve_placement_inner(self) -> str:
        return resolve_scan_placement(
            self.scan_analyzers, self.placement, self.monitor
        )

    def required_columns(self) -> List[str]:
        return self.builder.required_columns

    def _prepare(self, batch):
        """Host side of one batch: feature build + device placement. Runs on
        the prefetch thread so it overlaps the previous batch's device work
        (numpy / pyarrow / the native C++ kernels all release the GIL)."""
        with self.monitor.timed("feature_build"):
            features = self.builder.build(batch)
        fault_point("device_feed")
        with self.monitor.timed("device_feed"):
            if self.mesh is not None:
                from ..parallel import shard_features

                features = shard_features(
                    features, self.mesh, batch_rows=len(batch.row_mask)
                )
            else:
                features = jax.device_put(features)
        return features

    def run(
        self,
        data: Dataset,
        batch_size: Optional[int] = None,
        host_accumulators: Optional[Dict[Any, Any]] = None,
        host_update_fns: Optional[Dict[Any, Any]] = None,
        columns: Optional[Sequence[str]] = None,
        checkpointer: Optional[Any] = None,
        slim_fetch: bool = False,
    ) -> Tuple[List[Any], Dict[Any, Any]]:
        """Run the shared pass. Returns (device states per scan analyzer,
        host accumulator states keyed as given).

        ``checkpointer`` (a `reliability.IngestCheckpointer`) makes the
        multi-batch fold resumable: algebraic states persist every
        ``checkpointer.every`` batches, and a run over the same data shape
        restarts from the last checkpoint instead of batch 0 — the states
        fold identically (same batch boundaries, same batch indices), so
        the resumed result equals the uninterrupted one.

        ``slim_fetch``: the caller asserts the fetched states feed ONLY
        ``compute_metric_from`` (no persistence, no aggregation, no
        checkpoint) — each analyzer's non-metric-bearing leaves then skip
        the feed link and are reconstructed from identity values.

        Set ``DEEQU_TPU_PROFILE_DIR`` to capture a ``jax.profiler`` trace of
        every pass into that directory (SURVEY §5's optional profiler hook;
        view with tensorboard or Perfetto). The lightweight phase timers in
        RunMonitor are always on."""
        import contextlib

        from ..utils import env_str

        profile_dir = env_str(PROFILE_DIR_ENV)
        if profile_dir:
            import jax.profiler

            tracer = jax.profiler.trace(profile_dir)
        else:
            tracer = contextlib.nullcontext()
        with tracer:
            from ..reliability.watchdog import (
                rate_tracker,
                run_with_deadline,
                scan_deadline_s,
            )

            bs = effective_batch_size(data, batch_size)
            n_batches = max(1, -(-int(data.num_rows) // bs))
            n_rows = max(1, int(data.num_rows))
            tier = self._resolve_placement_inner()
            cold = 0
            if tier == "device" and self._update is not None:
                cold = self._update.cold_programs()
            deadline = scan_deadline_s(n_rows, tier, cold)
            bypass = getattr(_CACHE_BYPASS, "active", False)
            import time

            batches_before = self.monitor.batches
            t0 = time.perf_counter()
            with _trace.span(
                "engine_pass", kind="engine", tier=tier, rows=n_rows,
                batches=n_batches, analyzers=len(self.scan_analyzers),
            ):
                if deadline is None:
                    result = self._run_inner(
                        data, batch_size, host_accumulators, host_update_fns,
                        columns, checkpointer, slim_fetch,
                    )
                else:
                    # the pass body moves to the watchdog's worker thread;
                    # the per-thread cache-bypass flag (background warm
                    # runs) and the trace context must move with it, or a
                    # warm sample would enter the budget and the pass's
                    # phases would orphan into a fresh trace
                    ctx = _trace.capture()

                    def pass_body():
                        _CACHE_BYPASS.active = bypass
                        with _trace.attach(ctx):
                            return self._run_inner(
                                data, batch_size, host_accumulators,
                                host_update_fns, columns, checkpointer,
                                slim_fetch,
                            )

                    from ..exceptions import ScanStallError

                    try:
                        result = run_with_deadline(
                            pass_body, deadline, self.monitor, tier
                        )
                    except ScanStallError:
                        # the abandoned zombie must stop reporting costs
                        # into this monitor (best-effort: a flush already
                        # in flight at this instant is the same bounded
                        # race the rate tracker tolerates)
                        self._cancelled.set()
                        raise
            # only COMPLETED passes teach the rate tracker, and only
            # REPRESENTATIVE ones: background warm runs (1-row samples
            # under the cache bypass) and the batches a resume skipped
            # would both poison the EWMA toward a deadline no production
            # pass can meet — observe the batches this pass actually
            # processed (the monitor delta), never the nominal count. A
            # delta EXCEEDING the pass's own batch count proves another
            # pass (a watchdog-abandoned zombie, an overlapped profile
            # scan) bumped the shared monitor concurrently — skip the
            # observation rather than learn a contaminated rate
            if not bypass:
                folded = self.monitor.batches - batches_before
                if 0 < folded <= n_batches:
                    rate_tracker().observe(
                        tier, min(folded * bs, n_rows),
                        time.perf_counter() - t0,
                    )
            return result

    def _run_inner(
        self,
        data: Dataset,
        batch_size: Optional[int] = None,
        host_accumulators: Optional[Dict[Any, Any]] = None,
        host_update_fns: Optional[Dict[Any, Any]] = None,
        columns: Optional[Sequence[str]] = None,
        checkpointer: Optional[Any] = None,
        slim_fetch: bool = False,
    ) -> Tuple[List[Any], Dict[Any, Any]]:
        monitor = self.monitor
        monitor.bump("passes")
        bs = effective_batch_size(data, batch_size)
        if self.mesh is not None or checkpointer is not None:
            from ..parallel import mesh_batch_quantum

            # round to the LADDER quantum, not the mesh size: a checkpoint
            # pins batch_size, so batch boundaries must stay put when the
            # elastic layer rebuilds the mesh one rung smaller (8->4->2->1
            # all see the same effective batch size). Checkpointed
            # MESH-FREE runs round too — the documented mesh<->plain-host
            # resume legs need both sides to derive the same boundaries
            # from the same nominal batch size
            n_dev = 1 if self.mesh is None else int(self.mesh.devices.size)
            q = mesh_batch_quantum(n_dev)
            bs = ((bs + q - 1) // q) * q  # shardable batches
        host_states = dict(host_accumulators or {})
        update_fns = host_update_fns or {}
        has_battery = bool(self.scan_analyzers)
        if not has_battery and not host_states:
            return [], {}
        for a in self.scan_analyzers:
            # one probe per analyzer per pass: the injection point through
            # which tests pin "exactly the faulty analyzer degrades"
            fault_point("analyzer", tag=repr(a))
        # mesh runs checkpoint in CANONICAL (merged) form, so the meta is
        # mesh-shape independent: a checkpoint taken on 8 devices resumes
        # on 4 (the batch-size quantum above keeps batch boundaries put)
        ckpt = checkpointer
        resume = None
        ckpt_epoch = None
        if ckpt is not None:
            # fence any earlier pass over this checkpointer FIRST: a
            # watchdog-abandoned zombie still folding must not interleave
            # its saves with this pass's (see IngestCheckpointer.begin_run)
            ckpt_epoch = ckpt.begin_run()
            resume = ckpt.load(
                bs, int(data.num_rows), list(self.scan_analyzers),
                list(host_states), monitor=monitor,
            )
            if resume is not None:
                monitor.resumed_at_batch = resume.batch_index
                host_states.update(resume.host_states)
                _logger.info(
                    "resuming ingest from checkpoint at batch %d",
                    resume.batch_index,
                )
        if ckpt is not None:
            # checkpoints persist full states; a slim fetch would save
            # identity-valued leaves into the resume point
            slim_fetch = False
        if has_battery and self._resolve_placement() == "host":
            return self._run_host_tier(
                data, bs, host_states, update_fns, columns,
                checkpointer=ckpt, resume=resume, slim_fetch=slim_fetch,
                ckpt_epoch=ckpt_epoch,
            )
        if has_battery and self._update is None:
            # constructed under a host resolution but asked to run device
            # (defensive: resolution is deterministic per process)
            self._update = _fused_program(tuple(self.scan_analyzers), self.mesh)
        # device path: the packed carry IS the state; the pytree states only
        # materialize once, from unpack() after the last batch
        states: Tuple = ()
        cache_size_fn = getattr(self._update, "_cache_size", None)

        def compiled_count() -> int:
            try:
                return cache_size_fn() if cache_size_fn is not None else 0
            except Exception:  # noqa: BLE001
                return 0

        compiled_before = compiled_count()

        # pipelined pass: a single prefetch thread pulls batch i+1 and builds
        # its features while the (async-dispatched) device program chews on
        # batch i — the analog of Spark overlapping scan IO with aggregation
        batches = data.batches(bs, columns=columns)

        cache = device_feature_cache() if self._update is not None else None
        if cache is not None:
            cache_base = (
                id(data.arrow),
                bs,
                None if columns is None else tuple(columns),
                tuple(sorted(self.builder.specs)),
            )
        import itertools

        idx_counter = itertools.count()
        # the prefetch worker builds features on its own thread: carry the
        # trace context over so feature_build/device_feed phase spans stay
        # children of this pass instead of orphaning
        trace_ctx = _trace.capture()

        def produce():
            with _trace.attach(trace_ctx):
                return produce_inner()

        def produce_inner():
            index = next(idx_counter)
            try:
                batch = next(batches)
            except StopIteration:
                return None
            if self._update is None:
                return batch, None
            if cache is not None:
                key = cache_base + (index,)
                features = cache.get(key)
                if features is None:
                    features = self._prepare(batch)
                    nbytes = sum(v.nbytes for v in features.values())
                    # admit() pins the table only once something of it is
                    # cached (the id()-keyed entries must not outlive the
                    # table) and evicts LRU table groups to make room
                    cache.admit(key, data.arrow, features, nbytes)
                return batch, features
            return batch, self._prepare(batch)

        carry = self._update.init_carry() if self._update is not None else None
        cost_ledger = _CostLedger()
        folded = 0
        if resume is not None:
            # re-enter the fold at the checkpoint: restore the carry from
            # the persisted states and skip the already-folded batches
            # (index alignment preserved, so feature-cache keys and any
            # index-keyed analyzer logic see the same numbering)
            folded = resume.batch_index
            if self._update is not None:
                carry = self._update.pack_states(tuple(resume.scan_states))
            for _ in range(folded):
                next(idx_counter)
                next(batches)

        def save_checkpoint():
            with monitor.timed("checkpoint"):
                if carry is not None:
                    ck_states = _fetch_states_packed(self._update.unpack(carry))
                else:
                    ck_states = []
                ckpt.save(
                    folded, bs, int(data.num_rows),
                    list(self.scan_analyzers), ck_states, host_states,
                    epoch=ckpt_epoch,
                )
                monitor.bump("checkpoint_saves")

        # double-buffered feed pipeline (deequ_tpu.ingest.prefetch): the
        # feed thread stages batch k+1's feature build + host->device copy
        # (and with the default depth 2, k+2's) while batch k's fold
        # executes — transfer time hides under device compute instead of
        # serializing with it. DEEQU_TPU_PREFETCH_DEPTH=0 restores the
        # serial path (the measured baseline for the overlap numbers).
        # Single-batch passes (every streaming micro-batch fold) stage
        # inline: there is nothing to overlap, and the feed-thread spawn
        # was pure fixed cost on the micro-fold path.
        from ..ingest.prefetch import PrefetchingBatchIterator, staging_depth

        n_total_batches = max(1, -(-int(data.num_rows) // bs))
        with PrefetchingBatchIterator(
            produce, depth=staging_depth(n_total_batches)
        ) as staged:
            for item in staged:
                batch, features = item
                monitor.bump("batches")
                if features is not None:
                    fault_point("device_update", tag=str(folded + 1))
                    with monitor.timed("device_dispatch"):
                        carry = self._update(
                            carry, features, ledger=cost_ledger,
                            probe=(folded % _COST_PROBE_EVERY == 1),
                        )
                    monitor.bump("device_updates")
                with monitor.timed("host_accumulators"):
                    for key, fn in update_fns.items():
                        host_states[key] = fn(host_states[key], batch)
                folded += 1
                if ckpt is not None and folded % ckpt.every == 0:
                    save_checkpoint()
        if ckpt is not None:
            ckpt.complete(ckpt_epoch)
        if carry is not None:
            # drain the async dispatch queue UNDER the dispatch timer:
            # device execution time belongs to device_dispatch, so the
            # state_fetch phase measures the transfer alone (previously the
            # blocking fetch absorbed all queued compute and the warm
            # profile read as fetch-bound when it was not)
            with monitor.timed("device_dispatch"):
                jax.block_until_ready(jax.tree_util.tree_leaves(carry))
            states = self._update.unpack_final(carry)
            carry = None  # donated — it must never be touched again
        compiled = compiled_count()
        with _MONITOR_LOCK:
            monitor.jit_compiles = max(monitor.jit_compiles, compiled)
            monitor.program_compiles += max(0, compiled - compiled_before)
        with monitor.timed("state_fetch"):
            host_side = _fetch_states_packed(
                states,
                analyzers=tuple(self.scan_analyzers) if slim_fetch else None,
            )
        if not self._cancelled.is_set():
            cost_ledger.flush(monitor)
        return host_side, host_states

    def _run_host_tier(
        self, data, bs, host_states, update_fns, columns,
        checkpointer: Optional[Any] = None, resume: Optional[Any] = None,
        slim_fetch: bool = False, ckpt_epoch: Optional[int] = None,
    ) -> Tuple[List[Any], Dict[Any, Any]]:
        """Host ingest tier: per-batch partial states next to the data, then
        chunked device folds of the stacked partials (+ one packed state
        fetch) — total device traffic is O(state size), independent of row
        count.

        Per-batch partials are computed on a thread pool spanning all cores:
        the native C kernels and numpy release the GIL, so this is the
        executor-side parallelism of the reference's partial aggregation
        (`AnalysisRunner.scala:303-318`) realized with threads instead of
        Spark tasks. Partials are folded IN BATCH ORDER (the KLL sampler
        offsets key on the batch index), so results are identical to the
        sequential fold regardless of scheduling. Grouping-analyzer
        accumulators (`update_fns`) fold on the submitting thread, overlapped
        with the pool's work."""
        import os

        from ..analyzers.base import HostBatchContext

        monitor = self.monitor
        analyzers = tuple(self.scan_analyzers)
        mesh = self.mesh
        elastic = None
        if mesh is not None:
            # mesh x host tier: per-device states, each fold shards the
            # chunk's partials over the devices; a final collective merge
            # combines the per-device states. The global chunk size stays
            # ~_INGEST_CHUNK so the padding waste is mesh-independent.
            # The ElasticMeshFold owns the states: a shard lost mid-pass is
            # salvaged (surviving states merge), the mesh rebuilds one
            # ladder rung down and the lost shard's batches replay below.
            from ..parallel import ElasticMeshFold

            n_dev = int(mesh.devices.size)
            local_chunk = max(1, _INGEST_CHUNK // n_dev)
            chunk = local_chunk * n_dev
            elastic = ElasticMeshFold(analyzers, mesh, monitor=monitor)
            states = elastic.states
            program = None
        else:
            chunk = _INGEST_CHUNK
            bundles = _ingest_bundles(analyzers)
            program = [
                ((b, n_real_b), _ingest_program(tuple(analyzers[i] for i in b)))
                for b, n_real_b in bundles
            ]
            try:
                ingest_compiled_before = sum(
                    p._cache_size()
                    for p in {id(p): p for _, p in program}.values()
                )
            except Exception:  # noqa: BLE001
                ingest_compiled_before = 0
            # identity states built ON DEVICE, one jit'd dispatch per bundle
            # (eager per-analyzer init_state cost one feed-link dispatch per
            # state LEAF — ~12s of a 300-analyzer cold profile)
            states_list: List[Any] = [None] * len(analyzers)
            for b, n_real_b in bundles:
                sub = _ingest_init_program(tuple(analyzers[i] for i in b))()
                for j in range(n_real_b):
                    states_list[b[j]] = sub[j]
            states = tuple(states_list)
        start_batch = 0
        host_start = 0
        if resume is not None:
            start_batch = resume.batch_index
            # accumulators fold per SUBMITTED batch (ahead of the chunked
            # scan states), so they resume from their own high-water mark
            host_start = resume.host_batch_index
            if elastic is not None:
                # checkpoints store CANONICAL merged states: seeding them
                # into shard 0 of whatever mesh THIS run has is what makes
                # a checkpoint taken under one mesh shape resume under a
                # smaller one
                elastic.seed(tuple(resume.scan_states), start_batch)
                states = elastic.states
            else:
                states = tuple(resume.scan_states)

        # one token per pass: host partials may skip work a previous batch
        # of the SAME pass already contributed (e.g. HLL registers of
        # dictionary entries already seen) but never across passes
        run_token = object()

        # host partials run on a pool spanning all cores: carry the trace
        # context so host_partials phase spans stay in this pass's tree
        trace_ctx = _trace.capture()
        cost_ledger = _CostLedger()
        # repr strings precomputed once per pass (never on the fold path)
        bundle_reprs = (
            [[repr(analyzers[i]) for i in b[:n_real_b]]
             for (b, n_real_b), _ in program]
            if program is not None else []
        )

        def compute_partial(index: int, batch, token=None) -> Tuple:
            with _trace.attach(trace_ctx):
                fault_point("host_partial", tag=str(index))
                with monitor.timed("host_partials"):
                    ctx = HostBatchContext(
                        batch, batch_index=index,
                        run_token=token if token is not None else run_token,
                    )
                    return tuple(a.host_partial(ctx) for a in analyzers)

        def stack_group(group: List[Tuple]) -> Tuple:
            return tuple(
                jax.tree_util.tree_map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]),
                    *[p[i] for p in group],
                )
                for i in range(len(analyzers))
            )

        def fold_chunk(states, group: List[Tuple], n_real: int):
            import time as _time

            fault_point("ingest_fold")
            with monitor.timed("ingest_fold"):
                stacked = stack_group(group)
                flags = np.zeros(len(group), dtype=bool)
                flags[:n_real] = True
                monitor.bump("device_updates")
                if elastic is not None:
                    first = progress["folded"]
                    return elastic.fold(
                        stacked, flags,
                        batch_indices=range(first, first + n_real),
                    )
                # per-bundle async dispatches; states reassemble in the
                # original analyzer order. Pad slots (positions >= n_real
                # in a tail bundle) re-fold an analyzer another bundle owns
                # and their outputs are discarded. Each bundle's dispatch
                # wall time is attributed evenly across its real slots —
                # the host-tier arm of per-analyzer cost attribution.
                out = list(states)
                for ((b, n_real_b), prog), reprs in zip(program, bundle_reprs):
                    t0 = _time.perf_counter()
                    sub = prog(
                        tuple(states[i] for i in b),
                        flags,
                        tuple(stacked[i] for i in b),
                    )
                    cost_ledger.add_bundle(reprs, _time.perf_counter() - t0)
                    for j in range(n_real_b):
                        out[b[j]] = sub[j]
                return tuple(out)

        from collections import deque

        from ..utils import env_number

        # a typo'd sweep var must not crash every host-tier pass (which
        # the resilience layer would then bisect N times): env_number
        # warns once — including on negatives — and keeps the core-count
        # default (0/unset = default)
        workers = env_number(HOST_TIER_WORKERS_ENV, 0, int, minimum=0)
        workers = workers or max(2, os.cpu_count() or 1)
        window = workers + chunk  # in-flight bound: O(window) live batches
        pending: deque = deque()
        buffer: List[Tuple] = []
        n = start_batch
        #: folded = batches merged into `states`; saved = last checkpoint.
        #: Host-tier checkpoints land on chunk boundaries (states only
        #: advance per chunk fold), so a resume point is always chunk-
        #: aligned and the resumed fold replays identically.
        progress = {"folded": start_batch, "saved": start_batch}

        def maybe_checkpoint(states):
            if checkpointer is None:
                return
            if progress["folded"] - progress["saved"] < checkpointer.every:
                return
            if elastic is not None and elastic.pending_replay:
                # a shard loss left batches awaiting replay: the canonical
                # merge does not cover them yet, so a checkpoint here would
                # under-count exactly the lost shard's batches on resume
                return
            with monitor.timed("checkpoint"):
                if elastic is not None:
                    # CANONICAL merged form: mesh-shape independent, so the
                    # resume point works on any (smaller) mesh or the host
                    ck_states = _fetch_states_packed(tuple(elastic.canonical()))
                    if elastic.pending_replay:
                        # a shard died DURING the canonical merge: the
                        # snapshot under-counts its batches — skip this
                        # save (the end-of-pass replay restores coverage)
                        return
                else:
                    ck_states = _fetch_states_packed(tuple(states))
                checkpointer.save(
                    progress["folded"], bs, int(data.num_rows),
                    list(analyzers), ck_states,
                    host_states, host_batch_index=n, epoch=ckpt_epoch,
                )
                monitor.bump("checkpoint_saves")
            progress["saved"] = progress["folded"]

        def drain_one(states):
            buffer.append(pending.popleft().result())
            if len(buffer) == chunk:
                states = fold_chunk(states, list(buffer), n_real=chunk)
                buffer.clear()
                progress["folded"] += chunk
                maybe_checkpoint(states)
            return states

        with ThreadPoolExecutor(max_workers=workers) as pool:
            for index, batch in enumerate(
                data.batches(bs, columns=columns, pad_to_batch_size=False)
            ):
                if index < start_batch:
                    continue  # already folded into the resumed states
                monitor.bump("batches")
                n += 1
                pending.append(pool.submit(compute_partial, index, batch))
                if index >= host_start:
                    with monitor.timed("host_accumulators"):
                        for key, fn in update_fns.items():
                            host_states[key] = fn(host_states[key], batch)
                # backpressure: never let un-drained batches outgrow the
                # window, so peak memory stays O(window), not O(dataset)
                while len(pending) > window:
                    states = drain_one(states)
            # consume the rest in submission order (partials fold in batch
            # order, so results equal the sequential fold exactly)
            while pending:
                states = drain_one(states)
        if buffer:
            # pad the tail chunk with identity partials so ONE compiled
            # scan-fold program serves every run regardless of batch count —
            # no recompile treadmill, warmups always hit; the validity flags
            # make the device skip the padding steps
            n_real = len(buffer)
            empty = _empty_batch_like(data, columns)
            ident = compute_partial(n, empty)
            buffer.extend([ident] * (chunk - n_real))
            states = fold_chunk(states, buffer, n_real=n_real)
        if program is not None:
            try:
                compiled = sum(
                    p._cache_size()
                    for p in {id(p): p for _, p in program}.values()
                )
                with _MONITOR_LOCK:
                    monitor.jit_compiles = max(
                        monitor.jit_compiles,
                        max(prog._cache_size() for _, prog in program),
                    )
                    monitor.program_compiles += max(
                        0, compiled - ingest_compiled_before
                    )
            except Exception:  # noqa: BLE001
                pass
        if elastic is not None:
            # replay the batches lost with dead shards: recompute exactly
            # those partials (same batch indices, so index-keyed analyzer
            # logic replays identically) and fold them on whatever mesh
            # survived. Loops because a shard can die during replay too.
            def replay_pending():
                todo = set(elastic.take_lost_batches())
                _trace.add_event("mesh_replay", batches=len(todo))
                _logger.warning(
                    "replaying %d batches lost with dead mesh shards",
                    len(todo),
                )
                # a FRESH memo token per replay round: the pass token's
                # cross-batch skip (the HLL dictionary memo) may have
                # credited an entry to a batch the DEAD shard owned —
                # replaying that batch under the old token would skip the
                # entry and silently undercount. Within one round the
                # fresh token may share (the first replayed batch that
                # sees an entry re-contributes it into a SURVIVING
                # shard); a loss during replay starts another round with
                # another fresh token.
                replay_token = object()
                replay_buf: List[Tuple] = []
                replay_idx: List[int] = []

                def flush_replay(n_real: int):
                    group = list(replay_buf)
                    if n_real < chunk:
                        ident = compute_partial(n, _empty_batch_like(data, columns))
                        group.extend([ident] * (chunk - n_real))
                    flags = np.zeros(chunk, dtype=bool)
                    flags[:n_real] = True
                    with monitor.timed("ingest_fold"):
                        elastic.fold(
                            stack_group(group), flags, batch_indices=replay_idx
                        )
                    replay_buf.clear()
                    replay_idx.clear()

                last_todo = max(todo)
                for index, batch in enumerate(
                    data.batches(bs, columns=columns, pad_to_batch_size=False)
                ):
                    if index > last_todo:
                        break  # replay cost scales with len(todo), not rows
                    if index not in todo:
                        continue
                    replay_buf.append(
                        compute_partial(index, batch, token=replay_token)
                    )
                    replay_idx.append(index)
                    if len(replay_buf) == chunk:
                        flush_replay(chunk)
                if replay_buf:
                    flush_replay(len(replay_buf))

            # butterfly-merge the per-device states into one (the
            # treeReduce analog, riding ICI); on a broken mesh the merge
            # itself recovers (salvage + re-shard, host merge last) — and
            # a loss DURING the merge queues the dead shard's batches, so
            # loop until a merge completes with nothing left to replay
            while True:
                while elastic.pending_replay:
                    replay_pending()
                states = elastic.finish()
                if not elastic.pending_replay:
                    break
        if checkpointer is not None:
            checkpointer.complete(ckpt_epoch)
        with monitor.timed("state_fetch"):
            host_side = _fetch_states_packed(
                states, analyzers=analyzers if slim_fetch else None
            )
        if not self._cancelled.is_set():
            cost_ledger.flush(monitor)
        return host_side, host_states

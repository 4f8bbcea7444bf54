"""The tunable-knob registry: ONE audited surface for every routing
constant the hot paths read.

Before this module the crossover thresholds lived as hand-measured
literals scattered through the routing modules (the CrossoverRouter's
seeds in ``service/coalesce.py``, ``probably_low_cardinality``'s probe
sizes and 2M-row floor in ``analyzers/grouping.py``, the fleet sharding
floor, the prefetch depth, the frequency table/buffer capacities) — all
tuned on one CPU dev box and wrong by unknown factors on any other
substrate (ROADMAP item 3). Every one of them is now a registered
:class:`Knob` with

- a **name** (the registry key the calibrator and the online controller
  read/write through),
- an optional **env var** (the operator override; ALWAYS wins, parsed
  with the shared warn-once ``utils.env_number`` semantics the old
  readers used),
- the **static default** (the measured dev-box value the old literal
  carried — bit-for-bit the pre-registry behavior),
- **bounds** the calibrator/controller may never write outside of, and
- a **substrate-sensitivity** flag (whether boot-time calibration is
  expected to move it).

Resolution order of :func:`value`: env override > tuned value (only when
``DEEQU_TPU_AUTOTUNE`` is not "0") > static default. With
``DEEQU_TPU_AUTOTUNE=0`` the tuned layer is invisible and every read is
byte-identical to the pre-registry parser it replaced (pinned by
``tests/test_tuning.py``).

Tuned values enter through :func:`set_tuned` only — boot-time profile
application (``tuning.profile``) and shadow-route-guarded controller
promotions (``tuning.controller``) — and are clamped to the knob's
bounds, so a corrupt profile or a runaway controller can never push a
knob outside its audited range. The invariant linter's
``tuning-registry`` check (tools/statlint) flags any new hand-coded
routing threshold or registry-env read outside this module.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

#: "0" disables the whole self-tuning plane: no profile load, no tuned
#: values, no controller — every knob read is byte-identical to the
#: static-default behavior (the escape hatch, pinned by test)
AUTOTUNE_ENV = "DEEQU_TPU_AUTOTUNE"

#: directory holding persisted per-substrate calibration profiles
#: (default: a ``deequ_tpu_tuning`` directory beside the persistent XLA
#: compile cache, so a box that caches compiles also caches its measured
#: crossovers)
TUNING_PROFILE_DIR_ENV = "DEEQU_TPU_TUNING_PROFILE_DIR"

#: fraction of eligible folds the online controller routes under the
#: CANDIDATE setting while an experiment runs (default 0.05; 0 disables
#: shadow routing — candidates then never gather evidence and are never
#: promoted)
TUNING_SHADOW_FRACTION_ENV = "DEEQU_TPU_TUNING_SHADOW_FRACTION"
DEFAULT_TUNING_SHADOW_FRACTION = 0.05

#: measured folds each arm needs before a promotion/demotion decision
TUNING_MIN_SAMPLES_ENV = "DEEQU_TPU_TUNING_MIN_SAMPLES"
DEFAULT_TUNING_MIN_SAMPLES = 32

#: the tolerance band: a candidate promotes only when
#: its measured rate beats the incumbent by MORE than this fraction, and
#: a promoted setting demotes back to static when it falls this far
#: below the static reference rate
TUNING_BAND_ENV = "DEEQU_TPU_TUNING_BAND"
DEFAULT_TUNING_BAND = 0.25


def autotune_enabled() -> bool:
    from ..utils import env_flag

    return env_flag(AUTOTUNE_ENV, True)


def shadow_fraction() -> float:
    from ..utils import env_number

    value = env_number(
        TUNING_SHADOW_FRACTION_ENV, DEFAULT_TUNING_SHADOW_FRACTION, float,
        minimum=0.0,
    )
    return min(value, 0.5)  # the incumbent must keep majority traffic


def tuning_min_samples() -> int:
    from ..utils import env_number

    return env_number(
        TUNING_MIN_SAMPLES_ENV, DEFAULT_TUNING_MIN_SAMPLES, int, minimum=1
    )


def tuning_band() -> float:
    from ..utils import env_number

    return env_number(
        TUNING_BAND_ENV, DEFAULT_TUNING_BAND, float, minimum=0.0
    )


@dataclass(frozen=True)
class Knob:
    """One registered tunable: its audit record and parse semantics."""

    name: str                    #: registry key (calibrator/controller id)
    env: Optional[str]           #: operator env override (None = internal)
    static_default: Any          #: the measured dev-box literal it replaced
    cast: Callable               #: int or float
    lo: Any                      #: tuned-value clamp floor
    hi: Any                      #: tuned-value clamp ceiling
    substrate_sensitive: bool    #: does calibration expect to move it?
    description: str             #: what the knob governs (audit surface)
    #: minimum the ENV parser enforces (warn-once + fallback below it);
    #: None = no env-side bound. Kept separate from ``lo`` because the
    #: old readers' env semantics (e.g. fast_path_max_rows accepts -1)
    #: must stay bit-identical.
    env_minimum: Any = None


def _registry() -> Dict[str, Knob]:
    k = Knob
    knobs = [
        # -- streaming fold routing (service/coalesce.py) ------------------
        k("fast_path_max_rows", "DEEQU_TPU_FAST_PATH_MAX_ROWS", -1, int,
          lo=-1, hi=1 << 30, substrate_sensitive=True, env_minimum=-1,
          description=(
              "Fixed host-fast-path row ceiling; -1 = route from the "
              "measured per-analyzer-class crossover, 0 = always device."
          )),
        k("coalesce_max_width", "DEEQU_TPU_COALESCE_MAX_WIDTH", 16, int,
          lo=1, hi=1024, substrate_sensitive=True, env_minimum=1,
          description=(
              "Max sessions stacked into one coalesced device launch "
              "(pow2-bucketed widths)."
          )),
        k("fleet_stream_min_rows", "DEEQU_TPU_FLEET_STREAM_MIN_ROWS",
          65536, int, lo=0, hi=1 << 30, substrate_sensitive=True,
          env_minimum=0,
          description=(
              "Minimum micro-batch rows before a streaming fold shards "
              "over the tenant's fleet sub-mesh."
          )),
        # -- ingest feed pipeline (ingest/prefetch.py) ---------------------
        k("prefetch_depth", "DEEQU_TPU_PREFETCH_DEPTH", 2, int,
          lo=0, hi=64, substrate_sensitive=True, env_minimum=0,
          description=(
              "Staged batches in the double-buffered host->device feed "
              "pipeline (0 = serial inline)."
          )),
        # -- device frequency engine (analyzers/grouping.py) ---------------
        k("freq_table_slots", "DEEQU_TPU_FREQ_TABLE_SLOTS", 1 << 22, int,
          lo=1 << 10, hi=1 << 26, substrate_sensitive=True, env_minimum=1,
          description=(
              "Distinct-group capacity per device frequency table "
              "(pow2-rounded)."
          )),
        k("freq_buffer_entries", "DEEQU_TPU_FREQ_BUFFER_ENTRIES",
          1 << 25, int, lo=1 << 16, hi=1 << 28, substrate_sensitive=True,
          env_minimum=1,
          description=(
              "Raw u64 key-buffer cap; runs fitting it ride the RESIDENT "
              "compaction-free trace."
          )),
        k("device_freq_max_cardinality",
          "DEEQU_TPU_DEVICE_FREQ_MAX_CARDINALITY", 1 << 16, int,
          lo=1 << 8, hi=1 << 22, substrate_sensitive=True, env_minimum=1,
          description=(
              "Dictionary-size ceiling of the dense per-code device "
              "counting path."
          )),
        # -- grouping host-route pre-probe (probably_low_cardinality) ------
        k("freq_host_route_max_distinct",
          "DEEQU_TPU_FREQ_HOST_ROUTE_MAX_DISTINCT", 1 << 15, int,
          lo=1 << 6, hi=1 << 22, substrate_sensitive=True, env_minimum=1,
          description=(
              "Union-distinct ceiling for confidently routing a grouping "
              "set to the host group-by instead of the device table "
              "(~ the measured sweep knee / 4)."
          )),
        k("freq_probe_rows", "DEEQU_TPU_FREQ_PROBE_ROWS", 1 << 16, int,
          lo=1 << 10, hi=1 << 22, substrate_sensitive=False, env_minimum=1,
          description=(
              "Rows per head/mid/tail slice of the cardinality "
              "pre-routing probe."
          )),
        k("freq_host_route_min_rows",
          "DEEQU_TPU_FREQ_HOST_ROUTE_MIN_ROWS", 1 << 21, int,
          lo=0, hi=1 << 30, substrate_sensitive=True, env_minimum=0,
          description=(
              "Row floor below which the probe never answers host: the "
              "engines' absolute cost gap only buys wall-clock at scale "
              "(the dev box measured ~2M rows)."
          )),
        # -- CrossoverRouter seeds (service/coalesce.py; internal: the
        # router EWMAs refine them from live folds, calibration replaces
        # them with measured substrate values) -----------------------------
        k("router_host_rows_per_s", None, 20e6, float,
          lo=1e3, hi=1e12, substrate_sensitive=True,
          description=(
              "Seed host-kernel rows/s per analyzer class before any "
              "fold is measured (seeded LOW deliberately)."
          )),
        k("router_device_fixed_s", None, 0.02, float,
          lo=1e-6, hi=10.0, substrate_sensitive=True,
          description=(
              "Seed fixed seconds per device launch+fetch before any "
              "coalesced launch is measured."
          )),
        k("router_device_rows_per_s", None, 100e6, float,
          lo=1e3, hi=1e13, substrate_sensitive=True,
          description="Seed device per-row throughput of the cost model."),
    ]
    return {knob.name: knob for knob in knobs}


REGISTRY: Dict[str, Knob] = _registry()

#: process-global tuned layer (profile application + controller
#: promotions); guarded — value() reads race controller writes
_TUNED_LOCK = threading.Lock()
_TUNED: Dict[str, Any] = {}
_TUNED_SOURCE: Dict[str, str] = {}


def knob(name: str) -> Knob:
    return REGISTRY[name]


def static_value(name: str) -> Any:
    return REGISTRY[name].static_default


def value(name: str) -> Any:
    """Resolve one knob: env override > tuned (autotune on) > static."""
    from ..utils import env_number

    k = REGISTRY[name]
    fallback = k.static_default
    if autotune_enabled():
        with _TUNED_LOCK:
            tuned = _TUNED.get(name)
        if tuned is not None:
            fallback = tuned
    if k.env is None:
        return fallback
    return env_number(k.env, fallback, k.cast, minimum=k.env_minimum)


def set_tuned(name: str, new_value: Any, source: str = "controller") -> Any:
    """Install a tuned value (clamped to the knob's bounds); returns the
    value actually installed. Raises KeyError for unregistered names —
    profiles carrying unknown knobs skip them with a warning upstream."""
    k = REGISTRY[name]
    clamped = min(max(k.cast(new_value), k.lo), k.hi)
    with _TUNED_LOCK:
        _TUNED[name] = clamped
        _TUNED_SOURCE[name] = source
    return clamped


def clear_tuned(name: Optional[str] = None) -> None:
    """Drop one tuned value (back to static), or all of them."""
    with _TUNED_LOCK:
        if name is None:
            _TUNED.clear()
            _TUNED_SOURCE.clear()
        else:
            _TUNED.pop(name, None)
            _TUNED_SOURCE.pop(name, None)


def any_tuned() -> bool:
    """Cheap per-fold predicate for the controller's hot path."""
    with _TUNED_LOCK:
        return bool(_TUNED)


def tuned_snapshot() -> Dict[str, Dict[str, Any]]:
    """{name: {value, source, static}} for every currently-tuned knob."""
    with _TUNED_LOCK:
        return {
            name: {
                "value": v,
                "source": _TUNED_SOURCE.get(name, "?"),
                "static": REGISTRY[name].static_default,
            }
            for name, v in _TUNED.items()
        }

"""Boot-time calibration: micro-probes that measure THIS substrate.

``calibrate()`` reuses the engine's own execution paths as its
measurement harness — the same host-partial kernels, the same warmed
``do_analysis_run`` device dispatch, the same grouping engines the
CrossoverRouter and ``probably_low_cardinality`` route between — and
runs each probe a few times, keeping the **minimum** wall time (the
min is the least-noisy estimate of the true cost on a busy box). From
the raw probe measurements it derives values for every
substrate-sensitive knob in the registry via the same cost model the
router uses, clamps them to the registry bounds, and persists a
checksummed :class:`~deequ_tpu.tuning.profile.SubstrateProfile` beside
the XLA cache.

Probe sizes are deliberately small (the default measures ~1.5M rows
total): calibration runs once per substrate, at boot or from the CLI
below, and must cost seconds — not the minutes a full sweep costs. The
derived values are SEEDS with honest error bars, not gospel: the online
controller refines them under live traffic, and the shadow-route
guardrail catches any probe that mis-measured.

CLI: ``python -m deequ_tpu.tuning.calibrate --json [--no-save] [--dir D]
[--rows N]`` — calibrates this substrate, saves the profile (unless
``--no-save``) and prints it (as JSON with ``--json``).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from . import knobs as _knobs
from .profile import SubstrateProfile, save_profile, substrate_key

#: default rows for the host-partial rate probes
_HOST_PROBE_ROWS = 1 << 18
#: rows for the small (fixed-cost-dominated) device probe
_DEVICE_SMALL_ROWS = 1 << 12
#: rows for the large (per-row-dominated) device probe
_DEVICE_LARGE_ROWS = 1 << 20
#: distinct groups in the grouping-knee probe datasets
_GROUP_PROBE_CARDINALITY = 1 << 10


def _timed(fn: Callable[[], Any], repeats: int) -> Tuple[float, Any]:
    """Min wall seconds over ``repeats`` calls (after the caller warmed
    any compile), plus the last result."""
    best = float("inf")
    result = None
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def _pow2_at_most(value: float) -> int:
    """Largest power of two <= value (>= 1)."""
    return 1 << max(int(value).bit_length() - 1, 0)


def _probe_dataset(rows: int, cardinality: int = 0):
    from ..data import Dataset

    rng = np.random.default_rng(0xCA11B)
    cols: Dict[str, Any] = {"v": rng.standard_normal(rows)}
    if cardinality:
        cols["k"] = rng.integers(0, cardinality, size=rows)
    return Dataset.from_dict(cols)


def _probe_host_rates(rows: int, repeats: int) -> Dict[str, float]:
    """rows/s of each representative host-partial class on this box's
    cores — the numbers the router's observe_host EWMAs converge to."""
    from ..analyzers import Completeness, Maximum, Mean, Minimum, Sum
    from ..analyzers.base import HostBatchContext

    data = _probe_dataset(rows)
    batch = next(data.batches(rows, pad_to_batch_size=False))
    rates: Dict[str, float] = {}
    for analyzer in (Completeness("v"), Mean("v"), Sum("v"),
                     Minimum("v"), Maximum("v")):
        ctx = HostBatchContext(batch, batch_index=0)
        analyzer.host_partial(ctx)  # warm any lazy column materialization
        seconds, _ = _timed(
            lambda a=analyzer, c=ctx: a.host_partial(c), repeats
        )
        rates[f"host_rows_per_s_{type(analyzer).__name__}"] = (
            rows / max(seconds, 1e-9)
        )
    return rates


def _run_analysis(data, analyzers) -> float:
    from ..runners.analysis_runner import AnalysisRunner

    t0 = time.perf_counter()
    AnalysisRunner.do_analysis_run(data, analyzers)
    return time.perf_counter() - t0


def _probe_device_costs(repeats: int) -> Dict[str, float]:
    """Fixed dispatch seconds (small warm run), per-row rows/s (large warm
    run), and the marginal cost of stacking analyzers into one bundle."""
    from ..analyzers import Maximum, Mean, Minimum, Sum

    small = _probe_dataset(_DEVICE_SMALL_ROWS)
    large = _probe_dataset(_DEVICE_LARGE_ROWS)
    one = [Mean("v")]
    eight = [Mean("v"), Sum("v"), Minimum("v"), Maximum("v"),
             Mean("v", where="v > 0"), Sum("v", where="v > 0"),
             Minimum("v", where="v > 0"), Maximum("v", where="v > 0")]

    _run_analysis(small, one)  # compile warmup
    fixed_s, _ = _timed(lambda: _run_analysis(small, one), repeats)

    _run_analysis(large, one)
    large_s, _ = _timed(lambda: _run_analysis(large, one), repeats)
    per_row_s = max(large_s - fixed_s, 1e-9) / _DEVICE_LARGE_ROWS

    _run_analysis(small, eight)
    stacked_s, _ = _timed(lambda: _run_analysis(small, eight), repeats)
    stack_slope_s = max(stacked_s - fixed_s, 0.0) / (len(eight) - len(one))

    return {
        "device_fixed_s": fixed_s,
        "device_rows_per_s": 1.0 / per_row_s,
        "device_stack_slope_s": stack_slope_s,
    }


def _probe_staging_rate(repeats: int) -> Dict[str, float]:
    """Host->device transfer rows/s of the prefetch staging path."""
    import jax

    rows = _DEVICE_LARGE_ROWS
    host = np.random.default_rng(7).standard_normal(rows).astype(np.float32)

    def stage():
        jax.device_put(host).block_until_ready()

    stage()  # warm transfer machinery
    seconds, _ = _timed(stage, repeats)
    return {"staging_rows_per_s": rows / max(seconds, 1e-9)}


def _probe_grouping_knee(repeats: int) -> Dict[str, float]:
    """rows/s of the device frequency table vs the host group-by on the
    same grouping workload — the knee probably_low_cardinality routes on."""
    import os

    from ..analyzers import Uniqueness

    rows = 1 << 18
    data = _probe_dataset(rows, cardinality=_GROUP_PROBE_CARDINALITY)
    analyzers = [Uniqueness(["k"])]
    env = "DEEQU_TPU_DEVICE_FREQ"
    saved = os.environ.get(env)
    try:
        os.environ.pop(env, None)
        _run_analysis(data, analyzers)
        device_s, _ = _timed(lambda: _run_analysis(data, analyzers), repeats)
        os.environ[env] = "0"
        _run_analysis(data, analyzers)
        host_s, _ = _timed(lambda: _run_analysis(data, analyzers), repeats)
    finally:
        if saved is None:
            os.environ.pop(env, None)
        else:
            os.environ[env] = saved
    return {
        "group_device_rows_per_s": rows / max(device_s, 1e-9),
        "group_host_rows_per_s": rows / max(host_s, 1e-9),
    }


def derive_knobs(probes: Dict[str, float]) -> Dict[str, Any]:
    """Map raw probe measurements to knob values through the router's own
    cost model; every output is clamped to its registry bounds."""
    host_rates = [v for k, v in probes.items()
                  if k.startswith("host_rows_per_s_")]
    host_rate = float(np.median(host_rates)) if host_rates else (
        _knobs.static_value("router_host_rows_per_s"))
    fixed_s = probes.get(
        "device_fixed_s", _knobs.static_value("router_device_fixed_s"))
    device_rate = probes.get(
        "device_rows_per_s", _knobs.static_value("router_device_rows_per_s"))

    derived: Dict[str, Any] = {
        "router_host_rows_per_s": host_rate,
        "router_device_fixed_s": fixed_s,
        "router_device_rows_per_s": device_rate,
    }

    # A fleet shard only pays off once the batch amortizes several fixed
    # dispatches of cross-host merge traffic — sharding splits a DEVICE
    # fold, so the break-even is rows the device chews through in a few
    # fixed costs.
    derived["fleet_stream_min_rows"] = _pow2_at_most(
        max(0.25 * fixed_s * device_rate, 1.0))

    # Stacking stops paying when the marginal bundle cost approaches the
    # fixed dispatch it amortizes; below-resolution slopes keep the static
    # width (the probe cannot justify moving it either way).
    slope = probes.get("device_stack_slope_s", 0.0)
    if slope > 1e-7:
        derived["coalesce_max_width"] = _pow2_at_most(
            max(fixed_s / slope, 1.0))

    # Depth must cover the staging/compute rate gap with one spare slot;
    # a staging path faster than the device needs only the double buffer.
    staging = probes.get("staging_rows_per_s", 0.0)
    if staging > 0:
        derived["prefetch_depth"] = int(
            np.clip(round(device_rate / staging) + 1, 1, 8))

    g_host = probes.get("group_host_rows_per_s", 0.0)
    g_dev = probes.get("group_device_rows_per_s", 0.0)
    if g_host > 0 and g_dev > 0:
        # The host group-by needs this many rows before its rate advantage
        # (or the device's fixed cost) buys back the probe's own cost.
        derived["freq_host_route_min_rows"] = _pow2_at_most(
            max(8.0 * fixed_s * min(g_host, g_dev), 1.0))
        # Scale the distinct ceiling by the measured engine ratio: a box
        # whose host group-by keeps pace with the device can confidently
        # host-route proportionally larger key spaces.
        ratio = np.clip(g_host / g_dev, 0.25, 4.0)
        derived["freq_host_route_max_distinct"] = _pow2_at_most(
            _knobs.static_value("freq_host_route_max_distinct") * ratio)

    for name in list(derived):
        knob = _knobs.REGISTRY[name]
        derived[name] = min(max(knob.cast(derived[name]), knob.lo), knob.hi)
    return derived


def calibrate(save: bool = True,
              profile_dir: Optional[str] = None,
              rows: int = _HOST_PROBE_ROWS,
              repeats: int = 3) -> SubstrateProfile:
    """Run every probe, derive knob values, and (by default) persist the
    substrate profile. Returns the profile; ``profile.knob_values`` is NOT
    applied to the live registry here — that is the loader's decision."""
    from ..observability import trace

    t0 = time.perf_counter()
    probes: Dict[str, float] = {}
    with trace.span("tuning.calibrate", kind="tuning") as span:
        probes.update(_probe_host_rates(rows, repeats))
        probes.update(_probe_device_costs(repeats))
        probes.update(_probe_staging_rate(repeats))
        probes.update(_probe_grouping_knee(repeats))
        profile = SubstrateProfile(
            substrate=substrate_key(),
            probes=probes,
            knob_values=derive_knobs(probes),
            calibration_wall_s=time.perf_counter() - t0,
        )
        span.add_event(
            "calibrated",
            fingerprint=profile.fingerprint,
            wall_s=round(profile.calibration_wall_s, 3),
            knobs=len(profile.knob_values),
        )
        if save:
            path = save_profile(profile, profile_dir)
            span.add_event("profile_saved", path=path)
    return profile


def _main(argv=None) -> int:
    import argparse
    import json

    parser = argparse.ArgumentParser(
        description="Calibrate deequ-tpu's tuning profile for this substrate"
    )
    parser.add_argument("--json", action="store_true",
                        help="print the profile as JSON on stdout")
    parser.add_argument("--no-save", action="store_true",
                        help="measure and print without persisting")
    parser.add_argument("--dir", default=None,
                        help="profile directory (default: beside XLA cache)")
    parser.add_argument("--rows", type=int, default=_HOST_PROBE_ROWS,
                        help="rows per host-partial probe")
    parser.add_argument("--repeats", type=int, default=3,
                        help="probe repeats (min wall time wins)")
    args = parser.parse_args(argv)

    profile = calibrate(save=not args.no_save, profile_dir=args.dir,
                        rows=args.rows, repeats=args.repeats)
    if args.json:
        print(json.dumps({
            "substrate": profile.substrate,
            "fingerprint": profile.fingerprint,
            "probes": profile.probes,
            "knobs": profile.knob_values,
            "wall_s": profile.calibration_wall_s,
        }, sort_keys=True))
    else:
        print(f"calibrated substrate {profile.fingerprint} "
              f"in {profile.calibration_wall_s:.2f}s")
        for name, value in sorted(profile.knob_values.items()):
            print(f"  {name:32s} {value} (static "
                  f"{_knobs.static_value(name)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())

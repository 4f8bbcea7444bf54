"""Render the self-tuning plane's state: the active substrate profile
plus the live controller's experiments and decision history.

Two consumers:

- operators: ``python -m tools.tuning_report`` loads THIS substrate's
  persisted calibration profile (the same loader the service boots
  through, including checksum verification) and prints probes + derived
  knob values against their static defaults;
- chaos_soak: ``controller_report(service)`` renders the in-process
  controller — incumbent vs candidate rates per experiment, the
  promotion/demotion history, the ``deequ_service_tuning_*`` counters —
  into the soak summary, so every soak run documents what the tuner did
  to it.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional


def profile_report(directory: Optional[str] = None) -> Dict[str, Any]:
    """This substrate's profile as a report dict (CorruptStateError
    surfaces as a quarantine note, exactly like the service boot)."""
    from deequ_tpu.exceptions import CorruptStateError
    from deequ_tpu.tuning import knobs, profile as prof

    out: Dict[str, Any] = {
        "substrate": prof.substrate_key(),
        "fingerprint": prof.substrate_fingerprint(),
        "profile_dir": directory or prof.profile_dir(),
    }
    try:
        loaded = prof.load_profile(directory)
    except CorruptStateError as exc:
        out["profile"] = None
        out["quarantined"] = str(exc)
        return out
    if loaded is None:
        out["profile"] = None
        return out
    out["profile"] = {
        "created_at": loaded.created_at,
        "calibration_wall_s": loaded.calibration_wall_s,
        "probes": loaded.probes,
        "knobs": {
            name: {"calibrated": value, "static": knobs.static_value(name)}
            for name, value in sorted(loaded.knob_values.items())
            if name in knobs.REGISTRY
        },
    }
    return out


def controller_report(service) -> Dict[str, Any]:
    """The live controller's decisions + the tuning export series of one
    in-process VerificationService (chaos_soak's summary hook)."""
    controller = getattr(service, "tuning_controller", None)
    metrics = getattr(service, "metrics", None)
    out: Dict[str, Any] = {"enabled": controller is not None}
    if metrics is not None:
        out["series"] = {
            name: metrics.counter_value(name)
            for name in (
                "deequ_service_tuning_proposals_total",
                "deequ_service_tuning_promotions_total",
                "deequ_service_tuning_demotions_total",
                "deequ_service_tuning_shadow_folds_total",
            )
        }
    if controller is not None:
        out.update(controller.snapshot())
    return out


def _fmt(value: Any) -> str:
    if isinstance(value, float):
        return f"{value:.4g}"
    return str(value)


def render_text(report: Dict[str, Any]) -> str:
    lines: List[str] = []
    sub = report["substrate"]
    lines.append(
        f"substrate {report['fingerprint']}: {sub['backend']} "
        f"{sub['device_kind']} x{sub['chip_count']} on {sub['host']}"
    )
    lines.append(f"profile dir: {report['profile_dir']}")
    if report.get("quarantined"):
        lines.append(f"PROFILE QUARANTINED: {report['quarantined']}")
        return "\n".join(lines)
    profile = report.get("profile")
    if profile is None:
        lines.append("no profile for this substrate "
                     "(run python -m deequ_tpu.tuning.calibrate)")
        return "\n".join(lines)
    lines.append(
        f"calibrated in {profile['calibration_wall_s']:.2f}s; "
        f"{len(profile['probes'])} probes"
    )
    lines.append(f"{'knob':34s} {'calibrated':>14s} {'static':>14s}")
    for name, row in profile["knobs"].items():
        lines.append(
            f"{name:34s} {_fmt(row['calibrated']):>14s} "
            f"{_fmt(row['static']):>14s}"
        )
    lines.append("probes:")
    for name, value in sorted(profile["probes"].items()):
        lines.append(f"  {name:34s} {_fmt(value):>14s}")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.tuning_report",
        description=(
            "Show this substrate's calibration profile (and, via "
            "--snapshot, a serialized controller state)"
        ),
    )
    parser.add_argument("--dir", default=None,
                        help="profile directory (default: beside XLA cache)")
    parser.add_argument("--json", action="store_true",
                        help="emit the report as JSON")
    parser.add_argument("--snapshot", default=None,
                        help="also render a controller snapshot JSON file "
                             "(as written by chaos_soak)")
    args = parser.parse_args(argv)

    report = profile_report(args.dir)
    if args.snapshot:
        with open(args.snapshot, "r", encoding="utf-8") as fh:
            report["controller"] = json.load(fh)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True, default=str))
    else:
        print(render_text(report))
        controller = report.get("controller")
        if controller:
            print(f"controller: {len(controller.get('decisions', []))} "
                  f"decision(s), {len(controller.get('tuned', {}))} tuned "
                  "knob(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

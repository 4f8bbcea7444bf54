"""The self-tuning performance control plane (ROADMAP item 3).

Three layers close the loop the hand-measured constants left open:

- :mod:`~deequ_tpu.tuning.knobs` — the registry every tunable routing
  constant resolves through (env override > tuned > static default);
- :mod:`~deequ_tpu.tuning.calibrate` + :mod:`~deequ_tpu.tuning.profile`
  — boot-time micro-probes persisted as a checksummed per-substrate
  profile beside the XLA cache;
- :mod:`~deequ_tpu.tuning.controller` — the online re-fitter that
  shadow-routes candidates under live traffic and promotes only behind
  a tolerance band, with a never-below-static floor guardrail.

``DEEQU_TPU_AUTOTUNE=0`` disables all of it: no profile load, no
controller, every knob read byte-identical to the static defaults.
"""

from __future__ import annotations

import logging
from typing import Optional

from . import knobs
from .controller import TuningController
from .profile import SubstrateProfile, load_profile, save_profile

logger = logging.getLogger(__name__)

__all__ = [
    "knobs", "TuningController", "SubstrateProfile",
    "load_profile", "save_profile", "bootstrap_service",
]


def bootstrap_service(service) -> Optional[TuningController]:
    """Wire the tuning plane into a VerificationService at construction.

    Always describes the ``deequ_service_tuning_*`` series (a disabled
    plane still exports zeros, so dashboards don't gap). With autotune
    enabled: load this substrate's profile if one exists — a corrupt or
    stale profile is already quarantined by the loader and degrades to
    static defaults with a warning, never a failed boot — apply its knob
    values, reseed the router from the (possibly tuned) seeds, and start
    the online controller on the scheduler's harvest tick.
    """
    from ..exceptions import CorruptStateError

    metrics = getattr(service, "metrics", None)
    if metrics is not None:
        TuningController._describe_series(metrics)
    if not knobs.autotune_enabled():
        return None

    profile = None
    try:
        profile = load_profile()
    except CorruptStateError as exc:
        logger.warning(
            "tuning profile rejected (%s); booting on static defaults", exc
        )
    if profile is not None:
        applied = profile.apply(source="profile")
        logger.info(
            "tuning profile %s applied: %d knob(s) tuned for this substrate",
            profile.fingerprint, len(applied),
        )

    router = getattr(getattr(service, "coalescer", None), "router", None)
    controller = TuningController(
        metrics=metrics, router=router, profile=profile
    )
    if metrics is not None:
        controller.register_gauges(metrics)
    if router is not None:
        router.reseed_from_knobs()
    scheduler = getattr(service, "scheduler", None)
    if scheduler is not None and hasattr(scheduler, "add_harvest_listener"):
        scheduler.add_harvest_listener(controller.on_harvest)
    return controller

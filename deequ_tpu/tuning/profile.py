"""Versioned, checksummed per-substrate calibration profiles.

A :class:`SubstrateProfile` is what boot-time calibration measured on ONE
substrate — (backend, device kind, chip count, host fingerprint) — and
what a cold process on that same substrate loads at service start so it
boots with measured crossovers instead of the dev-box constants. Profiles
live beside the persistent XLA compile cache (same reasoning: the
expensive thing you computed about THIS box is worth keeping), one JSON
file per substrate fingerprint, so a home directory shared across a
heterogeneous fleet holds one profile per device kind without collisions.

The file carries the payload plus an xxhash64 content checksum
(:mod:`deequ_tpu.integrity`, the same digest every other durable artifact
uses) and a schema version. A profile that fails its checksum, fails to
parse, or carries a different schema version is **quarantined** — moved
to a ``.quarantine/`` sidecar so it can never poison a later boot — and
surfaces as the typed :class:`~deequ_tpu.exceptions.CorruptStateError`
that the data plane already treats as recoverable; the service-start
loader catches it and boots on static defaults. A profile for a
DIFFERENT substrate is simply absent, not corrupt.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ..exceptions import CorruptStateError
from ..integrity import checksum_bytes
from . import knobs as _knobs

logger = logging.getLogger(__name__)

#: bump on any incompatible payload change; older files quarantine on load
PROFILE_VERSION = 1


def profile_dir() -> str:
    """Profile directory: ``DEEQU_TPU_TUNING_PROFILE_DIR`` or
    ``<checkout>/.cache/tuning``."""
    from ..config import CACHE_ROOT
    from ..utils import env_str

    configured = env_str(_knobs.TUNING_PROFILE_DIR_ENV, "")
    if configured:
        return os.path.expanduser(configured)
    return os.path.join(CACHE_ROOT, "tuning")


def substrate_key() -> Dict[str, Any]:
    """The identity a profile is keyed by. Includes a host hardware
    fingerprint: two CPU-backend boxes with different core counts are
    different substrates (the host fast path runs on those cores)."""
    import platform

    import jax

    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "chip_count": len(devices),
        "host": f"{platform.machine()}-{os.cpu_count()}cpu",
    }


def substrate_fingerprint(key: Optional[Dict[str, Any]] = None) -> str:
    payload = json.dumps(key or substrate_key(), sort_keys=True)
    return checksum_bytes(payload.encode("utf-8"))


@dataclass
class SubstrateProfile:
    """One substrate's measured calibration results."""

    substrate: Dict[str, Any]
    #: raw probe measurements (rates in rows/s, costs in seconds) — kept
    #: for the tuning report and for re-deriving knobs offline
    probes: Dict[str, float] = field(default_factory=dict)
    #: derived knob values, name -> value; every name must be registered
    knob_values: Dict[str, Any] = field(default_factory=dict)
    created_at: float = 0.0
    calibration_wall_s: float = 0.0
    version: int = PROFILE_VERSION

    @property
    def fingerprint(self) -> str:
        return substrate_fingerprint(self.substrate)

    def to_payload(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_payload(cls, payload: Dict[str, Any]) -> "SubstrateProfile":
        try:
            profile = cls(**payload)
        except TypeError as exc:
            raise CorruptStateError(
                "tuning profile", "payload",
                f"structurally torn: {exc}",
            ) from exc
        if profile.version != PROFILE_VERSION:
            raise CorruptStateError(
                "tuning profile", "payload",
                f"schema version {profile.version} != {PROFILE_VERSION} "
                "(stale profile from another build)",
            )
        return profile

    def apply(self, source: str = "profile") -> Dict[str, Any]:
        """Install this profile's knob values into the tuned layer
        (clamped to registry bounds). Unknown knob names are skipped with
        a warning — a profile written by a newer build with extra knobs
        must not fail the boot. Returns {name: installed_value}."""
        applied: Dict[str, Any] = {}
        for name, value in self.knob_values.items():
            if name not in _knobs.REGISTRY:
                logger.warning(
                    "tuning profile carries unknown knob %r; skipped", name
                )
                continue
            applied[name] = _knobs.set_tuned(name, value, source=source)
        return applied


def _profile_path(directory: str, fingerprint: str) -> str:
    return os.path.join(directory, f"profile-{fingerprint}.json")


def save_profile(profile: SubstrateProfile,
                 directory: Optional[str] = None) -> str:
    """Atomically persist (tmp + replace) under the substrate fingerprint;
    returns the path written."""
    directory = directory or profile_dir()
    os.makedirs(directory, exist_ok=True)
    if not profile.created_at:
        profile.created_at = time.time()
    payload = profile.to_payload()
    body = json.dumps(payload, sort_keys=True)
    record = {
        "payload": payload,
        "checksum": checksum_bytes(body.encode("utf-8")),
    }
    path = _profile_path(directory, profile.fingerprint)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(record, fh, sort_keys=True, indent=1)
    os.replace(tmp, path)
    return path


def _quarantine(path: str) -> Optional[str]:
    """Move a bad profile into ``.quarantine/`` (content-addressed name so
    repeat offenders don't pile up); best-effort."""
    try:
        with open(path, "rb") as fh:
            digest = checksum_bytes(fh.read())
        qdir = os.path.join(os.path.dirname(path), ".quarantine")
        os.makedirs(qdir, exist_ok=True)
        dest = os.path.join(qdir, f"{digest}-{os.path.basename(path)}")
        os.replace(path, dest)
        return dest
    except OSError:
        return None


def load_profile(directory: Optional[str] = None,
                 fingerprint: Optional[str] = None,
                 ) -> Optional[SubstrateProfile]:
    """Load THIS substrate's profile, verifying the content checksum and
    schema version.

    Returns None when no profile exists for the substrate (normal on a
    fresh box). Raises :class:`CorruptStateError` after quarantining the
    file when it exists but cannot be trusted — the caller decides the
    fallback (the service boots on static defaults).
    """
    directory = directory or profile_dir()
    fingerprint = fingerprint or substrate_fingerprint()
    path = _profile_path(directory, fingerprint)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "r", encoding="utf-8") as fh:
            record = json.load(fh)
        payload = record["payload"]
        stored = record["checksum"]
    except (OSError, ValueError, KeyError, TypeError) as exc:
        dest = _quarantine(path)
        raise CorruptStateError(
            "tuning profile", path,
            f"unreadable ({exc}); quarantined to {dest}",
        ) from exc
    body = json.dumps(payload, sort_keys=True)
    actual = checksum_bytes(body.encode("utf-8"))
    if actual != stored:
        dest = _quarantine(path)
        raise CorruptStateError(
            "tuning profile", path,
            f"failed its content checksum (stored {stored}, computed "
            f"{actual}); quarantined to {dest}",
        )
    try:
        return SubstrateProfile.from_payload(payload)
    except CorruptStateError:
        _quarantine(path)
        raise

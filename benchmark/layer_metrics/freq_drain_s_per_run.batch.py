"""Seconds per run spent draining the device frequency tables on the host:
the program's ``freq_drain`` phase timer (``RunMonitor.phase_seconds``)."""


def read(record):
    phases = record.get("phase_seconds")
    if not phases or "freq_drain" not in phases or not record.get("runs"):
        return None
    return phases["freq_drain"] / record["runs"]

"""Seconds per run the program waited for the device to finish its queued
work: the program's ``device_wait`` phase timer (the pass-end drain and
the cost probe's waits, ``RunMonitor.phase_seconds``)."""


def read(record):
    phases = record.get("phase_seconds")
    if not phases or "device_wait" not in phases or not record.get("runs"):
        return None
    return phases["device_wait"] / record["runs"]

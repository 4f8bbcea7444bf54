"""Seconds per run of host planning around the passes (battery split,
preconditions, dictionary probes, the profiler's work between and after
its passes): the program's ``plan`` phase timer
(``RunMonitor.phase_seconds``)."""


def read(record):
    phases = record.get("phase_seconds")
    if not phases or "plan" not in phases or not record.get("runs"):
        return None
    return phases["plan"] / record["runs"]

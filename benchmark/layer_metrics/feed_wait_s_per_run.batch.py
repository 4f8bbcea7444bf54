"""Seconds per run the thread that dispatches a pass waited on the feed
thread for its next staged batch: the program's ``feed_wait`` phase
timer (``RunMonitor.phase_seconds``)."""


def read(record):
    phases = record.get("phase_seconds")
    if not phases or "feed_wait" not in phases or not record.get("runs"):
        return None
    return phases["feed_wait"] / record["runs"]

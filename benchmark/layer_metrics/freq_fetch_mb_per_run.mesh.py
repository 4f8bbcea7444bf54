"""Megabytes per run that the device frequency drain moved to the host
(the program's ``freq_drain_fetch_bytes`` counter). Nothing to read where
the program has no such counter."""


def read(record):
    fetched = record.get("freq_drain_fetch_bytes")
    if fetched is None or not record.get("runs"):
        return None
    return fetched / 1e6 / record["runs"]

"""The scan's share of its bandwidth roofline over the traced runs of a
cell on a mesh: the Arrow bytes of the columns the battery reads, split
over the cell's chips, at each chip's peak HBM bandwidth, over the seconds
in which a chip was busy (the trace's busy time, averaged over the
chips). A bound on every kernel of the run taken together, as
``scan_roofline.batch`` is on one chip."""

from benchmark.harness import read_json


def read(record):
    trace = record.get("trace")
    if not trace or trace["busy_s"] <= 0 or not record.get("runs_traced"):
        return None
    peaks = read_json("peaks.json")["devices"]
    kind = record["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    least_s = (record["scan_bytes"] * record["runs_traced"]
               / (record["chips"] * peaks[kind]["hbm_bytes_per_s"]))
    return 100.0 * least_s / trace["busy_s"]

"""Smoke of the main path on the chip, through the public API.

    python chip_smoke.py            # one chip: VerificationSuite + profiler
    python chip_smoke.py --chips 4  # mesh scan + fleet sub-meshes vs one chip

One chip (the default):

- **verify**: a ``VerificationSuite`` run over the BASELINE config-2 shape
  (``build_scan_data``: four nullable normal columns and a 100k-key
  categorical, plus a nullable three-value flag for the histogram) under the
  default ``auto`` placement. Its Uniqueness/Distinctness/CountDistinct set
  takes the compacting device frequency table (the key buffer is set below
  the row count, so the table compacts in the pass).
- **profile**: a ``ColumnProfilerRunner`` full profile of the TPC-H
  lineitem-shaped table (``build_lineitem_data``).

Four chips (``--chips 4``) runs only the multi-chip path and what it is
compared with: the same verification over ``make_mesh(4)`` and through a
two-tenant ``VerificationService`` whose fleet leases 2+2 sub-meshes, each
against a one-chip run of the same data in this process.

Every metric is checked against a numpy/pyarrow oracle: exact metrics to
1e-6 relative, grouping metrics exactly, HLL within 3x its relative
standard error, KLL by rank error |cdf(result) - q| <= 2 eps. Mesh and
fleet runs must equal the one-chip run: exactly for counts, extrema,
sketches and grouping metrics, to 1e-9 relative for float aggregates.

Earlier lines of stdout give each phase's seconds, the engine's phase
split, compile counts, the probed feed bandwidth and the placement. The
last line is ``{"ok": true, "device": {...}}`` and only when every check
passed on the TPU; any failure exits non-zero and prints no result.
``--cpu-rehearsal`` runs the same phases on the CPU backend at a small
size (with four virtual devices for ``--chips 4``) and never prints it.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time

import numpy as np

#: HLL++ relative standard error of deequ's ApproxCountDistinct (p = 9)
HLL_RELATIVE_SD = 0.05
#: key-buffer entries for the verify phase: below its row count, so the
#: device frequency table runs its compacting trace at default slots
COMPACTING_BUFFER_ENTRIES = 1 << 20
FLAGS = np.array(["A", "N", "R"])
#: a hung run dumps its stacks and exits before the driver's 1200 s limit
TIMEOUT_S = 1150.0


class Checks:
    """Collects failed checks instead of stopping at the first."""

    def __init__(self) -> None:
        self.failed: list = []

    def that(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)
            print(f"FAILED: {what}", flush=True)

    def close(self, what: str, got, want, rel: float) -> None:
        ok = got is not None and abs(got - want) <= rel * max(1.0, abs(want))
        self.that(ok, f"{what}: got {got!r}, oracle {want!r} (rel {rel})")

    def exact(self, what: str, got, want) -> None:
        self.that(got == want, f"{what}: got {got!r}, oracle {want!r}")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data and oracles
# ---------------------------------------------------------------------------


def build_scan_data(rows: int, seed: int = 42):
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    cols = {}
    for i in range(4):
        vals = rng.normal(100 * i, 10, rows)
        nulls = rng.random(rows) < 0.05
        cols[f"x{i}"] = pa.array(vals, mask=nulls)
    cols["cat"] = pa.array(rng.integers(0, 100_000, rows))
    return pa.table(cols)


def build_lineitem_data(rows: int, seed: int = 19):
    """TPC-H lineitem-shaped synthetic (BASELINE config 3): the 16 lineitem
    columns with realistic types/cardinalities — 4 int keys, 4 numeric
    measures, 2 flags, 3 dates (strings), ship instruction/mode categories,
    and a high-cardinality comment column (dictionary-encoded pool)."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    cols = {}
    cols["l_orderkey"] = pa.array(rng.integers(1, max(rows // 4, 2), rows))
    cols["l_partkey"] = pa.array(rng.integers(1, 200_001, rows))
    cols["l_suppkey"] = pa.array(rng.integers(1, 10_001, rows))
    cols["l_linenumber"] = pa.array(rng.integers(1, 8, rows))
    cols["l_quantity"] = pa.array(rng.integers(1, 51, rows).astype(np.float64))
    cols["l_extendedprice"] = pa.array(np.round(rng.uniform(900, 105_000, rows), 2))
    cols["l_discount"] = pa.array(np.round(rng.uniform(0, 0.10, rows), 2))
    cols["l_tax"] = pa.array(np.round(rng.uniform(0, 0.08, rows), 2))
    flags = np.array(["A", "N", "R"])
    cols["l_returnflag"] = pa.array(flags[rng.integers(0, 3, rows)])
    status = np.array(["F", "O"])
    cols["l_linestatus"] = pa.array(status[rng.integers(0, 2, rows)])
    day0 = np.datetime64("1992-01-01")
    for name in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        days = rng.integers(0, 2526, rows)  # 1992-01-01 .. 1998-12-01
        dates = (day0 + days.astype("timedelta64[D]")).astype("datetime64[D]")
        dic = pa.array(np.unique(dates).astype(str))
        codes = pa.array(
            np.searchsorted(np.unique(days), days).astype(np.int32)
        )
        cols[name] = pa.DictionaryArray.from_arrays(codes, dic)
    instr = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"])
    cols["l_shipinstruct"] = pa.array(instr[rng.integers(0, 4, rows)])
    modes = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
    cols["l_shipmode"] = pa.array(modes[rng.integers(0, 7, rows)])
    pool = np.array(
        [f"comment text fragment number {i} about the order" for i in range(1_000_000)]
    )
    codes = pa.array(rng.integers(0, len(pool), rows).astype(np.int32))
    cols["l_comment"] = pa.DictionaryArray.from_arrays(codes, pa.array(pool))
    return pa.table(cols)


def scan_table(rows: int, seed: int):
    import pyarrow as pa

    table = build_scan_data(rows, seed=seed)
    rng = np.random.default_rng(seed + 1)
    flag = pa.array(FLAGS[rng.integers(0, 3, rows)], mask=rng.random(rows) < 0.02)
    return table.append_column("flag", flag)


def scan_analyzers(grouping: bool = True):
    from deequ_tpu.analyzers import (
        ApproxCountDistinct, ApproxQuantile, Completeness, CountDistinct,
        Distinctness, Histogram, Maximum, Mean, Minimum, Size,
        StandardDeviation, Sum, Uniqueness,
    )

    out = [Size()]
    for i in range(4):
        c = f"x{i}"
        out += [Completeness(c), Mean(c), Sum(c), StandardDeviation(c),
                Minimum(c), Maximum(c)]
    out += [Histogram("flag"), ApproxCountDistinct("cat"),
            ApproxQuantile("x0", 0.5)]
    if grouping:
        out += [Uniqueness(["cat"]), Distinctness(["cat"]),
                CountDistinct(["cat"])]
    return out


def scan_check(rows: int, grouping: bool = True):
    from deequ_tpu import Check, CheckLevel

    check = (
        Check(CheckLevel.ERROR, "chip smoke")
        .has_size(lambda n: n == rows)
        .is_complete("cat")
        .has_completeness("x0", lambda c: 0.9 < c < 1.0)
    )
    if grouping:
        check = check.has_uniqueness(["cat"], lambda u: u < 0.5)
    return check


def rank_error(sorted_values: np.ndarray, value: float, q: float) -> float:
    """Distance of ``q`` from the rank interval ``value`` occupies. The KLL
    sketch keeps float32 items, so ranks are taken in float32 (``value``
    and ``sorted_values`` both)."""
    n = len(sorted_values)
    v = np.float32(value)
    lo = np.searchsorted(sorted_values, v, side="left") / n
    hi = np.searchsorted(sorted_values, v, side="right") / n
    return max(0.0, lo - q, q - hi)


def metric_value(metric):
    return metric.value.get() if metric.value.is_success else None


def check_scan_metrics(checks: Checks, table, metrics: dict, tag: str) -> None:
    """Every metric of :func:`scan_analyzers` against numpy."""
    rows = table.num_rows
    by_name = {(a.name, a.instance): metric_value(m) for a, m in metrics.items()}
    checks.exact(f"{tag} Size", by_name[("Size", "*")], float(rows))
    for i in range(4):
        c = f"x{i}"
        arr = table[c].to_numpy(zero_copy_only=False)
        vals = arr[~np.isnan(arr)]
        checks.close(f"{tag} Completeness({c})", by_name[("Completeness", c)],
                     len(vals) / rows, 1e-6)
        for name, want in (
            ("Mean", vals.mean()), ("Sum", vals.sum()),
            ("StandardDeviation", vals.std()), ("Minimum", vals.min()),
            ("Maximum", vals.max()),
        ):
            checks.close(f"{tag} {name}({c})", by_name[(name, c)], want, 1e-6)
    x0 = table["x0"].to_numpy(zero_copy_only=False)
    x0 = np.sort(x0[~np.isnan(x0)].astype(np.float32))
    q = by_name[("ApproxQuantile-0.5", "x0")]
    checks.that(
        q is not None and rank_error(x0, q, 0.5) <= 2 * 0.01,
        f"{tag} ApproxQuantile(x0, 0.5) = {q!r}: rank error above 2 x 0.01",
    )
    counts = np.bincount(table["cat"].to_numpy())
    distinct = int((counts > 0).sum())
    if ("Uniqueness", "cat") in by_name:
        checks.exact(f"{tag} Uniqueness(cat)", by_name[("Uniqueness", "cat")],
                     float((counts == 1).sum()) / rows)
        checks.exact(f"{tag} Distinctness(cat)",
                     by_name[("Distinctness", "cat")], float(distinct) / rows)
        checks.exact(f"{tag} CountDistinct(cat)",
                     by_name[("CountDistinct", "cat")], float(distinct))
    hll = by_name[("ApproxCountDistinct", "cat")]
    checks.that(
        hll is not None
        and abs(hll - distinct) <= 3 * HLL_RELATIVE_SD * distinct,
        f"{tag} ApproxCountDistinct(cat) = {hll!r} vs {distinct} distinct",
    )
    hist = by_name[("Histogram", "flag")]
    flag = table["flag"]
    want = {FLAGS[i]: 0 for i in range(3)}
    want.update(value_counts(flag))
    if flag.null_count:
        want["NullValue"] = flag.null_count
    got = None if hist is None else {k: v.absolute for k, v in hist.values.items()}
    checks.exact(f"{tag} Histogram(flag)", got, want)


def check_profiles(checks: Checks, table, profiles) -> None:
    """Completeness, distinct counts, numeric statistics, histograms and
    percentiles of every profiled column against numpy/pyarrow."""
    import pyarrow.compute as pc

    from deequ_tpu.profiles import NumericColumnProfile

    rows = table.num_rows
    checks.exact("profile columns", sorted(profiles.profiles),
                 sorted(table.column_names))
    for name in table.column_names:
        p = profiles.profiles.get(name)
        if p is None:
            continue
        col = table[name]
        if hasattr(col.type, "value_type"):  # dictionary: decode
            col = col.cast(col.type.value_type)
        checks.close(f"profile {name} completeness", p.completeness,
                     (rows - col.null_count) / rows, 1e-6)
        distinct = pc.count_distinct(col).as_py()
        checks.that(
            abs(p.approximate_num_distinct_values - distinct)
            <= 3 * HLL_RELATIVE_SD * distinct,
            f"profile {name} approx distinct "
            f"{p.approximate_num_distinct_values} vs {distinct}",
        )
        if p.histogram is not None:
            want = {_key(k): v for k, v in value_counts(col).items()}
            got = {_key(k): v.absolute for k, v in p.histogram.values.items()}
            checks.exact(f"profile {name} histogram", got, want)
        if isinstance(p, NumericColumnProfile):
            arr = col.to_numpy(zero_copy_only=False).astype(np.float64)
            for what, got, want in (
                ("mean", p.mean, arr.mean()), ("sum", p.sum, arr.sum()),
                ("min", p.minimum, arr.min()), ("max", p.maximum, arr.max()),
                ("std_dev", p.std_dev, arr.std()),
            ):
                checks.close(f"profile {name} {what}", got, want, 1e-6)
            pcts = p.approx_percentiles or []
            srt = np.sort(arr.astype(np.float32))
            worst = max(
                (rank_error(srt, v, (i + 1) / len(pcts))
                 for i, v in enumerate(pcts)),
                default=1.0,
            )
            checks.that(worst <= 2 * 0.01,
                        f"profile {name} percentiles: rank error {worst}")


def value_counts(col) -> dict:
    """Non-null value -> count, by hashing (a sort of 10M strings would
    take tens of seconds)."""
    import pyarrow.compute as pc

    vc = pc.value_counts(col.drop_null())
    return dict(zip(vc.field("values").to_pylist(),
                    vc.field("counts").to_pylist()))


def _key(k) -> str:
    """Histogram keys compared by value: "1.0", "1" and 1 are one key."""
    try:
        return repr(float(k))
    except (TypeError, ValueError):
        return str(k)


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------


class CompileCounter:
    """Backend compiles and persistent-cache hits seen by this process."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"backend_compiles={self.compiles} "
                f"compile_s={self.compile_s:.2f} cache_hits={self.cache_hits}")


class DeviceUse:
    """Per-device peak of ``bytes_in_use`` above its level at entry,
    sampled on a thread: which chips a run placed arrays on."""

    def __init__(self, devices, every_s: float = 0.002) -> None:
        self.devices = list(devices)
        self.every_s = every_s
        self.peak = [0] * len(self.devices)
        self._stop = threading.Event()

    @staticmethod
    def _in_use(d) -> int:
        stats = d.memory_stats()
        return 0 if not stats else int(stats.get("bytes_in_use", 0))

    def __enter__(self):
        self.base = [self._in_use(d) for d in self.devices]
        self._thread = threading.Thread(target=self._sample, daemon=True)
        self._thread.start()
        return self

    def _sample(self) -> None:
        while not self._stop.is_set():
            for i, d in enumerate(self.devices):
                self.peak[i] = max(self.peak[i], self._in_use(d) - self.base[i])
            self._stop.wait(self.every_s)

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def used(self, min_bytes: int = 1 << 16):
        """Ids of the devices that gained ``min_bytes``; None where the
        backend keeps no memory statistics (the CPU)."""
        if not self.devices[0].memory_stats():
            return None
        return {d.id for d, p in zip(self.devices, self.peak) if p >= min_bytes}


def check_devices_used(checks: Checks, what: str, use: DeviceUse, want) -> None:
    used = use.used()
    if used is None and use.devices[0].platform == "cpu":
        log(f"[{what}] device memory statistics unavailable on the CPU")
        return
    checks.exact(what, used, want)


def report_monitor(tag: str, mon, counter: CompileCounter) -> None:
    phases = {k: round(v, 3) for k, v in sorted(mon.phase_seconds.items())}
    log(f"[{tag}] placement={mon.placement} "
        f"feed_bandwidth_mbps={mon.feed_bandwidth_mbps} "
        f"program_compiles={mon.program_compiles} {counter.line()}")
    log(f"[{tag}] phase_seconds={json.dumps(phases)}")


def check_device_path(checks: Checks, tag: str, mon) -> None:
    checks.exact(f"{tag} placement", mon.placement, "device")
    checks.exact(f"{tag} device_failovers", mon.device_failovers, 0)
    checks.exact(f"{tag} degraded", list(mon.degraded), [])
    checks.exact(f"{tag} isolation_reruns", mon.isolation_reruns, 0)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def verify_run(data, rows: int, monitor=None, sharding=None,
               grouping: bool = True):
    from deequ_tpu import VerificationSuite

    builder = (
        VerificationSuite.on_data(data)
        .add_required_analyzers(scan_analyzers(grouping))
        .add_check(scan_check(rows, grouping))
    )
    if monitor is not None:
        builder = builder.with_monitor(monitor)
    if sharding is not None:
        builder = builder.with_sharding(sharding)
    return builder.run()


def phase_verify(checks: Checks, counter: CompileCounter, rows: int, seed: int):
    from deequ_tpu import CheckStatus
    from deequ_tpu.data import Dataset
    from deequ_tpu.runners import RunMonitor

    t0 = time.perf_counter()
    table = scan_table(rows, seed)
    data = Dataset.from_arrow(table)
    log(f"[verify] {rows:,} rows generated in {time.perf_counter() - t0:.2f}s")
    checks.that(rows > COMPACTING_BUFFER_ENTRIES,
                "verify rows must exceed the key buffer to compact")
    mon = RunMonitor()
    t0 = time.perf_counter()
    result = verify_run(data, rows, monitor=mon)
    log(f"[verify] run {time.perf_counter() - t0:.2f}s")
    report_monitor("verify", mon, counter)
    check_device_path(checks, "verify", mon)
    checks.exact("verify device frequency-table sets", mon.device_freq_sets, 1)
    checks.exact("verify frequency-table host fallbacks",
                 mon.freq_overflow_fallbacks, 0)
    checks.exact("verify check status", result.status, CheckStatus.SUCCESS)
    check_scan_metrics(checks, table, result.metrics, "verify")


def phase_profile(checks: Checks, counter: CompileCounter, rows: int, seed: int):
    from deequ_tpu.data import Dataset
    from deequ_tpu.profiles import ColumnProfilerRunner
    from deequ_tpu.runners import RunMonitor

    t0 = time.perf_counter()
    table = build_lineitem_data(rows, seed=seed)
    log(f"[profile] {rows:,} rows generated in {time.perf_counter() - t0:.2f}s")
    mon = RunMonitor()
    t0 = time.perf_counter()
    profiles = (
        ColumnProfilerRunner.on_data(Dataset.from_arrow(table))
        .with_monitor(mon).run()
    )
    log(f"[profile] run {time.perf_counter() - t0:.2f}s passes={mon.passes}")
    report_monitor("profile", mon, counter)
    check_device_path(checks, "profile", mon)
    check_profiles(checks, table, profiles)


#: scan metrics a mesh or fleet run may only round, never change
_FLOAT_AGGREGATES = ("Mean", "Sum", "StandardDeviation")


def compare_runs(checks: Checks, tag: str, got: dict, want: dict) -> None:
    """A multi-chip run against the one-chip run of the same data."""
    want_by = {(a.name, a.instance): metric_value(m) for a, m in want.items()}
    for a, m in got.items():
        key = (a.name, a.instance)
        g, w = metric_value(m), want_by.get(key)
        if a.name.startswith("ApproxQuantile"):
            continue  # merge order changes the sketch; rank-checked instead
        if a.name in _FLOAT_AGGREGATES:
            checks.close(f"{tag} {key} vs one chip", g, w, 1e-9)
        elif a.name == "Histogram":
            checks.exact(f"{tag} {key} vs one chip",
                         {k: v.absolute for k, v in g.values.items()},
                         {k: v.absolute for k, v in w.values.items()})
        else:
            checks.exact(f"{tag} {key} vs one chip", g, w)


def phase_mesh(checks: Checks, counter: CompileCounter, rows: int, seed: int):
    """The scan battery without its grouping set: the grouping set's
    multi-chip programs (the frequency-table update over a 4- and a 2-chip
    mesh, and its collective merge) take minutes to compile at default
    slots, which four chips would pay at four times the cost. Their v5e
    compiles are rehearsed without the chip."""
    import jax

    from deequ_tpu.data import Dataset
    from deequ_tpu.parallel import make_mesh
    from deequ_tpu.runners import RunMonitor
    from deequ_tpu.service import VerificationService

    devices = jax.devices()[:4]
    checks.exact("device count", len(devices), 4)
    # every array of a run must already sit on the chips its program runs
    # on: an implicit chip-to-chip copy raises instead of running
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    table = scan_table(rows, seed)
    data = Dataset.from_arrow(table)

    t0 = time.perf_counter()
    one = verify_run(data, rows, grouping=False).metrics
    log(f"[one-chip] run {time.perf_counter() - t0:.2f}s")
    check_scan_metrics(checks, table, one, "one-chip")

    mon = RunMonitor()
    t0 = time.perf_counter()
    with DeviceUse(devices) as use:
        mesh = verify_run(data, rows, monitor=mon, sharding=make_mesh(4),
                          grouping=False).metrics
    log(f"[mesh] run {time.perf_counter() - t0:.2f}s "
        f"device_peak_bytes={use.peak}")
    report_monitor("mesh", mon, counter)
    check_device_path(checks, "mesh", mon)
    check_devices_used(checks, "mesh devices used", use,
                       {d.id for d in devices})
    check_scan_metrics(checks, table, mesh, "mesh")
    compare_runs(checks, "mesh", mesh, one)

    # two tenants with leases held on both, so the fleet packs 2+2; each
    # job then runs alone and must touch only its own slice
    tables = {"a": table, "b": scan_table(rows, seed + 7)}
    with VerificationService(workers=2, background_warm=False,
                             fleet=True) as svc:
        for t in tables:
            svc.fleet.acquire(t)
        slices = {t: svc.fleet.devices_of(t) for t in tables}
        log(f"[fleet] slices={slices}")
        checks.that(
            sorted(len(s) for s in slices.values()) == [2, 2]
            and not set(slices["a"]) & set(slices["b"]),
            f"fleet slices {slices} are not disjoint 2+2",
        )
        for t, tab in tables.items():
            tdata = Dataset.from_arrow(tab)
            t0 = time.perf_counter()
            with DeviceUse(devices) as use:
                res = svc.verify(tdata, [scan_check(rows, False)], tenant=t,
                                 required_analyzers=scan_analyzers(False),
                                 timeout=600)
            log(f"[fleet] tenant {t} run {time.perf_counter() - t0:.2f}s "
                f"device_peak_bytes={use.peak}")
            want_ids = {devices[p].id for p in slices[t]}
            check_devices_used(checks, f"fleet tenant {t} devices used", use,
                               want_ids)
            check_scan_metrics(checks, tab, res.metrics, f"fleet-{t}")
            solo = (one if t == "a"
                    else verify_run(tdata, rows, grouping=False).metrics)
            compare_runs(checks, f"fleet-{t}", res.metrics, solo)
        for t in tables:
            svc.fleet.release(t)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=10_000_000,
                    help="rows of the verification table")
    ap.add_argument("--profile-rows", type=int, default=10_000_000)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU backend; never prints a result")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    faulthandler.dump_traceback_later(TIMEOUT_S, exit=True)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    os.environ.pop("DEEQU_TPU_PLACEMENT", None)  # the default: auto
    os.environ["DEEQU_TPU_FREQ_BUFFER_ENTRIES"] = str(COMPACTING_BUFFER_ENTRIES)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            )

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    log(f"[main] devices: {len(devices)} x {devices[0].device_kind} "
        f"({platform})")
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX platform {platform!r})", file=sys.stderr)
        return 2

    import deequ_tpu  # noqa: F401  (x64, compile cache)
    from deequ_tpu.config import compile_cache_dir

    from deequ_tpu.runners.engine import probe_feed_bandwidth

    log(f"[main] compile cache: {compile_cache_dir()}")
    log(f"[main] feed_bandwidth_probe_mbps={probe_feed_bandwidth()}")
    counter = CompileCounter()
    checks = Checks()
    if args.chips == 4:
        phases = [("mesh", phase_mesh, args.rows)]
    else:
        phases = [("verify", phase_verify, args.rows),
                  ("profile", phase_profile, args.profile_rows)]
    for name, fn, rows in phases:
        t0 = time.perf_counter()
        try:
            fn(checks, counter, rows, args.seed)
        except Exception as exc:  # noqa: BLE001 - reported, then fails
            import traceback

            traceback.print_exc()
            checks.that(False, f"phase {name} raised {exc!r}")
        log(f"[{name}] phase seconds {time.perf_counter() - t0:.2f} "
            f"({counter.line()})")
    faulthandler.cancel_dump_traceback_later()
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        log("[main] cpu rehearsal passed")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

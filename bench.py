"""Benchmarks on the real device, mirroring the BASELINE.json configs.

1. **Scan battery** (BASELINE config 2 shape): fused single-pass analyzer
   scan over a 50M-row table — completeness, moments, min/max, HLL distinct,
   KLL quantile sketches.
2. **Column profiler** (BASELINE config 3 shape, the north-star metric):
   `ColumnProfilerRunner` full profile over a wide mixed-type table
   (numeric + string + categorical columns), reporting rows/sec/chip.

Each stage compares against a single-core pandas/numpy oracle computing the
same statistics on the same data (the stand-in for the reference's
Spark-local per-core throughput; the reference publishes no numbers,
BASELINE.md). After EVERY stage a parse-able partial-result JSON line goes
to stdout ("partial": true, with everything measured so far), so a timeout
in a late stage keeps the earlier numbers; the final complete line carries
"partial": false and the north-star profiler metric.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def write_stage_trace(stage: str) -> None:
    """Drain the flight-recorder ring into a per-stage Chrome trace
    artifact (DEEQU_TPU_TRACE_DIR, default ./bench-traces): every bench
    stage leaves its span tree behind, so a slow stage is explainable from
    the artifact without re-running under a profiler. Draining keeps each
    artifact scoped to its own stage."""
    import os

    try:
        from deequ_tpu.observability import export as obs_export
        from deequ_tpu.observability import recorder as obs_recorder
        from deequ_tpu.observability import trace as obs_trace

        if not obs_trace.enabled():
            return
        out_dir = os.environ.get("DEEQU_TPU_TRACE_DIR", "bench-traces")
        spans = obs_recorder().drain()
        if not spans:
            return
        path = obs_export.write_chrome_trace(
            os.path.join(out_dir, f"bench-{stage}.trace.json"), spans
        )
        log(f"[{stage}] trace artifact: {path} ({len(spans)} spans)")
    except Exception as exc:  # noqa: BLE001 - artifacts are advisory
        log(f"[{stage}] trace artifact failed: {exc}")


def monitor_phase_fields(mon) -> dict:
    """The per-stage observability fields the partial JSON records for every
    monitored stage (VERDICT r5 ask #1b): NEW program compiles this run
    (``compiles`` — a compile regression shows as a nonzero value on a warm
    stage), plus the state_fetch vs device_dispatch phase split the r6
    acceptance gate reads."""
    return {
        "compiles": mon.program_compiles,
        "state_fetch_s": round(mon.phase_seconds.get("state_fetch", 0.0), 3),
        "device_dispatch_s": round(
            mon.phase_seconds.get("device_dispatch", 0.0), 3
        ),
    }


# ---------------------------------------------------------------------------
# per-stage hard deadlines (VERDICT r5 weak #1: the driver's wall-clock kill
# must never erase completed stages' numbers — each stage now gets its own
# enforced budget and a graceful skip leaves the partial JSON intact)
# ---------------------------------------------------------------------------

STAGE_BUDGET_ENV = "DEEQU_TPU_BENCH_STAGE_BUDGET_S"


class StageDeadline(BaseException):
    """A stage blew its wall-clock budget (raised from SIGALRM).
    BaseException, so no stage-internal ``except Exception`` can swallow
    the deadline — the same reason KeyboardInterrupt sits outside
    Exception."""


def stage_budget_s() -> float:
    import os

    return float(os.environ.get(STAGE_BUDGET_ENV, "180"))


def subprocess_timeout_s() -> float:
    """Wall-clock cap for detached stage subprocesses (prewarm, grouping
    points): generous enough to absorb a cold XLA compile longer than one
    stage budget, bounded so a hung child can never wedge the bench."""
    return max(stage_budget_s() * 2, 300)


def run_stage_with_deadline(name: str, fn, *args, budget_s=None, **kwargs):
    """Run one stage under a HARD wall-clock deadline: SIGALRM interrupts
    the main thread mid-stage (numpy/pyarrow/XLA dispatch all return to the
    interpreter frequently enough for delivery), the stage is recorded as
    ``skipped_deadline`` and the bench moves on — a slow stage costs its
    own numbers, never the stages after it. ``budget_s`` overrides the
    default stage budget (the xla_prewarm stage exists to absorb a cold
    compile LONGER than one stage budget, so it runs under an enlarged
    deadline). Returns (result | None, status, seconds)."""
    import signal

    budget = stage_budget_s() if budget_s is None else float(budget_s)

    def on_alarm(signum, frame):
        raise StageDeadline(name)

    prior = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, budget)
    t0 = time.perf_counter()
    try:
        result = fn(*args, **kwargs)
        return result, "ok", time.perf_counter() - t0
    except StageDeadline:
        elapsed = time.perf_counter() - t0
        log(
            f"[{name}] exceeded its {budget:.0f}s stage budget after "
            f"{elapsed:.1f}s — skipped (partial JSON keeps earlier stages)"
        )
        return None, "skipped_deadline", elapsed
    except Exception as exc:
        # a failing stage (dead subprocess, missing native lib, env issue)
        # costs its own numbers, never the stages after it — the same
        # contract the deadline path keeps. SystemExit (parity mismatch)
        # and KeyboardInterrupt still abort the bench.
        elapsed = time.perf_counter() - t0
        log(f"[{name}] stage FAILED after {elapsed:.1f}s: {exc!r}")
        return None, "failed", elapsed
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prior)


# ---------------------------------------------------------------------------
# stage 1: scan battery (BASELINE config 2)
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


def scan_battery():
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        KLLParameters,
        KLLSketch,
        Maximum,
        Mean,
        Minimum,
        StandardDeviation,
        Sum,
    )

    analyzers = []
    for i in range(4):
        c = f"x{i}"
        analyzers += [
            Completeness(c), Mean(c), Sum(c), Minimum(c), Maximum(c),
            StandardDeviation(c),
        ]
    analyzers.append(ApproxCountDistinct("cat"))
    analyzers += [KLLSketch("x0", KLLParameters(2048, 0.64, 100)),
                  KLLSketch("x1", KLLParameters(2048, 0.64, 100))]
    return analyzers


def run_scan_stage(rows: int, batch_size: int) -> dict:
    import pyarrow as pa

    from deequ_tpu.data import Dataset
    from deequ_tpu.runners import AnalysisRunner
    from deequ_tpu.runners.engine import RunMonitor

    log(f"[scan] building {rows:,}-row table")
    table = build_scan_data(rows)
    data = Dataset.from_arrow(table)
    analyzers = scan_battery()

    warm = Dataset.from_arrow(table.slice(0, batch_size))
    AnalysisRunner.do_analysis_run(warm, analyzers, batch_size=batch_size)

    mon = RunMonitor()
    t0 = time.perf_counter()
    ctx = AnalysisRunner.do_analysis_run(
        data, analyzers, batch_size=batch_size, monitor=mon
    )
    elapsed = time.perf_counter() - t0
    assert mon.passes == 1
    scan_phases = monitor_phase_fields(mon)
    tpu_vals = {}
    for a, m in ctx.metric_map.items():
        if m.value.is_success and a.name in ("Completeness", "Mean", "Sum"):
            tpu_vals[f"{a.name}:{a.instance}"] = m.value.get()

    df = table.to_pandas()
    t0 = time.perf_counter()
    base_vals = {}
    for i in range(4):
        c = f"x{i}"
        s = df[c]
        base_vals[f"Completeness:{c}"] = s.notna().mean()
        base_vals[f"Mean:{c}"] = s.mean()
        base_vals[f"Sum:{c}"] = s.sum()
        s.min(); s.max(); s.std(ddof=0)
    df["cat"].nunique()
    np.nanquantile(df["x0"].to_numpy(), np.linspace(0.01, 1, 100))
    np.nanquantile(df["x1"].to_numpy(), np.linspace(0.01, 1, 100))
    base_s = time.perf_counter() - t0

    for k, v in base_vals.items():
        tv = tpu_vals[k]
        if abs(tv - v) > 1e-6 * max(1.0, abs(v)):
            log(f"PARITY MISMATCH {k}: tpu={tv} oracle={v}")
            sys.exit(1)
    rate = rows / elapsed
    phases = ", ".join(f"{k}={v:.2f}s" for k, v in sorted(mon.phase_seconds.items()))
    log(
        f"[scan] {rows:,} rows x {len(analyzers)} analyzers: {elapsed:.2f}s "
        f"({rate/1e6:.2f}M rows/s/chip), single-core pandas {base_s:.2f}s "
        f"-> {rate/(rows/base_s):.1f}x"
    )
    log(f"[scan] placement={mon.placement} phases: {phases}")
    return {
        "rows_per_sec": rate,
        "vs_single_core": rate / (rows / base_s),
        **scan_phases,
    }


# ---------------------------------------------------------------------------
# stage 2: column profiler on a wide mixed table (BASELINE config 3)
# ---------------------------------------------------------------------------

N_NUMERIC = 16
N_STRING = 4
N_CAT = 4


def build_wide_data(rows: int, n_numeric=N_NUMERIC, n_string=N_STRING, n_cat=N_CAT):
    import pyarrow as pa

    rng = np.random.default_rng(7)
    cols = {}
    for i in range(n_numeric):
        vals = rng.normal(10 * i, 1 + i, rows)
        if i % 3 == 0:
            cols[f"n{i}"] = pa.array(vals, mask=rng.random(rows) < 0.02)
        else:
            cols[f"n{i}"] = pa.array(vals)
    base = np.array([f"id_{i:07d}" for i in range(100_000)])
    for i in range(n_string):
        cols[f"s{i}"] = pa.array(base[rng.integers(0, len(base), rows)])
    for i in range(n_cat):
        card = 20 * (i + 1)
        cats = np.array([f"c{j}" for j in range(card)])
        cols[f"c{i}"] = pa.array(cats[rng.integers(0, card, rows)])
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


#: rows the single-core pandas oracle actually runs on; its RATE is what we
#: compare against (per-row cost of these stats is constant, and a smaller
#: working set flatters the baseline's caches, so the ratio is conservative)
ORACLE_ROWS_CAP = 10_000_000

#: memoized single-core oracle rates keyed by the row count they ran on:
#: the device_profile (config-3) stage and the host profile stage share one
#: measurement instead of paying the pandas pass twice
_ORACLE_RATE_MEMO: dict = {}


def lineitem_single_core_rate(table, oracle_rows: int) -> float:
    """Single-core pandas oracle rate (rows/s) over a lineitem-shaped
    table: the same WORK the profiler does per reference semantics —
    completeness, approx-distinct, the numeric battery incl. quantiles,
    value histograms for low-cardinality columns, and per-value regex type
    inference on string columns (`profiles/ColumnProfiler.scala:122-139`).
    Categorical (dictionary) columns classify their categories only — the
    same advantage our engine takes. Memoized per row count so the
    config-3 stage and the host profile stage measure it once."""
    cached = _ORACLE_RATE_MEMO.get(oracle_rows)
    if cached is not None:
        return cached
    import pandas as pd

    from deequ_tpu.runners.features import (
        _BOOLEAN_RE,
        _FRACTIONAL_RE,
        _INTEGRAL_RE,
    )

    def classify_series(s):
        if isinstance(s.dtype, pd.CategoricalDtype):
            cats = pd.Series(s.cat.categories.astype(object))
            cls = np.select(
                [
                    cats.str.fullmatch(_FRACTIONAL_RE),
                    cats.str.fullmatch(_INTEGRAL_RE),
                    cats.str.fullmatch(_BOOLEAN_RE),
                ],
                [1, 2, 3],
                default=4,
            )
            np.bincount(cls[s.cat.codes[s.cat.codes >= 0]], minlength=5)
            return
        sv = s.dropna()  # already str-typed; no re-stringification timed
        cls = np.select(
            [
                sv.str.fullmatch(_FRACTIONAL_RE),
                sv.str.fullmatch(_INTEGRAL_RE),
                sv.str.fullmatch(_BOOLEAN_RE),
            ],
            [1, 2, 3],
            default=4,
        )
        np.bincount(cls, minlength=5)

    df = table.slice(0, oracle_rows).to_pandas()
    t0 = time.perf_counter()
    for name in df.columns:
        s = df[name]
        s.notna().mean()
        nunique = s.nunique()
        if s.dtype.kind in "if":
            s.mean(); s.min(); s.max(); s.std(ddof=0); s.sum()
            np.nanquantile(
                s.to_numpy(dtype=np.float64), np.linspace(0.01, 1, 100)
            )
        elif s.dtype == object or isinstance(s.dtype, pd.CategoricalDtype):
            classify_series(s)
        if nunique <= 120:
            s.value_counts()
    base_rate = oracle_rows / (time.perf_counter() - t0)
    _ORACLE_RATE_MEMO[oracle_rows] = base_rate
    return base_rate


def run_profile_stage(rows: int) -> dict:
    from deequ_tpu.data import Dataset
    from deequ_tpu.profiles import ColumnProfilerRunner
    from deequ_tpu.runners.engine import RunMonitor

    log(f"[profile] building {rows:,}-row TPC-H-lineitem-shaped table (16 cols)")
    table = build_lineitem_data(rows)
    data = Dataset.from_arrow(table)

    # warmup on a slice: compile every program shape the profile needs
    warm = Dataset.from_arrow(table.slice(0, 1 << 18))
    ColumnProfilerRunner.on_data(warm).run()

    mon = RunMonitor()
    t0 = time.perf_counter()
    profiles = ColumnProfilerRunner.on_data(data).with_monitor(mon).run()
    elapsed = time.perf_counter() - t0
    rate = rows / elapsed
    phases = ", ".join(f"{k}={v:.2f}s" for k, v in sorted(mon.phase_seconds.items()))
    log(f"[profile] passes={mon.passes} placement={mon.placement} phases: {phases}")

    # full-data numeric parity guard (cheap numpy reductions)
    for name in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        arr = table[name].to_numpy()
        p = profiles.profiles[name]
        for got, want in (
            (p.mean, arr.mean()), (p.minimum, arr.min()), (p.maximum, arr.max()),
            (p.std_dev, arr.std()), (p.sum, arr.sum()),
        ):
            if abs(got - want) > 1e-6 * max(1.0, abs(want)):
                log(f"PARITY MISMATCH {name}: got={got} want={want}")
                sys.exit(1)

    # single-core pandas oracle on a capped subsample; compare RATES (see
    # lineitem_single_core_rate for the oracle's work definition — shared,
    # memoized, with the config-3 device_profile stage)
    oracle_rows = min(rows, ORACLE_ROWS_CAP)
    base_rate = lineitem_single_core_rate(table, oracle_rows)

    complete = len(profiles.profiles)
    vs_single = rate / base_rate
    log(
        f"[profile] {rows:,} rows x 16 cols ({complete} profiled): "
        f"{elapsed:.2f}s ({rate/1e6:.2f}M rows/s/chip); single-core pandas "
        f"{base_rate/1e6:.2f}M rows/s on {oracle_rows:,} rows -> {vs_single:.1f}x "
        f"single-core, {vs_single/64:.2f}x a hypothetical perfectly-linear "
        f"64-core baseline"
    )
    return {
        "rows_per_sec": rate,
        "vs_single_core": vs_single,
        "vs_64core_linear": vs_single / 64,
        **monitor_phase_fields(mon),
    }


# ---------------------------------------------------------------------------
# stage 2b: DEVICE-RESIDENT fused scan + sketch merge (VERDICT r3 ask #1:
# quantify the TPU itself — batches live in device memory, no host feed in
# the timed path, so the number is the chip's, not the link's)
# ---------------------------------------------------------------------------


def run_device_resident_stage(
    rows_per_batch: int = 1 << 20, n_batches: int = 2, target_seconds: float = 5.0
) -> dict:
    """Chip-side throughput of the PRODUCTION program: chained donated
    dispatches of the fused packed-carry update over device-resident
    feature batches.

    TIMING METHODOLOGY: every timed region ends with a FULL host fetch
    (``np.asarray``) of the final states — the fetch forces real
    completion, and its own cost is amortized over the whole chain of
    dispatches."""
    import jax

    from deequ_tpu.data import Dataset
    from deequ_tpu.runners.engine import ScanEngine

    import jax.numpy as jnp
    from jax import random as jrandom

    analyzers = scan_battery()
    engine = ScanEngine(analyzers, placement="device")
    # ONE tiny real batch establishes the exact feature keys/dtypes the
    # fused program consumes; the full-size batches are then generated ON
    # DEVICE (same shapes/dtypes/distributions), so the stage quantifies
    # chip compute without paying the host feed for data whose values the
    # timing does not depend on (streaming-stage parity checks cover
    # correctness)
    tiny_rows = 1 << 10
    table = build_scan_data(tiny_rows)
    for batch in Dataset.from_arrow(table).batches(
        tiny_rows, columns=engine.required_columns()
    ):
        break
    template = engine._prepare(batch)

    t_feed0 = time.perf_counter()

    @jax.jit
    def gen_batch(key):
        out = {}
        for name in sorted(template):
            t = template[name]
            key, sub = jrandom.split(key)
            shape = (rows_per_batch,) + tuple(t.shape[1:])
            if t.dtype == jnp.bool_:
                out[name] = jrandom.uniform(sub, shape) > 0.05
            elif jnp.issubdtype(t.dtype, jnp.floating):
                out[name] = jrandom.normal(sub, shape).astype(t.dtype)
            else:
                info = jnp.iinfo(t.dtype)
                out[name] = jrandom.randint(
                    sub, shape, 0, min(info.max, 1 << 15), dtype=jnp.int32
                ).astype(t.dtype)
        return out

    feature_sets = [gen_batch(jrandom.PRNGKey(b)) for b in range(n_batches)]
    feed_bytes = sum(v.nbytes for v in feature_sets[0].values()) * n_batches
    for features in feature_sets:
        jax.block_until_ready(features)
    feed_s = time.perf_counter() - t_feed0

    program = engine._update

    def fetch(carry):
        return jax.tree_util.tree_map(np.asarray, carry)

    def chain(n_dispatches):
        carry = program.init_carry()
        t0 = time.perf_counter()
        for i in range(n_dispatches):
            carry = program(carry, feature_sets[i % n_batches])
        fetch(carry)
        return time.perf_counter() - t0

    chain(n_batches)  # warm/compile both feature-set shapes
    # two chain lengths; the SLOPE is the per-batch cost with the fixed
    # fetch round-trip cancelled out. RTT jitter can rival the compute of a
    # short chain, so the delta is kept >= 64 batches and the median of
    # three slopes is reported.
    k1 = max(8, n_batches)
    t1 = chain(k1)
    k2 = k1 + max(64, int(target_seconds / max(t1 / k1, 1e-4)))
    slopes = []
    rows = 0
    for _ in range(3):
        ta, tb = chain(k1), chain(k2)
        slopes.append((tb - ta) / (k2 - k1))
        rows += rows_per_batch * (k1 + k2)
    per_batch = sorted(slopes)[1]
    if per_batch <= 0:  # jitter beat the delta; quote the conservative bound
        per_batch = tb / k2
    rate = rows_per_batch / per_batch
    bytes_per_row = feed_bytes / (rows_per_batch * n_batches)
    achieved_gbps = rate * bytes_per_row / 1e9
    log(
        f"[device-scan] {rows:,} device-resident rows x {len(analyzers)} "
        f"analyzers ({k1}+{k2} chained dispatches, fetch-forced sync, "
        f"RTT-cancelling slope {per_batch*1e3:.1f}ms/batch) -> "
        f"{rate/1e6:.1f}M rows/s/chip "
        f"({bytes_per_row:.0f} B/row touched, {achieved_gbps:.1f} GB/s achieved; "
        f"on-device generation of {feed_bytes/1e6:.0f}MB took {feed_s:.1f}s)"
    )
    return {
        "rows_per_sec": rate,
        "bytes_per_row": bytes_per_row,
        "achieved_gbps": achieved_gbps,
    }


def run_mesh_scaling_stage(rows: int = 2_000_000) -> dict:
    """ROADMAP item 2's acceptance artifact: 1→2→4→8-device sharded-scan
    throughput plus a chaos point that kills one shard mid-stage and
    records the recovery wall-time (salvage + re-shard + replay vs the
    clean run at the same mesh size). Runs in a DETACHED subprocess so the
    stage can force a multi-device platform (8 virtual CPU devices when no
    accelerator mesh exists) without re-configuring this process's jax.
    On CPU the absolute points model nothing (virtual devices share the
    same cores) — what transfers is the SHAPE and the measured recovery
    cost; a TPU host runs the same stage over its real mesh."""
    import json as _json
    import os
    import subprocess

    # the tool runs on the CPU backend (it forces jax_platforms=cpu)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mesh_scaling_bench", "--stage-json",
         str(rows)],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=subprocess_timeout_s(),
        env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh_scaling subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    result = _json.loads(proc.stdout.strip().splitlines()[-1])
    result["stage_seconds"] = time.perf_counter() - t0
    chaos = result.get("chaos") or {}
    log(
        "[mesh_scaling] points "
        + " ".join(
            f"{k}dev {v / 1e6:.2f}M rows/s"
            for k, v in sorted(result["points"].items(), key=lambda kv: int(kv[0]))
        )
        + (
            f"; chaos recovery {chaos['recovery_s']:.2f}s "
            f"(losses {chaos['shard_losses']}, reshards "
            f"{chaos['mesh_reshards']}, parity "
            f"{'ok' if chaos['parity_ok'] else 'MISMATCH'})"
            if chaos else "; chaos drill skipped (single device)"
        )
    )
    return result


def run_xla_prewarm_stage() -> dict:
    """Pre-warm the persistent XLA compilation cache from a DETACHED
    staging process (ROADMAP item 1): a subprocess runs the 1-batch
    production-shaped device profile, compiling the ~8 signature-bundled
    programs into the shared on-disk cache (config.py sets
    jax_compilation_cache_dir), so the measured device_profile stage's
    compile probe DESERIALIZES instead of compiling — the r05 failure mode
    (1140s of XLA compile inside the measured stage) cannot recur. The
    subprocess's own wall time is reported as this stage's cost."""
    import os
    import subprocess

    script = (
        "import bench; "
        "from deequ_tpu.data import Dataset; "
        "from deequ_tpu.profiles import ColumnProfilerRunner; "
        "t = bench.build_lineitem_data(1 << 20); "
        "ColumnProfilerRunner.on_data(Dataset.from_arrow(t))"
        ".with_placement('device').with_batch_size(1 << 20).run(); "
        "print('prewarm done')"
    )
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True,
            timeout=subprocess_timeout_s(),
        )
    except subprocess.TimeoutExpired:
        # a blown prewarm costs its own stage, never the measured ones:
        # the cache is simply (partially) cold for device_profile
        elapsed = time.perf_counter() - t0
        log(f"[xla-prewarm] staging subprocess timed out after {elapsed:.1f}s")
        return {"seconds": elapsed, "ok": False}
    elapsed = time.perf_counter() - t0
    ok = proc.returncode == 0
    log(
        f"[xla-prewarm] detached staging process "
        f"{'populated the persistent XLA cache' if ok else 'FAILED (rc=%d)' % proc.returncode} "
        f"in {elapsed:.1f}s"
    )
    if not ok:
        log(f"[xla-prewarm] stderr tail: {proc.stderr[-500:]}")
    return {"seconds": elapsed, "ok": ok}


def run_device_profile_stage(target_rows: int | None = None) -> dict:
    """DEVICE-PLACEMENT full column profile at config-3 (lineitem) shape:
    the REAL ColumnProfilerRunner over REAL data with `placement="device"`
    and the engine's device feature cache enabled, so the timed (second)
    run reads every feature batch from HBM — no host feed in the timed
    path. Unlike the synthetic [device-scan] stage this produces real
    metrics, which are parity-checked below; timing is plain wall clock of
    the whole run, whose own state fetches force device completion (the
    block_until_ready trap does not apply to full host fetches).

    Row count adapts to the probed feed bandwidth so the one-time staging
    run fits DEEQU_TPU_BENCH_STAGE_BUDGET_S (default 180s)."""
    import os

    from deequ_tpu.data import Dataset
    from deequ_tpu.profiles import ColumnProfilerRunner
    from deequ_tpu.runners.engine import (
        RunMonitor,
        clear_device_feature_cache,
        probe_feed_bandwidth,
    )

    bytes_per_row = 150.0  # pass-1 features at lineitem shape
    compile_probe_s = 0.0
    if target_rows is None:
        budget_s = stage_budget_s()
        bw = probe_feed_bandwidth()
        # MEASURED 1-batch compile probe (VERDICT r5 weak #1b): run the
        # device-placed profile once over a single production-shaped batch
        # and charge the measured time — dominated by XLA compile — against
        # the stage budget. The old model budgeted feed bytes only and the
        # staging run blew a 180s budget by 6x of pure compile. The probe
        # doubles as the warmup: the staging run below reuses its programs.
        probe_table = build_lineitem_data(1 << 20)
        t0 = time.perf_counter()
        (
            ColumnProfilerRunner.on_data(Dataset.from_arrow(probe_table))
            .with_placement("device")
            .with_batch_size(1 << 20)
            .run()
        )
        compile_probe_s = time.perf_counter() - t0
        del probe_table
        feed_budget_s = max(budget_s - compile_probe_s, 0.1 * budget_s)
        target_rows = int(bw * 1e6 * feed_budget_s / bytes_per_row)
        log(
            f"[device-profile] compile probe: {compile_probe_s:.1f}s for 1 "
            f"batch (budget {budget_s:.0f}s -> {feed_budget_s:.0f}s left "
            f"for feed at {bw:.0f} MB/s)"
        )
    rows = max(2 << 20, min(target_rows, 32 << 20))
    rows = (rows >> 20) << 20  # whole 1M-row batches
    log(f"[device-profile] building {rows:,}-row lineitem table (16 cols)")
    table = build_lineitem_data(rows)
    data = Dataset.from_arrow(table)

    prior = os.environ.get("DEEQU_TPU_DEVICE_FEATURE_CACHE")
    os.environ["DEEQU_TPU_DEVICE_FEATURE_CACHE"] = "8"
    try:
        stage_mon = RunMonitor()
        t0 = time.perf_counter()
        runner = (
            ColumnProfilerRunner.on_data(data)
            .with_placement("device")
            .with_batch_size(1 << 20)
            .with_monitor(stage_mon)
        )
        profiles = runner.run()  # stages features into HBM + compiles
        stage_s = time.perf_counter() - t0

        mon = RunMonitor()
        t0 = time.perf_counter()
        profiles = (
            ColumnProfilerRunner.on_data(data)
            .with_placement("device")
            .with_batch_size(1 << 20)
            .with_monitor(mon)
            .run()
        )
        elapsed = time.perf_counter() - t0
    finally:
        clear_device_feature_cache()
        if prior is None:
            os.environ.pop("DEEQU_TPU_DEVICE_FEATURE_CACHE", None)
        else:
            os.environ["DEEQU_TPU_DEVICE_FEATURE_CACHE"] = prior

    # parity: real metrics from the device run vs full-data numpy oracles
    for name in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        arr = table[name].to_numpy()
        p = profiles.profiles[name]
        for got, want in (
            (p.mean, arr.mean()), (p.minimum, arr.min()), (p.maximum, arr.max()),
            (p.std_dev, arr.std()), (p.sum, arr.sum()),
        ):
            if abs(got - want) > 1e-6 * max(1.0, abs(want)):
                log(f"PARITY MISMATCH {name}: got={got} want={want}")
                sys.exit(1)
    flags = profiles.profiles["l_returnflag"].histogram
    import pyarrow.compute as pc

    vc = pc.value_counts(table["l_returnflag"])
    want_counts = {
        str(v["values"]): int(v["counts"]) for v in vc.to_pylist()
    }
    got_counts = {k: v.absolute for k, v in flags.values.items()}
    if got_counts != want_counts:
        log(f"PARITY MISMATCH l_returnflag histogram: {got_counts} != {want_counts}")
        sys.exit(1)

    rate = rows / elapsed
    # the NORTH-STAR ratio must exist the moment config-3 completes (a
    # later-stage timeout then can never erase it from the partial JSON):
    # a small-capped oracle here (cache-flattered, so the ratio is
    # conservative); the full profile stage re-measures at its larger cap
    # and overwrites with the canonical number when it completes
    oracle_rows = min(rows, 2 << 20)
    vs_single = rate / lineitem_single_core_rate(table, oracle_rows)
    phases = ", ".join(f"{k}={v:.2f}s" for k, v in sorted(mon.phase_seconds.items()))
    fetch_s = mon.phase_seconds.get("state_fetch", 0.0)
    dispatch_s = mon.phase_seconds.get("device_dispatch", 0.0)
    log(
        f"[device-profile] {rows:,} rows x 16 cols, placement=device, warm "
        f"feature cache: {elapsed:.2f}s -> {rate/1e6:.1f}M rows/s/chip "
        f"({vs_single:.1f}x single-core on a {oracle_rows:,}-row oracle; "
        f"passes={mon.passes}; staging+compile run took {stage_s:.1f}s, "
        f"{stage_mon.program_compiles} staging compiles; metrics "
        f"parity-checked vs numpy/arrow oracles)"
    )
    log(f"[device-profile] phases: {phases}")
    log(
        f"[device-profile] warm state_fetch={fetch_s:.2f}s vs "
        f"device_dispatch={dispatch_s:.2f}s -> "
        f"{'fetch-bound' if fetch_s > dispatch_s else 'dispatch-bound'}"
    )
    return {
        "rows_per_sec": rate,
        "rows": rows,
        "vs_single_core": vs_single,
        "stage_seconds": stage_s,
        "compile_probe_seconds": compile_probe_s,
        "staging_compiles": stage_mon.program_compiles,
        **monitor_phase_fields(mon),
    }


def run_device_merge_stage(
    n_states: int = 64, n_hll_states: int = 2048, target_seconds: float = 3.0
) -> dict:
    """On-device sketch-merge throughput: lax.scan fold of the analyzers'
    semigroup merges over stacked DEVICE-RESIDENT states (the program
    merge_states_batched compiles), timed without any host fetch."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops.hll import M as HLL_M
    from deequ_tpu.ops.kll import kll_init, kll_merge, kll_update

    rng = np.random.default_rng(3)

    # realistic populated states: KLL sketches built from 64k values each
    base = kll_init()
    ones = jnp.ones(1 << 16, dtype=bool)
    build = jax.jit(lambda s, v: kll_update(s, v, ones))
    kll_states = []
    for i in range(n_states):
        vals = jnp.asarray(rng.normal(size=1 << 16))
        kll_states.append(build(base, vals))
    kll_stacked = jax.device_put(
        jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *kll_states)
    )
    hll_stacked = jax.device_put(
        jnp.asarray(rng.integers(0, 40, (n_hll_states, HLL_M)), dtype=jnp.int32)
    )
    jax.block_until_ready((kll_stacked, hll_stacked))

    # the product's batched-merge path (sequential scan fold: measured 4x
    # FASTER than a vmapped log-depth tree for KLL on a v5e chip, whose
    # compaction dynamic_update_slices lower to gathers under vmap)
    @jax.jit
    def fold_kll(stacked):
        first = jax.tree_util.tree_map(lambda x: x[0], stacked)
        rest = jax.tree_util.tree_map(lambda x: x[1:], stacked)
        return jax.lax.scan(lambda acc, s: (kll_merge(acc, s), None), first, rest)[0]

    @jax.jit
    def fold_hll(regs):
        return jax.lax.scan(
            lambda acc, r: (jnp.maximum(acc, r), None), regs[0], regs[1:]
        )[0]

    kll_bytes = sum(
        np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(kll_stacked)
    )
    hll_bytes = hll_stacked.nbytes

    results = {}
    for name, fold, stacked, nbytes in (
        ("kll", fold_kll, kll_stacked, kll_bytes),
        ("hll", fold_hll, hll_stacked, hll_bytes),
    ):
        # fetch-forced sync (see run_device_resident_stage): each timed
        # region ends with a full host fetch of the folded state
        def fetch(out):
            return jax.tree_util.tree_map(np.asarray, out)

        def timed_chain(iters):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fold(stacked)
            fetch(out)
            return time.perf_counter() - t0

        timed_chain(1)  # compile + one forced run
        # rough per-fold estimate from one (2, 8) pair, then size the
        # measurement delta so the compute difference dwarfs RTT jitter
        # (the single-run `once` carries the fetch round trip — calibrating
        # from it would let that dominate). Floors: a jitter-negative delta
        # falls back to the RTT-inclusive t8/8 (never near-zero), and k2 is
        # capped so a bad estimate cannot turn the stage into a 30k-fold
        # marathon.
        t8 = timed_chain(8)
        rough = (t8 - timed_chain(2)) / 6
        if rough <= 0:
            rough = t8 / 8
        k1 = 2
        k2 = k1 + min(max(32, int(target_seconds / rough)), 512)
        # median slope over three (k1, k2) pairs cancels the fetch RTT
        chain_times = [(timed_chain(k2), timed_chain(k1)) for _ in range(3)]
        slopes = sorted((tb - ta) / (k2 - k1) for tb, ta in chain_times)
        per_fold = slopes[1]
        note = ""
        if per_fold <= 0:  # jitter beat the delta even at this size
            per_fold = chain_times[-1][0] / k2  # reuse the measured k2 chain
            note = " (RTT-polluted upper bound: slope fell below jitter)"
        gbps = nbytes / per_fold / 1e9
        results[name] = gbps
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        log(
            f"[device-merge] {name}: {n} states ({nbytes/1e6:.1f}MB) "
            f"folded on device in {per_fold*1e3:.1f}ms -> {gbps:.2f} GB/s{note}"
        )
    return results


# ---------------------------------------------------------------------------
# stage 2c: ingestion plane (ROADMAP item 4 / PR 9 acceptance) — sustained
# in-process Arrow IPC throughput, the double-buffered transfer overlap on
# the device tier, and a bounded-admission concurrency soak point
# ---------------------------------------------------------------------------


def build_overlap_data(rows: int):
    """Mixed workload whose STAGED host cost (feature build + transfer) is
    a real fraction of the pass: numeric columns feed the device battery,
    plain high-cardinality string columns pay genuine per-batch host
    feature work (native hash/length kernels) on the feed thread — the
    shape where double buffering has something to hide on every platform
    (on a TPU the host->device copy itself dominates the staged cost; on
    CPU XLA the copy is a memcpy and the feature kernels are what
    overlap)."""
    import pyarrow as pa

    rng = np.random.default_rng(5)
    base = np.array([
        f"user-{i:08x}-{i * 2654435761 % 100000007:09d}"
        for i in range(1 << 16)
    ])

    def strings():
        return pa.array(np.char.add(
            base[rng.integers(0, len(base), rows)],
            np.char.mod("%07d", rng.integers(0, 10**7, rows)),
        ))

    return pa.table({
        "x0": pa.array(rng.normal(size=rows)),
        "x1": pa.array(rng.normal(size=rows)),
        "s0": strings(),
        "s1": strings(),
    })


def run_ingest_overlap(rows: int, batch_size: int = 1 << 20) -> dict:
    """Serial (DEEQU_TPU_PREFETCH_DEPTH=0) vs double-buffered (depth 2)
    device-tier fold over the same data: the wall-clock saving divided by
    the serial run's staged host cost (feature build + host->device
    transfer) is the fraction of transfer time the pipeline HIDES under
    device compute. Median of three runs per depth (the saving is a
    difference of walls, so single samples are jitter-bound); metrics
    must match bit-exact across depths."""
    import os

    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        KLLSketch,
        MaxLength,
        Mean,
    )
    from deequ_tpu.data import Dataset
    from deequ_tpu.runners import AnalysisRunner
    from deequ_tpu.runners.engine import RunMonitor

    data = Dataset.from_arrow(build_overlap_data(rows))
    analyzers = [Mean("x0"), Mean("x1"), KLLSketch("x0")]
    for s in ("s0", "s1"):
        analyzers += [Completeness(s), MaxLength(s), ApproxCountDistinct(s)]

    def run(depth: int):
        prior = os.environ.get("DEEQU_TPU_PREFETCH_DEPTH")
        os.environ["DEEQU_TPU_PREFETCH_DEPTH"] = str(depth)
        try:
            mon = RunMonitor()
            t0 = time.perf_counter()
            ctx = AnalysisRunner.do_analysis_run(
                data, analyzers, batch_size=batch_size, monitor=mon,
                placement="device",
            )
            wall = time.perf_counter() - t0
        finally:
            if prior is None:
                os.environ.pop("DEEQU_TPU_PREFETCH_DEPTH", None)
            else:
                os.environ["DEEQU_TPU_PREFETCH_DEPTH"] = prior
        metrics = {
            repr(a): m.value.get()
            for a, m in ctx.metric_map.items() if m.value.is_success
        }
        staged_s = (
            mon.phase_seconds.get("feature_build", 0.0)
            + mon.phase_seconds.get("device_feed", 0.0)
        )
        return wall, staged_s, metrics

    run(2)  # warm: compile + page the table in
    points = [(run(0), run(2)) for _ in range(3)]
    m0, m2 = points[0][0][2], points[0][1][2]
    for (w0, s0, a), (w2, _s2, b) in points:
        if a != m0 or b != m2:
            log("PARITY MISMATCH ingest overlap: repeat runs disagree")
            sys.exit(1)
    if m0 != m2:
        log(f"PARITY MISMATCH ingest overlap: {m0} != {m2}")
        sys.exit(1)
    wall0 = sorted(p[0][0] for p in points)[1]
    staged0 = sorted(p[0][1] for p in points)[1]
    wall2 = sorted(p[1][0] for p in points)[1]
    hidden = (wall0 - wall2) / staged0 if staged0 > 0 else 0.0
    log(
        f"[ingest] double-buffer overlap on {rows:,} rows (median of 3): "
        f"serial {wall0:.2f}s (staged host cost {staged0:.2f}s) vs "
        f"pipelined {wall2:.2f}s -> {hidden:.0%} of transfer hidden, "
        f"metrics bit-exact"
    )
    return {
        "serial_s": round(wall0, 3), "pipelined_s": round(wall2, 3),
        "staged_s": round(staged0, 3), "hidden_fraction": round(hidden, 3),
    }


def run_ingest_stage(rows: int) -> dict:
    """Three acceptance points: (1) sustained in-process Arrow IPC stream
    throughput (decode + checksum-free fold through the real session
    path, target >= 500 MB/s); (2) the double-buffered host->device overlap (>= 50% of staged transfer
    hidden); (3) a >=1000-concurrent-session bounded-admission soak point
    (sessions/s + MB/s sustained through the scheduler)."""
    from tools.ingest_soak import run_concurrency_soak, run_stream_throughput

    stream_rows = max(min(rows, 32_000_000), 1 << 20)
    # enough volume that per-stream session overhead amortizes: MB/s here
    # means SUSTAINED, not first-stream
    stream_mb = max(stream_rows * 32 / 1e6, 768)  # 4 f64-ish wire cols
    tput = run_stream_throughput(target_mb=stream_mb, workers=4)
    if not tput["parity_ok"]:
        log("PARITY MISMATCH ingest stream throughput")
        sys.exit(1)
    log(
        f"[ingest] in-process Arrow stream: {tput['ingested_mb']:.0f}MB in "
        f"{tput['wall_s']:.2f}s -> {tput['mb_per_s']:.0f} MB/s "
        f"({tput['rows_per_s']/1e6:.1f}M rows/s) at 1M-row frames, "
        f"metrics parity ok"
    )
    big = run_stream_throughput(
        target_mb=stream_mb, workers=4, rows_per_batch=4 << 20
    )
    if not big["parity_ok"]:
        log("PARITY MISMATCH ingest stream throughput (4M-row frames)")
        sys.exit(1)
    log(
        f"[ingest] 4M-row frames: {big['mb_per_s']:.0f} MB/s "
        f"({big['rows_per_s']/1e6:.1f}M rows/s)"
    )

    overlap = run_ingest_overlap(max(min(rows, 8_000_000), 1 << 20))

    soak = run_concurrency_soak(
        sessions=1000, batches=2, rows=4096, workers=8, queue_depth=256,
    )
    log(
        f"[ingest] soak: {soak['sessions']} sessions x "
        f"{soak['batches_per_session']} batches under bounded admission "
        f"(queue {soak['queue_depth']}): {soak['wall_s']:.1f}s -> "
        f"{soak['sessions_per_s']:.0f} sessions/s, {soak['mb_per_s']:.0f} "
        f"MB/s, shed={soak['shed']}, failed={soak['failed_folds']}"
    )
    if "fold_latency_p99_s" in soak:
        log(
            f"[ingest] soak tail latency: fold "
            f"p50={soak.get('fold_latency_p50_s', 0) * 1e3:.1f}ms "
            f"p99={soak['fold_latency_p99_s'] * 1e3:.1f}ms, admission "
            f"wait p99={soak.get('admission_wait_p99_s', 0) * 1e3:.1f}ms "
            "(from the per-tenant SLO histograms)"
        )
    if not soak["ok"]:
        log("[ingest] soak FAILED (incomplete sessions or failed folds)")
        sys.exit(1)
    return {
        "mb_per_s": tput["mb_per_s"],
        "mb_per_s_4m_frames": big["mb_per_s"],
        "stream_rows_per_s": tput["rows_per_s"],
        "overlap_hidden_fraction": overlap["hidden_fraction"],
        "overlap_serial_s": overlap["serial_s"],
        "overlap_pipelined_s": overlap["pipelined_s"],
        "soak_sessions": soak["sessions"],
        "soak_sessions_per_s": soak["sessions_per_s"],
        "soak_mb_per_s": soak["mb_per_s"],
        "soak_shed": soak["shed"],
        # absent on runs whose histograms never filled (bench_diff
        # tolerates missing scalars in OLDER runs by design)
        **{
            k: soak[k]
            for k in (
                "fold_latency_p50_s", "fold_latency_p99_s",
                "admission_wait_p50_s", "admission_wait_p99_s",
            )
            if k in soak
        },
    }


# ---------------------------------------------------------------------------
# stage 2d: streaming knee (ISSUE 10 acceptance) — sessions/s with and
# without cross-session fold coalescing on the PR 9 soak workload
# ---------------------------------------------------------------------------


def run_streaming_knee_stage() -> dict:
    """Sessions/s at {100, 1000} sessions x {4096, 65536}-row micro-batches,
    coalescing ON vs OFF, plus the bit-exact parity gate between the two
    modes (tools/streaming_knee.py). Runs in a DETACHED subprocess so each
    grid point's service/scheduler state starts cold and an interpreter
    carrying this bench's device programs cannot flatter the numbers."""
    import json as _json
    import os
    import subprocess

    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.streaming_knee", "--stage-json"],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=subprocess_timeout_s(),
    )
    if proc.returncode != 0 and not proc.stdout.strip():
        raise RuntimeError(
            f"streaming_knee subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    result = _json.loads(proc.stdout.strip().splitlines()[-1])
    result["stage_seconds"] = time.perf_counter() - t0
    if not result["parity"]["bit_exact"]:
        log("PARITY MISMATCH streaming knee: coalesced != serial metrics")
        sys.exit(1)
    for p in result["points"]:
        log(
            f"[streaming_knee] {p['sessions']} sessions x {p['rows']} rows: "
            f"serial {p['serial_sessions_per_s']:.0f}/s -> coalesced "
            f"{p['coalesced_sessions_per_s']:.0f}/s ({p['speedup']:.1f}x, "
            f"shed={p['shed']})"
        )
    log(
        f"[streaming_knee] headline (1000x4096): "
        f"{result['headline_sessions_per_s']:.0f} sessions/s "
        f"({result['headline_speedup']:.1f}x serial), parity bit-exact"
    )
    return result


# ---------------------------------------------------------------------------
# stage 2d': self-tuning calibration (ISSUE 18 acceptance) — the boot-time
# calibrator measured end to end, then the SAME streaming+grouping point
# static vs tuned; bench_diff gates tuned >= static within the band
# ---------------------------------------------------------------------------


def run_calibration_stage() -> dict:
    """Run ``deequ_tpu.tuning.calibrate`` fresh in a DETACHED subprocess
    against a throwaway profile dir (probe values + derived knobs + wall
    time land in the partial JSON), then measure one streaming+grouping
    throughput point twice in two more detached service processes:
    STATIC (``DEEQU_TPU_AUTOTUNE=0``) and TUNED (the freshly calibrated
    profile loaded at service boot). Each point starts from a cold
    interpreter so neither arm inherits the other's compiled programs or
    router EWMAs. bench_diff gates tuned >= static within the band."""
    import json as _json
    import os
    import subprocess
    import tempfile

    t0 = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    profile_dir = tempfile.mkdtemp(prefix="bench-tuning-profile-")
    base_env = dict(os.environ)
    base_env["DEEQU_TPU_TUNING_PROFILE_DIR"] = profile_dir

    def detached(module_args: list, extra_env: dict, label: str) -> dict:
        env = dict(base_env)
        env.update(extra_env)
        proc = subprocess.run(
            [sys.executable, "-m"] + module_args,
            cwd=here, capture_output=True, text=True,
            timeout=subprocess_timeout_s(), env=env,
        )
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"calibration {label} subprocess rc={proc.returncode}: "
                f"{proc.stderr[-500:]}"
            )
        return _json.loads(proc.stdout.strip().splitlines()[-1])

    cal = detached(["deequ_tpu.tuning.calibrate", "--json"], {}, "probe")
    log(
        f"[calibration] {len(cal['probes'])} probes in "
        f"{cal['wall_s']:.2f}s on substrate {cal['fingerprint']}: "
        f"device_fixed {cal['probes']['device_fixed_s'] * 1e3:.2f}ms, "
        f"device {cal['probes']['device_rows_per_s'] / 1e6:.0f}M rows/s, "
        f"group host/device "
        f"{cal['probes']['group_host_rows_per_s'] / 1e6:.1f}M/"
        f"{cal['probes']['group_device_rows_per_s'] / 1e6:.1f}M rows/s"
    )
    static = detached(["tools.tuning_report", "--bench-point"],
                      {"DEEQU_TPU_AUTOTUNE": "0"}, "static-point")
    tuned = detached(["tools.tuning_report", "--bench-point"], {},
                     "tuned-point")
    log(
        f"[calibration] streaming {static['sessions_per_s']:.0f} static -> "
        f"{tuned['sessions_per_s']:.0f} tuned sessions/s "
        f"({tuned['sessions_per_s'] / static['sessions_per_s']:.2f}x); "
        f"grouping {static['grouping_rows_per_s'] / 1e6:.1f}M static -> "
        f"{tuned['grouping_rows_per_s'] / 1e6:.1f}M tuned rows/s "
        f"({tuned['grouping_rows_per_s'] / static['grouping_rows_per_s']:.2f}x); "
        f"tuned knobs: {', '.join(tuned['tuned_knobs']) or 'none'}"
    )
    return {
        "wall_s": cal["wall_s"],
        "fingerprint": cal["fingerprint"],
        "probes": cal["probes"],
        "knobs": cal["knobs"],
        "static": static,
        "tuned": tuned,
        "stage_seconds": time.perf_counter() - t0,
    }


# ---------------------------------------------------------------------------
# stage 2e: anomaly fleet (ISSUE 15 acceptance) — the fleet watch's
# per-harvest scoring core: 10k tenants' metric histories, serial vs ONE
# batched detect_batch call, parity-gated
# ---------------------------------------------------------------------------


def run_anomaly_fleet_stage(n_series: int = 10_000) -> dict:
    """Series/s for the fleet-watch scoring pass (tools/
    anomaly_fleet_bench.py): N ragged series with newest-point intervals,
    scored serially (one detect per series) and batched (ONE detect_batch
    over the fleet tensor), flag indices and messages element-identical.
    Runs DETACHED so the child's numpy working set starts cold."""
    import json as _json
    import os
    import subprocess

    t0 = time.perf_counter()
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.anomaly_fleet_bench",
            "--series", str(n_series),
        ],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=subprocess_timeout_s(),
    )
    if not proc.stdout.strip():
        raise RuntimeError(
            f"anomaly_fleet subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    result = _json.loads(proc.stdout.strip().splitlines()[-1])
    result["stage_seconds"] = time.perf_counter() - t0
    if not result["parity"]:
        log("PARITY MISMATCH anomaly fleet: batched != serial scoring")
        sys.exit(1)
    log(
        f"[anomaly_fleet] {result['series']:,} series "
        f"({result['points_total']:,} points): batched "
        f"{result['series_per_s']:,.0f} series/s in "
        f"{result['detect_calls']} call vs serial "
        f"{result['serial_series_per_s']:,.0f}/s "
        f"({result['speedup']:.1f}x), {result['flagged']} flagged, "
        f"parity element-exact"
    )
    return result


# ---------------------------------------------------------------------------
# stage 2f: multi-host cluster soak (ISSUE 16 acceptance) — aggregate
# sessions/s across 1 and 2 real worker PROCESSES routed by the front
# tier, parity-gated against the closed-form exact-sum oracle
# ---------------------------------------------------------------------------


def run_cluster_soak_stage(
    procs=(1, 2), sessions: int = 8, batches: int = 8, rows: int = 4096,
) -> dict:
    """Cluster tier scale-out (tools/cluster_soak.py): each point spawns N
    worker processes — whole service planes with their own scheduler and
    HTTP ingest endpoint — behind the consistent-hash front tier on one
    shared partition store, and measures aggregate sessions/s. Every point
    carries the bit-exact parity gate (integer-valued sums are fold-order
    independent, so the routed cluster must equal the closed-form oracle
    EXACTLY). Runs DETACHED per point so each cluster starts cold and a
    point's worker processes can never leak into the next. On one box the
    processes share cores, so the 2-proc point understates real two-host
    scaling — the SHAPE (and the ≥1.6x gate tools/bench_diff tracks via
    cluster_soak_sessions_per_s) is what transfers."""
    import json as _json
    import os
    import subprocess

    t0 = time.perf_counter()
    points = {}
    for n in procs:
        proc = subprocess.run(
            [
                sys.executable, "-m", "tools.cluster_soak", "--stage-json",
                "--procs", str(n), "--sessions", str(sessions),
                "--batches", str(batches), "--rows", str(rows),
            ],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=subprocess_timeout_s(),
        )
        if not proc.stdout.strip():
            raise RuntimeError(
                f"cluster_soak subprocess rc={proc.returncode}: "
                f"{proc.stderr[-500:]}"
            )
        point = _json.loads(proc.stdout.strip().splitlines()[-1])
        if point.get("skipped"):
            # the environment cannot spawn the worker processes (no free
            # ports, sandboxed sockets): the stage reports itself skipped
            # instead of failing the bench
            log(f"[cluster_soak] skipped: {point.get('reason')}")
            return {"skipped": True, "reason": point.get("reason")}
        if point["parity_failures"]:
            log(
                f"PARITY MISMATCH cluster soak at {n} procs: "
                f"{point['parity_failures'][:3]}"
            )
            sys.exit(1)
        points[str(n)] = point
        log(
            f"[cluster_soak] {n} proc: "
            f"{point['sessions_per_s']:.1f} sessions/s "
            f"({point['folds_per_s']:.0f} folds/s), parity bit-exact"
        )
    head = points[str(procs[-1])]
    base = points[str(procs[0])]
    scaling = head["sessions_per_s"] / base["sessions_per_s"]
    log(
        f"[cluster_soak] headline ({procs[-1]} procs): "
        f"{head['sessions_per_s']:.1f} sessions/s, "
        f"{scaling:.2f}x vs {procs[0]} proc"
    )
    return {
        "points": {
            k: {
                "sessions_per_s": p["sessions_per_s"],
                "folds_per_s": p["folds_per_s"],
                "elapsed_s": p["elapsed_s"],
            } for k, p in points.items()
        },
        "sessions_per_s": head["sessions_per_s"],
        "scaling_vs_1p": round(scaling, 3),
        "routes_total": head["counters"][
            "deequ_service_cluster_routes_total"
        ],
        "stage_seconds": time.perf_counter() - t0,
    }


def run_catalog_soak_stage(
    registered: int = 400, active: int = 24,
    gate_batches: int = 24, gate_rows: int = 65_536,
) -> dict:
    """Tenant isolation plane (tools/catalog_soak.py): registered >>
    active catalog tiering with the mid-soak edit and corrupt-edit
    drills, plus the gated-vs-ungated throughput fraction (acceptance
    floor 0.8; tools/bench_diff tracks it as a throughput scalar so the
    row gate's steady-state cost cannot silently grow). Runs DETACHED so
    the soak's service plane starts cold."""
    import json as _json
    import os
    import subprocess

    t0 = time.perf_counter()
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.catalog_soak", "--stage-json",
            "--registered", str(registered), "--active", str(active),
            "--gate-batches", str(gate_batches),
            "--gate-rows", str(gate_rows),
        ],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=subprocess_timeout_s(),
    )
    if not proc.stdout.strip():
        raise RuntimeError(
            f"catalog_soak subprocess rc={proc.returncode}: "
            f"{proc.stderr[-500:]}"
        )
    summary = _json.loads(proc.stdout.strip().splitlines()[-1])
    if not summary["ok"]:
        log(
            "catalog soak VERDICT FAILED: "
            f"soak={summary['soak'].get('ok')} "
            f"gate={summary['gate'].get('ok')} "
            f"fraction={summary['gated_throughput_fraction']}"
        )
        sys.exit(1)
    log(
        f"[catalog_soak] {registered} registered / {active} active: "
        f"{summary['soak']['sessions_per_s']:.1f} sessions/s hot, "
        f"edit + corrupt drills ok; gate fraction "
        f"{summary['gated_throughput_fraction']:.2f} "
        f"({summary['gate']['gated_mb_per_s']:.0f} vs "
        f"{summary['gate']['ungated_mb_per_s']:.0f} MB/s), bit-exact"
    )
    return {
        "registered": registered,
        "active": active,
        "sessions_per_s": summary["soak"]["sessions_per_s"],
        "registers_per_s": summary["soak"]["registers_per_s"],
        "edit_drill": summary["soak"]["edit_drill"]["ok"],
        "corrupt_drill": summary["soak"]["corrupt_drill"]["ok"],
        "gated_throughput_fraction": summary["gated_throughput_fraction"],
        "gated_mb_per_s": summary["gate"]["gated_mb_per_s"],
        "ungated_mb_per_s": summary["gate"]["ungated_mb_per_s"],
        "stage_seconds": time.perf_counter() - t0,
    }


# ---------------------------------------------------------------------------
# stage 3: incremental/stateful partitions + sketch-state merge (BASELINE
# config 4: partition states persisted, table metrics refreshed from merged
# states WITHOUT rescanning data, anomaly check on the history)
# ---------------------------------------------------------------------------


def run_incremental_stage(rows_per_partition: int, n_partitions: int = 2) -> dict:
    """BASELINE config 4: day partitions persist states; table metrics
    refresh from merged states with no rescan; an anomaly check on
    Size/Mean runs over the metric history (the part the round-3 bench
    omitted)."""
    import jax

    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        KLLSketch,
        Mean,
        Size,
    )
    from deequ_tpu.analyzers.state_provider import InMemoryStateProvider
    from deequ_tpu.anomalydetection import RelativeRateOfChangeStrategy
    from deequ_tpu.checks import CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.repository import ResultKey
    from deequ_tpu.repository.memory import InMemoryMetricsRepository
    from deequ_tpu.runners import AnalysisRunner
    from deequ_tpu.verification import VerificationSuite

    analyzers = [Size(), Completeness("x0"), Mean("x0"), Mean("x1"),
                 ApproxCountDistinct("cat"), KLLSketch("x0")]
    log(f"[incremental] {n_partitions} day partitions x {rows_per_partition:,} rows")
    providers = []
    repo = InMemoryMetricsRepository()
    table = build_scan_data(rows_per_partition * n_partitions)
    for p in range(n_partitions):
        part = Dataset.from_arrow(
            table.slice(p * rows_per_partition, rows_per_partition)
        )
        sp = InMemoryStateProvider()
        AnalysisRunner.do_analysis_run(
            part, analyzers, save_states_with=sp,
            metrics_repository=repo,
            save_or_append_results_with_key=ResultKey(p, {"day": str(p)}),
        )
        providers.append(sp)
    schema = Dataset.from_arrow(table.slice(0, 1)).schema

    # warm the merge programs, then time the state-only refresh
    AnalysisRunner.run_on_aggregated_states(schema, analyzers, providers)
    state_bytes = 0
    for sp in providers:
        for a in analyzers:
            state = sp.load(a)
            leaves = jax.tree_util.tree_leaves(state)
            state_bytes += sum(np.asarray(x).nbytes for x in leaves)
    t0 = time.perf_counter()
    ctx = AnalysisRunner.run_on_aggregated_states(schema, analyzers, providers)
    merge_s = time.perf_counter() - t0
    total_rows = rows_per_partition * n_partitions
    assert ctx.metric(Size()).value.get() == float(total_rows)

    # anomaly check over the day-partition metric history: a steady day-N+1
    # passes, a half-size day fails (config 4's "anomaly detection on
    # Size/Mean")
    def day(rows: int, key: int):
        part = Dataset.from_arrow(table.slice(0, rows))
        return (
            VerificationSuite.on_data(part)
            .use_repository(repo)
            .save_or_append_result(ResultKey(key, {"day": str(key)}))
            .add_anomaly_check(
                RelativeRateOfChangeStrategy(max_rate_increase=1.5,
                                             max_rate_decrease=0.5),
                Size(),
            )
            .add_anomaly_check(
                RelativeRateOfChangeStrategy(max_rate_increase=1.1,
                                             max_rate_decrease=0.9),
                Mean("x1"),  # mean ~100; x0's mean ~0 makes ratios unstable
            )
            .run()
        )
    from deequ_tpu.checks import CheckStatus

    steady = day(rows_per_partition, n_partitions)
    anomalous = day(max(rows_per_partition // 4, 1), n_partitions + 1)
    assert steady.status == CheckStatus.SUCCESS, steady.status
    assert anomalous.status != CheckStatus.SUCCESS, anomalous.status
    log(
        f"[incremental] table metrics refreshed from {n_partitions} partition "
        f"states in {merge_s*1e3:.0f}ms — no data rescan "
        f"({state_bytes/1e6:.1f}MB of sketch states, "
        f"{state_bytes/merge_s/1e9:.2f}GB/s merge); anomaly check on "
        f"Size/Mean: steady day passes, quarter-size day flagged"
    )
    result = {"merge_seconds": merge_s, "state_bytes": state_bytes}
    result.update(run_partition_growth_point(table))
    return result


def run_partition_growth_point(table) -> dict:
    """ISSUE 13 acceptance point: a partitioned table grows by ~1% and the
    incremental verify must touch <= 2% of the rows and cost <= 10% of the
    measured full-scan wall time, with suite metrics BIT-EXACT against the
    full re-scan (partition-aligned batches, so merges associate
    identically). The stored baseline is populated through the
    PartitionStateStore's own scan path; the +1% point is measured twice —
    cold (first merge of the grown shape compiles) and steady-state (the
    daily-growth repeat, after invalidating the growth partition) — and
    the steady-state number is the gated one."""
    import tempfile

    from deequ_tpu.checks import Check, CheckLevel
    from deequ_tpu.data import Dataset
    from deequ_tpu.repository.partition_store import PartitionStateStore
    from deequ_tpu.runners.engine import RunMonitor
    from deequ_tpu.verification import VerificationSuite

    # cap the point's scale: its METRICS are ratios (cost fraction, reuse
    # ratio), and populate pays one engine pass per baseline partition —
    # at the full 50M-row stage shape that alone would eat the per-stage
    # SIGALRM budget the existing halves of this stage already share
    total_rows = min(int(table.num_rows), 10_000_000)
    table = table.slice(0, total_rows)
    # ~1% growth granularity needs ~100 baseline partitions; floor the
    # partition size so smoke-scale runs still exercise the full protocol
    # (their ratios are recorded but only meaningful at real scale)
    n_base = min(100, max(4, total_rows // 50_000))
    part_rows = total_rows // n_base
    checks = [
        Check(CheckLevel.ERROR, "incremental growth")
        .has_size(lambda n: n > 0)
        .is_complete("x0")
        .has_mean("x0", lambda m: -50 < m < 50)
        .has_sum("x1", lambda s: s != 0)
        .has_approx_count_distinct("cat", lambda c: c > 0)
    ]
    analyzers = scan_battery()

    def part_name(i: int) -> str:
        return f"2026-{1 + i // 28:02d}-{1 + i % 28:02d}"

    def partition(i: int) -> Dataset:
        return Dataset.from_arrow(table.slice(i * part_rows, part_rows))

    base = {part_name(i): (lambda i=i: partition(i)) for i in range(n_base)}
    versions = {part_name(i): f"v-{i}" for i in range(n_base)}
    store_dir = tempfile.mkdtemp(prefix="deequ-bench-partition-store-")
    store = PartitionStateStore(store_dir)
    log(
        f"[incremental] partition growth point: {n_base} x {part_rows:,}"
        f"-row partitions + 1 growth partition"
    )
    t0 = time.perf_counter()
    VerificationSuite.verify_partitioned(
        store, "bench", base, checks, analyzers,
        checksums=versions, batch_size=part_rows,
    )
    populate_s = time.perf_counter() - t0

    # two growth days of FRESH ~1% partitions: day 1 is the COLD point
    # (the rollup+suffix merge shape compiles once), day 2 is the
    # steady-state daily cost — scan one partition, fold it onto the
    # rollup cache, rewrite the rollup — which is what the 10%-of-full
    # acceptance bar gates
    import pyarrow as pa

    def growth_part(day: int):
        rng = np.random.default_rng(7 + day)
        return pa.table({
            **{f"x{i}": pa.array(rng.normal(100 * i, 10, part_rows),
                                 mask=rng.random(part_rows) < 0.05)
               for i in range(4)},
            "cat": pa.array(rng.integers(0, 100_000, part_rows)),
        })

    g1, g2 = growth_part(1), growth_part(2)
    grown = dict(base)
    gname1, gname2 = part_name(n_base), part_name(n_base + 1)
    grown[gname1] = lambda: Dataset.from_arrow(g1)
    gversions = dict(versions)
    gversions[gname1] = "v-growth-1"

    # full-scan baseline over the final grown table, partition-aligned
    full_data = Dataset.from_arrow(pa.concat_tables([table, g1, g2]))
    t0 = time.perf_counter()
    full = VerificationSuite.do_verification_run(
        full_data, checks, analyzers, batch_size=part_rows,
    )
    full_s = time.perf_counter() - t0

    mon = RunMonitor()
    t0 = time.perf_counter()
    inc = VerificationSuite.verify_partitioned(
        store, "bench", grown, checks, analyzers,
        checksums=gversions, batch_size=part_rows, monitor=mon,
    )
    delta_cold_s = time.perf_counter() - t0
    assert inc.incremental.plan.scan == [gname1], inc.incremental.plan.scan

    # steady state: day-2 growth (merge programs warm, rollup advances)
    grown[gname2] = lambda: Dataset.from_arrow(g2)
    gversions[gname2] = "v-growth-2"
    mon2 = RunMonitor()
    t0 = time.perf_counter()
    inc2 = VerificationSuite.verify_partitioned(
        store, "bench", grown, checks, analyzers,
        checksums=gversions, batch_size=part_rows, monitor=mon2,
    )
    delta_s = time.perf_counter() - t0
    assert inc2.incremental.plan.scan == [gname2], inc2.incremental.plan.scan
    assert mon2.partitions_rolled_up == n_base + 1, mon2.partitions_rolled_up
    report = inc2.incremental

    # non-sketch metrics are BIT-EXACT (partition-aligned batches make the
    # merges associate identically); KLL sketches compact differently when
    # folded per-partition vs continuously, so they hold their documented
    # rank-error envelope instead: identical bucket boundaries (min/max
    # merge exactly) and CDFs within 2% rank error
    parity = all(
        inc2.metrics[a].value.get() == m.value.get()
        for a, m in full.metrics.items()
        if a.name not in ("KLLSketch",)
    )

    def kll_close(got, want) -> bool:
        gb, wb = got.buckets, want.buckets
        if len(gb) != len(wb):
            return False
        if gb and (gb[0].low_value != wb[0].low_value
                   or gb[-1].high_value != wb[-1].high_value):
            return False
        n_g = sum(b.count for b in gb)
        n_w = sum(b.count for b in wb)
        if n_g != n_w or n_g == 0:
            return False
        cg = cw = 0
        for g, w in zip(gb, wb):
            cg += g.count
            cw += w.count
            if abs(cg - cw) / n_g > 0.02:
                return False
        return True

    kll_parity = all(
        kll_close(inc2.metrics[a].value.get(), m.value.get())
        for a, m in full.metrics.items()
        if a.name == "KLLSketch"
    )
    out = {
        "partitions": n_base + 2,
        "partition_rows": part_rows,
        "populate_s": round(populate_s, 3),
        "full_scan_s": round(full_s, 3),
        "delta_cold_s": round(delta_cold_s, 3),
        "delta_s": round(delta_s, 3),
        "cost_fraction": round(delta_s / full_s, 4) if full_s else None,
        "speedup_vs_full": round(full_s / delta_s, 2) if delta_s else None,
        "reuse_ratio": round(report.reuse_ratio, 4),
        "rows_touched_fraction": round(report.rows_touched_fraction, 4),
        "rows_scanned": report.rows_scanned,
        "rows_total": report.rows_total,
        "parity_bit_exact": bool(parity and kll_parity),
    }
    log(
        f"[incremental] +1% growth: full scan {full_s:.2f}s vs incremental "
        f"{delta_s:.3f}s ({out['cost_fraction']:.1%} of full, cold "
        f"{delta_cold_s:.3f}s) — reuse ratio {out['reuse_ratio']:.2%}, "
        f"rows touched {out['rows_touched_fraction']:.2%}, parity "
        f"bit-exact={out['parity_bit_exact']}"
    )
    import shutil

    shutil.rmtree(store_dir, ignore_errors=True)
    return {"partition_growth": out}


# ---------------------------------------------------------------------------
# stage 3a2: device-resident frequency engine (ROADMAP item 3) — the
# BENCH_r04 [spill] workload shape through the device table path, with the
# host group-by measured in a sibling process for the before/after ratio
# ---------------------------------------------------------------------------


def run_grouping_stage(rows: int) -> dict:
    """25M rows / ~3.6M distinct (rows//7) grouping battery through the
    DEVICE frequency engine, versus the same workload through the host
    accumulator — each in a FRESH subprocess so peak RSS is the engine's
    own, not this process's high-water mark. Metrics must be BIT-exact
    across the two engines; the host point runs under the r04 [spill]
    stage's frequency-entry budget so the 'before' includes the disk-spill
    cost the device engine eliminates."""
    import subprocess

    from tools.grouping_sweep import subprocess_point

    distinct = max(rows // 7, 1000)
    budget = max(distinct // 8, 1000)  # the r04 spill-forcing budget

    def point(engine: str, extra_env: dict) -> dict:
        try:
            return subprocess_point(
                rows, distinct, engine, seed=1,
                timeout=subprocess_timeout_s(), extra_env=extra_env,
            )
        except subprocess.TimeoutExpired:
            # the stage's SIGALRM normally fires first (its budget is below
            # this cap); if the child itself times out, record the stage as
            # deadline-skipped rather than killing the stages after it
            raise StageDeadline("grouping") from None

    dev = point("device", {})
    host = point("host", {"DEEQU_TPU_MAX_FREQUENCY_ENTRIES": str(budget)})
    if dev["metrics"] != host["metrics"]:
        log(f"PARITY MISMATCH grouping engines: {dev['metrics']} != {host['metrics']}")
        sys.exit(1)
    ratio = dev["rows_per_sec"] / host["rows_per_sec"]
    # the r04 comparison only means something at the r04 workload shape
    # (25M rows / 3.6M distinct); a smoke-scale run must not write the
    # ROADMAP acceptance ratio from an incomparable workload
    r04_rate = 1.66e6 if rows == 25_000_000 else None
    r04_clause = (
        f"{dev['rows_per_sec']/r04_rate:.1f}x the r04 host-spill rate; "
        if r04_rate else ""
    )
    log(
        f"[grouping] {rows:,} rows / {dev['distinct']:.0f} distinct: device "
        f"table {dev['seconds']:.2f}s ({dev['rows_per_sec']/1e6:.1f}M rows/s, "
        f"peak RSS {dev['peak_rss_gb']:.2f}GB, overflow fallbacks="
        f"{dev['freq_overflow_fallbacks']}) vs host spill "
        f"{host['seconds']:.2f}s ({host['rows_per_sec']/1e6:.2f}M rows/s, "
        f"peak RSS {host['peak_rss_gb']:.2f}GB) -> {ratio:.1f}x live, "
        f"{r04_clause}metrics bit-exact"
    )
    out = {
        "rows_per_sec": dev["rows_per_sec"],
        "peak_rss_gb": dev["peak_rss_gb"],
        "distinct": dev["distinct"],
        "host_rows_per_sec": host["rows_per_sec"],
        "host_peak_rss_gb": host["peak_rss_gb"],
        "vs_host_spill": round(ratio, 2),
        "overflow_fallbacks": dev["freq_overflow_fallbacks"],
    }
    if r04_rate:
        out["vs_r04_spill"] = round(dev["rows_per_sec"] / r04_rate, 2)
    return out


# ---------------------------------------------------------------------------
# stage 3b: high-cardinality frequency spill (the Spark shuffle-spill
# analog): Uniqueness completes under a deliberately small budget —
# SINCE the device frequency engine landed this is the LAST-RESORT tier,
# measured here with the engine disabled
# ---------------------------------------------------------------------------


def run_spill_stage(rows: int) -> dict:
    import os
    import resource

    from deequ_tpu.analyzers import CountDistinct, Uniqueness
    from deequ_tpu.data import Dataset
    from deequ_tpu.runners import AnalysisRunner

    distinct = max(rows // 7, 1000)
    budget = max(distinct // 8, 1000)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, distinct, rows)
    data = Dataset.from_dict({"k": keys})
    prior_budget = os.environ.get("DEEQU_TPU_MAX_FREQUENCY_ENTRIES")
    os.environ["DEEQU_TPU_MAX_FREQUENCY_ENTRIES"] = str(budget)
    try:
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        t0 = time.perf_counter()
        ctx = AnalysisRunner.do_analysis_run(
            data, [Uniqueness(["k"]), CountDistinct(["k"])], placement="host"
        )
        elapsed = time.perf_counter() - t0
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    finally:
        if prior_budget is None:
            del os.environ["DEEQU_TPU_MAX_FREQUENCY_ENTRIES"]
        else:
            os.environ["DEEQU_TPU_MAX_FREQUENCY_ENTRIES"] = prior_budget
    rate = rows / elapsed
    got = ctx.metric(CountDistinct(["k"])).value.get()
    vc = np.bincount(keys, minlength=distinct)
    assert got == float((vc > 0).sum()), (got, (vc > 0).sum())
    log(
        f"[spill] Uniqueness over {rows:,} rows / {got:.0f} distinct under a "
        f"{budget:,}-entry budget: {elapsed:.1f}s ({rate/1e6:.2f}M rows/s), "
        f"peak RSS {rss1:.2f}GB (was {rss0:.2f}GB before)"
    )
    return {
        "rows_per_sec": rate, "distinct": got, "budget": budget,
        "peak_rss_gb": round(rss1, 3),
    }


# ---------------------------------------------------------------------------
# stage 4: constraint suggestion on the wide mixed table (BASELINE config 5
# shape: profile + rule application + held-out evaluation of the suggested
# constraints)
# ---------------------------------------------------------------------------


def run_suggestion_stage(rows: int) -> dict:
    from deequ_tpu.data import Dataset
    from deequ_tpu.suggestions import ConstraintSuggestionRunner, Rules

    # config 5 SHAPE: 50 mixed-type columns (30 numeric / 10 string / 10
    # categorical); row count scales with the CLI arg
    n_numeric, n_string, n_cat = 30, 10, 10
    n_cols = n_numeric + n_string + n_cat
    log(f"[suggest] {rows:,}-row x {n_cols}-col constraint suggestion run")
    table = build_wide_data(rows, n_numeric=n_numeric, n_string=n_string, n_cat=n_cat)
    data = Dataset.from_arrow(table)

    def run_once() -> tuple:
        t0 = time.perf_counter()
        result = (
            ConstraintSuggestionRunner.on_data(data)
            .add_constraint_rules(Rules.DEFAULT)
            .use_train_test_split_with_testset_ratio(0.25, testset_split_random_seed=0)
            .run()
        )
        return time.perf_counter() - t0, result

    # the held-out evaluation's constraint battery is data-dependent, so its
    # fused fold program compiles on first use; report cold (incl. compile)
    # and warm (program-cache hit) separately like the other stages' warmups
    cold_s, result = run_once()
    warm_s, result = run_once()
    n_suggestions = len(result.all_suggestions)
    evaluated = result.verification_result is not None
    log(
        f"[suggest] {n_suggestions} suggestions over {len(result.column_profiles)} "
        f"columns: cold {cold_s:.2f}s (persistent-XLA-cache-assisted), warm "
        f"{warm_s:.2f}s ({rows/warm_s/1e6:.2f}M rows/s, held-out evaluation="
        f"{'yes' if evaluated else 'no'})"
    )
    return {"seconds": warm_s, "cold_seconds": cold_s, "suggestions": n_suggestions}


def measure_profile_rate() -> float:
    """Rows/s of a warm 1M-row lineitem profile (the row-count calibration
    of :func:`main`)."""
    from deequ_tpu.data import Dataset
    from deequ_tpu.profiles import ColumnProfilerRunner

    cal_table = build_lineitem_data(1 << 20)
    # warm on the SAME 1M shape the timed run uses (a smaller warm slice
    # would leave the 1<<20 batch program uncompiled and the timed run
    # would measure XLA compile, not throughput)
    ColumnProfilerRunner.on_data(Dataset.from_arrow(cal_table)).run()
    t0 = time.perf_counter()
    ColumnProfilerRunner.on_data(Dataset.from_arrow(cal_table)).run()
    return (1 << 20) / (time.perf_counter() - t0)


#: stages that touch JAX, each run by :func:`run_stage_child` in a process
#: of its own. The parent never imports JAX: a process that has touched it
#: holds the chip, and a child that needs the chip would then fail or hang.
CHILD_STAGES = {
    "profile_rate": measure_profile_rate,
    "device_profile": run_device_profile_stage,
    "profile": run_profile_stage,
    "scan": run_scan_stage,
    "ingest": run_ingest_stage,
    "device_scan": run_device_resident_stage,
    "device_merge": run_device_merge_stage,
    "incremental": run_incremental_stage,
    "spill": run_spill_stage,
    "suggest": run_suggestion_stage,
}


def run_stage_child(name: str, *args):
    """Run ``CHILD_STAGES[name](*args)`` in a child process and return its
    result (the last stdout line, JSON). Its stderr is this process's, so
    the stage's log lines pass through. A nonzero exit raises."""
    import os
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--stage", name,
         json.dumps(list(args))],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        stdout=subprocess.PIPE, text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"{name} stage process exited rc={proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def stage_child_main(name: str, args_json: str) -> None:
    import jax

    from deequ_tpu.runners.engine import probe_feed_bandwidth

    log(f"[{name}] devices: {jax.devices()}; feed-link probe: "
        f"{probe_feed_bandwidth():.0f} MB/s")
    result = CHILD_STAGES[name](*json.loads(args_json))
    print(json.dumps(result, default=float), flush=True)


def main() -> None:
    import os

    scan_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 50_000_000
    profile_rows = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000_000

    # Partial-result protocol: a wall-clock kill (rc:124) in ANY stage must
    # not destroy the numbers the earlier stages already measured — that
    # exact failure erased two rounds of benchmarks. After EVERY stage a
    # full parse-able JSON snapshot of everything measured so far goes to
    # stdout with "partial": true; the driver takes the LAST JSON line, so
    # a timeout leaves the freshest snapshot as the artifact. On top of
    # that, every stage runs under a HARD per-stage deadline
    # (DEEQU_TPU_BENCH_STAGE_BUDGET_S, run_stage_with_deadline): a stage
    # that blows its budget is marked "skipped_deadline" in the "stages"
    # map and the bench proceeds — no stage can starve the ones after it.
    out: dict = {}
    completed: list = []
    stages: dict = {}

    def checkpoint(stage: str, status: str = "ok", extra: dict | None = None) -> None:
        # each stage's entry carries its status plus the compile/fetch
        # observability fields (compiles, state_fetch_s, device_dispatch_s)
        # so a compile or fetch regression is parseable from the artifact
        entry = {"status": status}
        if extra:
            entry.update(extra)
        stages[stage] = entry
        if status == "ok":
            completed.append(stage)
        write_stage_trace(stage)
        line = dict(out)
        line["partial"] = True
        line["completed_stages"] = list(completed)
        line["stages"] = dict(stages)
        print(json.dumps(line), flush=True)

    def staged(name: str, fn, *args, **kwargs):
        result, status, _seconds = run_stage_with_deadline(name, fn, *args, **kwargs)
        if status != "ok":
            checkpoint(name, status)
        return result

    def phase_extra(result: dict) -> dict:
        keys = ("compiles", "state_fetch_s", "device_dispatch_s",
                "staging_compiles")
        return {k: result[k] for k in keys if k in result}

    # NORTH-STAR-FIRST stage order (VERDICT r5 ask #1b): the device-placed
    # profile and the config-3 profile produce the numbers the project is
    # judged on, so they run before the synthetic device stages — a late
    # wall-clock kill costs synthetic numbers, never the headline ones.
    # The detached prewarm subprocess populates the persistent XLA cache
    # FIRST, so the measured stage deserializes its programs instead of
    # compiling them (the r05 rc:124 root cause).
    prewarm = staged(
        "xla_prewarm", run_xla_prewarm_stage,
        # the stage exists to absorb a cold compile LONGER than one stage
        # budget — under the default 1x SIGALRM a >budget compile would be
        # killed mid-prewarm, leaving a partial cache for the measured
        # stage to re-pay (the r05 failure mode). The subprocess enforces
        # its own timeout; the alarm is the backstop above it.
        budget_s=subprocess_timeout_s() + 30,
    )
    if prewarm is not None:
        out["xla_prewarm_s"] = round(prewarm["seconds"], 1)
        checkpoint("xla_prewarm", status="ok" if prewarm["ok"] else "failed")

    device_profile = staged("device_profile", run_stage_child, "device_profile")
    if device_profile is not None:
        out["device_profile_rows_per_sec"] = round(device_profile["rows_per_sec"], 1)
        out["device_profile_rows"] = device_profile["rows"]
        out["device_profile_compile_probe_s"] = round(
            device_profile["compile_probe_seconds"], 1
        )
        out["device_profile_staging_s"] = round(device_profile["stage_seconds"], 2)
        out["device_profile_state_fetch_s"] = device_profile["state_fetch_s"]
        out["device_profile_device_dispatch_s"] = device_profile["device_dispatch_s"]
        # vs_baseline lands in EVERY partial line from config-3 on (VERDICT
        # r5 ask #4): a later-stage timeout can no longer erase the
        # north-star ratio. The host profile stage overwrites it with its
        # larger-oracle measurement when it completes.
        out["vs_baseline"] = round(device_profile["vs_single_core"], 2)
        checkpoint("device_profile", extra=phase_extra(device_profile))

    # The bench host is SHARED: under heavy contention the host-tier stages
    # can run 10-50x slower than on a quiet box, and the BASELINE-shape row
    # counts would blow any reasonable wall-clock. The reported METRIC is
    # rows/s, so when a 1M-row calibration projects a stage far past its
    # budget, shrink the row count (never below the round-3 scale) and say
    # so — a completed smaller run beats a timed-out full-shape one.
    # the calibration budget must never exceed the per-stage SIGALRM: a
    # row count sized to 600s of projected work under a 180s stage
    # deadline guarantees a skipped_deadline, not a bigger number
    profile_budget = float(
        os.environ.get(
            "DEEQU_TPU_BENCH_PROFILE_BUDGET_S", str(0.9 * stage_budget_s())
        )
    )
    if profile_rows > 4_000_000:
        cal_rate = run_stage_child("profile_rate")
        projected = profile_rows / cal_rate
        if projected > profile_budget:
            effective = min(
                profile_rows, max(10_000_000, int(cal_rate * profile_budget))
            )
            log(
                f"[main] box congested: calibration {cal_rate/1e6:.2f}M rows/s "
                f"projects {projected:.0f}s for {profile_rows:,} profile rows "
                f"(budget {profile_budget:.0f}s) -> running {effective:,} rows"
            )
            profile_rows = effective
            scan_rows = min(scan_rows, max(10_000_000, profile_rows // 2))

    profile = staged("profile", run_stage_child, "profile", profile_rows)
    if profile is not None:
        out["metric"] = "column_profiler_rows_per_sec_per_chip"
        out["value"] = round(profile["rows_per_sec"], 1)
        out["unit"] = "rows/s"
        out["vs_baseline"] = round(profile["vs_single_core"], 2)
        out["vs_64core_linear"] = round(profile["vs_64core_linear"], 3)
        checkpoint("profile", extra=phase_extra(profile))

    scan = staged("scan", run_stage_child, "scan", scan_rows, 1 << 20)
    if scan is not None:
        out["scan_rows_per_sec_per_chip"] = round(scan["rows_per_sec"], 1)
        out["scan_vs_baseline"] = round(scan["vs_single_core"], 2)
        checkpoint("scan", extra=phase_extra(scan))

    ingest = staged("ingest", run_stage_child, "ingest", max(scan_rows // 4, 1 << 20))
    if ingest is not None:
        out["ingest_mb_per_s"] = ingest["mb_per_s"]
        out["ingest_overlap_hidden"] = ingest["overlap_hidden_fraction"]
        out["ingest_soak_sessions"] = ingest["soak_sessions"]
        out["ingest_soak_sessions_per_s"] = ingest["soak_sessions_per_s"]
        out["ingest_soak_mb_per_s"] = ingest["soak_mb_per_s"]
        for q_key in (
            "fold_latency_p50_s", "fold_latency_p99_s",
            "admission_wait_p50_s", "admission_wait_p99_s",
        ):
            if q_key in ingest:
                out[f"ingest_{q_key}"] = ingest[q_key]
        checkpoint("ingest", extra=ingest)

    device = staged("device_scan", run_stage_child, "device_scan")
    if device is not None:
        out["device_scan_rows_per_sec"] = round(device["rows_per_sec"], 1)
        out["device_scan_gbps"] = round(device["achieved_gbps"], 2)
        checkpoint("device_scan")

    merge = staged("device_merge", run_stage_child, "device_merge")
    if merge is not None:
        out["sketch_merge_gbps"] = round(merge["kll"], 3)
        out["hll_merge_gbps"] = round(merge["hll"], 3)
        checkpoint("device_merge")

    incremental = staged(
        "incremental", run_stage_child, "incremental",
        max(scan_rows // 2, 100_000), 2,
    )
    if incremental is not None:
        out["state_merge_seconds"] = round(incremental["merge_seconds"], 3)
        out["state_merge_bytes"] = incremental["state_bytes"]
        growth = incremental.get("partition_growth") or {}
        if growth:
            # the ISSUE-13 acceptance point: +1% growth verified at a
            # fraction of full-scan cost, gated by tools/bench_diff
            out["incremental_full_scan_s"] = growth["full_scan_s"]
            out["incremental_delta_s"] = growth["delta_s"]
            out["incremental_cost_fraction"] = growth["cost_fraction"]
            out["incremental_speedup_vs_full"] = growth["speedup_vs_full"]
            out["incremental_reuse_ratio"] = growth["reuse_ratio"]
            out["incremental_rows_touched_fraction"] = growth[
                "rows_touched_fraction"
            ]
            out["incremental_parity_bit_exact"] = growth["parity_bit_exact"]
        checkpoint(
            "incremental",
            extra={"partition_growth": growth} if growth else None,
        )

    grouping = staged("grouping", run_grouping_stage, max(scan_rows // 2, 100_000))
    if grouping is not None:
        out["grouping_rows_per_sec"] = round(grouping["rows_per_sec"], 1)
        out["grouping_peak_rss_gb"] = grouping["peak_rss_gb"]
        out["grouping_vs_host_spill"] = grouping["vs_host_spill"]
        if "vs_r04_spill" in grouping:
            out["grouping_vs_r04_spill"] = grouping["vs_r04_spill"]
        checkpoint("grouping", extra={
            "peak_rss_gb": grouping["peak_rss_gb"],
            "host_rows_per_sec": grouping["host_rows_per_sec"],
            "host_peak_rss_gb": grouping["host_peak_rss_gb"],
            "distinct": grouping["distinct"],
        })

    spill = staged("spill", run_stage_child, "spill", max(scan_rows // 2, 100_000))
    if spill is not None:
        out["spill_rows_per_sec"] = round(spill["rows_per_sec"], 1)
        out["spill_peak_rss_gb"] = spill["peak_rss_gb"]
        checkpoint("spill", extra={"peak_rss_gb": spill["peak_rss_gb"]})

    knee = staged(
        "streaming_knee", run_streaming_knee_stage,
        # four soak grid points x two modes in one detached child: give it
        # the subprocess budget, not one in-process stage's
        budget_s=subprocess_timeout_s() + 30,
    )
    if knee is not None:
        out["streaming_knee_sessions_per_s"] = knee[
            "headline_sessions_per_s"
        ]
        out["streaming_knee_speedup"] = knee["headline_speedup"]
        checkpoint("streaming_knee", extra={
            "points": [
                {k: p[k] for k in (
                    "sessions", "rows", "serial_sessions_per_s",
                    "coalesced_sessions_per_s",
                    "coalesced_sessions_per_s_min",
                    "coalesced_sessions_per_s_max",
                    "speedup", "shed",
                ) if k in p}
                for p in knee["points"]
            ],
            "parity_bit_exact": knee["parity"]["bit_exact"],
        })

    calibration = staged(
        "calibration", run_calibration_stage,
        # three detached children (probe + two measured points), each with
        # its own interpreter startup
        budget_s=3 * subprocess_timeout_s() + 30,
    )
    if calibration is not None:
        out["calibration_wall_s"] = round(calibration["wall_s"], 2)
        out["tuning_streaming_sessions_per_s_static"] = round(
            calibration["static"]["sessions_per_s"], 1
        )
        out["tuning_streaming_sessions_per_s_tuned"] = round(
            calibration["tuned"]["sessions_per_s"], 1
        )
        out["tuning_grouping_rows_per_s_static"] = round(
            calibration["static"]["grouping_rows_per_s"], 1
        )
        out["tuning_grouping_rows_per_s_tuned"] = round(
            calibration["tuned"]["grouping_rows_per_s"], 1
        )
        checkpoint("calibration", extra={
            "fingerprint": calibration["fingerprint"],
            "probes": {
                k: round(v, 6) for k, v in calibration["probes"].items()
            },
            "knobs": calibration["knobs"],
            "tuned_knobs": calibration["tuned"]["tuned_knobs"],
        })

    anomaly_fleet = staged(
        "anomaly_fleet", run_anomaly_fleet_stage,
        # detached child with its own process startup: give it the
        # subprocess budget, not one in-process stage's
        budget_s=subprocess_timeout_s() + 30,
    )
    if anomaly_fleet is not None:
        out["anomaly_fleet_series_per_s"] = anomaly_fleet["series_per_s"]
        out["anomaly_fleet_serial_series_per_s"] = anomaly_fleet[
            "serial_series_per_s"
        ]
        out["anomaly_fleet_speedup"] = anomaly_fleet["speedup"]
        out["anomaly_fleet_flagged"] = anomaly_fleet["flagged"]
        checkpoint("anomaly_fleet", extra={
            "series": anomaly_fleet["series"],
            "detect_calls": anomaly_fleet["detect_calls"],
            "parity": anomaly_fleet["parity"],
        })

    cluster_soak = staged(
        "cluster_soak", run_cluster_soak_stage,
        # two detached points (1-proc, 2-proc), each spawning worker
        # processes with their own interpreter startup: give the stage
        # two subprocess budgets, not one in-process stage's
        budget_s=2 * subprocess_timeout_s() + 30,
    )
    if cluster_soak is not None and not cluster_soak.get("skipped"):
        out["cluster_soak_sessions_per_s"] = cluster_soak["sessions_per_s"]
        out["cluster_soak_scaling_vs_1p"] = cluster_soak["scaling_vs_1p"]
        checkpoint("cluster_soak", extra={
            "points": cluster_soak["points"],
            "scaling_vs_1p": cluster_soak["scaling_vs_1p"],
            "routes_total": cluster_soak["routes_total"],
        })
    elif cluster_soak is not None:
        checkpoint("cluster_soak", status="skipped_env",
                   extra={"reason": cluster_soak.get("reason")})

    catalog_soak = staged(
        "catalog_soak", run_catalog_soak_stage,
        # one detached soak process with its own interpreter startup
        budget_s=subprocess_timeout_s() + 30,
    )
    if catalog_soak is not None:
        out["catalog_soak_sessions_per_s"] = catalog_soak["sessions_per_s"]
        out["gated_throughput_fraction"] = catalog_soak[
            "gated_throughput_fraction"
        ]
        checkpoint("catalog_soak", extra={
            "registered": catalog_soak["registered"],
            "active": catalog_soak["active"],
            "registers_per_s": catalog_soak["registers_per_s"],
            "gated_mb_per_s": catalog_soak["gated_mb_per_s"],
            "ungated_mb_per_s": catalog_soak["ungated_mb_per_s"],
        })

    mesh_scaling = staged(
        "mesh_scaling", run_mesh_scaling_stage,
        min(2_000_000, max(scan_rows // 25, 400_000)),
    )
    if mesh_scaling is not None:
        out["mesh_scaling_rows_per_sec"] = {
            k: round(v, 1) for k, v in mesh_scaling["points"].items()
        }
        # the SUBSTRATE rides the partial JSON so a virtual-CPU-device
        # scaling curve can never be misread as an accelerator one (the
        # r06 vs_baseline lesson applied to mesh points): real mesh vs
        # 8-virtual-CPU-device fallback, device kind, chip count
        substrate = mesh_scaling.get("mesh_substrate") or {}
        if substrate:
            out["mesh_substrate"] = substrate
        chaos = mesh_scaling.get("chaos") or {}
        if chaos:
            out["mesh_recovery_s"] = chaos["recovery_s"]
            out["mesh_chaos_parity_ok"] = chaos["parity_ok"]
        checkpoint("mesh_scaling", extra={
            "points": {k: round(v, 1) for k, v in mesh_scaling["points"].items()},
            **({"mesh_substrate": substrate} if substrate else {}),
            **({"chaos": chaos} if chaos else {}),
        })

    suggest = staged(
        "suggest", run_stage_child, "suggest", max(profile_rows // 20, 100_000)
    )
    if suggest is not None:
        out["suggest_seconds"] = round(suggest["seconds"], 2)
        out["suggest_cold_seconds"] = round(suggest["cold_seconds"], 2)
        out["suggestions"] = suggest["suggestions"]
        checkpoint("suggest")

    # perf-regression EPILOGUE (ROADMAP item 1's standing gate): diff this
    # run against the latest committed BENCH_r*/KNEE_r* trajectory and
    # record the verdict in the artifact. Report-only here — the bench's
    # job is to emit its numbers; CI enforces with `python -m
    # tools.bench_diff <fresh.json>` whose exit code is the gate.
    def run_bench_diff_stage() -> dict:
        from tools.bench_diff import render_report, run_diff_on_metrics

        fresh = dict(out)
        fresh["stages"] = dict(stages)
        fresh["completed_stages"] = list(completed)
        try:
            # ONE orchestration shared with the CLI gate (`python -m
            # tools.bench_diff`): same baseline/knee discovery, same
            # comparison — the epilogue and CI can never disagree about
            # what was compared
            result = run_diff_on_metrics(
                fresh, repo_dir=os.path.dirname(os.path.abspath(__file__))
            )
        except FileNotFoundError:
            return {"ok": True, "note": "no committed baseline parses",
                    "regressions": []}
        for line in render_report(result).splitlines():
            log(f"[bench_diff] {line}")
        return result

    bench_diff = staged("bench_diff", run_bench_diff_stage)
    if bench_diff is not None:
        out["bench_diff_ok"] = bench_diff["ok"]
        checkpoint("bench_diff", extra={
            "ok": bench_diff["ok"],
            "baseline": bench_diff.get("baseline"),
            "regressions": [
                f"{r['stage']}:{r['metric']}"
                for r in bench_diff.get("regressions", [])
            ],
        })

    final = dict(out)
    final["partial"] = False
    final["completed_stages"] = completed
    final["stages"] = stages
    print(json.dumps(final), flush=True)
    unfinished = sorted(
        k for k, v in stages.items() if v["status"] not in ("ok", "skipped_env")
    )
    if unfinished:
        log(f"[main] stages that did not complete: {unfinished}")
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--stage":
        stage_child_main(sys.argv[2], sys.argv[3])
    else:
        main()

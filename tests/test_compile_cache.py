"""Persistent XLA compilation cache: fresh processes must hit the on-disk
cache instead of re-paying tens of seconds of XLA compiles. config.py keeps
it where ``JAX_COMPILATION_CACHE_DIR`` says, else in the fixed in-checkout
``.cache/xla``; ``DEEQU_TPU_NO_COMPILE_CACHE=1`` turns it off (the tier-1
conftest does, so CPU entries never land in the checkout)."""

import os
import subprocess
import sys

_WORKLOAD = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import deequ_tpu  # noqa: F401  (applies cache config on import)

hits = {"n": 0}
from jax._src import monitoring
def _listener(event, **kw):
    if "compilation_cache/cache_hits" in event:
        hits["n"] += 1
monitoring.register_event_listener(_listener)

from deequ_tpu.analyzers import Completeness, Mean, StandardDeviation
from deequ_tpu.data import Dataset
from deequ_tpu.runners import AnalysisRunner

rng = np.random.default_rng(5)
data = Dataset.from_dict({"x": rng.normal(size=50_000)})
ctx = AnalysisRunner.do_analysis_run(
    data, [Mean("x"), StandardDeviation("x"), Completeness("x")]
)
assert ctx.metric(Mean("x")).value.is_success
print("CACHE_HITS", hits["n"])
"""


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_CHECKOUT = os.path.join(REPO, ".cache", "xla")


def _env(**overrides) -> dict:
    env = dict(os.environ)
    for key in ("DEEQU_TPU_NO_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(key, None)
    env.update(overrides)
    return env


def _run(cache_dir: str) -> int:
    env = _env(JAX_COMPILATION_CACHE_DIR=cache_dir)
    # force every compile to be cache-eligible regardless of compile time
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run(
        [sys.executable, "-c", _WORKLOAD],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    for line in out.stdout.splitlines():
        if line.startswith("CACHE_HITS"):
            return int(line.split()[1])
    raise AssertionError(out.stdout)


def _listing(path: str) -> list:
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


_SHOW_CONFIG = (
    "import jax; jax.config.update('jax_platforms','cpu');"
    "import deequ_tpu;"
    "print(jax.config.jax_enable_compilation_cache,"
    " jax.config.jax_compilation_cache_dir)"
)


class TestPersistentCompilationCache:
    def test_env_dir_populated_then_hit_across_processes(self, tmp_path):
        cache = str(tmp_path / "xla-cache")
        before = _listing(IN_CHECKOUT)
        hits_cold = _run(cache)
        entries = os.listdir(cache)
        assert entries, "first process must populate the cache directory"
        hits_warm = _run(cache)
        assert hits_warm > hits_cold, (hits_cold, hits_warm)
        # JAX_COMPILATION_CACHE_DIR is the only place written
        assert _listing(IN_CHECKOUT) == before

    def test_in_checkout_default_and_opt_out(self):
        out = subprocess.run(
            [sys.executable, "-c", _SHOW_CONFIG],
            capture_output=True, text=True, env=_env(), timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split() == ["True", IN_CHECKOUT]
        out = subprocess.run(
            [sys.executable, "-c", _SHOW_CONFIG],
            capture_output=True, text=True, timeout=120,
            env=_env(DEEQU_TPU_NO_COMPILE_CACHE="1",
                     JAX_COMPILATION_CACHE_DIR=IN_CHECKOUT + "-never"),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split()[0] == "False"


class TestBoundedLruCaches:
    """VERDICT r4 #9: the bounded program caches evict least-recently-USED,
    so a hot key survives churn that previously (FIFO) evicted it."""

    def test_lru_keeps_hot_key_under_churn(self):
        from deequ_tpu.utils import BoundedLRU

        lru = BoundedLRU(4)
        lru["hot"] = "H"
        for i in range(100):
            lru[f"cold{i}"] = i
            assert lru.get("hot") == "H"  # touch -> stays resident
        assert len(lru) == 4

    def test_fifo_order_without_touches(self):
        from deequ_tpu.utils import BoundedLRU

        lru = BoundedLRU(2)
        lru["a"] = 1
        lru["b"] = 2
        lru["c"] = 3
        assert lru.get("a") is None and lru.get("b") == 2 and lru.get("c") == 3

    def test_merge_fold_cache_hot_key_survives(self):
        import numpy as np

        from deequ_tpu.analyzers import Mean
        from deequ_tpu.analyzers.base import _MERGE_FOLD_CACHE, merge_states_batched
        from deequ_tpu.analyzers.states import MeanState

        def state(v, c):
            return MeanState(np.float64(v), np.int64(c))

        hot = Mean("hot_col")
        merge_states_batched(hot, [state(1, 1), state(2, 1)])
        hot_key = (hot, 2)
        assert hot_key in _MERGE_FOLD_CACHE
        for i in range(_MERGE_FOLD_CACHE.max_size + 5):
            # churn with distinct shard counts; touch the hot key each time
            merge_states_batched(Mean(f"c{i}"), [state(1, 1)] * 3)
            merged = merge_states_batched(hot, [state(1, 1), state(2, 1)])
            assert hot_key in _MERGE_FOLD_CACHE
        assert float(merged.total) == 3.0

"""Test environment: force an 8-device CPU platform BEFORE jax initializes,
so shard-merge tests exercise real multi-device code paths — the analog of
the reference forcing 2 shuffle partitions to push partial-state merges
through cluster code paths (`SparkContextSpec.scala:75-84`)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# deterministic placement: tests exercise the device-stream path by default
# (the host ingest tier has explicit placement="host" tests)
os.environ.setdefault("DEEQU_TPU_PLACEMENT", "device")
# CPU compiles stay out of the in-checkout compile cache (.cache/xla), which
# belongs to the chip runs; test_compile_cache.py turns it on per process
os.environ.setdefault("DEEQU_TPU_NO_COMPILE_CACHE", "1")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def df_full():
    """4 complete rows (reference `utils/FixtureSupport.scala getDfFull`)."""
    from deequ_tpu.data import Dataset

    return Dataset.from_dict(
        {
            "item": ["1", "2", "3", "4"],
            "att1": ["a", "b", "a", "a"],
            "att2": ["c", "d", "d", "f"],
        }
    )


@pytest.fixture
def df_missing():
    """12 rows with nulls in att1/att2 (reference `FixtureSupport.getDfMissing`)."""
    import pyarrow as pa

    from deequ_tpu.data import Dataset

    rows = [
        ("1", "a", "f"),
        ("2", "b", "d"),
        ("3", None, "f"),
        ("4", "a", None),
        ("5", "a", "f"),
        ("6", None, "d"),
        ("7", None, "d"),
        ("8", "b", None),
        ("9", "a", "f"),
        ("10", None, None),
        ("11", None, "f"),
        ("12", None, "d"),
    ]
    return Dataset.from_arrow(
        pa.table(
            {
                "item": pa.array([r[0] for r in rows]),
                "att1": pa.array([r[1] for r in rows]),
                "att2": pa.array([r[2] for r in rows]),
            }
        )
    )


@pytest.fixture
def df_numeric():
    """6 rows of numeric values (reference `FixtureSupport.getDfWithNumericValues`)."""
    from deequ_tpu.data import Dataset

    return Dataset.from_dict(
        {
            "item": ["1", "2", "3", "4", "5", "6"],
            "att1": [1, 2, 3, 4, 5, 6],
            "att2": [0, 0, 0, 5, 6, 7],
            "att3": [0, 0, 0, 4, 6, 7],
        }
    )

"""v5e compiles of the main path's device programs, for a described
``v5e:2x2`` topology with no chip attached: what the TPU compiler refuses
(scoped VMEM, a program that cannot be partitioned) fails here, at no chip
time. Nothing runs; only shapes go in.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file. Keep these tests in this one file."""

import os

import numpy as np
import pytest

BATCH = 1 << 20
SLOTS = 1 << 22  # freq_table_slots() default
BUFFER = 1 << 20  # a compacting buffer: one batch, below the row count


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: os.environ.get(k) for k in ("TPU_LOG_DIR",)}
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs under /tmp
    cache_was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _features(analyzers, batch, sharding_for):
    """ShapeDtypeStructs of the battery's features at ``batch`` rows, from
    the dtypes the feature builder gives a small real batch."""
    import jax
    import pyarrow as pa

    from deequ_tpu.data import Dataset
    from deequ_tpu.runners.features import FeatureBuilder

    small = 4096
    rng = np.random.default_rng(0)
    table = pa.table({
        "x": pa.array(rng.normal(size=small), mask=rng.random(small) < 0.05),
        "cat": pa.array(rng.integers(0, 1000, small)),
    })
    builder = FeatureBuilder([s for a in analyzers for s in a.feature_specs()])
    built = builder.build(next(iter(Dataset.from_arrow(table).batches(small))))
    out = {}
    for key, value in built.items():
        value = np.asarray(value)
        shape = tuple(batch if d == small else d for d in value.shape)
        out[key] = jax.ShapeDtypeStruct(shape, value.dtype,
                                        sharding=sharding_for(shape))
    return out


def _compile_update(prog, features, state_sharding):
    """Compile a PackedScanProgram's fused update for the described chip."""
    import jax

    carry = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=state_sharding),
        jax.eval_shape(prog.init_carry),
    )
    slots = tuple(tuple(features[k] for k in keys) for keys in prog._spec_keys)
    return prog._update.lower(carry, slots).compile()


def test_scan_bundle_at_batch_2pow20(one_chip):
    from deequ_tpu.analyzers import StandardDeviation
    from deequ_tpu.runners.engine import BundledScanProgram

    battery = (StandardDeviation("x"),) * 4
    prog = BundledScanProgram(battery, None)
    assert len(prog._programs) == 1  # one signature bundle
    compiled = _compile_update(
        prog._programs[0], _features(battery, BATCH, lambda s: one_chip),
        one_chip,
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_compacting_frequency_table_update_default_slots(one_chip):
    """The trace that compacts in the pass: before PR 21 the v5e compiler
    refused its prefix sum (19.1M of 16M scoped VMEM)."""
    from deequ_tpu.analyzers.grouping import DeviceFrequencyTableScan
    from deequ_tpu.runners.engine import PackedScanProgram

    scan = DeviceFrequencyTableScan(
        ("cat",), ("num",), slots=SLOTS, buffer_entries=BUFFER
    )
    _compile_update(
        PackedScanProgram((scan,), None),
        _features([scan], BATCH, lambda s: one_chip), one_chip,
    )


def test_freq_compact_merge_default_slots(one_chip):
    """The semigroup merge's compaction of two full tables."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops import freq_compact
    from deequ_tpu.ops.hashing import FREQ_KEY_SENTINEL

    keys = jax.ShapeDtypeStruct((2 * SLOTS,), jnp.uint64, sharding=one_chip)
    counts = jax.ShapeDtypeStruct((2 * SLOTS,), jnp.int64, sharding=one_chip)
    jax.jit(
        lambda k, c: freq_compact(k, c, SLOTS, jnp.uint64(FREQ_KEY_SENTINEL))
    ).lower(keys, counts).compile()


def test_sharded_update_over_four_chips(topo):
    """GSPMD scan over a 2x2 mesh: rows sharded, states replicated, the
    compiler inserts the cross-chip reductions."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from deequ_tpu.analyzers import (
        ApproxCountDistinct, Completeness, Maximum, Mean, Minimum,
        StandardDeviation,
    )
    from deequ_tpu.parallel import ROW_AXIS, make_mesh, sharded_update

    battery = [Completeness("x"), Mean("x"), StandardDeviation("x"),
               Minimum("x"), Maximum("x"), ApproxCountDistinct("cat")]
    mesh = make_mesh(devices=topo.devices[:4])
    batch = 1 << 16

    def rows_sharded(shape):
        return NamedSharding(mesh, PartitionSpec(
            *(ROW_AXIS if d == batch else None for d in shape)))

    replicated = NamedSharding(mesh, PartitionSpec())
    states = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: tuple(a.init_state() for a in battery)),
    )
    compiled = sharded_update(battery, mesh).lower(
        states, _features(battery, batch, rows_sharded)
    ).compile()
    assert "all-reduce" in compiled.as_text()

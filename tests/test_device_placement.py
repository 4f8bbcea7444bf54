"""Arrays of a sub-mesh run live on that sub-mesh. On virtual CPU devices
an array left on the default device (device 0) costs nothing, so code that
only met them could leave carries, placeholders or index constants there;
on chips that is a chip-to-chip copy into every program of a sub-mesh
without device 0 (and halted a v5e 2+2 fleet run, PR 21). Implicit
device-to-device transfers are disallowed here, so any such array raises."""

import jax
import numpy as np
import pytest


@pytest.fixture
def no_implicit_transfers():
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    yield
    jax.config.update("jax_transfer_guard_device_to_device", "allow")


def _data(rows=60_000, seed=3):
    import pyarrow as pa

    from deequ_tpu.data import Dataset

    rng = np.random.default_rng(seed)
    return Dataset.from_arrow(pa.table({
        "x": pa.array(rng.normal(size=rows), mask=rng.random(rows) < 0.05),
        "cat": pa.array(rng.integers(0, 500, rows)),
    }))


def _battery():
    from deequ_tpu.analyzers import (
        ApproxCountDistinct, ApproxQuantile, Completeness, Maximum, Mean,
        StandardDeviation,
    )

    return [Completeness("x"), Mean("x"), StandardDeviation("x"),
            Maximum("x"), ApproxCountDistinct("cat"), ApproxQuantile("x", 0.5)]


@pytest.mark.parametrize("placement", ["device", "host"])
def test_sub_mesh_pass_stays_on_its_devices(no_implicit_transfers, placement):
    from deequ_tpu.parallel import make_mesh
    from deequ_tpu.runners.engine import RunMonitor, ScanEngine

    mesh = make_mesh(devices=jax.devices()[2:4])
    mon = RunMonitor()
    states, _ = ScanEngine(
        _battery(), monitor=mon, sharding=mesh, placement=placement
    ).run(_data(), batch_size=8192, slim_fetch=True)
    assert mon.placement == placement
    assert len(states) == len(_battery())


def test_mesh_carry_is_born_on_the_mesh():
    from deequ_tpu.parallel import make_mesh
    from deequ_tpu.runners.engine import BundledScanProgram

    devices = jax.devices()[2:4]
    carry = BundledScanProgram(tuple(_battery()), make_mesh(devices=devices)).init_carry()
    placed = {d for leaf in jax.tree_util.tree_leaves(carry) for d in leaf.devices()}
    assert placed == set(devices)


def test_fleet_tenants_stay_on_their_slices(no_implicit_transfers):
    from deequ_tpu import Check, CheckLevel
    from deequ_tpu.service import VerificationService

    check = Check(CheckLevel.ERROR, "placement").has_size(lambda n: n > 0)
    with VerificationService(workers=2, background_warm=False,
                             fleet=True) as svc:
        for t in ("a", "b"):
            svc.fleet.acquire(t)
        for t in ("a", "b"):
            res = svc.verify(_data(seed=ord(t)), [check], tenant=t,
                             required_analyzers=_battery(), timeout=300)
            failed = [repr(a) for a, m in res.metrics.items()
                      if not m.value.is_success]
            assert not failed, failed
        for t in ("a", "b"):
            svc.fleet.release(t)

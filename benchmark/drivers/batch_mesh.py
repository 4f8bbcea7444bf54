"""Closed-loop batch jobs over a mesh of the cell's chips: the batch
driver's runs of ``VerificationSuite``, with the rows split over
``make_mesh(chips)`` in the engine's batches.

Traffic parameters as the batch driver's; ``entry`` is ``verify``. The
record adds ``chips`` and the program's frequency-drain counters, each
summed over the window's runs (left out where the program has none).
"""

from __future__ import annotations

from benchmark.drivers import batch

#: RunMonitor counters of the device frequency drain
COUNTERS = (
    "freq_device_summaries", "freq_keys_drained", "freq_drain_fetch_bytes",
    "freq_shard_max_keys",
)


class Driver(batch.Driver):
    def setup(self) -> None:
        import jax

        from deequ_tpu.parallel import make_mesh

        if self.entry != "verify":
            raise ValueError(f"batch_mesh runs the verify entry, not {self.entry!r}")
        devices = jax.devices()
        chips = self.cell.chips
        if len(devices) < chips:
            raise RuntimeError(
                f"the cell needs {chips} devices for its mesh; "
                f"{len(devices)} exist")
        self.mesh = make_mesh(devices=devices[:chips])
        super().setup()

    def _run(self, table, monitor):
        from deequ_tpu import VerificationSuite
        from deequ_tpu.data import Dataset

        return (VerificationSuite.on_data(Dataset.from_arrow(table))
                .add_check(batch.build_check(self.battery, self.cell.name))
                .with_sharding(self.mesh)
                .with_monitor(monitor).run())

    def measure(self, seconds: float) -> dict:
        outcome = super().measure(seconds)
        record = outcome["record"]
        record["chips"] = self.cell.chips
        for name in COUNTERS:
            values = [getattr(m, name, None) for m in self.monitors]
            if values and None not in values:
                record[name] = sum(values)
        return outcome

"""Seconds of set-up that `Simulator.__init__` spends handing the stacked
shards and the test splits to the device (`jnp.asarray`): until the host is
free again, not until the copy is done, which runs on after it on the
runtime's threads.
The program's own phase `sim.to_device` on the clock the Simulator carries
(`sim.phases`; also the span `biscotti:sim.to_device` in a trace)."""

from benchmark.stages import phase_total_s


def read(record):
    return phase_total_s(getattr(record.get("sim"), "phases", None),
                         "sim.to_device")

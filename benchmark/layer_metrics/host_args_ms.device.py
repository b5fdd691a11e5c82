"""Host time (ms, median a round) of what `Simulator.round_step` builds on
every call before it dispatches (today the seed's `jnp.asarray`, a device
program of its own under x64).
The program's own span `biscotti:sim.round.args`, read from the host
planes of the traced slice: the profiler's clock, the same as the device's."""

from benchmark.stages import host_span_median_ms


def read(record):
    return host_span_median_ms(record, "sim.round.args")

"""Host time (ms, median a round) of the call of the jitted round program,
until it returns to the host (the device works on; the sync after it is
the benchmark's).
The program's own span `biscotti:sim.round.dispatch`, read from the host
planes of the traced slice: the profiler's clock, the same as the device's."""

from benchmark.stages import host_span_median_ms


def read(record):
    return host_span_median_ms(record, "sim.round.dispatch")

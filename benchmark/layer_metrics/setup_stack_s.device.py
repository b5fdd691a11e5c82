"""Seconds of set-up that `Simulator.__init__` spends in `np.stack`, making
the [N, rows, d] and [N, rows] host arrays out of the peers' shards.
The program's own phase `sim.stack` on the clock the Simulator carries
(`sim.phases`; also the span `biscotti:sim.stack` in a trace)."""

from benchmark.stages import phase_total_s


def read(record):
    return phase_total_s(getattr(record.get("sim"), "phases", None),
                         "sim.stack")

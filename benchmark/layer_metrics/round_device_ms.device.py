"""Device time of the round's XLA program per execution (median, ms):
the "XLA Modules" line of the device trace."""

import statistics

from benchmark.spans import program_runs


def read(record):
    runs = program_runs(record, "round_step")
    return statistics.median(runs) if runs else None

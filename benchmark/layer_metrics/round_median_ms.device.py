"""Median host-clock round (ms) of the traced slice: the steadier
statistic beside `device_round_ms`, which is all the window's time over
all its rounds."""

import statistics


def read(record):
    if not record.get("round_s"):
        return None
    return 1e3 * statistics.median(record["round_s"])

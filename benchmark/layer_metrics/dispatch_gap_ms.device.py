"""What a round costs beyond its device program (ms): the median
host-clock round minus the median device time of the round's program,
both from the traced slice. Dispatch, the sync, and whatever the host
does between rounds."""

import statistics

from benchmark.spans import program_runs


def read(record):
    runs = program_runs(record, "round_step")
    if not runs or not record.get("round_s"):
        return None
    return (1e3 * statistics.median(record["round_s"])
            - statistics.median(runs))

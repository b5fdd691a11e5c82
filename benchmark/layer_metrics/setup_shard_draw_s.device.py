"""Seconds of set-up spent drawing the peers' shards: the program's own
phase `shard_draw` around `data/datasets.py:_draw`, on the loader's
module-level clock (`datasets.CLOCK`). Busy-seconds over all threads: the
benchmark asks for the shards from eight threads before the Simulator
asks for them in turn, so this is more than the wall clock it took."""

import sys

from benchmark.stages import phase_total_s


def read(record):
    loader = sys.modules.get("biscotti_tpu.data.datasets")
    return phase_total_s(getattr(loader, "CLOCK", None), "shard_draw")

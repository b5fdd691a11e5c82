"""Share of the device's bf16 peak that the WHOLE round's MODEL FLOPs
reach: forward and activation backward of the sampled windows through all
sixteen layers (the rule at 96 | 192, the attention core, every frozen
product) and the head over 100,352 classes, forward of the held-out
windows (`benchmark/flops/olmo_hybrid.py`, from shapes alone; the
program's own recomputation, the zero columns, the noise, Krum and the
sum not counted), over `round_device_ms.device` x the peak of the device
the run reports (`benchmark/peaks.py`; an unknown device is an error). The
share of the whole step that bounds any later claim in this cell. Under 1.
None where the traced model is not the dense delta-net hybrid."""

import statistics

from benchmark.flops.olmo_hybrid import round_model_flops
from benchmark.olmo_stages import stages, windows
from benchmark.peaks import peak
from benchmark.spans import program_runs


def read(record):
    runs = program_runs(record, "round_step")
    if stages(record) is None or not runs:
        return None
    flops = round_model_flops(record["cell"]["config"], *windows(record))
    return flops / (statistics.median(runs) * 1e-3
                    * peak(record["device"]["kind"], "bf16_flops"))

"""Share of the device's bf16 peak that the WHOLE round's MODEL FLOPs
reach: forward and activation backward of the sampled windows through all
seven layers (both kinds of attention core over the pairs a query sees,
every frozen product, the routed experts at a uniform router's held share)
and the head, forward of the held-out windows
(`benchmark/flops/mimo_v2.py`, from shapes alone; the program's own
recomputation, the noise, Krum and the sum not counted), over
`round_device_ms.device` x the peak of the device the run reports
(`benchmark/peaks.py`; an unknown device is an error). The share of the
whole step that bounds any later claim in this cell. Under 1. None where
the traced model declares no part `attn_core_swa` (any model but
MiMo-V2.5's)."""

import statistics

from benchmark.flops.mimo_v2 import round_model_flops
from benchmark.lm_substages import subscopes_of
from benchmark.peaks import peak
from benchmark.spans import program_runs


def read(record):
    runs = program_runs(record, "round_step")
    if "attn_core_swa" not in (subscopes_of(record.get("sim")) or ()) \
            or not runs:
        return None
    cfg = record["cfg"]
    flops = round_model_flops(record["cell"]["config"],
                              cfg.num_samples * cfg.batch_size,
                              len(record["sim"].x_val))
    return flops / (statistics.median(runs) * 1e-3
                    * peak(record["device"]["kind"], "bf16_flops"))

"""Share of the device's bf16 peak that the WHOLE round's MODEL FLOPs
reach: forward and activation backward of the sampled windows through all
twelve layers (the rule, the attention core, every frozen product, the
routed experts at a uniform router's held share) and the head, forward of
the held-out windows (`benchmark/flops/qwen3_next.py`, from shapes alone;
the program's own recomputation, the noise, Krum and the sum not counted),
over `round_device_ms.device` x the peak of the device the run reports
(`benchmark/peaks.py`; an unknown device is an error). The share of the
whole step that bounds any later claim in this cell. Under 1. None where
the traced model is not the delta-net hybrid."""

import statistics

from benchmark.flops.qwen3_next import round_model_flops
from benchmark.lm_stages import scope_ms
from benchmark.peaks import peak
from benchmark.spans import program_runs


def read(record):
    found = scope_ms(record)
    runs = program_runs(record, "round_step")
    if found is None or "gdn_rule" not in found["stages"] or not runs:
        return None
    cfg = record["cfg"]
    flops = round_model_flops(record["cell"]["config"],
                              cfg.num_samples * cfg.batch_size,
                              len(record["sim"].x_val))
    return flops / (statistics.median(runs) * 1e-3
                    * peak(record["device"]["kind"], "bf16_flops"))

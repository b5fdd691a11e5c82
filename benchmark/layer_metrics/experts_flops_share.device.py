"""Share of the device's bf16 peak that the routed experts' MODEL FLOPs
reach in their own time: the grouped products' forward and activation
backward for the token-expert assignments the round really made
(`benchmark/flops/laguna.py`; recomputation not counted), median a round,
over `lm_experts_ms.device` x the peak of the device the run reports
(`benchmark/peaks.py`; an unknown device is an error). A share: under 1."""

import statistics

from benchmark.flops.laguna import expert_step_flops
from benchmark.lm_stages import scope_total
from benchmark.peaks import peak


def read(record):
    made = (record.get("moe") or {}).get("assignments_held")
    ms = scope_total(record, "lm_experts")
    if not made or not ms:
        return None
    config = record["cell"]["config"]
    flops = expert_step_flops(statistics.median(made), config["hidden_size"],
                              config["moe_intermediate_size"])
    return flops / (ms * 1e-3 * peak(record["device"]["kind"], "bf16_flops"))

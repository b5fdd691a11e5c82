"""Device time (ms) one execution of the round's program spends in this
model's routed experts (scope `lm_experts`: the sort by expert, the
dispatch, the three grouped products over the 32 held experts and the
combine; no shared expert), six sparse layers, forward, recomputation and
backward, the held-out windows' forward included. The double of
`lm_experts_ms.device`, whose entry this PR leaves as it is (ISSUE 40),
read through `benchmark/lm_stages.py`. None where the traced program's
model declares no part `attn_core_swa` (any model but MiMo-V2.5's)."""

from benchmark.lm_stages import scope_ms
from benchmark.lm_substages import subscopes_of


def read(record):
    if "attn_core_swa" not in (subscopes_of(record.get("sim")) or ()):
        return None
    found = scope_ms(record)
    return found and found["stages"].get("lm_experts")

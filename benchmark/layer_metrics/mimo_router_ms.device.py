"""Device time (ms) one execution of the round's program spends in this
model's router (scope `lm_router`: the product with `W_r`, the sigmoid over
all 256 experts, the choice bias, the top eight and their renormalised
weights), six sparse layers, forward, recomputation and backward, the
held-out windows' forward included. The double of `lm_router_ms.device`,
read as `mimo_experts_ms.device` is. None where the traced program's model
declares no part `attn_core_swa`."""

from benchmark.lm_stages import scope_ms
from benchmark.lm_substages import subscopes_of


def read(record):
    if "attn_core_swa" not in (subscopes_of(record.get("sim")) or ()):
        return None
    found = scope_ms(record)
    return found and found["stages"].get("lm_router")

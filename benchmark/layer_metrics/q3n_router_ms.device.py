"""Device time (ms) one execution of the round's program spends in this
model's router (scope `lm_router`: the product with `W_r`, the softmax over
all 512 experts, the top ten and their renormalisation), all twelve layers,
forward, recomputation and backward, the held-out windows' forward
included. The double of `lm_router_ms.device`, whose entry this PR leaves
as it is (ISSUE 38), read as `gdn_rule_ms.device` is. Nothing to read
(None) where the traced program's model is not the delta-net hybrid (it
opens no scope `gdn_rule`)."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    if found is None or "gdn_rule" not in found["stages"]:
        return None
    return found["stages"].get("lm_router")

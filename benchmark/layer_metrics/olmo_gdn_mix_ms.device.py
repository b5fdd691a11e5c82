"""Device time (ms) one execution of the round's program spends on the
delta-net layers' elementwise parts around the rule: the causal depthwise
conv over 11,520 channels with its silu, the split, beta and g (scope
`gdn_conv`) and the gated RMSNorm a head of 192 (scope `gdn_gate`),
forward, recomputation and backward. Read as `olmo_gdn_rule_ms.device`
is; None where the traced model is not the dense delta-net hybrid."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "gdn_conv", "gdn_gate")

"""Device time (ms) one execution of the round's program spends on the
delta-net layers' elementwise parts around the rule: the causal depthwise
conv with its silu, the split, beta and g (scope `gdn_conv`) and the gated
RMSNorm (scope `gdn_gate`), forward, recomputation and backward. Read as
`gdn_rule_ms.device` is; None where the model opens neither scope."""

from benchmark.lm_stages import scope_ms, scope_total


def read(record):
    found = scope_ms(record)
    if found is None or not ({"gdn_conv", "gdn_gate"} & set(found["stages"])):
        return None
    return scope_total(record, "gdn_conv", "gdn_gate")

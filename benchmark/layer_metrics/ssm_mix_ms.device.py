"""Device time (ms) one execution of the round's program spends on the
state-space layers' elementwise parts around the scan: the causal
depthwise conv with its silu and the split (scope `ssm_conv`) and the
gated RMSNorm (scope `ssm_gate`), forward, recomputation and backward.
Read as `ssm_scan_ms.device` is; None where the model opens neither
scope."""

from benchmark.lm_stages import scope_ms, scope_total


def read(record):
    found = scope_ms(record)
    if found is None or not ({"ssm_conv", "ssm_gate"} & set(found["stages"])):
        return None
    return scope_total(record, "ssm_conv", "ssm_gate")

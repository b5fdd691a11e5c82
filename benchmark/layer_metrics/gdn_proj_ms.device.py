"""Device time (ms) one execution of the round's program spends in the
delta-net layers' projections (scope `gdn_proj`: the block norm,
`in_proj_qkvz`, `in_proj_ba`, `out_proj`, their adapters and the
residual), forward, recomputation and backward. Read as
`gdn_rule_ms.device` is; None where the model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("gdn_proj")

"""Device time (ms) one execution of the round's program spends on the
state-space layers' projections (scope `ssm_proj`: the block norm,
`in_proj` 2,048 x 8,512, `out_proj` 4,096 x 2,048, their adapters and the
residual), forward, recomputation and backward. Read as
`ssm_scan_ms.device` is; None where the model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("ssm_proj")

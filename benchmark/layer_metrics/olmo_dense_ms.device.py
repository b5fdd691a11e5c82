"""Device time (ms) one execution of the round's program spends on the 16
dense SwiGLUs (scope `lm_dense`: 3,840 x 11,008 gate and up, 11,008 x
3,840 down, the norm on the result and the residual), forward,
recomputation and backward. Read as `olmo_gdn_rule_ms.device` is; None
where the traced model is not the dense delta-net hybrid."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "lm_dense")

"""Device time (ms) one execution of the round's program spends in the
four full-attention layers' mixers (scope `lm_attention`: q, k, v, the q
and k norms over the whole projection, the layouts, the core at 30 heads
of 128 | 128, `W_o`, the norm on the result and the residual), forward,
recomputation and backward, the held-out windows' forward included. Read
as `olmo_gdn_rule_ms.device` is; None where the traced model is not the
dense delta-net hybrid."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "lm_attention")

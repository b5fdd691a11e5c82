"""Device time (ms) one execution of the round's program spends on the
delta-net layers' products with frozen weights (scope `gdn_proj`: W_qkvz
3,840 x 17,280, W_ba, W_out 5,760 x 3,840, their adapters, the norm on the
mixer's result and the residual), forward, recomputation and backward.
Read as `olmo_gdn_rule_ms.device` is; None where the traced model is not
the dense delta-net hybrid."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "gdn_proj")

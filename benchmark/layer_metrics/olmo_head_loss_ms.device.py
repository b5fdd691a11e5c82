"""Device time (ms) one execution of the round's program spends on the
final norm, the head's product over all 100,352 classes and the
cross-entropy (scope `lm_head_loss`), forward and backward: the head a
deployment holds on the SECOND stage, held here so that the round has its
loss. Read as `olmo_gdn_rule_ms.device` is; None where the traced model is
not the dense delta-net hybrid."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "lm_head_loss")

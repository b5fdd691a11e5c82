"""Device time (ms) one execution of the round's program spends in this
model's gated attention layers' mixers (scope `lm_attention`: the block
norm, q with its gate, k, v, the head norms, rotary, the core, the output
gate and `W_o`), forward, recomputation and backward, the held-out windows'
forward included. The double of `lm_attention_ms.device`, whose entry this
PR leaves as it is (ISSUE 38), read as `gdn_rule_ms.device` is. Nothing to
read (None) where the traced program's model is not the delta-net hybrid
(it opens no scope `gdn_rule`)."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    if found is None or "gdn_rule" not in found["stages"]:
        return None
    return found["stages"].get("lm_attention")

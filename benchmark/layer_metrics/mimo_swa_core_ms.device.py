"""Device time (ms) one execution of the round's program spends in the
WINDOW layers' attention core alone (part `attn_core_swa`: the
`ops/attention.attention` call under the window of 128 and the sink,
whichever side of its dispatch runs; at the published size the fused
kernel's `attention_forward*` and `attention_backward*`, the copies of a
key/value head its sub-groups read and the relayouts the compiler puts at
their edges), five layers, forward, recomputation and backward. Read as
`mimo_attn_proj_ms.device` is; None where the traced program's model
declares no such part."""

from benchmark.lm_substages import part_ms


def read(record):
    return part_ms(record, "attn_core_swa")

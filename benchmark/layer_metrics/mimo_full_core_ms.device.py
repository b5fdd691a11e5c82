"""Device time (ms) one execution of the round's program spends in the
FULL layers' attention core alone (part `attn_core_full`: the
`ops/attention.attention` call under the causal mask, no sink), two
layers, forward, recomputation and backward. Read as
`mimo_swa_core_ms.device` is; None where the traced program's model
declares no such part."""

from benchmark.lm_substages import part_ms


def read(record):
    return part_ms(record, "attn_core_full")

"""Device time (ms) one execution of the round's program spends moving the
attention block's arrays between layouts (part `attn_layout`: the reshapes
and head-major transposes both ways, the casts to the kernel's dtype, the
value's slice: what a kernel that read `[W, T, heads x width]` as the
projections write it would not need), forward, recomputation and backward.
Median over the traced executions of the self time of that part's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`) under the model's `SCOPES
+ SUBSCOPES` (`benchmark/lm_substages.py`): a part is opened INSIDE the
attention block's scope (`mla_proj` of DeepSeek-V2, `lm_attention` of
Laguna) and is a part of what `mla_proj_ms.device` /
`lm_attention_ms.device` read. Nothing to read (None) where the traced
program's model declares no such part."""

from benchmark.lm_substages import part_ms


def read(record):
    return part_ms(record, "attn_layout")

"""Device time (ms) one execution of the round's program spends in the
attention block's norms (part `attn_norms`: the block norm, and
DeepSeek-V2's two inner norms of the compressed query and key/value),
forward, recomputation and backward. Median over the traced executions of
the self time of that part's instructions: the device trace's "XLA Ops",
joined to the program's scopes through its compiled HLO
(`benchmark/stages.py`) under the model's `SCOPES + SUBSCOPES`
(`benchmark/lm_substages.py`): a part is opened INSIDE the attention
block's scope (`mla_proj` of DeepSeek-V2, `lm_attention` of Laguna) and is
a part of what `mla_proj_ms.device` / `lm_attention_ms.device` read.
Nothing to read (None) where the traced program's model declares no such
part."""

from benchmark.lm_substages import part_ms


def read(record):
    return part_ms(record, "attn_norms")

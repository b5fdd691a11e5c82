"""Device time (ms) one execution of the round's program spends in this
model's attention projections (parts `attn_in` + `attn_out`: the fused
`W_qkv` product and `W_o`, each with its adapter), all seven layers,
forward, recomputation and backward, the held-out windows' forward
included. Median over the traced executions of the self time of those
parts' instructions: the device trace's "XLA Ops", joined to the program's
scopes through its compiled HLO (`benchmark/stages.py`) under the model's
`SCOPES + SUBSCOPES` (`benchmark/lm_substages.py`). Nothing to read (None)
where the traced program's model does not split its core by kind (it
declares no part `attn_core_swa`: any model but MiMo-V2.5's, any commit
before it)."""

from benchmark.lm_substages import part_ms, subscopes_of


def read(record):
    if "attn_core_swa" not in (subscopes_of(record.get("sim")) or ()):
        return None
    parts = [part_ms(record, part) for part in ("attn_in", "attn_out")]
    return None if None in parts else sum(parts)

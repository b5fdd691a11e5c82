"""Device time (ms) one execution of the round's program spends in this
model's routed experts (scope `lm_experts`: the sort, the dispatch, the
grouped products of the 40 held experts, the combine). The double of
`lm_experts_ms.device`, whose entry this PR leaves as it is (ISSUE 31),
forward, recomputation and backward, the held-out windows' forward
included. Median over the traced executions of the self time of that
scope's instructions: the device trace's "XLA Ops", joined to the program's
scopes through its compiled HLO (`benchmark/stages.py`) with the model's
own vocabulary (`benchmark/lm_stages.py`). Nothing to read (None) where the
traced program's model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("lm_experts")

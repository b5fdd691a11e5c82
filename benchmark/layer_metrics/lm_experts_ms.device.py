"""Device time (ms) one execution of the round's program spends in the
routed experts (scope `lm_experts`: the sort by expert, the grouped
products over the held experts, the unsort and the weighted sum), forward,
recomputation and backward.
Median over the traced executions of the self time of that scope's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`) with the model's own
vocabulary (`benchmark/lm_stages.py`)."""

from benchmark.lm_stages import scope_total


def read(record):
    return scope_total(record, "lm_experts")

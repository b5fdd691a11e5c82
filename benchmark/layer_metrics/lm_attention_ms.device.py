"""Device time (ms) one execution of the round's program spends in the
attention blocks (scope `lm_attention`: norms, projections with their
adapters, rotary, scores, softmax, the per-head gate, the output
projection), forward, recomputation and backward, the held-out windows'
forward included.
Median over the traced executions of the self time of that scope's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`) with the model's own
vocabulary (`benchmark/lm_stages.py`)."""

from benchmark.lm_stages import scope_total


def read(record):
    return scope_total(record, "lm_attention")

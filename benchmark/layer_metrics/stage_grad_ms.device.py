"""Device time (ms) one execution of the round's program spends on the
peers' loss gradients and their clip (scope `round_grad`, vmapped over the
sampled peers).
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "round_grad")

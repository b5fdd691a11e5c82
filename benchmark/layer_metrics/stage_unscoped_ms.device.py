"""Device time (ms) of one execution of the round's program that no stage
could be charged with: instructions the rules of `benchmark/stages.py`
could not place (`unscoped`) and fusions that compute for several stages
(`mixed`).
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "unscoped", "mixed")

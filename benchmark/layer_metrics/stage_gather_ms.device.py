"""Device time (ms) one execution of the round's program spends moving
shards (scope `round_gather`: the sampled peers' whole shards out of the
stack, then each peer's minibatch rows, with the layout copies the compiler
makes for them).
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "round_gather")

"""Device time (ms) one execution of the round's program spends after the
defence (scopes `round_aggregate` + `round_ledger` + `round_eval`: the
masked sum and w + agg, the stake scatter, the test error).
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "round_aggregate", "round_ledger", "round_eval")

"""Device time (ms) one execution of the round's program spends on the
defence (scopes `krum_prepare` + `krum_scores` + `krum_select`: cast, pad
and norms; the scores; the accept mask).
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "krum_prepare", "krum_scores", "krum_select")

"""Device time (ms) one execution of the round's program spends on Krum's
scoring alone (scope `krum_scores`: the Pallas kernel on its side of the
dispatch, Gram matmul + distances + top_k on the other). Inside
`stage_defence_ms.device`, not beside it.
Median over the traced executions of the self time of that stage's
instructions: the device trace's "XLA Ops", joined to the program's scopes
through its compiled HLO (`benchmark/stages.py`)."""

from benchmark.stages import stages_total


def read(record):
    return stages_total(record, "krum_scores")

"""Device time (ms) one execution of the round's program spends in the
attention core alone (scope `mla_core`: `softmax(s q k^T + causal) v` of
the 128 heads, whichever side of ops/attention.py's dispatch runs; at the
published size the fused kernel's calls `attention_forward*` and
`attention_backward*` and the relayouts the compiler puts around them),
forward, recomputation and backward, the held-out windows' forward
included. Median over the traced executions of the self time of that
scope's instructions: the device trace's "XLA Ops", joined to the program's
scopes through its compiled HLO (`benchmark/stages.py`) with the model's
own vocabulary (`benchmark/lm_stages.py`). Nothing to read (None) where the
traced program's model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("mla_core")

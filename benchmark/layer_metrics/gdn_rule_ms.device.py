"""Device time (ms) one execution of the round's program spends in the
gated delta rule alone (scope `gdn_rule`: from the two l2 norms to o_t;
biscotti_tpu/ops/delta_rule.py, whatever implements it), of the nine
delta-net layers, forward, recomputation and backward, the held-out
windows' forward included. Median over the traced executions of the self
time of that scope's instructions: the device trace's "XLA Ops", joined to
the program's scopes through its compiled HLO (`benchmark/stages.py`) with
the model's own vocabulary (`benchmark/lm_stages.py`). Nothing to read
(None) where the traced program's model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("gdn_rule")

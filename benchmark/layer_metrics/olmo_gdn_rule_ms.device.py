"""Device time (ms) one execution of the round's program spends in
Olmo-Hybrid-7B's gated delta rule alone (scope `gdn_rule`: from the two l2
norms to o_t, the zero columns its heads are laid in whole lane tiles with
included; biscotti_tpu/ops/delta_rule.py), of the twelve delta-net layers,
forward, recomputation and backward, the held-out windows' forward
included. Median over the traced executions of the self time of that
scope's instructions, joined to the program's scopes through its compiled
HLO (`benchmark/lm_stages.py`). Nothing to read (None) where the traced
model is not the dense delta-net hybrid (`benchmark/olmo_stages.py`)."""

from benchmark.olmo_stages import total


def read(record):
    return total(record, "gdn_rule")

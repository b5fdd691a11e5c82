"""Device time (ms) one execution of the round's program spends in the latent-
attention block around its core (scope `mla_proj`: the block norm, the
query and the key/value compressions with their inner norms, the decoupled
rotary, the adapters, the head-major transposes, the output projection),
forward, recomputation and backward, the held-out windows' forward
included. Median over the traced executions of the self time of that
scope's instructions: the device trace's "XLA Ops", joined to the program's
scopes through its compiled HLO (`benchmark/stages.py`) with the model's
own vocabulary (`benchmark/lm_stages.py`). Nothing to read (None) where the
traced program's model opens no such scope."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("mla_proj")

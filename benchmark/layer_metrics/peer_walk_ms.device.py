"""Device time (ms) one execution of the round's program spends on the walk
of a block's peers itself (scope `peer_walk`, opened by
`models/lm.py:peer_at_a_time` around its `lax.map`; what the loop's body
computes opens its own scope inside and is read there): a peer's rows
sliced out of the block's arrays, the results and the residuals kept for
the backward pass stacked with `dynamic-update-slice`s, their zero
buffers, the loop's counters. Median over the traced executions of the
self time of that scope's instructions: the device trace's "XLA Ops",
joined to the program's scopes through its compiled HLO
(`benchmark/stages.py`) with the model's own vocabulary
(`benchmark/lm_stages.py`). Nothing to read (None) where the traced
program's model declares no such scope: before PR 36 the loop's
instructions carried no scope and were booked to their neighbours'
(`mla_core`, `lm_dense`, `peer_clip`, `lm_head_loss`), and a block of one
peer walks nothing."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    return found and found["stages"].get("peer_walk")

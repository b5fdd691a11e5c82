"""A second vocabulary for the stage join of `benchmark/stages.py`: the
scopes a language model opens INSIDE the round's `round_grad` stage
(`lm_embed`, `lm_attention`, `lm_router`, `lm_experts`, `lm_dense`,
`lm_head_loss`, `peer_clip`; the program's own list is
`biscotti_tpu.models.laguna.SCOPES`). They are not in `sim.STAGES`: a
traced instruction takes the LAST stage token of its `op_name`, so a
nested stage would take its time out of `stage_grad_ms.device` and break
the seven stage metrics' partition. Read through the same table with this
vocabulary instead, an instruction is `unscoped` unless one of these scopes
holds it; the scopes the round's evaluation passes through too (the
held-out windows' forward, under `round_eval`) count where they run.

Imports nothing of the program: a traced object whose model declares no
scopes (any commit before they existed, any classifier) reads as "nothing
to read", never as an error.
"""

import sys

from benchmark import stages


def scopes_of(sim):
    """The scope names the traced object's model declares, or None."""
    info = getattr(getattr(sim, "model", None), "info", None) or {}
    module = sys.modules.get(type(info.get("config")).__module__)
    return getattr(module, "SCOPES", None)


def scope_ms(record):
    """`stages.stage_table` of the run's traced slice under the model's own
    scopes; None where there is nothing to read. Kept on the record."""
    if "_lm_scope_ms" not in record:
        record["_lm_scope_ms"] = None
        sim = record.get("sim")
        scopes = scopes_of(sim)
        round_hlo = getattr(sim, "round_hlo", None)
        loaded = stages._loaded(record) if scopes and round_hlo else None
        if loaded:
            record["_lm_scope_ms"] = stages.stage_table(loaded, round_hlo(),
                                                        scopes)
            stages.print_table(record["_lm_scope_ms"])
    return record["_lm_scope_ms"]


def scope_total(record, *scopes):
    """Sum of the named scopes' milliseconds an execution; None where
    `scope_ms` is None."""
    found = scope_ms(record)
    if found is None:
        return None
    return sum(found["stages"].get(scope, 0.0) for scope in scopes)

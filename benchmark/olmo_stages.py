"""What the readers of Olmo-Hybrid-7B's per-layer metrics share
(`benchmark/layer_metrics/olmo_*.py`): the stage join of
`benchmark/lm_stages.py`, read only where the traced model is the DENSE
delta-net hybrid: its scopes hold `gdn_rule` and `lm_dense` and no
`lm_router` (Qwen3-Next's hold a router, Granite's no rule). Any other
traced object, a commit before the model existed among them, reads as
"nothing to read", never as an error. Imports nothing of the program.
"""

from benchmark.lm_stages import scope_ms, scopes_of


def stages(record):
    """{scope: ms an execution} of the traced round under the model's own
    scopes, or None where the model is not the dense delta-net hybrid."""
    scopes = scopes_of(record.get("sim")) or ()
    if not {"gdn_rule", "lm_dense"} <= set(scopes) or "lm_router" in scopes:
        return None
    found = scope_ms(record)
    return found and found["stages"]


def total(record, *scopes):
    """Sum of the named scopes' milliseconds an execution, or None."""
    found = stages(record)
    if found is None or not set(scopes) & set(found):
        return None
    return sum(found.get(scope, 0.0) for scope in scopes)


def windows(record):
    """(sampled windows stepped, held-out windows evaluated) a round."""
    cfg = record["cfg"]
    return cfg.num_samples * cfg.batch_size, len(record["sim"].x_val)

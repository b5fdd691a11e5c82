"""Device time (ms) one execution of the round's program spends in what
surrounds this model's attention products and cores (parts `attn_norms` +
`attn_rotary` + `attn_layout`: the block norm; the partial rotary and its
concatenations; the fused product's split, the head-major transposes, the
casts and the values' scale), all seven layers, forward, recomputation and
backward. Read as `mimo_attn_proj_ms.device` is; None where the traced
program's model declares no part `attn_core_swa`."""

from benchmark.lm_substages import part_ms, subscopes_of


def read(record):
    if "attn_core_swa" not in (subscopes_of(record.get("sim")) or ()):
        return None
    parts = [part_ms(record, part)
             for part in ("attn_norms", "attn_rotary", "attn_layout")]
    return None if None in parts else sum(parts)

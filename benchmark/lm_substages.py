"""A third reading of the stage join of `benchmark/stages.py`: the parts a
language model names INSIDE its widest scope (`mla_proj` of
`biscotti_tpu.models.deepseek_v2`, `lm_attention` of `models.laguna`: the
module's second tuple, `SUBSCOPES`: `attn_norms`, `attn_in`, `attn_rotary`,
`attn_layout`, `attn_core`, `attn_out`). Each is opened nested in the
coarse scope, so under `SCOPES` alone the last token of such an
instruction is still the coarse one and `benchmark/lm_stages.py` reads
what it read; under `SCOPES + SUBSCOPES`, the table built here, the last
token is the part.

The vocabulary is the UNION on purpose. An instruction without a token
takes its neighbours' (`stages._place_the_rest`), so a table under
`SUBSCOPES` alone would hand the whole program (experts, head, Krum) to
six names of the attention block.

The fine table partitions the coarse scope: its parts + what stays under
the bare coarse token + what the finer vocabulary moves to `mixed` (a
fusion that computes for two PARTS of one scope was that scope's, and is
`mixed` now) and to `unscoped` = the coarse table's scope. Both tables
are printed on standard error side by side so that this can be checked by
eye, with the `mixed` fusions by the set of scopes they compute for.

Imports nothing of the program: a traced object whose model declares no
`SUBSCOPES` (any earlier commit, Granite, a classifier) reads as "nothing
to read", never as an error.
"""

import re
import sys
import time

from benchmark import lm_stages, stages


def subscopes_of(sim):
    """The parts the traced object's model declares, or None."""
    info = getattr(getattr(sim, "model", None), "info", None) or {}
    module = sys.modules.get(type(info.get("config")).__module__)
    return getattr(module, "SUBSCOPES", None)


def mixed_sets(computations, vocabulary):
    """{fusion: "a+b"} of the fusions that compute for several names of
    `vocabulary`, by `stages.stage_of_ops`'s own rule (the last token of
    each fused instruction that computes or moves something): what
    `mixed` is made of. `computations` is `stages.parse_hlo`'s."""
    token = re.compile(r"(?<![\w])(?:%s)(?![\w])"
                       % "|".join(re.escape(s) for s in vocabulary))

    def fused(computation, seen):
        for _, opcode, _, called, op_name in computations.get(computation,
                                                              ()):
            found = token.findall(op_name or "")
            if found and opcode not in stages.SHAPE_ONLY:
                seen.add(found[-1])
            for inner in called:
                fused(inner, seen)
        return seen

    sets = {}
    for instructions in computations.values():
        for name, opcode, _, called, _ in instructions:
            if opcode == "fusion":
                inner = set()
                for computation in called:
                    fused(computation, inner)
                if len(inner) > 1:
                    sets[name] = "+".join(sorted(inner))
    return sets


def mixed_by_set(table, sets):
    """[[set of scopes, ms an execution, operations], ...] of a stage
    table's `mixed` operations, by time."""
    total, count = {}, {}
    for name, stage, ms in table["ops"]:
        if stage == stages.MIXED:
            key = sets.get(name, "placed by its neighbours")
            total[key] = total.get(key, 0.0) + ms
            count[key] = count.get(key, 0) + 1
    return [[key, ms, count[key]]
            for key, ms in sorted(total.items(), key=lambda kv: -kv[1])]


def fine_table(loaded, hlo_text, scopes, parts, coarse=None):
    """`stages.stage_table` under `scopes + parts`, with what the table
    under `scopes` alone read beside it (`coarse`: that table, made here
    unless given): {"coarse": its stages, "mixed_sets" and
    "coarse_mixed_sets": `mixed_by_set` of either table, "seconds": what
    this took}."""
    began = time.perf_counter()
    vocabulary = tuple(scopes) + tuple(parts)
    coarse = coarse or stages.stage_table(loaded, hlo_text, scopes)
    fine = stages.stage_table(loaded, hlo_text, vocabulary)
    computations = stages.parse_hlo(hlo_text)
    fine.update(
        coarse=coarse["stages"],
        mixed_sets=mixed_by_set(fine, mixed_sets(computations, vocabulary)),
        coarse_mixed_sets=mixed_by_set(coarse,
                                       mixed_sets(computations, scopes)),
        seconds=time.perf_counter() - began)
    return fine


def fine_ms(record):
    """`fine_table` of the run's traced slice under the model's `SCOPES +
    SUBSCOPES`; None where there is nothing to read. Kept on the record:
    one more join a traced run, no compile (`round_hlo()` keeps its text,
    `stages._loaded` its trace, `lm_stages.scope_ms` the coarse table)."""
    if "_lm_fine_ms" not in record:
        record["_lm_fine_ms"] = None
        sim = record.get("sim")
        scopes, parts = lm_stages.scopes_of(sim), subscopes_of(sim)
        round_hlo = getattr(sim, "round_hlo", None)
        coarse = lm_stages.scope_ms(record) if scopes and parts else None
        if coarse and round_hlo:
            record["_lm_fine_ms"] = fine_table(
                stages._loaded(record), round_hlo(), scopes, parts, coarse)
            print_fine(record["_lm_fine_ms"], parts)
    return record["_lm_fine_ms"]


def print_fine(fine, parts, top=12):
    """The fine table beside the coarse one, on standard error."""
    def say(text):
        print(f"[benchmark] {text}", file=sys.stderr, flush=True)

    was, now = fine["coarse"], fine["stages"]
    say(f"parts of {stages.PROGRAM}'s scopes, ms an execution (median of "
        f"{fine['executions']}); read in {fine['seconds']:.1f} s")
    for part in parts:
        say(f"  part  {part:<16} {now.get(part, 0.0):9.4f}")
    say(f"  parts together         {sum(now.get(p, 0.0) for p in parts):9.4f}")
    say("  scope                coarse      fine  (fine: what stays under "
        "the bare token)")
    for scope in sorted(set(was) | (set(now) - set(parts)),
                        key=lambda s: -was.get(s, 0.0)):
        say(f"  scope {scope:<12} {was.get(scope, 0.0):9.4f} "
            f"{now.get(scope, 0.0):9.4f}")
    for label, sets in (("coarse", fine["coarse_mixed_sets"]),
                        ("fine", fine["mixed_sets"])):
        for key, ms, count in sets[:top]:
            say(f"  mixed, {label:<6} {key:<44} {ms:9.4f}  ({count} ops)")


def part_ms(record, part):
    """Milliseconds an execution of one declared part; None where the
    traced model declares no such part or there is no trace."""
    if part not in (subscopes_of(record.get("sim")) or ()):
        return None
    found = fine_ms(record)
    return found and found["stages"].get(part, 0.0)

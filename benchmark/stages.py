"""From a device trace to the round's stages: which stage of the round
program each traced instruction belongs to, the device time of each stage
per execution, and the program's own host spans on the same clock.

The trace names a device operation by its HLO instruction (`fusion.3`) and
carries no scope. The scope path lives in the compiled program: every
instruction of the optimized HLO text has `metadata={op_name="jit(round_
step)/round_gather/gather"}`, and the program under test names its stages
there (`jax.named_scope`; the vocabulary is the `STAGES` tuple of the
module that defines the traced object, and the text comes from its
`round_hlo()`). This file owns the join and the reduction; the readers in
`layer_metrics/stage_*.py` pick their stages from it. It imports nothing
of the program: a program without `round_hlo()` or `STAGES` (any commit
before they existed) reads as "nothing to read", never as an error.
"""

import bisect
import os
import re
import statistics
import sys

from benchmark import trace as trace_reduction
from benchmark.spans import program_runs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXED, UNSCOPED = "mixed", "unscoped"
SPAN_PREFIX = "biscotti:"  # the program's host spans (utils/profiling.py)
PROGRAM = "round_step"
# Instructions that only index or shape: inside a fusion they do no work of
# their own, and CSE hands one constant or broadcast to several stages
# under the name of the first, so they do not say what a fusion computes
# (nor does a nested fusion's own name: what it holds is looked into).
SHAPE_ONLY = frozenset(("parameter", "constant", "iota", "broadcast",
                        "bitcast", "reshape", "slice", "transpose", "tuple",
                        "get-tuple-element", "fusion"))

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_CALLED_SET = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")


# ------------------------------------------------------------ the HLO text


def _closing(text, start):
    """Index just past the bracket that closes the one at `start`."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _top_level(text, sep=","):
    """`text` split at the separators outside any bracket."""
    parts, depth, last = [], 0, 0
    for i, c in enumerate(text):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == sep and depth == 0:
            parts.append(text[last:i])
            last = i + 1
    parts.append(text[last:])
    return parts


def _instruction(rhs):
    """(opcode, operand names, called computations, op_name) of the text
    right of an instruction's ` = `: `<type> <opcode>(<operands>), <attrs>`.
    A tuple type is bracketed and holds spaces; no other type does."""
    after_type = _closing(rhs, 0) if rhs.startswith("(") else rhs.find(" ")
    rest = rhs[after_type:].lstrip()
    paren = rest.find("(")
    if paren < 0:
        return rest, [], [], None
    end = _closing(rest, paren)
    operands = [piece.split()[-1].lstrip("%")
                for piece in _top_level(rest[paren + 1:end - 1])
                if piece.strip()]
    attrs = rest[end:]
    called = _CALLED.findall(attrs)
    for group in _CALLED_SET.findall(attrs):
        called += [c.strip().lstrip("%") for c in group.split(",")
                   if c.strip()]
    name = _OP_NAME.search(attrs)
    return rest[:paren], operands, called, name.group(1) if name else None


def parse_hlo(hlo_text):
    """{computation: [(name, opcode, operands, called, op_name), ...]} of
    an HLO module's text, as `compiled.as_text()` prints it."""
    computations, current = {}, None
    for line in hlo_text.splitlines():
        if current is None:
            header = _HEADER.match(line)
            if header and not line[0].isspace():
                current = computations.setdefault(header.group(1), [])
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTR.match(line)
            if m:
                current.append((m.group(1),) + _instruction(m.group(2)))
    return computations


def stage_of_ops(hlo_text, stages):
    """{instruction name: stage} for every instruction of the module.

    An instruction's stage is the last of `stages` that its `op_name`
    holds as a whole token (`vmap(round_gather)` holds `round_gather`;
    `jit(krum_scores_pallas)` does not hold `krum_scores`). A fusion takes
    the stages of the instructions fused into it that compute or move
    something (not SHAPE_ONLY): one stage is its own, several make it
    `mixed`, none leaves it its own `op_name`. An instruction with no stage of its own (the
    compiler's layout copies, bitcasts, tuple plumbing) takes the stage of
    the instructions that read it when they agree, else that of the
    instructions whose results it reads when those agree. What is left is
    `unscoped`."""
    computations = parse_hlo(hlo_text)
    token = re.compile(r"(?<![\w])(?:%s)(?![\w])"
                       % "|".join(re.escape(s) for s in stages))

    def own(op_name):
        found = token.findall(op_name or "")
        return found[-1] if found else None

    def fused(computation, seen):
        """Stages of everything a fusion holds, nested calls included."""
        for _, opcode, _, called, op_name in computations.get(computation,
                                                              ()):
            stage = own(op_name)
            if stage and opcode not in SHAPE_ONLY:
                seen.add(stage)
            for inner in called:
                fused(inner, seen)
        return seen

    placed = {}
    for instructions in computations.values():
        stage = {}
        for name, opcode, _, called, op_name in instructions:
            inner = set()
            if opcode == "fusion":
                for computation in called:
                    fused(computation, inner)
            stage[name] = (MIXED if len(inner) > 1
                           else inner.pop() if inner else own(op_name))
        _place_the_rest(instructions, stage)
        for name, _, _, _, _ in instructions:
            placed[name] = stage[name] or UNSCOPED
    return placed


def _place_the_rest(instructions, stage):
    """Give the instructions of one computation that have no stage their
    readers' or, failing that, their writers'; in place."""
    readers = {name: [] for name, *_ in instructions}
    writers = {}
    for name, _, operands, _, _ in instructions:
        writers[name] = [o for o in operands if o in readers]
        for o in writers[name]:
            readers[o].append(name)

    def agreed(names, all_of_them):
        found = {stage[n] for n in names}
        if all_of_them and None in found:
            return None
        found.discard(None)
        return found.pop() if len(found) == 1 else None

    # strictest first, so that a label rests on all the evidence there
    # is: every reader placed and agreeing; then the readers that could be
    # placed at all (the rest is plumbing that ends in the ROOT tuple);
    # then the writers
    rules = ((readers, True), (readers, False), (writers, False))
    moved = True
    while moved:
        moved = False
        for table, all_of_them in rules:
            for name, *_ in instructions:
                if stage[name] is None and table[name]:
                    stage[name] = agreed(table[name], all_of_them)
                    moved = moved or stage[name] is not None
            if moved:
                break  # back to the strictest rule with what was learned


# --------------------------------------------------------------- the trace


def self_times(events):
    """Self time of each (start, end, name) event of one trace line: its
    duration less what the events nested directly in it cover (a `while`
    and the operations of its body are all events). Same order as given."""
    own = [end - start for start, end, _ in events]
    open_events = []
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][0], -events[i][1])):
        start, end, _ = events[i]
        while open_events and events[open_events[-1]][1] <= start:
            open_events.pop()
        if open_events:
            parent = open_events[-1]
            own[parent] -= min(end, events[parent][1]) - start
        open_events.append(i)
    return own


def read_xplane(path, wanted=None):
    """What the stage table needs of one `.xplane.pb`: dict(runs
    [(start_ns, duration_ns)] of the round's program, ops [(start, end,
    name)] sorted, of the plane that ran it, host {span name: [ms, ...]}
    of the program's `biscotti:` spans on the host planes). The program is
    the one named `*round_step*` whose executions took `wanted`
    milliseconds (the list `program_runs` gives), or without `wanted` the
    one that took most time. None where no plane ran such a program."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    best = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = trace_reduction._plane_reduce(plane)
            for name, runs in modules.items():
                if PROGRAM not in name:
                    continue
                if wanted is not None:
                    if [d / 1e6 for _, d in runs] == list(wanted):
                        best = (runs, ops)
                elif best is None or (sum(d for _, d in runs)
                                      > sum(d for _, d in best[0])):
                    best = (runs, ops)
    if best is None:
        return None
    host = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.setdefault(ev.name[len(SPAN_PREFIX):],
                                        []).append(ev.duration_ns / 1e6)
    return {"runs": best[0], "ops": sorted(best[1]), "host": host}


def stage_table(loaded, hlo_text, stages):
    """Device milliseconds of each stage in one execution of the round's
    program (median over the traced executions of that stage's self
    time), with what it was made from:

        {"stages": {stage: ms}, "busy_ms": median of an execution's sum,
         "executions": how many, "ops": [[instruction, stage, ms an
         execution], ...] by time, "missing": [instruction names the
         trace has and the HLO text has not]}

    `loaded` is `read_xplane`'s; `hlo_text` and `stages` are the traced
    program's (`Simulator.round_hlo()`, `parallel.sim.STAGES`)."""
    table = stage_of_ops(hlo_text, stages)
    ops = loaded["ops"]
    starts = [start for start, _, _ in ops]
    per_run, per_op, missing = [], {}, set()
    for start, duration in loaded["runs"]:
        inside = ops[bisect.bisect_left(starts, start):
                     bisect.bisect_left(starts, start + duration)]
        charged = {}
        for (_, _, name), own in zip(inside, self_times(inside)):
            if name not in table:
                missing.add(name)
            stage = table.get(name, UNSCOPED)
            charged[stage] = charged.get(stage, 0.0) + own / 1e6
            per_op[name] = per_op.get(name, 0.0) + own / 1e6
        per_run.append(charged)
    n = len(per_run)
    return {
        "stages": {stage: statistics.median(
            charged.get(stage, 0.0) for charged in per_run)
            for stage in sorted({s for c in per_run for s in c})},
        "busy_ms": statistics.median(sum(c.values()) for c in per_run),
        "executions": n,
        "ops": [[name, table.get(name, UNSCOPED), ms / n] for name, ms
                in sorted(per_op.items(), key=lambda kv: -kv[1])],
        "missing": sorted(missing),
    }


def print_table(found, top=24):
    """The stage table on standard error, for whoever reads the run."""
    def say(text):
        print(f"[benchmark] {text}", file=sys.stderr, flush=True)

    say(f"stages of {PROGRAM}, ms an execution (median of "
        f"{found['executions']}); an execution's sum "
        f"{found['busy_ms']:.4f}")
    for stage, ms in sorted(found["stages"].items(), key=lambda kv: -kv[1]):
        say(f"  stage {stage:<16} {ms:9.4f}")
    for name, stage, ms in found["ops"][:top]:
        say(f"  op {name:<40} {stage:<16} {ms:9.4f}")
    if found["missing"]:
        say(f"  not in the HLO text: {found['missing']}")


# ------------------------------------------------- what the readers call


def _loaded(record):
    """`read_xplane` of the traced slice, once a run and kept on the
    record. `run.py` keeps the slice's directory to itself, so it is found
    by its rule: `<root>/.bench_trace/<cell>` (a record may name another
    under `trace_dir`; the tests do). None where there is no trace, or the
    file there is not the one `record["trace"]` was reduced from."""
    if "_xplane" not in record:
        record["_xplane"] = None
        wanted = program_runs(record, PROGRAM)
        if wanted:
            try:
                path = trace_reduction.newest_xplane(
                    record.get("trace_dir") or os.path.join(
                        ROOT, ".bench_trace", record["cell"]["name"]))
            except (FileNotFoundError, KeyError, TypeError):
                return None
            record["_xplane"] = read_xplane(path, wanted)
    return record["_xplane"]


def stage_ms(record):
    """`stage_table` of the run's traced slice, through the traced
    object's own `round_hlo()` and its module's `STAGES`; None where there
    is nothing to read: no trace, or a program that has neither. Kept on
    the record: one parse and one compile a run. Call it before the
    driver's `check` frees `record["sim"]`."""
    if "_stage_ms" not in record:
        record["_stage_ms"] = None
        sim = record.get("sim")
        round_hlo = getattr(sim, "round_hlo", None)
        stages = getattr(sys.modules.get(type(sim).__module__), "STAGES",
                         None)
        loaded = _loaded(record) if round_hlo and stages else None
        if loaded:
            record["_stage_ms"] = stage_table(loaded, round_hlo(), stages)
            print_table(record["_stage_ms"])
    return record["_stage_ms"]


def stages_total(record, *stages):
    """Sum of the named stages' milliseconds; a stage the program has and
    this trace did not run counts 0.0; None where `stage_ms` is None."""
    found = stage_ms(record)
    if found is None:
        return None
    return sum(found["stages"].get(stage, 0.0) for stage in stages)


def host_span_median_ms(record, name):
    """Median milliseconds of the `biscotti:<name>` spans the program
    wrote into the traced slice (host planes, the profiler's clock), or
    None where it wrote none."""
    spans = (_loaded(record) or {"host": {}})["host"].get(name)
    return statistics.median(spans) if spans else None


def phase_total_s(clock, name):
    """Seconds a `PhaseClock` of the program (an object with `totals`)
    charged to `name`, or None where there is no such clock or phase."""
    return getattr(clock, "totals", {}).get(name)

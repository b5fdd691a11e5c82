"""The join of a device trace to the round program's stages
(`benchmark/stages.py`) and the thirteen readers that pick from it: on a
hand-written HLO text, on hand-made events, and on the small trace recorded
on the v5e (PR 23) with an HLO text written for a few of its instruction
names. CPU; nothing here reports a time."""

import gzip
import importlib.util
import json
import os
import shutil
import types

import pytest

from benchmark import stages, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STAGES = ("round_sample", "round_gather", "round_grad", "krum_scores")

# what `compiled.as_text()` prints, cut to what the join reads: a scoped
# fusion; a fusion that computes for two stages; one whose second stage is
# only a constant that CSE shared; a copy with no metadata read by one
# stage, one read by two; a `while` with a body; plumbing to the ROOT
HLO = """HloModule jit_round_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->(f32[8]{0}, s32[])}

FileNames
1 "/somewhere/sim.py"

%fused_gather (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %dynamic-slice.1 = f32[8]{0} dynamic-slice(%param_0), metadata={op_name="jit(round_step)/vmap(round_gather)/gather" stack_frame_id=3}
}

%fused_two (param_0.1: f32[8], param_1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %multiply.1 = f32[8]{0} multiply(%param_0.1, %param_1), metadata={op_name="jit(round_step)/vmap(round_grad)/mul"}
  ROOT %add.1 = f32[8]{0} add(%multiply.1, %param_1), metadata={op_name="jit(round_step)/round_sample/add"}
}

%fused_shared_constant (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %constant.7 = f32[] constant(2), metadata={op_name="jit(round_step)/round_sample/mul"}
  %broadcast.7 = f32[8]{0} broadcast(%constant.7), dimensions={}, metadata={op_name="jit(round_step)/round_sample/mul"}
  ROOT %multiply.2 = f32[8]{0} multiply(%param_0.2, %broadcast.7), metadata={op_name="jit(round_step)/vmap(round_grad)/transpose(jvp(mul))"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %v = f32[8]{0} get-tuple-element(%p), index=1
  %sort.9 = f32[8]{0} sort(%v), dimensions={0}, to_apply=%less, metadata={op_name="jit(round_step)/round_sample/jit(_shuffle)/while/body/sort"}
  %copy.9 = f32[8]{0:T(256)} copy(%sort.9)
  ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%i, %copy.9)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main.4 (x.1: f32[8]) -> (f32[8], s32[]) {
  %x.1 = f32[8]{0:T(256)} parameter(0), sharding={replicated}, metadata={op_name="x"}
  %fusion.1 = f32[8]{0:T(256)S(1)} fusion(f32[8]{0:T(256)} %x.1), kind=kLoop, calls=%fused_gather, metadata={op_name="jit(round_step)/vmap(round_gather)/gather" stack_frame_id=3}, backend_config={"flag_configs":[]}
  %copy.1 = f32[8]{0:T(128)} copy(%fusion.1), backend_config={"flag_configs":[]}
  %fusion.2 = f32[8]{0} fusion(%copy.1, %x.1), kind=kLoop, calls=%fused_two
  %copy.2 = f32[8]{0:T(512)} copy(%fusion.1)
  %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_shared_constant, metadata={op_name="jit(round_step)/vmap(round_grad)/transpose(jvp(mul))"}
  %custom-call.5 = f32[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_step)/jit(krum_scores_pallas)/krum_scores/pallas_call"}
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%constant.1, %fusion.3)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(round_step)/round_sample/jit(_shuffle)/while"}
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%while.1), index=1
  %get-tuple-element.2 = s32[] get-tuple-element(%while.1), index=0
  %bitcast.1 = f32[8]{0} bitcast(%custom-call.5)
  %copy.3 = s32[] copy(%get-tuple-element.2)
  %custom-call.6 = f32[8]{0} custom-call(%x.1), custom_call_target="Nobody", metadata={op_name="jit(round_step)/jit(krum_scores_pallas)/pad"}
  ROOT %tuple.2 = (f32[8]{0}, s32[]) tuple(%get-tuple-element.1, %copy.3)
}
"""


def test_parse_reads_names_operands_and_called_computations():
    computations = stages.parse_hlo(HLO)
    assert set(computations) == {"fused_gather", "fused_two",
                                 "fused_shared_constant", "body", "cond",
                                 "main.4"}
    entry = {i[0]: i for i in computations["main.4"]}
    assert entry["fusion.1"][1:4] == ("fusion", ["x.1"], ["fused_gather"])
    assert entry["fusion.2"][2] == ["copy.1", "x.1"]
    assert entry["while.1"][1] == "while"
    assert sorted(entry["while.1"][3]) == ["body", "cond"]
    assert entry["while.1"][4].endswith("jit(_shuffle)/while")
    assert entry["copy.1"][4] is None
    assert entry["tuple.2"][2] == ["get-tuple-element.1", "copy.3"]
    assert [i[0] for i in computations["body"]][-1] == "tuple.9"


@pytest.mark.parametrize("name, stage", [
    ("fusion.1", "round_gather"),        # a scoped fusion
    ("fusion.2", stages.MIXED),          # computes for two stages
    ("fusion.3", "round_grad"),          # the other stage is a constant
    ("copy.1", stages.MIXED),            # no metadata, one reader
    ("copy.2", "round_gather"),          # two readers disagree: its writer
    ("custom-call.5", "krum_scores"),    # the last token of the path
    ("custom-call.6", stages.UNSCOPED),  # `krum_scores_pallas` is no token
    ("while.1", "round_sample"),
    ("sort.9", "round_sample"),          # the body's own instruction
    ("copy.9", "round_sample"),          # a body's copy, through its writer
    ("get-tuple-element.1", "round_sample"),
    ("copy.3", "round_sample"),          # plumbing to the ROOT, its writer
    ("bitcast.1", "krum_scores"),        # never read: its writer
])
def test_stage_of_ops(name, stage):
    assert stages.stage_of_ops(HLO, STAGES)[name] == stage


def test_stage_of_ops_takes_text_without_percent_signs():
    bare = HLO.replace("%", "")
    assert stages.stage_of_ops(bare, STAGES) == \
        stages.stage_of_ops(HLO, STAGES)


def test_self_time_of_nested_events():
    events = [(0, 100, "while.1"), (10, 30, "sort.9"), (30, 50, "copy.9"),
              (60, 101, "sort.9"),      # one tick past its parent's end
              (101, 120, "fusion.1"),   # starts as the last ends: beside
              (130, 130, "bitcast.1")]
    own = stages.self_times(events)
    assert own == [100 - 20 - 20 - 40, 20, 20, 41, 19, 0]
    # the order given is the order returned
    assert stages.self_times(events[::-1]) == own[::-1]
    # two levels: a body's while inside a while
    nested = [(0, 50, "a"), (5, 45, "b"), (10, 20, "c")]
    assert stages.self_times(nested) == [10, 30, 10]


@pytest.fixture()
def traced(tmp_path):
    """A record as `run.py` hands it to the readers, made from the small
    trace recorded on the v5e: 3 executions of `jit_round_step`."""
    out = tmp_path / "plugins" / "profile" / "2026_09_27" / "v5e.xplane.pb"
    out.parent.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data", "small_v5e.xplane.pb.gz")) \
            as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    reduction = trace.reduce_xplane(trace.newest_xplane(str(tmp_path)))
    return {"cell": {"name": "a_cell"}, "trace": reduction,
            "trace_dir": str(tmp_path), "round_s": [0.001, 0.001, 0.001]}


# an HLO text for a few of the recorded trace's instruction names
SMALL_HLO = """HloModule jit_round_step

ENTRY %main (x.1: f32[10,320,24]) -> f32[25] {
  %x.1 = f32[10,320,24]{1,2,0:T(8,128)} parameter(0)
  %sort.38 = (u32[4,320]{1,0}, s32[4,320]{1,0}) sort(%x.1), dimensions={1}, to_apply=%lt, metadata={op_name="jit(round_step)/vmap(round_sample)/jit(_shuffle)/sort"}
  %sort.31 = u32[10]{0} sort(%x.1), dimensions={0}, to_apply=%lt, metadata={op_name="jit(round_step)/round_sample/jit(_shuffle)/sort"}
  %fusion.1 = f32[4,320,24]{1,2,0:T(8,128)S(1)} fusion(%x.1, %sort.31), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(round_step)/round_gather/gather"}
  %copy.3 = f32[4,320,24]{2,1,0:T(8,128)S(1)} copy(%fusion.1)
  %fusion.3 = f32[40,24]{1,0:T(8,128)S(1)} fusion(%copy.3, %sort.38), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(round_step)/vmap(round_gather)/gather"}
  ROOT %multiply_reduce_fusion.1 = f32[25]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(round_step)/vmap(round_grad)/transpose(jvp(dot_general))"}
}
"""


class _Sim:
    """Stands where the traced `Simulator` stands in a record."""

    calls = 0

    def round_hlo(self):
        type(self).calls += 1
        return SMALL_HLO


@pytest.fixture()
def program(monkeypatch):
    """The module of `_Sim` carries the vocabulary, as parallel/sim.py."""
    import sys

    monkeypatch.setattr(sys.modules[_Sim.__module__], "STAGES", STAGES,
                        raising=False)
    _Sim.calls = 0
    return _Sim()


def test_stage_ms_on_the_recorded_v5e_trace(traced, program, capsys):
    traced["sim"] = program
    found = stages.stage_ms(traced)
    assert found["executions"] == 3 and found["missing"]
    assert set(found["stages"]) == {"round_sample", "round_gather",
                                    "round_grad", stages.UNSCOPED}
    by_name = {name: stage for name, stage, _ in found["ops"]}
    assert by_name["sort.38"] == "round_sample"
    assert by_name["copy.3"] == "round_gather"  # no metadata: its reader
    assert by_name["multiply_reduce_fusion.1"] == "round_grad"
    assert by_name["custom-call.1"] == stages.UNSCOPED  # not in the text
    assert "custom-call.1" in found["missing"]
    assert "sort.38" not in found["missing"]
    # most of this text-less program is unscoped, and nothing is lost: an
    # execution's stages add up to the time its operations kept the
    # device busy
    loaded = stages.read_xplane(trace.newest_xplane(traced["trace_dir"]))
    busiest = sorted(
        sum(b - a for a, b in trace._union(
            [(a, b) for a, b, _ in loaded["ops"]
             if start <= a < start + duration])) / 1e6
        for start, duration in loaded["runs"])
    assert found["busy_ms"] == pytest.approx(busiest[1], rel=1e-9)
    assert sum(found["stages"].values()) == pytest.approx(
        found["busy_ms"], rel=0.02)
    assert found["stages"][stages.UNSCOPED] > found["stages"]["round_grad"]
    # one parse and one compile a run, however many readers ask
    assert stages.stage_ms(traced) is found and program.calls == 1
    assert stages.stages_total(traced, "round_sample", "round_gather") == \
        pytest.approx(found["stages"]["round_sample"]
                      + found["stages"]["round_gather"])
    assert stages.stages_total(traced, "krum_scores") == 0.0
    assert "stage round_sample" in capsys.readouterr().err


def test_a_trace_of_another_run_is_not_read(traced, program):
    traced["sim"] = program
    traced["trace"] = dict(traced["trace"], programs={
        "jit_round_step": [ms * 1.5 for ms in
                           traced["trace"]["programs"]["jit_round_step"]]})
    assert stages.stage_ms(traced) is None and program.calls == 0


# ---- a trace of a program that names its stages, with its own HLO text:
# 3 rounds of a 10-peer creditcard Simulator (Krum + DP noise, 6 sampled)
# recorded on the v5e by PR 24, and what its `round_hlo()` returned there

PROGRAM_STAGES = ("round_sample", "round_gather", "round_grad",
                  "round_noise", "krum_prepare", "krum_scores",
                  "krum_select", "round_aggregate", "round_ledger",
                  "round_eval")


@pytest.fixture()
def scoped(tmp_path, monkeypatch):
    import sys

    out = tmp_path / "plugins" / "profile" / "2026_09_27" / "v5e.xplane.pb"
    out.parent.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data",
                                "small_v5e_scoped.xplane.pb.gz")) as src, \
            open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(HERE, "data", "small_v5e_scoped.hlo.txt.gz"),
                   "rt") as f:
        text = f.read()
    monkeypatch.setattr(sys.modules[_Sim.__module__], "STAGES",
                        PROGRAM_STAGES, raising=False)
    sim = _Sim()
    sim.round_hlo = lambda: text
    reduction = trace.reduce_xplane(trace.newest_xplane(str(tmp_path)))
    return {"cell": {"name": "a_cell"}, "trace": reduction, "sim": sim,
            "trace_dir": str(tmp_path), "round_s": [0.002, 0.002, 0.002]}


def test_every_traced_instruction_of_a_scoped_program_is_placed(scoped):
    found = stages.stage_ms(scoped)
    assert found["executions"] == 3
    assert found["missing"] == []  # every traced name is in round_hlo()
    assert set(PROGRAM_STAGES) - set(found["stages"]) <= {"krum_prepare"}
    by_name = {name: stage for name, stage, _ in found["ops"]}
    assert by_name["sort.39"] == "round_sample"  # the 6 x 320 permutations
    assert by_name["custom-call.2"] == "krum_scores"
    # what the rules could not place is a sliver of this program
    apart = (found["stages"].get(stages.UNSCOPED, 0.0)
             + found["stages"].get(stages.MIXED, 0.0))
    assert apart < 0.2 * found["busy_ms"]
    assert found["stages"].get(stages.UNSCOPED, 0.0) < \
        0.03 * found["busy_ms"]
    parts = [n for n in NEW if n.startswith("stage_")]
    assert sum(_reader(n)(scoped) for n in parts) == pytest.approx(
        found["busy_ms"], rel=0.02)
    assert 0 < _reader("krum_kernel_ms.device")(scoped) <= \
        _reader("stage_defence_ms.device")(scoped)


def test_the_programs_own_spans_are_read_from_the_host_planes(scoped):
    """`biscotti:sim.round.args` and `.dispatch`, three of each, on the
    profiler's clock: the medians, in milliseconds."""
    loaded = stages._loaded(scoped)
    assert {k: len(v) for k, v in loaded["host"].items()} == {
        "sim.round.args": 3, "sim.round.dispatch": 3}
    args = _reader("host_args_ms.device")(scoped)
    dispatch = _reader("host_dispatch_ms.device")(scoped)
    assert args == sorted(loaded["host"]["sim.round.args"])[1]
    assert dispatch == sorted(loaded["host"]["sim.round.dispatch"])[1]
    assert 0.01 < args < 5 and 0.01 < dispatch < 5
    assert stages.host_span_median_ms(scoped, "no.such.span") is None


NEW = ["stage_sample_ms.device", "stage_gather_ms.device",
       "stage_grad_ms.device", "stage_noise_ms.device",
       "stage_defence_ms.device", "krum_kernel_ms.device",
       "stage_aggregate_ms.device", "stage_unscoped_ms.device",
       "host_args_ms.device", "host_dispatch_ms.device",
       "setup_shard_draw_s.device", "setup_stack_s.device",
       "setup_to_device_s.device"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_without_a_trace(name, traced,
                                                    monkeypatch):
    """A run without a trace; a traced run of a program from before the
    scopes, the spans and the clocks (the parent commit, which the driver
    runs with these files laid over it): None, and nothing raises."""
    import sys

    monkeypatch.setitem(sys.modules, "biscotti_tpu.data.datasets",
                        types.ModuleType("datasets_before_the_clock"))
    read = _reader(name)
    assert read({"cell": {"name": "a_cell"}, "round_s": [0.04],
                 "trace": None}) is None
    assert read({"cell": {"name": "no_such_cell_was_traced"},
                 "round_s": [0.04], "sim": object(),
                 "trace": traced["trace"]}) is None
    traced["sim"] = object()  # no round_hlo, no phases, no STAGES
    assert read(traced) is None


def test_the_readers_pick_their_stages_spans_and_clocks(traced, program,
                                                        monkeypatch):
    import sys

    traced["sim"] = program
    program.phases = types.SimpleNamespace(
        totals={"sim.stack": 3.5, "sim.to_device": 7.25})
    loader = types.ModuleType("datasets")
    loader.CLOCK = types.SimpleNamespace(totals={"shard_draw": 88.0})
    monkeypatch.setitem(sys.modules, "biscotti_tpu.data.datasets", loader)
    found = stages.stage_ms(traced)["stages"]
    assert _reader("stage_sample_ms.device")(traced) == \
        found["round_sample"]
    assert _reader("stage_gather_ms.device")(traced) == \
        found["round_gather"]
    assert _reader("stage_grad_ms.device")(traced) == found["round_grad"]
    assert _reader("stage_unscoped_ms.device")(traced) == \
        found[stages.UNSCOPED]
    for absent in ("stage_noise_ms.device", "stage_defence_ms.device",
                   "krum_kernel_ms.device", "stage_aggregate_ms.device"):
        assert _reader(absent)(traced) == 0.0
    # the seven that partition the program add up to what it was busy
    parts = [n for n in NEW if n.startswith("stage_")]
    assert len(parts) == 7
    assert sum(_reader(n)(traced) for n in parts) == pytest.approx(
        stages.stage_ms(traced)["busy_ms"], rel=0.02)
    assert _reader("setup_stack_s.device")(traced) == 3.5
    assert _reader("setup_to_device_s.device")(traced) == 7.25
    assert _reader("setup_shard_draw_s.device")(traced) == 88.0
    # the recorded trace is of a program from before the spans
    assert _reader("host_args_ms.device")(traced) is None
    traced["_xplane"]["host"] = {"sim.round.args": [0.4, 0.2, 0.3],
                                 "sim.round.dispatch": [1.0, 3.0]}
    assert _reader("host_args_ms.device")(traced) == 0.3
    assert _reader("host_dispatch_ms.device")(traced) == 2.0


def test_the_thirteen_entries_are_appended_and_have_their_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[-13:] == NEW
    for m in per_layer[-13:]:
        assert "workloads" not in m  # read in every cell that moves it
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "device_round_ms")
        assert m["source"] == ("device_trace" if "stage_" in m["name"]
                               or "krum" in m["name"] else "program_span")

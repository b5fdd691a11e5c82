"""The parts of a language model's attention scope
(`benchmark/lm_substages.py`) and the seven readers that pick from it and
from `lm_stages.scope_ms`: on a hand-written HLO text and hand-made events.
CPU; nothing here reports a time."""

import importlib.util
import os
import sys
import types

import pytest

from benchmark import lm_stages, lm_substages, stages

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SCOPES = ("lm_attention", "lm_experts", "peer_walk")
SUBSCOPES = ("attn_norms", "attn_in", "attn_layout", "attn_core", "attn_out")
PATH = "jit(round_step)/round_grad/jit(_layer_as)/peer_walk/while/body/"

# a walked attention block and the experts that read it, cut to what the
# join reads: a product under a part; a copy with no metadata between two
# parts; the kernel's call; a fusion that computes for two PARTS (the
# coarse scope's own under SCOPES, `mixed` under the union); the residual
# under the bare coarse token; the loop's slice; the experts, which name
# no part and read the block's last product
HLO = f"""HloModule jit_round_step, is_scheduled=true

%fused_in (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %dot.1 = f32[8]{{0}} dot(%param_0, %param_0), metadata={{op_name="{PATH}lm_attention/attn_in/dot_general"}}
}}

%fused_layout (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %copy.2 = f32[8]{{0}} copy(%param_0.1), metadata={{op_name="{PATH}lm_attention/attn_layout/transpose"}}
}}

%fused_norm_in (param_0.2: f32[8]) -> f32[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %multiply.3 = f32[8]{{0}} multiply(%param_0.2, %param_0.2), metadata={{op_name="{PATH}lm_attention/attn_norms/mul"}}
  ROOT %dot.3 = f32[8]{{0}} dot(%multiply.3, %param_0.2), metadata={{op_name="{PATH}lm_attention/attn_in/dot_general"}}
}}

%fused_out (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  ROOT %dot.4 = f32[8]{{0}} dot(%param_0.3, %param_0.3), metadata={{op_name="{PATH}lm_attention/attn_out/dot_general"}}
}}

%fused_experts (param_0.4: f32[8]) -> f32[8] {{
  %param_0.4 = f32[8]{{0}} parameter(0)
  ROOT %dot.5 = f32[8]{{0}} dot(%param_0.4, %param_0.4), metadata={{op_name="jit(round_step)/round_grad/lm_experts/dot_general"}}
}}

ENTRY %main.1 (x.1: f32[8]) -> (f32[8]) {{
  %x.1 = f32[8]{{0}} parameter(0)
  %dynamic-slice.9 = f32[8]{{0}} dynamic-slice(%x.1), metadata={{op_name="{PATH}dynamic_slice"}}
  %fusion.10 = f32[8]{{0}} fusion(%dynamic-slice.9), kind=kOutput, calls=%fused_in
  %copy.11 = f32[8]{{0:T(256)}} copy(%fusion.10)
  %fusion.12 = f32[8]{{0}} fusion(%copy.11), kind=kLoop, calls=%fused_layout
  %custom-call.13 = f32[8]{{0}} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={{op_name="{PATH}lm_attention/attn_core/pallas_call"}}
  %fusion.14 = f32[8]{{0}} fusion(%custom-call.13), kind=kOutput, calls=%fused_norm_in
  %fusion.15 = f32[8]{{0}} fusion(%fusion.14), kind=kOutput, calls=%fused_out
  %add.16 = f32[8]{{0}} add(%fusion.15, %x.1), metadata={{op_name="jit(round_step)/round_grad/jit(_layer_as)/lm_attention/add"}}
  %fusion.17 = f32[8]{{0}} fusion(%fusion.15), kind=kOutput, calls=%fused_experts
  %copy.18 = f32[8]{{0:T(256)}} copy(%fusion.17)
  ROOT %tuple.1 = (f32[8]{{0}}) tuple(%copy.18, %add.16)
}}
"""

# nanoseconds each instruction runs in one execution, in program order
NS = {"dynamic-slice.9": 7, "fusion.10": 100, "copy.11": 11,
      "fusion.12": 30, "custom-call.13": 60, "fusion.14": 45,
      "fusion.15": 80, "add.16": 5, "fusion.17": 400, "copy.18": 13}
ATTENTION = sum(NS.values()) - NS["dynamic-slice.9"] - NS["fusion.17"] \
    - NS["copy.18"]


def _loaded(executions=3):
    """What `stages.read_xplane` returns, hand-made: the same execution
    `executions` times, its operations one after the other."""
    runs, ops, clock = [], [], 0
    for _ in range(executions):
        start = clock
        for name, ns in NS.items():
            ops.append((clock, clock + ns * 1_000_000, name))
            clock += ns * 1_000_000
        runs.append((start, clock - start))
        clock += 1_000_000
    return {"runs": runs, "ops": ops, "host": {}}


class _Config:
    """Stands where a model's config dataclass stands: its MODULE carries
    the vocabularies (here this test file)."""


class _Sim:
    calls = 0

    def __init__(self):
        self.model = types.SimpleNamespace(info={"config": _Config()})

    def round_hlo(self):
        type(self).calls += 1
        return HLO


@pytest.fixture()
def traced(monkeypatch):
    """A traced run's record of a program whose model declares both
    tuples, as models/laguna.py does."""
    module = sys.modules[_Config.__module__]
    monkeypatch.setattr(module, "SCOPES", SCOPES, raising=False)
    monkeypatch.setattr(module, "SUBSCOPES", SUBSCOPES, raising=False)
    _Sim.calls = 0
    return {"cell": {"name": "a_cell"}, "sim": _Sim(),
            "_xplane": _loaded()}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


NEW = ["peer_walk_ms.device", "attn_norms_ms.device", "attn_in_ms.device",
       "attn_rotary_ms.device", "attn_layout_ms.device",
       "attn_out_ms.device", "attn_core_ms.device"]


def test_the_parts_and_the_bare_remainder_add_up_to_the_coarse_scope(
        traced, capsys):
    coarse = lm_stages.scope_ms(traced)["stages"]
    fine = lm_substages.fine_ms(traced)
    now = fine["stages"]
    assert coarse == {"lm_attention": ATTENTION, "lm_experts": 413.0,
                      "peer_walk": 7.0}
    # the copy between two parts is its reader's; the fusion of two parts
    # is `mixed`; the residual add stays under the bare token
    by_name = {name: stage for name, stage, _ in fine["ops"]}
    assert by_name["copy.11"] == "attn_layout"
    assert by_name["fusion.14"] == stages.MIXED
    assert by_name["add.16"] == "lm_attention"
    assert now["attn_in"] == 100 and now["attn_layout"] == 41
    assert now["attn_core"] == 60 and now["attn_out"] == 80
    assert "attn_norms" not in now  # only inside the mixed fusion
    parts = sum(now.get(p, 0.0) for p in SUBSCOPES)
    assert parts + now["lm_attention"] + now[stages.MIXED] \
        - coarse.get(stages.MIXED, 0.0) == coarse["lm_attention"]
    assert now["lm_attention"] == NS["add.16"]
    # what names no part is read as it was
    assert now["lm_experts"] == coarse["lm_experts"]
    assert now["peer_walk"] == coarse["peer_walk"]
    assert fine["busy_ms"] == sum(NS.values())
    assert fine["mixed_sets"] == [["attn_in+attn_norms", 45.0, 1]]
    assert fine["coarse_mixed_sets"] == []
    # one more parse a run and no compile, however many readers ask
    assert lm_substages.fine_ms(traced) is fine
    err = capsys.readouterr().err
    assert "part  attn_layout" in err
    assert "mixed, fine   attn_in+attn_norms" in err
    assert "scope lm_attention" in err


def test_the_parts_alone_would_flood_the_program():
    """Why the fine table is read under SCOPES + SUBSCOPES: under the
    parts alone an instruction that names none takes its neighbours', and
    the experts' 413 ms read as the attention's way out."""
    alone = stages.stage_table(_loaded(), HLO, SUBSCOPES)
    by_name = {name: stage for name, stage, _ in alone["ops"]}
    assert by_name["fusion.17"] == by_name["copy.18"] == "attn_out"
    assert by_name["dynamic-slice.9"] == "attn_in"  # the loop's too
    assert alone["stages"]["attn_out"] == 80 + 413 + NS["add.16"]
    union = stages.stage_table(_loaded(), HLO, SCOPES + SUBSCOPES)
    assert union["stages"]["attn_out"] == 80


def test_the_readers_pick_their_parts(traced):
    now = lm_substages.fine_ms(traced)["stages"]
    assert _reader("peer_walk_ms.device")(traced) == 7.0
    assert _reader("attn_in_ms.device")(traced) == now["attn_in"]
    assert _reader("attn_layout_ms.device")(traced) == now["attn_layout"]
    assert _reader("attn_core_ms.device")(traced) == now["attn_core"]
    assert _reader("attn_out_ms.device")(traced) == now["attn_out"]
    # declared and never alone in an instruction: 0.0, a reading
    assert _reader("attn_norms_ms.device")(traced) == 0.0
    # a part this model does not declare: nothing to read
    assert _reader("attn_rotary_ms.device")(traced) is None
    assert _Sim.calls == 2  # the two tables; the real round_hlo() keeps it


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_where_there_is_nothing(name, traced,
                                                           monkeypatch):
    """A run without a trace; a traced object that is no language model;
    a model from before PR 36, or Granite: `SCOPES` without `peer_walk`
    and no `SUBSCOPES` (what the driver's run of the parent commit, with
    these files laid over it, meets). None, and nothing raises."""
    read = _reader(name)
    assert read({"cell": {"name": "a_cell"}, "round_s": [0.04],
                 "trace": None}) is None
    assert read({"cell": {"name": "a_cell"}, "sim": object(),
                 "_xplane": _loaded()}) is None
    no_trace = dict(traced, _xplane=None)
    assert read(no_trace) is None
    module = sys.modules[_Config.__module__]
    monkeypatch.delattr(module, "SUBSCOPES")
    monkeypatch.setattr(module, "SCOPES", ("lm_attention", "lm_experts"))
    before = {"cell": {"name": "a_cell"}, "sim": _Sim(),
              "_xplane": _loaded()}
    assert read(before) is None
    assert lm_substages.fine_ms(before) is None
    # the coarse table is still there for the readers that were
    assert lm_stages.scope_ms(before)["stages"]["lm_attention"] > ATTENTION

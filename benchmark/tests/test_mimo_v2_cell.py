"""The `device_round_swa` driver end to end on the CPU at a tiny mix (the
chip check lifted here only), each control coming out not correct, the
cores' FLOP count against XLA's own, the nine readers on a hand-made trace,
and the new cell's files found by the harness with no edit to a file that
was there."""

import importlib.util
import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mimo_v2_fedlora.device_round"
CONFIG = "mimo_v2.5_fedlora"
NEW_METRICS = ["mimo_attn_proj_ms.device", "mimo_swa_core_ms.device",
               "mimo_full_core_ms.device", "mimo_attn_other_ms.device",
               "mimo_experts_ms.device", "mimo_router_ms.device",
               "mimo_swa_core_roofline_share.device",
               "mimo_full_core_roofline_share.device",
               "mimo_round_mfu.device"]
CONTROLS = ["bfloat16", "no_sink", "sink_on_full", "no_window", "window_256",
            "rotary_full", "one_theta", "no_value_scale", "kv_heads_swapped",
            "softmax_router", "no_choice_bias", "no_renormalise"]


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_swa", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """A MiMoV2Config in the published config.json's keys, as a
    configuration file states them."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads[0],
        "swa_num_key_value_heads": cfg.kv_heads[1],
        "head_dim": cfg.head_dim, "v_head_dim": cfg.value_dim,
        "partial_rotary_factor": cfg.rotary_factor,
        "rope_theta": int(cfg.rope_theta[0]),
        "swa_rope_theta": int(cfg.rope_theta[1]),
        "sliding_window": cfg.window,
        "attention_value_scale": cfg.value_scale,
        "intermediate_size": cfg.dense_width,
        "moe_intermediate_size": cfg.expert_width,
        "n_routed_experts": cfg.experts_held, "n_shared_experts": None,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "layernorm_epsilon": cfg.eps, "tie_word_embeddings": False,
        "vocab_size": cfg.vocab,
        "published": {"n_routed_experts": cfg.num_experts},
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length,
                  "held_first_expert": cfg.first_expert},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny window / full attention cell:
    new files and new entries only."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("swa_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny", "mimo_v2_tiny")
    cfg = model.info["config"]
    config = published(cfg, model.d_in)
    # the lists whole, as a configuration's file keeps them: longer than
    # the layers held
    config["hybrid_layer_pattern"] = list(cfg.pattern) + [1, 0]
    config["moe_layer_freq"] = list(cfg.sparse) + [1, 1]
    config["model"].update(num_params=model.num_params,
                           frozen_params=lm.frozen_count(model))
    config.update(
        name="mimo_v2_tiny", source="a test", reduced=[], assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny", "model_name": "mimo_v2_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.005},
        # float32 program against the float64 reference; a router's tie is
        # a flip inside the band
        limits={"tiny_swa": {"w_next_leaf_gap": 2e-4, "err_gap": 0.04,
                             "logit_gap": 2e-5,
                             "router_flips_beyond_ties": 0}})
    with open(here / "configs" / "mimo_v2_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_swa_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_swa", trace_seconds=1)
    with open(here / "traffic" / "tiny_swa.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "mimo_v2_tiny", "source": "a test",
                             "file": "benchmark/configs/mimo_v2_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.swa", "config": "mimo_v2_tiny",
         "traffic": "tiny_swa", "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.swa")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.swa")
    driver = grown.load_module("drivers", "device_round_swa")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_swa_driver_end_to_end(grown):
    result = grown.run_cell("tiny.swa", 2**31 + 4321, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and says so


def test_sound_run_passes_every_check(record):
    driver, rec, sound = record
    assert all(ok for *_, ok in sound), sound
    assert {name for name, *_ in sound} == set(driver.LIMITS)
    assert rec["peer_block"] == 8 and rec["moe"]["tokens_dropped"][0] == 0
    # what the program says of its sinks: a third to two thirds of a row
    assert 0.3 < rec["detail"][0]["sink_mass"] < 0.8
    json.dumps([{n: v for n, v, *_ in sound}, rec["detail"],
                rec["end_to_end"], rec["peer_block"], rec["moe"]])


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("no_sink", "logit_gap"),
    ("sink_on_full", "logit_gap"),
    ("no_window", "logit_gap"),
    ("window_256", "logit_gap"),
    ("rotary_full", "logit_gap"),
    ("one_theta", "logit_gap"),
    ("no_value_scale", "logit_gap"),
    ("kv_heads_swapped", "logit_gap"),
    ("softmax_router", "router_flips_beyond_ties"),
    ("no_choice_bias", "router_flips_beyond_ties"),
    ("no_renormalise", "logit_gap"),
])
def test_each_control_comes_out_not_correct(record, control, by):
    driver, rec, _ = record
    assert control in driver.CONTROLS
    found = driver.check(rec, control=control)
    failed = {name for name, *_, ok in found if not ok}
    assert by in failed, (control, found)
    # what benchmark/controls.py prints of it: plain numbers
    json.dumps([{n: v for n, v, *_ in found}, rec.pop("detail")])


def test_every_control_of_the_issue_has_a_test():
    run = load_run(os.path.join(ROOT, "benchmark"))
    driver = run.load_module("drivers", "device_round_swa")
    assert set(driver.CONTROLS) == set(CONTROLS) and len(CONTROLS) == 12
    assert driver.CONTROLS["window_256"] == {"window": 256}


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.swa")
    driver = grown.load_module("drivers", "device_round_swa")
    from benchmark.compile_meter import CompileMeter

    def refused(config, said):
        with pytest.raises(RuntimeError, match=said):
            driver.run(cell=dict(cell, config=config),
                       fields=grown.biscotti_fields(cell, 1), seconds=0.1,
                       trace_dir=None, meter=CompileMeter(), t0=0.0)

    config = cell["config"]
    refused(dict(config, model=dict(config["model"], num_params=7)),
            "states 7")
    refused(dict(config, model=dict(config["model"], window_tokens=32)),
            "the program holds")
    refused(dict(config, sliding_window=8), "the program holds")
    refused(dict(config, swa_rope_theta=10000000), "the program holds")
    refused(dict(config, attention_value_scale=1.0), "the program holds")
    flipped = list(config["hybrid_layer_pattern"])
    flipped[1], flipped[3] = flipped[3], flipped[1]  # d stays what it was
    refused(dict(config, hybrid_layer_pattern=flipped), "the program holds")


# ------------------------------------------------------------ the counts


def test_core_flops_against_xla():
    """The dense [T, T] products as XLA counts them: XLA takes the whole
    square, T^2 pairs a head where the causal mask lets T (T + 1) / 2
    through and a window of w lets w T - w (w - 1) / 2; the backward is
    twice the forward (four products where two)."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops import mimo_v2 as count

    t, heads, kv, d, e = 256, 4, 2, 24, 16

    def dense(q, k, v):
        scores = jnp.einsum("hgid,hjd->hgij", q, k)
        return jnp.sum(jnp.einsum("hgij,hje->hgie", scores, v))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (kv, heads // kv, t, d), (kv, t, d), (kv, t, e))]
    square = jax.jit(dense).lower(*shapes).compile().cost_analysis()["flops"]
    causal = count.core_forward_flops(1, t, t, heads, kv, d, e)
    assert causal == heads * t * (t + 1) // 2 * (2 * d + 2 * e)
    assert 0.99 < square / (causal * 2 * t / (t + 1)) < 1.02
    assert count.pairs(t, 64) == 64 * t - 64 * 63 // 2
    assert count.pairs(t, 4 * t) == count.pairs(t, t) == t * (t + 1) // 2
    assert count.core_step_flops(3, t, 64, heads, kv, d, e) \
        == 9 * heads * count.pairs(t, 64) * (2 * d + 2 * e)
    # the published shapes: a window layer's queries see 6.1% of the
    # square and a full layer's 50.02%; a stepped window of a window layer
    # is 10.4 GFLOP x 3 and 384 MB of least bytes (bytes bind: 0.47 ms
    # against 0.16)
    assert round(count.pairs(2048, 128) / 2048 ** 2, 4) == 0.0606
    swa = (2048, 128, 64, 8, 192, 128)
    assert round(count.core_forward_flops(1, *swa) / 1e9, 2) == 10.40
    assert count.core_forward_bytes(1, *swa) == 2 * 2048 * (
        64 * 192 + 8 * 320) + 4 * 2048 * 64 * 128 == 127926272
    assert count.core_step_bytes(1, *swa) == 383778816


def test_round_flops_are_the_issues_count():
    """The products with frozen weights a token forward, twice that a
    stepped token, and the two cores and the adapters on top: a round of 21
    + 2 windows of 2,048 tokens; the attention block (products and core)
    about three quarters of a sparse layer's."""
    from benchmark.flops.mimo_v2 import (core_round, layers_of,
                                         round_model_flops)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        config = json.load(f)
    sparse = 2 * 4096 * 256 + 8 / 8 * 6 * 4096 * 2048
    dense = 6 * 4096 * 16384
    full = 2 * 4096 * 13568 + 2 * 8192 * 4096
    window = 2 * 4096 * 14848 + 2 * 8192 * 4096
    weights = dense + 6 * sparse + 2 * full + 5 * window + 2 * 4096 * 19072
    least = 2048 * (21 * 2 + 2) * weights
    got = round_model_flops(config, 21, 2)
    assert least < got < 1.15 * least
    assert round_model_flops(config, 42, 4) == 2 * got
    assert 200e12 < got < 220e12
    assert layers_of(config, "window") == 5 and layers_of(config, "full") == 2
    core = 64 * (128 * 2048 - 128 * 127 // 2) * 640 / 2048
    assert 0.7 < (window + core) / (window + core + sparse) < 0.8
    flops, moved = core_round(config, "window", 21, 2)
    assert flops == 5 * (21 * 3 + 2) * 64 * (128 * 2048 - 64 * 127) * 640
    assert 0.04 < moved / 819e9 < 0.06 and flops / 197e12 < moved / 819e9
    flops, moved = core_round(config, "full", 21, 2)
    assert flops == 2 * 65 * 64 * (2048 * 2049 // 2) * 640
    assert flops / 197e12 > moved / 819e9   # the full layers' are compute's


# ------------------------------------- the readers, on a hand-made trace

PATH = "jit(round_step)/round_grad/jit(_layer_of)/"
HLO = f"""HloModule jit_round_step, is_scheduled=true

ENTRY %main.1 (x.1: f32[8]) -> (f32[8]) {{
  %x.1 = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} multiply(%x.1, %x.1), metadata={{op_name="{PATH}lm_attention/attn_norms/mul"}}
  %fusion.2 = f32[8]{{0}} dot(%fusion.1, %x.1), metadata={{op_name="{PATH}lm_attention/attn_in/dot_general"}}
  %fusion.3 = f32[8]{{0}} add(%fusion.2, %x.1), metadata={{op_name="{PATH}lm_attention/attn_rotary/add"}}
  %fusion.4 = f32[8]{{0}} copy(%fusion.3), metadata={{op_name="{PATH}lm_attention/attn_layout/transpose"}}
  %custom-call.5 = f32[8]{{0}} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="{PATH}lm_attention/attn_core_swa/pallas_call"}}
  %custom-call.6 = f32[8]{{0}} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="{PATH}lm_attention/attn_core_full/pallas_call"}}
  %fusion.7 = f32[8]{{0}} dot(%custom-call.5, %custom-call.6), metadata={{op_name="{PATH}lm_attention/attn_out/dot_general"}}
  %fusion.8 = f32[8]{{0}} dot(%fusion.7, %x.1), metadata={{op_name="{PATH}lm_router/dot_general"}}
  %fusion.9 = f32[8]{{0}} dot(%fusion.8, %x.1), metadata={{op_name="{PATH}lm_experts/dot_general"}}
  ROOT %tuple.1 = (f32[8]{{0}}) tuple(%fusion.9)
}}
"""
# milliseconds each instruction runs in one execution, in program order
MS = {"fusion.1": 3, "fusion.2": 100, "fusion.3": 20, "fusion.4": 30,
      "custom-call.5": 400, "custom-call.6": 250, "fusion.7": 60,
      "fusion.8": 7, "fusion.9": 500}


def _loaded(executions=3):
    runs, ops, clock = [], [], 0
    for _ in range(executions):
        start = clock
        for name, ms in MS.items():
            ops.append((clock, clock + ms * 1_000_000, name))
            clock += ms * 1_000_000
        runs.append((start, clock - start))
        clock += 1_000_000
    return {"runs": runs, "ops": ops, "host": {}}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture()
def traced():
    """A traced run's record of a program whose model is MiMo-V2.5's: the
    config's MODULE carries the vocabularies."""
    from biscotti_tpu.models import mimo_v2

    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        config = json.load(f)
    sim = types.SimpleNamespace(
        model=types.SimpleNamespace(
            info={"config": mimo_v2.PRESETS["mimo_v2_tiny"]}),
        round_hlo=lambda: HLO, x_val=[0, 1])
    total = float(sum(MS.values()))
    return {"cell": {"name": CELL, "config": config}, "sim": sim,
            "cfg": types.SimpleNamespace(num_samples=21, batch_size=1),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"programs": {"jit_round_step(1)": [total] * 3}},
            "_xplane": _loaded()}


def test_the_readers_pick_their_parts_and_scopes(traced):
    from benchmark.flops import mimo_v2 as count

    got = {name: _reader(name)(traced) for name in NEW_METRICS}
    assert got["mimo_attn_proj_ms.device"] == 160.0
    assert got["mimo_swa_core_ms.device"] == 400.0
    assert got["mimo_full_core_ms.device"] == 250.0
    assert got["mimo_attn_other_ms.device"] == 53.0
    assert got["mimo_experts_ms.device"] == 500.0
    assert got["mimo_router_ms.device"] == 7.0
    config = traced["cell"]["config"]
    flops, moved = count.core_round(config, "window", 21, 2)
    assert got["mimo_swa_core_roofline_share.device"] == pytest.approx(
        max(flops / 197e12, moved / 819e9) / 0.4)
    flops, moved = count.core_round(config, "full", 21, 2)
    assert got["mimo_full_core_roofline_share.device"] == pytest.approx(
        max(flops / 197e12, moved / 819e9) / 0.25)
    assert got["mimo_round_mfu.device"] == pytest.approx(
        count.round_model_flops(config, 21, 2) / (1.37 * 197e12))
    # at these hand-made times all three are shares: in (0, 1.05]
    for name in NEW_METRICS[-3:]:
        assert 0.0 < got[name] <= 1.05, name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing(name, traced):
    """An empty record; a run without a trace; a traced object that is no
    language model; a sibling model, which splits no core by kind (what the
    driver's run of the parent commit, with these files laid over it,
    meets in any other cell). None, and nothing raises."""
    from biscotti_tpu.models import laguna

    read = _reader(name)
    assert read({}) is None
    assert read({"cell": {"name": CELL}, "round_s": [0.04],
                 "trace": None}) is None
    assert read({"cell": {"name": CELL}, "sim": object(),
                 "_xplane": _loaded()}) is None
    assert read(dict(traced, _xplane=None, trace=None)) is None
    sibling = dict(traced)
    sibling["sim"] = types.SimpleNamespace(
        model=types.SimpleNamespace(
            info={"config": laguna.PRESETS["laguna_tiny"]}),
        round_hlo=lambda: HLO, x_val=[0, 1])
    assert read(sibling) is None
    assert sys.modules[laguna.__name__].SUBSCOPES  # it has parts, not ours


# ----------------------------------------------------- the cell, by name


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["mix"]["driver"] == "device_round_swa"
    names = [m["name"] for m in cell["per_layer"]]
    # (at least: a later PR may widen an accepted metric to this cell)
    assert set(NEW_METRICS) <= set(names) and len(names) >= 17 + 9
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens_mimo"
    assert fields["model_name"] == "mimo_v2_fedlora"
    assert fields["noising"] is True and fields["poison_fraction"] == 0.0
    assert fields["seed"] == (2**31 + 9) % (2**31 - 1)
    mix = cell["mix"]
    assert (mix["warm_rounds"], mix["checked_rounds"],
            mix["trace_seconds"]) == (2, 1, 16)
    # its files exist, each where the harness looks for it by name
    for path in ("configs/mimo_v2.5_fedlora.json",
                 "traffic/device_round_swa_dp.json",
                 "drivers/device_round_swa.py", "reference/mimo_v2.py",
                 "flops/mimo_v2.py"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", path)), path
    # the other cells read none of the new metrics
    for other in ("emnist_softmax.device_round",
                  "laguna_fedlora.device_round",
                  "deepseek_v2_fedlora.device_round",
                  "granite_h_fedlora.device_round",
                  "qwen3_next_fedlora.device_round"):
        found = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert not set(NEW_METRICS) & found
    driver = run.load_module("drivers", "device_round_swa")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)
    assert all(v is not None for v in driver.limits_of(cell).values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended AFTER everything PR 39's file had, together and in the
    # issue's order: contiguous, in order, at least (a later PR appends
    # after them: "last" would turn this test red)
    config = [c["name"] for c in bench["configs"]].index(CONFIG)
    assert config >= 5 and bench["configs"][config]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert [w["name"] for w in bench["workloads"]].index(CELL) >= 5
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert first >= 50 and names[first:first + 9] == NEW_METRICS
    assert all(m["workloads"][0] == CELL
               and m["moves"] == "device_round_ms"
               and m["layer"] == "language model"
               and m["source"] == "device_trace"
               for m in bench["per_layer"][first:first + 9])
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200 and len(entry.get(
            "source", "")) <= 200


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "mimo_v2.py")) as f:
        text = f.read()
    assert "import biscotti_tpu" not in text
    assert "from biscotti_tpu" not in text


def test_the_configuration_carries_every_published_number():
    """Every key of the catalog row's `config` under the same key,
    unchanged but for the three in `reduced` (the two lists whole); the
    driver's sizes come out of the built model; every assumption of ISSUE
    40 is in the file."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        config = json.load(f)
    from biscotti_tpu.models import lm, mimo_v2

    preset = mimo_v2.PRESETS["mimo_v2_fedlora"]
    for key, value in published(preset, 2048).items():
        if key not in ("adapters", "model", "published"):
            assert config[key] == value, key
    assert tuple(config["hybrid_layer_pattern"][:7]) == preset.pattern
    assert tuple(config["moe_layer_freq"][:7]) == preset.sparse
    assert len(config["hybrid_layer_pattern"]) == 48
    assert preset.rank == config["adapters"]["rank"]
    assert preset.alpha == config["adapters"]["alpha"]
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 256,
                                   "vocab_size": 152576}
    model = mimo_v2.mimo_v2_model("mimo_v2_fedlora", preset, 2048)
    assert model.num_params == config["model"]["num_params"] == 2080768
    assert lm.frozen_count(model) == config["model"]["frozen_params"] \
        == 5847250752
    assert config["model"]["window_tokens"] == 2048
    assert "ixty-four v5e chips" in config["deployment"]
    said = " ".join(config["assumed"])
    for word in ("multi-token-prediction", "encoders", "[q | k | v]",
                 "attention_chunk_size", "hybrid_block_size",
                 "BOTH kinds of layer", "N(log 128, 1)", "N(0, 0.05^2)",
                 "unit variance", "joins its softmax's denominator",
                 "noaux_tc"):
        assert word in said, word
    assert any("all 256 experts and no routed token is dropped" in g
               for g in config["guarantees"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert config["source"].startswith(row["source_url"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == set(config["reduced"])

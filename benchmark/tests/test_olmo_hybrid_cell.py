"""The `device_round_gdn_dense` driver end to end on the CPU at a tiny mix
(the chip check lifted here only), each control coming out not correct,
the rule's and the round's counts at the MODEL's widths, the readers on a
record of their own, and the new cell's files found by the harness with no
edit to a file that was there."""

import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmo_hybrid_fedlora.device_round"
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "olmo_hybrid_7b_fedlora.json")
NEW_METRICS = ["olmo_gdn_rule_ms.device", "olmo_gdn_proj_ms.device",
               "olmo_gdn_mix_ms.device", "olmo_attention_ms.device",
               "olmo_dense_ms.device", "olmo_head_loss_ms.device",
               "olmo_gdn_rule_roofline_share.device",
               "olmo_gdn_padded_share.device", "olmo_round_mfu.device"]


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_gdn_dense", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """An OlmoHybridConfig in the published config.json's keys, as a
    configuration file states them."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "layer_types": [f"{kind}_attention" for kind in cfg.layer_types],
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads,
        "intermediate_size": cfg.mlp_width,
        "linear_num_key_heads": cfg.key_heads,
        "linear_num_value_heads": cfg.value_heads,
        "linear_key_head_dim": cfg.key_dim,
        "linear_value_head_dim": cfg.value_dim,
        "linear_conv_kernel_dim": cfg.conv, "rms_norm_eps": cfg.eps,
        "tie_word_embeddings": False, "vocab_size": cfg.vocab,
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length, "rule_chunk": cfg.chunk},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny cell of the dense delta-net
    hybrid: new files and new entries only."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("gdn_dense_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny", "olmo_hybrid_tiny")
    config = published(model.info["config"], model.d_in)
    config["model"].update(num_params=model.num_params,
                           frozen_params=lm.frozen_count(model),
                           rule_kernel=0)
    config.update(
        name="olmo_hybrid_tiny", source="a test", reduced=[], assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny",
                  "model_name": "olmo_hybrid_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.005},
        # float32 program against the float64 reference
        limits={"tiny_gdn_dense": {"w_next_leaf_gap": 1e-4, "err_gap": 0.04,
                                   "logit_gap": 1e-5}})
    with open(here / "configs" / "olmo_hybrid_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_gdn_dense_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_gdn_dense", trace_seconds=1)
    with open(here / "traffic" / "tiny_gdn_dense.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "olmo_hybrid_tiny", "source": "a test",
                             "file": "benchmark/configs/"
                                     "olmo_hybrid_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.gdn_dense", "config": "olmo_hybrid_tiny",
         "traffic": "tiny_gdn_dense", "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.gdn_dense")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.gdn_dense")
    driver = grown.load_module("drivers", "device_round_gdn_dense")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_gdn_dense_driver_end_to_end(grown):
    result = grown.run_cell("tiny.gdn_dense", 2**31 + 4848, 0.5, False,
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
    assert "router_flips_beyond_ties" not in driver.LIMITS  # no router
    assert rec["peer_block"] == 8 and "moe" not in rec
    # the checked round's detail carries the block the round walked
    assert rec["detail"][-1]["peer_block"] == 8
    json.dumps([{n: v for n, v, *_ in sound}, rec["detail"],
                rec["end_to_end"], rec["peer_block"]])


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("decay_bfloat16", "logit_gap"),
    ("beta_not_doubled", "logit_gap"),
    ("beta_one", "logit_gap"),
    ("no_delta", "logit_gap"),
    ("no_carry", "logit_gap"),
    ("no_l2norm", "logit_gap"),
    ("gate_before_norm", "logit_gap"),
    ("norm_before_mixer", "logit_gap"),
    ("no_qk_norm", "logit_gap"),
    ("rotary_on", "logit_gap"),
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
    driver = run.load_module("drivers", "device_round_gdn_dense")
    assert len(driver.CONTROLS) == 11


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.gdn_dense")
    driver = grown.load_module("drivers", "device_round_gdn_dense")
    from benchmark.compile_meter import CompileMeter

    for key, value, said in (("num_params", 7, "states 7"),
                             ("window_tokens", 32, "the program holds"),
                             ("rule_kernel", 1, "the program holds")):
        wrong = dict(cell, config=dict(cell["config"], model=dict(
            cell["config"]["model"], **{key: value})))
        with pytest.raises(RuntimeError, match=said):
            driver.run(cell=wrong, fields=grown.biscotti_fields(cell, 1),
                       seconds=0.1, trace_dir=None, meter=CompileMeter(),
                       t0=0.0)


def test_the_rule_is_counted_at_the_models_widths():
    """`flops/qwen3_next.py`'s count (imported, not copied) at 30 key heads
    of 96 serving 30 value heads of 192, whatever the kernel pads to: the
    state's three products 6 D E a token and head, the pairs inside a
    chunk, the substitution; 12 layers, 21 stepped windows and 2
    evaluated."""
    from benchmark.flops import olmo_hybrid as count
    from benchmark.flops import qwen3_next

    assert count.rule_forward_flops is qwen3_next.rule_forward_flops
    with open(CONFIG) as f:
        config = json.load(f)
    assert count.rule_shape(config) == (1024, 30, 96, 30, 192)
    assert count.rule_layers(config) == 12
    t, pairs, below = 1024, 1024 * 65 // 2, 1024 * 63 // 2
    forward = pairs * (4 * 30 * 96 + 2 * 30 * 192) \
        + below * 2 * 30 * (192 + 96) + 6 * t * 30 * 96 * 192
    assert count.rule_forward_flops(1, t, 30, 96, 30, 192) == forward
    padded = count.rule_forward_flops(1, t, 30, 128, 30, 256)
    assert 1.3 * forward < padded < 1.78 * forward  # what is NOT counted
    flops, moved = count.rule_round(config, 21, 2)
    assert flops == 12 * (21 * 3 + 2) * forward
    ins = 2 * 30 * 96 + 30 * 192 + 2 * 30
    assert moved == 12 * 4 * t * ((21 * 2 + 2) * 30 * 192
                                  + (21 * 3 + 2) * ins)


def test_round_flops_are_the_frozen_products_and_a_little():
    """7.43 GFLOP a token forward in products with frozen weights (12
    delta-net layers, 4 full layers, 16 SwiGLUs, the head over 100,352
    classes), twice that a stepped token, and the rule, the attention core
    and the adapters on top: a round of 21 + 2 windows."""
    from benchmark.flops.olmo_hybrid import round_model_flops

    with open(CONFIG) as f:
        config = json.load(f)
    weights = 12 * 2 * (3840 * 17280 + 3840 * 60 + 5760 * 3840) \
        + 4 * 2 * 4 * 3840 * 3840 + 16 * 6 * 3840 * 11008 \
        + 2 * 3840 * 100352
    assert round(weights / 1e9, 2) == 7.43
    least = 1024 * (21 * 2 + 2) * weights
    got = round_model_flops(config, 21, 2)
    assert least < got < 1.08 * least
    assert round_model_flops(config, 42, 4) == 2 * got


class _Sim:
    """What a reader asks of the traced object, with no program behind."""

    def __init__(self, module, info):
        config = type("Config", (), {"__module__": module})()
        self.model = type("Model", (), {"info": dict(info, config=config)})()
        self.x_val = [0, 0]


def test_the_readers_read_only_the_dense_delta_net_hybrid():
    """A record whose model opens Qwen3-Next's scopes (a router) or
    Granite's (no rule), or none, reads as nothing; the padded share is the
    program's own gauge."""
    from benchmark import olmo_stages
    from biscotti_tpu.models import (granite_hybrid, olmo_hybrid,  # noqa
                                     qwen3_next)

    run = load_run(os.path.join(ROOT, "benchmark"))
    for module in ("biscotti_tpu.models.qwen3_next",
                   "biscotti_tpu.models.granite_hybrid", "builtins"):
        record = {"sim": _Sim(module, {"gdn_rule": {"kernel": 1}})}
        assert olmo_stages.stages(record) is None, module
        for name in NEW_METRICS:
            assert run.load_module("layer_metrics", name).read(record) \
                is None, (module, name)
    mine = {"sim": _Sim("biscotti_tpu.models.olmo_hybrid",
                        {"gdn_rule": {"kernel": 1, "padded_share": 0.4375}}),
            "_lm_scope_ms": {"stages": {"gdn_rule": 700.0, "gdn_conv": 50.0,
                                        "gdn_gate": 25.0, "lm_dense": 900.0}}}
    read = lambda name: run.load_module(  # noqa: E731
        "layer_metrics", name).read(mine)
    assert read("olmo_gdn_rule_ms.device") == 700.0
    assert read("olmo_gdn_mix_ms.device") == 75.0
    assert read("olmo_dense_ms.device") == 900.0
    assert read("olmo_attention_ms.device") is None  # no such scope traced
    assert read("olmo_gdn_padded_share.device") == 0.4375
    with open(CONFIG) as f:
        mine.update(cell={"config": json.load(f)},
                    cfg=type("Cfg", (), {"num_samples": 21,
                                         "batch_size": 1})(),
                    device={"kind": "TPU v5 lite"})
    share = read("olmo_gdn_rule_roofline_share.device")
    assert 0.0 < share < 1.0
    mine["device"] = {"kind": "a chip with no peaks on file"}
    with pytest.raises(KeyError, match="no peaks"):
        read("olmo_gdn_rule_roofline_share.device")


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["mix"]["driver"] == "device_round_gdn_dense"
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) <= set(names) and len(names) == 17 + 9
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens_olmo"
    assert fields["noising"] is True and fields["poison_fraction"] == 0.0
    # the traffic is the four 1,024-token siblings' to the letter
    for sibling in ("device_round_gdn_dp", "device_round_ssm_dp"):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               f"{sibling}.json")) as f:
            other = json.load(f)
        for key in ("switches", "scale", "warm_rounds", "checked_rounds",
                    "trace_seconds", "tie_rel"):
            assert cell["mix"][key] == other[key], (sibling, key)
    # the other cells read none of the new metrics
    for other in ("emnist_softmax.device_round",
                  "qwen3_next_fedlora.device_round",
                  "granite_h_fedlora.device_round"):
        found = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert not set(NEW_METRICS) & found
    # every reader file loads, and finds nothing in an empty record
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read({}) is None
    driver = run.load_module("drivers", "device_round_gdn_dense")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)
    assert all(v is not None for v in driver.limits_of(cell).values())
    assert cell["mix"]["trace_seconds"] >= 12
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended AFTER everything PR 40's file had, together and in the
    # issue's order (no assertion that they are the LAST: a later PR
    # appends after them)
    config = [c["name"] for c in bench["configs"]].index(
        "olmo_hybrid_7b_fedlora")
    assert config >= 6
    assert bench["configs"][config]["reduced"] == ["num_hidden_layers"]
    assert [w["name"] for w in bench["workloads"]].index(CELL) >= 6
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert first >= 59 and names[first:first + 9] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "device_round_ms"
               and m["layer"] == "language model"
               for m in bench["per_layer"][first:first + 9])


def test_the_configuration_carries_every_published_number():
    """Every key of the catalog row's `config` under the same key, but
    `num_hidden_layers` (the one key `reduced` names); `layer_types` whole;
    the driver's sizes come out of the built model, and the table's counts
    add up."""
    with open(CONFIG) as f:
        config = json.load(f)
    from biscotti_tpu.models import lm, olmo_hybrid

    preset = olmo_hybrid.PRESETS["olmo_hybrid_fedlora"]
    for key, value in published(preset, 1024).items():
        if key == "layer_types":
            assert config[key][:16] == value and len(config[key]) == 32
        elif key not in ("adapters", "model"):
            assert config[key] == value, key
    assert preset.rank == config["adapters"]["rank"]
    assert preset.alpha == config["adapters"]["alpha"]
    assert preset.chunk == config["model"]["rule_chunk"]
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["linear_allow_neg_eigval"] is True
    assert config["rope_parameters"] == {"rope_theta": None}
    for key in ("deployment", "assumed", "guarantees", "precision",
                "limits", "parameters"):
        assert config[key], key
    assert "second stage" in config["deployment"]
    model = olmo_hybrid.olmo_hybrid_model("olmo_hybrid_fedlora", preset, 1024)
    assert model.num_params == config["model"]["num_params"] == 5038080
    assert lm.frozen_count(model) == config["model"]["frozen_params"] \
        == config["parameters"]["frozen"] == 4103615184
    assert model.info["gdn_rule"]["kernel"] == config["model"]["rule_kernel"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert config["source"].startswith(row["source_url"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == {"num_hidden_layers"}

"""The `device_round_lm` driver end to end on the CPU at a tiny mix (the
chip check lifted here only), each control coming out not correct, the
experts' FLOP count against XLA's own, and the new cell's files found by
the harness with no edit to a file that was there."""

import dataclasses
import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna_fedlora.device_round"
NEW_METRICS = {"lm_attention_ms.device", "lm_experts_ms.device",
               "lm_router_ms.device", "lm_head_loss_ms.device",
               "moe_load_max_over_mean.device", "experts_flops_share.device"}


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_lm", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """A LagunaConfig in the published config.json's keys, as a
    configuration file states them."""
    layers = cfg.layers
    return {
        "hidden_size": cfg.hidden, "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.kv_heads, "num_hidden_layers": layers,
        "num_attention_heads_per_layer": list(cfg.heads),
        "layer_types": [kind + "_attention" for kind in cfg.layer_types],
        "mlp_only_layers": list(cfg.dense_layers),
        "sliding_window": cfg.window, "intermediate_size": cfg.dense_width,
        "moe_intermediate_size": cfg.expert_width,
        "shared_expert_intermediate_size": cfg.shared_width,
        "num_experts": cfg.experts_held, "vocab_size": cfg.vocab,
        "num_experts_per_tok": cfg.top_k,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "rope_parameters": {
            "full_attention": dict(cfg.rope_full, rope_type="yarn"),
            "sliding_attention": dict(cfg.rope_sliding,
                                      rope_type="default")},
        "rms_norm_eps": cfg.eps,
        "published": {"num_experts": cfg.num_experts},
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length, "held_first_expert":
                  cfg.first_expert},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny Laguna cell: new files and new
    entries only."""
    from biscotti_tpu.models import laguna
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("lm_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny")
    config = published(model.info["config"], model.d_in)
    config["model"].update(num_params=model.num_params,
                           frozen_params=laguna.frozen_count(model))
    config.update(
        name="laguna_tiny", source="a test", reduced={}, assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny", "model_name": "laguna_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.05},
        # float32 program against the float64 reference
        limits={"tiny_lm": {"w_next_leaf_gap": 1e-4, "err_gap": 0.04,
                            "logit_gap": 1e-4}})
    with open(here / "configs" / "laguna_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_lm_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_lm", trace_seconds=1)
    with open(here / "traffic" / "tiny_lm.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "laguna_tiny", "source": "a test",
                             "file": "benchmark/configs/laguna_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.lm", "config": "laguna_tiny", "traffic": "tiny_lm",
         "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.lm")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.lm")
    driver = grown.load_module("drivers", "device_round_lm")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_lm_driver_end_to_end(grown):
    result = grown.run_cell("tiny.lm", 2**31 + 4321, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and says so


def test_sound_run_passes_every_check_and_counts_its_routing(record):
    driver, rec, sound = record
    assert all(ok for *_, ok in sound), sound
    assert {name for name, *_ in sound} == set(driver.LIMITS)
    moe = rec["moe"]
    assert all(v == 0 for v in moe["tokens_dropped"])
    assert all(v >= 1 for v in moe["load_max_over_mean"])
    # 8 sampled peers x 2 windows x 16 tokens x 3 experts a token x 2
    # sparse layers... of which a quarter is held, more or less
    made = moe["assignments_held"][0]
    assert 0 < made < 8 * 2 * 16 * 3 * 2


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("nine_experts", "router_flips_beyond_ties"),
    ("no_shared", "logit_gap"),
    ("no_window", "logit_gap"),
    ("no_gate", "logit_gap"),
    ("no_scale", "logit_gap"),
])
def test_each_control_comes_out_not_correct(record, control, by):
    driver, rec, _ = record
    found = driver.check(rec, control=control)
    failed = {name for name, *_, ok in found if not ok}
    assert by in failed, (control, found)


def test_a_stuck_round_is_not_correct(grown, monkeypatch):
    from biscotti_tpu.parallel import sim as simmod

    real_init = simmod.Simulator.__init__

    def broken_init(self, *a, **kw):
        real_init(self, *a, **kw)
        real_step = self.round_step

        def stuck(w, stake, it):
            keep_w, keep_stake = w + 0, stake + 0
            _, _, mask, err = real_step(w, stake, it)
            return keep_w, keep_stake, mask, err

        self.round_step = stuck

    monkeypatch.setattr(simmod.Simulator, "__init__", broken_init)
    result = grown.run_cell("tiny.lm", 11, 0.3, False, require_tpu=False)
    assert result["correct"] is False


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.lm")
    driver = grown.load_module("drivers", "device_round_lm")
    from benchmark.compile_meter import CompileMeter

    wrong = dict(cell, config=dict(
        cell["config"], model=dict(cell["config"]["model"], num_params=7)))
    with pytest.raises(RuntimeError, match="the configuration states 7"):
        driver.run(cell=wrong, fields=grown.biscotti_fields(cell, 1),
                   seconds=0.1, trace_dir=None, meter=CompileMeter(),
                   t0=0.0)


def test_expert_flops_against_xla():
    """6 H F a token-expert assignment forward, as much again for the
    activations' backward: XLA counts the same for one expert's SwiGLU on
    that many rows, differentiated with respect to its input alone."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops.laguna import (expert_forward_flops,
                                        expert_step_flops)

    rows, hidden, width = 96, 64, 32

    def expert(x, w_gate, w_up, w_down):
        return jnp.sum((jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down)

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((rows, hidden), (hidden, width), (hidden, width),
                        (width, hidden))]
    forward = jax.jit(expert).lower(*shapes).compile().cost_analysis()
    step = jax.jit(jax.value_and_grad(expert)).lower(
        *shapes).compile().cost_analysis()
    want_f = expert_forward_flops(rows, hidden, width)
    want_s = expert_step_flops(rows, hidden, width)
    assert want_s == 2 * want_f == 12 * rows * hidden * width
    # XLA adds the elementwise work (silu, products, the sum): a few percent
    assert want_f <= forward["flops"] <= 1.1 * want_f
    assert want_s <= step["flops"] <= 1.1 * want_s


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "device_round_lm"
    names = [m["name"] for m in cell["per_layer"]]
    assert NEW_METRICS <= set(names) and len(names) == 17 + 6
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens" and fields["noising"] is True
    # the softmax cell reads none of the new metrics
    other = run.load_cell("emnist_softmax.device_round")
    assert not NEW_METRICS & {m["name"] for m in other["per_layer"]}
    # every reader file loads, and finds nothing in an empty record
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read({}) is None
    driver = run.load_module("drivers", "device_round_lm")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)


def test_the_configuration_carries_every_published_number():
    """Every number of the catalog row's `config` under the same key,
    unchanged but for the three listed in `reduced`; the driver's sizes
    come out of the built model."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2.1_fedlora.json")) as f:
        config = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert config["source"].startswith(row["source_url"])
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in changed}

    from biscotti_tpu.models import laguna

    preset = laguna.PRESETS["laguna_s_fedlora"]
    mine = published(preset, 1024)
    for key, value in mine.items():
        if key in ("published", "adapters", "model", "rope_parameters"):
            continue
        stated = config[key]
        if isinstance(value, list):
            stated = stated[:len(value)]
        assert stated == value, key
    for kind, rope in mine["rope_parameters"].items():
        for key, value in rope.items():
            assert config["rope_parameters"][kind][key] == value, (kind, key)
    assert dataclasses.asdict(preset)["rank"] == config["adapters"]["rank"]
    model = laguna.laguna_model("laguna_s_fedlora", preset, 1024)
    assert model.num_params == config["model"]["num_params"] == 1048576
    assert laguna.frozen_count(model) == config["model"]["frozen_params"]
